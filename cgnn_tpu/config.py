"""Config dataclasses shared by train.py / predict.py (SURVEY.md §5).

The reference embeds its argparse namespace inside checkpoints so
``predict.py`` can rebuild the exact model; these dataclasses are that
contract, serialized into checkpoint metadata as a flat dict.
"""

from __future__ import annotations

import dataclasses

import jax.numpy as jnp


@dataclasses.dataclass
class ModelConfig:
    atom_fea_len: int = 64
    n_conv: int = 3
    h_fea_len: int = 128
    n_h: int = 1
    num_targets: int = 1
    classification: bool = False
    num_classes: int = 2
    dropout: float = 0.0
    dtype: str = "float32"  # 'float32' | 'bfloat16'
    aggregation: str | None = None  # None -> global default
    # config #3: per-task MLP stacks over the shared trunk instead of one
    # shared fc_out with T outputs (models/heads.py MultiTaskHead)
    multi_task_head: bool = False
    # dense edge-slot layout (data/graph.py pack_graphs dense_m): scatter-
    # free aggregation, ~2x faster train step on TPU; 0/None = flat COO.
    # Serialized so predict.py packs batches the way the model expects.
    dense_m: int = 0
    # fused BN1->gate->mask->sum epilogue: '' (off) | 'xla' | 'pallas'
    # (ops/fused_epilogue.py). Runtime choice with identical parameters —
    # checkpoints restore across settings — but serialized so predict
    # rebuilds what was trained.
    fused_epilogue: str = ""
    # WHOLE-conv fused kernel: '' (off) | 'xla' | 'pallas'
    # (ops/pallas_cgconv.py — gather+fc_full+BN1+gate+sum as one op).
    # Same parameter tree as the unfused path (checkpoints restore
    # across settings); cgconv_window is the caller-guaranteed neighbor
    # window bound (0 = whole node range, always correct), derived from
    # the dataset via pallas_cgconv.window_width — serialized together
    # so predict rebuilds what was trained.
    cgconv_impl: str = ""
    cgconv_window: int = 0

    def to_meta(self) -> dict:
        return dataclasses.asdict(self) | {
            "aggregation": self.aggregation or "__none__"
        }

    @classmethod
    def from_meta(cls, meta: dict) -> "ModelConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in meta.items() if k in fields}
        kw["classification"] = bool(kw.get("classification", 0))
        kw["multi_task_head"] = bool(kw.get("multi_task_head", 0))
        kw["dense_m"] = int(kw.get("dense_m", 0))
        kw["fused_epilogue"] = str(kw.get("fused_epilogue", "") or "")
        kw["cgconv_impl"] = str(kw.get("cgconv_impl", "") or "")
        kw["cgconv_window"] = int(kw.get("cgconv_window", 0))
        if kw.get("aggregation") in ("__none__", None):
            kw["aggregation"] = None
        return cls(**kw)

    def for_arbitrary_inputs(self) -> "ModelConfig":
        """This config with data-derived bounds widened to always-correct
        settings — the ONE place the invariant lives for inference entry
        points (predict.py, serve load_server, any future export path).

        The serialized ``cgconv_window`` covers the TRAINING set only;
        arbitrary inference inputs can exceed it, and an undersized
        bound silently zeroes out-of-window neighbors in the fused
        conv's in-kernel gather (ops/pallas_cgconv.py contract).
        ``cgconv_window=0`` = full-range gather, always correct."""
        if not self.cgconv_impl or self.cgconv_window == 0:
            return self
        return dataclasses.replace(self, cgconv_window=0)

    def kernel_impls(self) -> tuple[str | None, str | None]:
        """(fused_epilogue, cgconv) implementations that will RUN on this
        backend. The Pallas kernels lower only on TPU; 'xla' is their
        numerically identical twin, so a TPU-trained checkpoint stays
        loadable for CPU prediction/fine-tuning. On TPU 'pallas' means
        the compiled kernel — never an interpreted one."""
        import jax

        def resolve(requested: str) -> str | None:
            if requested == "pallas" and jax.default_backend() != "tpu":
                return "xla"
            return requested or None

        return resolve(self.fused_epilogue), resolve(self.cgconv_impl)

    def impl_summary(self) -> str:
        """One line naming what :meth:`build` selects on this backend
        (entry points log it at model build; chip_smoke.py reads it)."""
        import jax

        fused, cgconv = self.kernel_impls()

        def show(requested: str, chosen: str | None) -> str:
            if chosen and chosen != requested:
                return f"{chosen} (XLA twin; {requested} requested)"
            return chosen or "off"

        return (
            f"model impl: backend={jax.default_backend()} "
            f"dtype={self.dtype} "
            f"layout={'dense' if self.dense_m else 'coo'} "
            f"aggregation={self.aggregation or 'xla'} "
            f"cgconv={show(self.cgconv_impl, cgconv)} "
            f"fused_epilogue={show(self.fused_epilogue, fused)}"
        )

    def build(self, head=None, edge_axis_name: str | None = None):
        """``edge_axis_name`` activates edge-sharded graph parallelism
        (psum over that mesh axis inside every conv). It is a runtime
        parallelism choice, not model identity — deliberately NOT part of
        ``to_meta()``, so checkpoints restore as plain single-device models
        with identical parameters."""
        from cgnn_tpu.models import CrystalGraphConvNet

        if head is None and self.multi_task_head and not self.classification:
            from cgnn_tpu.models.heads import MultiTaskHead

            head = MultiTaskHead(
                num_tasks=self.num_targets,
                h_fea_len=self.h_fea_len,
                n_h=self.n_h,
                dtype=jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32,
            )
        fused, cgconv = self.kernel_impls()
        return CrystalGraphConvNet(
            atom_fea_len=self.atom_fea_len,
            n_conv=self.n_conv,
            h_fea_len=self.h_fea_len,
            n_h=self.n_h,
            num_targets=self.num_targets,
            classification=self.classification,
            num_classes=self.num_classes,
            dropout_rate=self.dropout,
            dtype=jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32,
            aggregation_impl=self.aggregation,
            head=head,
            edge_axis_name=edge_axis_name,
            dense_m=self.dense_m or None,
            fused_epilogue=fused,
            cgconv_impl=cgconv,
            cgconv_window=self.cgconv_window,
        )


def build_model(model_cfg: "ModelConfig", data_cfg: "DataConfig",
                task: str = "regression",
                edge_axis_name: str | None = None, log_fn=None):
    """Build the model for a task; the force task needs the edge featurization
    hyperparameters in-model (distances are recomputed differentiably from
    positions — models/forcefield.py). ``log_fn`` receives the one line
    naming the kernel implementations selected for this backend."""
    if log_fn is not None:
        log_fn(model_cfg.impl_summary())
    if task == "force":
        if edge_axis_name is not None:
            raise NotImplementedError(
                "graph sharding is not supported for the force task"
            )
        from cgnn_tpu.models.forcefield import ForceFieldCGCNN

        return ForceFieldCGCNN(
            atom_fea_len=model_cfg.atom_fea_len,
            n_conv=model_cfg.n_conv,
            h_fea_len=model_cfg.h_fea_len,
            dmin=data_cfg.dmin,
            dmax=data_cfg.radius,
            step=data_cfg.step,
            dtype=jnp.bfloat16 if model_cfg.dtype == "bfloat16" else jnp.float32,
            aggregation_impl=model_cfg.aggregation,
            dense_m=model_cfg.dense_m or None,
        )
    return model_cfg.build(edge_axis_name=edge_axis_name)


@dataclasses.dataclass
class DataConfig:
    radius: float = 8.0
    max_num_nbr: int = 12
    dmin: float = 0.0
    step: float = 0.2

    def to_meta(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_meta(cls, meta: dict) -> "DataConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in meta.items() if k in fields})

    def featurize_config(self):
        from cgnn_tpu.data.dataset import FeaturizeConfig

        return FeaturizeConfig(
            radius=self.radius,
            max_num_nbr=self.max_num_nbr,
            dmin=self.dmin,
            step=self.step,
        )
