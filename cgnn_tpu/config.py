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
    # config #3: per-task MLP stacks over the shared trunk instead of one
    # shared fc_out with T outputs (models/heads.py MultiTaskHead)
    multi_task_head: bool = False
    # dense edge-slot layout (data/graph.py pack_graphs dense_m): scatter-
    # free aggregation, ~2x faster train step on TPU; 0/None = flat COO.
    # Serialized so predict.py packs batches the way the model expects.
    dense_m: int = 0
    # the normalisation after each conv's neighbour sum: 'batch' (bn2, the
    # lineage's) or 'layer' (the Open Catalyst CGCNN's LayerNorm; parameters
    # conv_i/ln in bn2's place). bn1 is BatchNorm either way.
    node_norm: str = "batch"
    # softplus on the pooled vector before conv_to_fc (the lineage has one,
    # the Open Catalyst CGCNN none)
    pool_softplus: bool = True

    def to_meta(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_meta(cls, meta: dict) -> "ModelConfig":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in meta.items() if k in fields}
        kw["classification"] = bool(kw.get("classification", 0))
        kw["multi_task_head"] = bool(kw.get("multi_task_head", 0))
        kw["dense_m"] = int(kw.get("dense_m", 0))
        kw["pool_softplus"] = bool(kw.get("pool_softplus", 1))
        return cls(**kw)

    def impl_summary(self) -> str:
        """One line naming what :meth:`build` gives on this backend
        (entry points log it at model build; chip_smoke.py reads it)."""
        import jax

        return (
            f"model impl: backend={jax.default_backend()} "
            f"dtype={self.dtype} "
            f"layout={'dense' if self.dense_m else 'coo'}"
        )

    def build(self, head=None):
        from cgnn_tpu.models import CrystalGraphConvNet

        if head is None and self.multi_task_head and not self.classification:
            from cgnn_tpu.models.heads import MultiTaskHead

            head = MultiTaskHead(
                num_tasks=self.num_targets,
                h_fea_len=self.h_fea_len,
                n_h=self.n_h,
                dtype=jnp.bfloat16 if self.dtype == "bfloat16" else jnp.float32,
            )
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
            head=head,
            dense_m=self.dense_m or None,
            node_norm=self.node_norm,
            pool_softplus=self.pool_softplus,
        )


def build_model(model_cfg: "ModelConfig", data_cfg: "DataConfig",
                task: str = "regression", log_fn=None):
    """Build the model for a task; the force task needs the edge featurization
    hyperparameters in-model (distances are recomputed differentiably from
    positions — models/forcefield.py). ``log_fn`` receives the one line
    naming backend, dtype and layout."""
    if log_fn is not None:
        log_fn(model_cfg.impl_summary())
    if task == "force":
        from cgnn_tpu.models.forcefield import ForceFieldCGCNN

        if data_cfg.var is not None:
            raise NotImplementedError(
                "the force model rebuilds its Gaussians at width = step; a "
                "separate width (var) is not supported for the force task"
            )
        return ForceFieldCGCNN(
            atom_fea_len=model_cfg.atom_fea_len,
            n_conv=model_cfg.n_conv,
            h_fea_len=model_cfg.h_fea_len,
            dmin=data_cfg.dmin,
            dmax=data_cfg.radius,
            step=data_cfg.step,
            dtype=jnp.bfloat16 if model_cfg.dtype == "bfloat16" else jnp.float32,
            dense_m=model_cfg.dense_m or None,
        )
    return model_cfg.build()


@dataclasses.dataclass
class DataConfig:
    radius: float = 8.0
    max_num_nbr: int = 12
    dmin: float = 0.0
    step: float = 0.2
    # the Gaussians' width in exp(-(d - mu)^2 / var^2); None = ``step``
    var: float | None = None

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
            var=self.var,
        )
