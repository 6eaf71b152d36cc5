"""Kind ``ocp_train``: the production epoch driver on the Open Catalyst
Project's baseline CGCNN, whole epochs back to back.

``ScanEpochDriver`` with the train body on the L1 loss, the divergence guard,
compact staging, snug packing and ``chunk_steps`` as ``fit`` builds it for
``train.py --device-resident --bf16 --node-norm layer --no-pool-softplus
--loss l1 --optim Adam``. The window, its clock, the schedule's rng
(``ScheduleRng``: the batches' order from ``--seed``, the chunk lengths from
``pack_seed``), the deferred fetch, the evidence and the rows of the
first-steps comparison are kind ``train``'s own (``kinds/train.py``), which
this driver extends. What differs is what ``train.Driver.setup`` builds
inline and the harness therefore forces a kind to build itself: the model
(LayerNorm after the neighbour sum, a hidden stack in the head;
``system.build_model`` passes the lineage's five keys), the step's loss, the
optimizer (Adam: the first gradient is read off its first moment), the
weights, the reference (``reference/ocp_ref.py``) and the counts.

A step here is ~60 ms of device time, thirty times the flagship's, so the
resident set is sized for an epoch that the window holds whole
(``data.resident_copies``; PERF.md section 4): a window of 20 s is one epoch,
every structure of it counted once whatever the seed.
"""

from __future__ import annotations

import inspect

import numpy as np

from benchmark import counts, counts_ocp, system
from benchmark.kinds import train
from benchmark.reference import ocp_ref as ref
from benchmark.weights_ocp import make_weights

# name -> keywords of ``Driver.check`` (``benchmark/control.py``): the
# reference computed that way stands in the program's place and has to come
# out as not correct. ``float8``: e4m3 on every matmul operand, the precision
# below the bfloat16 this configuration states. The other two are faults of a
# training step that no precision explains. ``half_batch``: every step sees
# the first half of its batch's slabs only (a packer or a mask that drops
# structures). ``raw_targets``: the loss on the energies as the data set has
# them, not standardised (a normaliser that was not applied).
CONTROLS = {"float8": {"control_mm": ref.mm_fp8},
            "half_batch": {"fault": "half_batch"},
            "raw_targets": {"fault": "raw_targets"}}
# what the configuration changes in the conv and no other counter says
# (train/loop.py conv_shape_gauges), and how far the gather's transpose
# engages its overflow tier at this M (transpose_overflow_stats)
SHAPE_COUNTERS = ("conv_row_lanes", "dense_m", "edge_gaussians",
                  "transpose_overflow_rows", "transpose_overflow_cap",
                  "transpose_overflow_max_run")


def first_gradient(opt_state):
    """The gradient Adam was given in its first step, as host arrays: after
    one step its first moment is (1 - b1) times that gradient."""
    import jax

    mu = [t for t in jax.tree_util.tree_leaves(
        opt_state, is_leaf=lambda x: hasattr(x, "mu"))
        if hasattr(t, "mu")][0].mu
    return jax.tree_util.tree_map(
        lambda m: np.array(m) / (1.0 - ref.ADAM_B1), mu)


def build_model(config: dict):
    """The model through the builder the configuration names, with the two
    fields that make it the Open Catalyst one."""
    import importlib

    from cgnn_tpu.config import DataConfig, ModelConfig

    mod, fn = config["builder"].split(":")
    m = config["model"]
    if m["edge_norm"] != "batch":
        raise ValueError("the conv normalises its edges with BatchNorm "
                         "(bn1); no other edge_norm is built")
    model_cfg = ModelConfig(
        dtype=config["precision"]["compute"],
        dense_m=int(config["layout"]["dense_m"]),
        node_norm=m["node_norm"], pool_softplus=bool(m["pool_softplus"]),
        **{k: m[k] for k in ("atom_fea_len", "n_conv", "h_fea_len", "n_h",
                             "num_targets")},
    )
    return getattr(importlib.import_module(mod), fn)(
        model_cfg, DataConfig(**config["featurize"]), "regression",
        log_fn=print)


class Driver(train.Driver):

    # ---- set-up -------------------------------------------------------

    def setup(self) -> None:
        import jax

        from cgnn_tpu.data.compact import (
            CompactSpec,
            compact_pack_fn,
            make_expander,
        )
        from cgnn_tpu.data import dataset
        from cgnn_tpu.data.graph import (
            batch_shape_key,
            bucketed_batch_iterator,
        )
        from cgnn_tpu.resilience.guard import guard_step
        from cgnn_tpu.train import loop
        from cgnn_tpu.train.step import (
            l1_regression_loss,
            make_eval_step,
            make_train_step,
        )

        ctx, cfg, tr = self.ctx, self.config, self.config["train"]
        if tr["optim"].lower() != "adam" or tr["loss"] != "l1":
            raise ValueError("ocp_train follows Adam's first steps on the L1 "
                             "loss (reference/ocp_ref.py adam_steps)")
        # the model first: a program without the L1 loss or LayerNorm after
        # the sum (the parent of the PR that added this kind) fails at the
        # import above or here, at once, before a pool of gigabytes is
        # featurized
        with ctx.span("init"):
            self.model = build_model(cfg)
        fcfg = system.featurize_config(cfg)
        gauss = int(cfg["model"]["num_gaussians"])
        if fcfg.gdf().num_features != gauss:
            raise ValueError(
                f"featurize gives {fcfg.gdf().num_features} Gaussians, the "
                f"configuration states {gauss} (np.arange over dmin, radius "
                f"and step rounds to one more or one fewer)")
        # system.load_pool hands the loader no keyword of the data block:
        # the slabs' spacing is the loader's default, held to the block's
        loader_a0 = inspect.signature(getattr(
            dataset, cfg["data"]["generator"])).parameters["a0"].default
        if loader_a0 != cfg["data"]["a0"]:
            raise ValueError(f"the loader builds slabs at a0 = {loader_a0}, "
                             f"the configuration states {cfg['data']['a0']}")
        with ctx.span("data"):
            graphs, info = system.load_pool(cfg)
        print(f"pool: {len(graphs)} structures "
              f"({'built' if info['built'] else 'loaded'} in "
              f"{info['seconds']:.1f} s)")
        if graphs[0].edge_fea.shape[1] != gauss:
            raise ValueError("the pool's edge features are not the "
                             "configuration's: another featurization's "
                             "cache file?")
        self.graphs = graphs
        self.t_mean, self.t_std = system.target_stats(graphs)
        dense_m = int(cfg["layout"]["dense_m"])
        edge_dtype = system.edge_dtype(cfg)

        with ctx.span("pack_stage"):
            compact = CompactSpec.build(graphs, fcfg.gdf(), dense_m=dense_m,
                                        edge_dtype=edge_dtype)
            inner = compact_pack_fn(compact)
            members: list = []  # one entry a packed batch, in pack order

            def pack(batch_graphs, node_cap, *a, **kw):
                members.append((list(batch_graphs), int(node_cap)))
                return inner(batch_graphs, node_cap, *a, **kw)

            # the packing rng is the configuration's, not the seed's: group
            # lengths key the compiled scan programs
            rng = np.random.default_rng(int(cfg["data"]["pack_seed"]))
            batches = list(bucketed_batch_iterator(
                graphs, int(tr["batch_size"]), int(tr["buckets"]),
                shuffle=True, rng=rng, dense_m=dense_m, snug=True,
                edge_dtype=edge_dtype, pack_fn=pack))
            overflow = loop.transpose_overflow_stats(batches)
            # every packed batch is staged ``resident_copies`` times
            # (distinct buffers, the same structures), and an epoch visits
            # every copy: kinds/train.py
            copies = int(cfg["data"].get("resident_copies", 1))
            staged = copies * loop.staged_nbytes(batches)
            print(f"staging {len(batches)} batches x {copies} copies: "
                  f"{staged / 1e6:.1f} MB on the device")
            batches = batches * copies
            members = members * copies
        if len(members) != len(batches):
            raise RuntimeError("a batch was split while packing: membership "
                               "no longer lines up with the packed batches")
        self.members = members
        self.steps_per_epoch = len(batches)
        self.structures_per_epoch = sum(len(m) for m, _ in members)
        real_edges = sum(g.num_edges for m, _ in members for g in m)
        ctx.obs["counts"].update(
            real_nodes=sum(g.num_nodes for m, _ in members for g in m),
            node_slots=sum(cap for _, cap in members),
            steps_per_epoch=self.steps_per_epoch,
            structures_per_epoch=self.structures_per_epoch,
        )
        self._note_roofline()
        seen: dict = {}
        self.where = []
        for b in batches:
            k = batch_shape_key(b)
            self.where.append((k, seen.get(k, 0)))
            seen[k] = seen.get(k, 0) + 1

        with ctx.span("init"):
            state = self._seeded_state(ctx.seed)
        shape = loop.conv_shape_gauges(state.params, dense_m)
        ctx.obs["counts"].update(
            {k: v for k, v in {**shape, **overflow}.items()
             if k in SHAPE_COUNTERS})
        print(f"conv: rows of {shape['conv_row_lanes']} lanes, "
              f"{shape['dense_m']} slots a node, {shape['edge_gaussians']} "
              f"Gaussians; overflow tier "
              f"{overflow['transpose_overflow_rows'] * copies} of "
              f"{real_edges} real edges "
              f"({100.0 * overflow['transpose_overflow_rows'] * copies / real_edges:.2f}%), "
              f"run capacity {overflow['transpose_overflow_max_run']}")
        with ctx.span("pack_stage"):
            self.driver = loop.ScanEpochDriver(
                guard_step(make_train_step(loss_fn=l1_regression_loss)),
                make_eval_step(loss_fn=l1_regression_loss),
                batches, [], self._schedule_rng(),
                expand=make_expander(compact),
                chunk_steps=int(self.traffic["chunk_steps"]),
                telemetry=ctx.telemetry, preempt=self.clock,
            )
        del batches
        with ctx.span("compile"):
            state = self.driver.warm(state)
            jax.block_until_ready(state.params)
        self.state = self._first_steps(state)

    def _seeded_state(self, seed: int):
        import jax

        g0 = self.graphs[0]
        params, stats = make_weights(seed, self.config["model"],
                                     g0.atom_fea.shape[1],
                                     g0.edge_fea.shape[1])
        # host copies for the reference, before the program touches them
        self.params0 = jax.tree_util.tree_map(np.array, params)
        self.stats0 = jax.tree_util.tree_map(np.array, stats)
        # committed to its device, as warm()'s scratch copy is (an
        # uncommitted state would miss every jit cache entry warm filled)
        return jax.device_put(
            system.build_state(self.config, self.model, params, stats,
                               self.t_mean, self.t_std,
                               self.steps_per_epoch),
            jax.devices()[0])

    def _note_roofline(self) -> None:
        """The least time an average step could take on this chip."""
        import jax

        if jax.devices()[0].platform != "tpu":
            return  # no roofline off the chip
        g0 = self.graphs[0]
        per_epoch = counts_ocp.step_counts(
            self.ctx.obs["counts"]["real_nodes"],
            sum(g.num_edges for m, _ in self.members for g in m),
            self.structures_per_epoch, self.config["model"],
            g0.edge_fea.shape[1], g0.atom_fea.shape[1])
        least, bound = counts.least_seconds(
            per_epoch, counts.peaks_for(jax.devices()[0].device_kind))
        self.ctx.obs["counts"]["least_s_per_traced_steps"] = (
            least / self.steps_per_epoch)
        print(f"roofline: least {1e3 * least / self.steps_per_epoch:.4f} "
              f"ms a step, bound by {bound} "
              f"({per_epoch['flops'] / self.steps_per_epoch:.4g} FLOP, "
              f"{per_epoch['bytes'] / self.steps_per_epoch:.4g} B a step)")

    def _first_steps(self, state):
        """Kind ``train``'s first steps under Adam: the warmed driver's own
        one-step programs through the first three steps, one batch of each
        bucket shape in turn. The state that comes out is the window's."""
        import jax

        tmap = jax.tree_util.tree_map
        d = self.driver
        keys = list(d._train_groups)
        self.check_batches = []  # index into self.members, per step
        got = {"loss": []}
        for s in range(train.N_CHECK_STEPS):
            key = keys[s % len(keys)]
            pos = s // len(keys)
            self.check_batches.append(self.where.index((key, pos)))
            fn = d._scan_fn(d._train_scans, (key, 1), d._train_body, True)
            perm = jax.device_put(np.array([pos], np.int32))
            state, sums = fn(state, d._train_groups[key], perm)
            sums = tmap(float, jax.device_get(sums))
            got["loss"].append(sums["loss_sum"] / max(sums["count"], 1.0))
            if s == 0:
                got["grad"] = first_gradient(state.opt_state)
                got["grad_norm"] = ref.leaf_norms(got["grad"])
        after = tmap(np.array, state.params)
        got["delta_norm"] = ref.leaf_norms(tmap(
            lambda a, b: a - b, after, self.params0))
        self.got = got
        return state

    # ---- the comparison -----------------------------------------------

    def check(self, control_mm=None, fault=None) -> list:
        """The reference follows the same first steps from the same seeded
        weights on the same batches' structures. With ``control_mm`` or a
        ``fault`` (``CONTROLS``) the reference computed that way stands in
        the program's place."""
        import jax.numpy as jnp

        def follow(fault=None, **kw):
            if fault not in (None, "half_batch", "raw_targets"):
                raise ValueError(f"no fault {fault!r}")
            members = [self.members[b][0] for b in self.check_batches]
            if fault == "half_batch":
                members = [m[:len(m) // 2] for m in members]
            mean, std = ((0.0, 1.0) if fault == "raw_targets"
                         else (self.t_mean, self.t_std))
            return ref.adam_steps(
                ref.as_jnp(self.params0), ref.as_jnp(self.stats0),
                [ref.coo_batch([system.graph_as_ref(g) for g in m])
                 for m in members],
                jnp.float32(mean), jnp.float32(std),
                lr=float(self.config["train"]["lr"]), **kw)

        self.want = follow()
        got = self.got
        if control_mm is not None or fault is not None:
            kw = {} if control_mm is None else {"mm": control_mm}
            got = self.control = follow(fault, **kw)
        return train.compare(got, self.want,
                             self.config["limits"]["ocp_train"])
