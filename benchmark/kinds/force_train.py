"""Kind ``force_train``: the production epoch driver on the energy-and-force
task, whole epochs back to back.

``ScanEpochDriver`` with the force train body (``make_force_train_step``:
forces as -dE/dx inside the loss, the parameter gradient through them), the
divergence guard, full staging (the force task cannot take compact staging:
``train.py``) and ``chunk_steps`` as ``fit`` builds it for ``train.py --task
force --device-resident``. The window, the deferred fetch, the rate and the
rows of the first-steps comparison are kind ``train``'s own
(``kinds/train.py``), which this driver extends; what differs is the model,
its step, its weights, its optimizer (Adam: the first steps read its first
moment), its reference, its counts, and two more numbers compared: the forces
themselves and the gradient off the mean energy's direction.

What ``--seed`` changes: the weights and the order in which an epoch visits
the batches. What it does not: the pool and its packing, hence every compiled
shape (one bucket: every chunk is ``chunk_steps`` long, whatever the seed).
"""

from __future__ import annotations

import numpy as np

from benchmark import counts, counts_force, system
from benchmark.kinds import train
from benchmark.reference import force_ref as ref
from benchmark.weights_force import make_weights

# the seeded model's mean energy error on its first batch, in standardised
# units (see _seeded_state)
ENERGY_OFFSET = 0.25
# the program's staging counters (train/loop.py), copied for the readers
STAGING_COUNTERS = ("staged_bytes", "staged_edge_fea_bytes")
# the control (``benchmark/control.py``): every matmul operand rounded to
# bfloat16, the precision below the float32 this configuration states
CONTROLS = {"bfloat16": {"control_mm": ref.mm_bf16}}


def frame_as_ref(g) -> dict:
    """A pool frame as the reference reads it (the data set's own features,
    geometry, neighbour list and labels; nothing the program derived)."""
    return {"atom_fea": g.atom_fea, "positions": g.positions,
            "lattice": g.lattice, "centers": g.centers,
            "neighbors": g.neighbors, "offsets": g.offsets,
            "energy": g.target, "forces": g.forces}


def build_model(config: dict):
    """The force model through the builder the configuration names."""
    import importlib

    from cgnn_tpu.config import DataConfig, ModelConfig

    mod, fn = config["builder"].split(":")
    model_cfg = ModelConfig(
        dtype=config["precision"]["compute"],
        dense_m=int(config["layout"]["dense_m"]),
        **{k: config["model"][k] for k in (
            "atom_fea_len", "n_conv", "h_fea_len")},
    )
    return getattr(importlib.import_module(mod), fn)(
        model_cfg, DataConfig(**config["featurize"]), config["task"],
        log_fn=print)


class Driver(train.Driver):

    _ref_energies = None  # the reference's energies, jitted once

    # ---- set-up -------------------------------------------------------

    def setup(self) -> None:
        import jax

        from cgnn_tpu.data.graph import (
            batch_iterator,
            capacities_for,
            pack_graphs,
        )
        from cgnn_tpu.resilience.guard import guard_step
        from cgnn_tpu.train.force_step import (
            make_force_eval_step,
            make_force_predict_step,
            make_force_train_step,
        )
        from cgnn_tpu.train.loop import ScanEpochDriver, staged_nbytes

        ctx, cfg, tr = self.ctx, self.config, self.config["train"]
        if int(tr["buckets"]) != 1:
            raise ValueError("force_train packs one molecule, one shape: "
                             "buckets must be 1")
        with ctx.span("data"):
            graphs, info = system.load_pool(cfg)
        print(f"pool: {len(graphs)} frames "
              f"({'built' if info['built'] else 'loaded'} in "
              f"{info['seconds']:.1f} s)")
        self.graphs = graphs
        self.t_mean, self.t_std = system.target_stats(graphs)
        dense_m = int(cfg["layout"]["dense_m"])
        edge_dtype = system.edge_dtype(cfg)
        weights = (float(tr["energy_weight"]), float(tr["force_weight"]))

        with ctx.span("pack_stage"):
            members: list = []  # one entry a packed batch, in pack order

            def pack(batch_graphs, node_cap, *a, **kw):
                members.append((list(batch_graphs), int(node_cap)))
                return pack_graphs(batch_graphs, node_cap, *a, **kw)

            # the packing rng is the configuration's, not the seed's
            rng = np.random.default_rng(int(cfg["data"]["pack_seed"]))
            bsz = int(tr["batch_size"])
            nc, _ = capacities_for(graphs, bsz, dense_m=dense_m, snug=True)
            batches = list(batch_iterator(
                graphs, bsz, nc, nc * dense_m, shuffle=True, rng=rng,
                dense_m=dense_m, snug=True, edge_dtype=edge_dtype,
                pack_fn=pack))
            # every packed batch is staged ``resident_copies`` times
            # (distinct buffers, the same frames): the trajectory a
            # deployment keeps on the chip is far longer than the pool that
            # set-up can featurize, and an epoch visits every copy
            copies = int(cfg["data"].get("resident_copies", 1))
            staged = copies * staged_nbytes(batches)
            print(f"staging {len(batches)} batches x {copies} copies: "
                  f"{staged / 1e6:.1f} MB on the device, "
                  f"{staged / copies / len(graphs):.0f} B a frame "
                  f"(node capacity {nc})")
            batches = batches * copies
            members = members * copies
        if len(members) != len(batches):
            raise RuntimeError("a batch was split while packing: membership "
                               "no longer lines up with the packed batches")
        self.members = members
        self.steps_per_epoch = len(batches)
        self.structures_per_epoch = sum(len(m) for m, _ in members)
        ctx.obs["counts"].update(
            real_nodes=sum(g.num_nodes for m, _ in members for g in m),
            node_slots=sum(cap for _, cap in members),
            steps_per_epoch=self.steps_per_epoch,
            structures_per_epoch=self.structures_per_epoch,
        )
        self._note_roofline()

        with ctx.span("init"):
            self.model = build_model(cfg)
            state = self._seeded_state(ctx.seed)
        with ctx.span("pack_stage"):
            self.driver = ScanEpochDriver(
                guard_step(make_force_train_step(*weights)),
                make_force_eval_step(*weights), batches, [],
                self._schedule_rng(),
                chunk_steps=int(self.traffic["chunk_steps"]),
                telemetry=ctx.telemetry, preempt=self.clock,
            )
            # device_put returns before the transfer ends: the resident set
            # arrives inside the span that staged it, not inside the first
            # program that reads it (~45 s of 12.3 GB on the v5e's host)
            jax.block_until_ready(self.driver._train_groups)
        del batches
        if ctx.telemetry is not None:
            seen = ctx.telemetry.counters()
            ctx.obs["counts"].update(
                {k: seen[k] for k in STAGING_COUNTERS if k in seen})
        # the program's own inner gradient, for the forces the comparison
        # reads: the predict step differentiates the same energies as the
        # train body does inside its loss. Its first use compiles it.
        self._predict = jax.jit(make_force_predict_step())
        self._batch0 = None
        with ctx.span("compile"):
            self._predict(state, self._first_batch())
            state = self.driver.warm(state)
            jax.block_until_ready(state.params)
        self.state = self._first_steps(state)

    def _seeded_state(self, seed: int):
        import jax

        if self.config["train"]["optim"].lower() != "adam":
            raise ValueError("force_train follows Adam's first steps "
                             "(reference/force_ref.py adam_steps)")
        g0 = self.graphs[0]
        params = make_weights(seed, self.config["model"],
                              g0.atom_fea.shape[1], g0.edge_fea.shape[1])
        # The output bias is set from the first batch, as a practitioner
        # sets it from the labels' mean: a frame's energy is a sum over 21
        # atoms, and a random offset of tens of standardised units would
        # make the energy term all of the gradient and the path through the
        # forces, which this cell exists for, a rounding error. The seeded
        # model's energies are the REFERENCE's, so that no constant error
        # of the program's energies goes into the weights both sides share.
        # ENERGY_OFFSET above the labels' mean and not at it: with the mean
        # error at zero the energy term's gradient is the small difference
        # of large numbers (as benchmark/weights.py found for the
        # first-order cells).
        frames = self.members[0][0]
        batch = ref.coo_batch([frame_as_ref(g) for g in frames])
        if self._ref_energies is None:
            featurize = dict(self.config["featurize"])
            self._ref_energies = jax.jit(lambda p, b: ref.energies(
                p, b, b["positions"], featurize))
        with jax.default_matmul_precision("highest"):
            energy = np.asarray(self._ref_energies(ref.as_jnp(params), batch))
        labels = (np.asarray(batch["energies"]) - self.t_mean) / self.t_std
        offset = float(np.mean(energy - labels))
        atoms = sum(g.num_nodes for g in frames)
        out = params["ForceHead_0"]["out"]
        out["bias"] = out["bias"] - (offset - ENERGY_OFFSET) * len(frames) \
            / atoms
        # a host copy for the reference, before the program touches them
        self.params0 = jax.tree_util.tree_map(np.array, params)
        # committed to its device, as warm()'s scratch copy is (an
        # uncommitted state would miss every jit cache entry warm filled)
        return jax.device_put(
            system.build_state(self.config, self.model, params, {},
                               self.t_mean, self.t_std,
                               self.steps_per_epoch),
            jax.devices()[0])

    def _note_roofline(self) -> None:
        """The least time an average step could take on this chip."""
        import jax

        if jax.devices()[0].platform != "tpu":
            return  # no roofline off the chip
        g0 = self.graphs[0]
        per_epoch = counts_force.step_counts(
            self.ctx.obs["counts"]["real_nodes"],
            sum(g.num_edges for m, _ in self.members for g in m),
            self.config["model"], g0.edge_fea.shape[1],
            g0.atom_fea.shape[1], nbr_per_edge=False)
        least, bound = counts.least_seconds(
            per_epoch, counts.peaks_for(jax.devices()[0].device_kind))
        self.ctx.obs["counts"]["least_s_per_traced_steps"] = (
            least / self.steps_per_epoch)
        print(f"roofline: least {1e3 * least / self.steps_per_epoch:.4f} "
              f"ms a step, bound by {bound} "
              f"({per_epoch['flops'] / self.steps_per_epoch:.4g} FLOP, "
              f"{per_epoch['bytes'] / self.steps_per_epoch:.4g} B a step)")

    def _first_batch(self):
        """The first staged batch, sliced off the resident stack by one
        jitted program (leaf by leaf, eagerly, each of the 19 slices is a
        program of its own to compile)."""
        import jax

        if self._batch0 is None:
            stacked = next(iter(self.driver._train_groups.values()))
            self._batch0 = jax.jit(lambda s: jax.tree_util.tree_map(
                lambda x: x[0], s))(stacked)
        return self._batch0

    def _first_steps(self, state):
        """Kind ``train``'s first steps under Adam: the forces at the seeded
        weights from the program's inner gradient, standardised as the loss
        reads them, over the real atoms of the first batch; then the warmed
        driver's own one-step program through the first three batches. The
        state that comes out is the window's."""
        import jax

        tmap = jax.tree_util.tree_map
        n_real = sum(g.num_nodes for g in self.members[0][0])
        forces = np.asarray(self._predict(state, self._first_batch())[1])
        got = {"loss": [], "forces": forces[:n_real] / self.t_std}
        d = self.driver
        (key, stacked), = d._train_groups.items()
        fn = d._scan_fn(d._train_scans, (key, 1), d._train_body, True)
        self.check_batches = list(range(train.N_CHECK_STEPS))
        for s in self.check_batches:
            state, sums = fn(state, stacked,
                             jax.device_put(np.array([s], np.int32)))
            sums = tmap(float, jax.device_get(sums))
            got["loss"].append(sums["loss_sum"] / max(sums["count"], 1.0))
            if s == 0:
                # Adam: after one step its first moment is (1 - b1) times
                # the gradient the optimizer was given
                mu = [t for t in jax.tree_util.tree_leaves(
                    state.opt_state, is_leaf=lambda x: hasattr(x, "mu"))
                    if hasattr(t, "mu")][0].mu
                got["grad"] = tmap(
                    lambda m: np.array(m) / (1.0 - ref.ADAM_B1), mu)
                got["grad_norm"] = ref.leaf_norms(got["grad"])
        after = tmap(np.array, state.params)
        got["delta_norm"] = ref.leaf_norms(tmap(
            lambda a, b: a - b, after, self.params0))
        self.got = got
        return state

    # ---- the comparison -----------------------------------------------

    def check(self, control_mm=None) -> list:
        """The reference follows the same first steps from the same seeded
        weights on the same batches' frames. With ``control_mm`` the
        reference computed with that matmul stands in the program's place
        (``CONTROLS``: every operand rounded to bfloat16)."""
        import jax.numpy as jnp

        tr = self.config["train"]
        batches = [ref.coo_batch([frame_as_ref(g)
                                  for g in self.members[b][0]])
                   for b in self.check_batches]

        def follow(**kw):
            return ref.adam_steps(
                ref.as_jnp(self.params0), batches,
                jnp.float32(self.t_mean), jnp.float32(self.t_std),
                featurize=self.config["featurize"],
                w_e=float(tr["energy_weight"]),
                w_f=float(tr["force_weight"]), lr=float(tr["lr"]), **kw)

        self.want = follow()
        got = self.got
        if control_mm is not None:
            got = self.control = follow(mm=control_mm)
        return compare(got, self.want, self.config["limits"]["force_train"])


def compare(got: dict, want: dict, limits: dict) -> list:
    """Kind ``train``'s rows, and the forces' own."""
    rows = train.compare(got, want, limits)
    rows.append({"name": "grad_diff_off_energy_median_leaf",
                 "value": ref.off_energy_diff(got["grad"], want["grad"],
                                              want["energy_dir"]),
                 "limit": limits["grad_diff_off_energy_median_leaf"]})
    rows.append({"name": "force_diff_rel",
                 "value": ref.rel_diff(got["forces"], want["forces"]),
                 "limit": limits["force_diff_rel"]})
    return rows
