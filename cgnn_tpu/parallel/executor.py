"""MeshExecutor: one mesh-based execution layer for the forward path
(ISSUE 10, ROADMAP item 1).

Until this module the repo ran TWO parallelism stacks: training's
``shard_map`` over ``parallel/mesh.py`` meshes, and serving's
thread-per-device ``DeviceSet`` (ISSUE 5) — N Python dispatch threads,
N param replica tuples, N executables per program. ``MeshExecutor``
collapses the serving/inference side onto the SAME ``Mesh`` +
``NamedSharding`` + jit mechanism training uses (SNIPPETS.md [2]-[3]):

- **One program, one dispatch.** A forward program is the single-device
  predict body wrapped in ``shard_map`` over a 1-D ``('data',)`` mesh:
  per-shard sub-batches stack on a leading device axis, the stacked
  batch is ``device_put`` with ``NamedSharding(mesh, P('data'))`` (each
  device receives exactly its slice — nothing is replicated), params
  are placed ONCE replicated (``P()``), and one jitted call runs every
  device. The jit cache holds ONE entry per (rung, staging form, tier)
  — not ``programs x N`` executables like ``DeviceSet`` — and the
  dispatch path has no router, no per-device queues, no per-device
  threads.

- **Bit-exact by construction.** Inside ``shard_map`` each device runs
  the UNPARTITIONED body on its own sub-batch — the same HLO a
  single-device dispatch of that sub-batch runs (the leading-axis
  squeeze/expand are layout no-ops). Mesh-vs-DeviceSet parity over
  identical packed batches is therefore exact, pinned by
  tests/test_executor.py across the ladder, compact staging, and the
  ragged tail.

- **One sharded param tree.** ``place_params`` returns a single
  replicated-over-the-mesh state; ``serve.reload.ParamStore`` holds it
  as its one entry per tier (``placer=``), so a hot swap publishes one
  tree under one version — the per-device replica tuple disappears.

- **Multi-host ready.** The same mesh layer extends across processes:
  ``parallel/dist.py`` stages host-local stacks as global arrays and
  coordinates checkpoint commits/hot reloads; a ``MeshExecutor`` over
  ``jax.devices()`` in a ``jax.distributed`` run is the pod-serving
  shape (this container proves the single-host 8-device slice, the
  2-process CPU dryrun the cross-host mechanics).

The classic failure mode this layer must never regress into: a batch
``device_put`` WITHOUT the sharding (or with ``P()``) silently
replicates every byte to every device — N x the H2D traffic and HBM of
the sharded layout with identical outputs. graftaudit's GA-SHARD check
budgets the compiled program's per-device argument bytes against the
``params + batch/N`` model so that mistake blocks CI
(analysis/program_audit.py).
"""

from __future__ import annotations

from typing import Callable, Sequence

import numpy as np



class MeshExecutor:
    """Mesh + shardings + the sharded-program factory for one device set.

    ``devices`` defaults to the backend-aware ``resolve_devices('auto')``
    (serve/devices.py: all local devices on accelerators, one on CPU —
    an explicit list forces, which is how the 8-host-device dryrun runs
    in-container).
    """

    def __init__(self, devices: Sequence | None = None, *,
                 axis: str = "data"):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        if devices is None:
            from cgnn_tpu.serve.devices import resolve_devices

            devices = resolve_devices("auto")
        devices = list(devices)
        if not devices:
            raise ValueError("a MeshExecutor needs at least one device")
        self.devices = tuple(devices)
        self.axis = axis
        self.mesh = Mesh(np.array(devices), (axis,))
        self.param_sharding = NamedSharding(self.mesh, P())
        self.batch_sharding = NamedSharding(self.mesh, P(axis))
        self._jax = jax

    def __len__(self) -> int:
        return len(self.devices)

    # ---- placement ----

    def place_params(self, state):
        """ONE replicated-over-the-mesh param tree (the ParamStore
        entry). Committed placement: dispatches follow it to the mesh
        with no per-call device routing."""
        return self._jax.device_put(state, self.param_sharding)

    def stage(self, stacked):
        """Stage a host-stacked ``[N, ...]`` batch pytree batch-axis
        SHARDED: each device receives exactly its ``[1, ...]`` slice.
        This line is the whole point — ``device_put`` without the
        sharding would replicate the full stack to every device (the
        GA-SHARD failure mode)."""
        return self._jax.device_put(stacked, self.batch_sharding)

    def stack(self, batches: Sequence):
        """Stack N same-shape per-shard batches on the leading device
        axis (host-side; pytree structure preserved, so a CompactBatch
        stays a CompactBatch and the predict body's trace-time staging
        dispatch still sees its type)."""
        if len(batches) != len(self):
            raise ValueError(
                f"need exactly {len(self)} per-shard batches "
                f"(one per mesh device), got {len(batches)}"
            )
        return self._jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *batches)

    # ---- the sharded program ----

    def shard_predict(self, predict_body: Callable):
        """The ONE jitted sharded forward program factory.

        ``predict_body`` is the unjitted (state, batch) -> [G, T] body
        (train.step.make_predict_step). Returns a jitted callable over
        (replicated state, ``[N, ...]`` stacked batch) -> ``[N, G, T]``
        whose single dispatch covers every mesh device. Each traced
        (rung, staging form, tier) is ONE cache entry and ONE compiled
        multi-device executable — the compile count is ``programs``,
        never ``programs x N``.
        """
        from jax.sharding import PartitionSpec as P

        jax = self._jax

        def stacked_body(state, batch):
            # inside shard_map the batch slice is [1, ...]: squeeze to
            # the single-device batch, run the UNCHANGED body, restack —
            # per-shard HLO identical to a single-device dispatch.
            # tree_map, not [None]: the raw-wire program returns a
            # (preds, overflow, n_edges) TUPLE (ISSUE 11) and every
            # output leaf restacks on the device axis the same way
            sub = jax.tree_util.tree_map(lambda x: x[0], batch)
            return jax.tree_util.tree_map(
                lambda x: x[None], predict_body(state, sub))

        return jax.jit(jax.shard_map(
            stacked_body, mesh=self.mesh,
            in_specs=(P(), P(self.axis)), out_specs=P(self.axis),
            check_vma=False,  # no collectives in the forward body
        ))

    # ---- serving-side shard planning ----

    def split_round_robin(self, items: Sequence) -> list[list]:
        """items[j] -> shard j % N (row j // N): the flush split. Keeps
        shard loads within one item of each other, and the (shard, row)
        coordinate of every item is a pure function of its index."""
        n = len(self)
        return [list(items[i::n]) for i in range(n)]

    def plan_flush(self, graphs: Sequence, shape_set):
        """Split a flush's graphs across the mesh and pick ONE common
        rung for every shard -> (groups, shape, counts).

        Every shard's sub-batch must pack the same compiled shape (the
        stack axis is uniform), so the rung is the smallest one that
        fits the LARGEST shard group. Shards the round-robin leaves
        empty are packed with a filler copy of the first graph — their
        output rows are never read (``counts`` records real graphs per
        shard; accounting and response mapping key on it)."""
        groups = self.split_round_robin(list(graphs))
        counts = [len(g) for g in groups]
        need_g = need_n = need_e = 1
        for g in groups:
            if not g:
                continue
            n = sum(x.num_nodes for x in g)
            e = sum(shape_set.graph_counts(x)[1] for x in g)
            need_g = max(need_g, len(g))
            need_n = max(need_n, n)
            need_e = max(need_e, e)
        shape = shape_set.shape_for(need_g, need_n, need_e)
        if shape is None:
            raise ValueError(
                f"no rung fits the per-shard split "
                f"({need_g} graphs / {need_n} nodes / {need_e} edge "
                f"slots) — the flush should have been admitted smaller"
            )
        filler = [graphs[0]]
        groups = [g if g else filler for g in groups]
        return groups, shape, counts

    def abstract_stacked(self, batch_aval):
        """Stacked ``[N, ...]`` avals from one per-shard batch aval —
        the graftaudit lowering surface for the mesh program."""
        jax = self._jax

        def stackaval(x):
            return jax.ShapeDtypeStruct((len(self), *x.shape), x.dtype)

        return jax.tree_util.tree_map(stackaval, batch_aval)
