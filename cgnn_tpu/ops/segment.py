"""Gathers, their declared transposes, and segment reductions.

The reference's hottest device loop is the per-edge gather + per-node
sum inside its conv layer (SURVEY.md §3.3): on GPU it is ATen
``index_select`` + ``sum(dim=1)``. Here the dense conv gathers through
``gather_slot_major`` (a row gather whose transpose is a second row
gather through the packer's mapping; the sum over M needs no op of its
own; the rows it moves are the nodes' fc_full projections, 2F wide, and
the force model's positions: models/cgcnn.py _SplitFcFull) and the
flat COO conv through ``gather`` and ``aggregate_edge_messages``, a
``jax.ops.segment_sum`` over the sorted centres the packers emit: the one
XLA scatter left in this file, which no dense batch and no cell runs.

The reductions over a batch's graphs live here too: ``segment_mean`` (the
per-crystal pooling, models/cgcnn.py) and ``segment_sum`` (the force
model's per-frame energy) are a matmul by the 0/1 matrix of the nodes'
graphs (``_segment_totals``), with a row gather as its declared transpose.
They were the step's last two scatters, ~10 ns a row to add ~24,000 rows
of 64 numbers into ~500 (PERF.md section 6, PR 50).

XLA compiles all of it; there is no hand-written kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


# rows of the overflow list summed by one matmul: the MXU's tile on the v5e
_RUN_BLOCK = 128


def _run_windows(o_slots, o_nodes, run_cap: int):
    """The overflow list cut into the blocks its runs are summed in:
    ``(window slots [B, halo + 128] i32, same_run [B, 128, halo + 128]
    bool)`` from the list's slots and (sorted) nodes, both [O].

    A node's entries are one run of at most ``run_cap`` (a Python int:
    ``over_runs``' length). Block b holds entries [128 b, 128 (b + 1)) and
    is gathered with the ``run_cap - 1`` (8-aligned) entries before it, a
    halo: a run may straddle a block's start, and never needs more.
    ``same_run[b, i, j]`` says that window entry j belongs to block entry
    i's node and lies at or before it, so ``same_run[b] @ rows[b]`` leaves
    at every run's LAST entry the run's total. There is always a block
    past the list's end (B = O // 128 + 1): entries beyond the end sum
    nothing, their totals are zero, and that is where the pointer of a node
    that owns no run points (entry O).

    Index arithmetic on the mapping alone, the same for every conv of a
    step: the gathers compute it beside the forward pass, not in the
    transpose, so a step computes it once.
    """
    block = _RUN_BLOCK
    halo = -(-(run_cap - 1) // 8) * 8  # whole sublanes
    if halo > block:
        raise ValueError(f"run_cap {run_cap} exceeds what a block of "
                         f"{block} overflow entries can look back over")
    n_blocks = o_slots.shape[0] // block + 1

    def windows(x, fill):  # [O] -> [n_blocks, halo + block]
        # window b = entries [b*block - halo, (b+1)*block); ``fill`` before
        # the list's start and after its end
        x = jnp.pad(x, (halo, n_blocks * block - x.shape[0]),
                    constant_values=fill)
        return jnp.concatenate(
            [x[:n_blocks * block].reshape(n_blocks, block)[:, :halo],
             x[halo:].reshape(n_blocks, block)], axis=1)

    ids = windows(o_nodes, -1)
    mine = ids[:, halo:, None]  # the block's own entries
    upto = jnp.arange(halo + block)[None, :] <= (
        halo + jnp.arange(block))[:, None]
    same_run = (mine == ids[:, None, :]) & upto & (mine >= 0)
    return windows(o_slots, 0), same_run


def _run_totals(ct, o_win, o_same):
    """``ct``'s rows at the overflow list's slots, every node's run of them
    summed in place: [E, F] -> [B * 128, F], row e the sum of entry e's
    run up to and including e (``_run_windows``), rows past the list zero.

    One [128, halo + 128] 0/1 matrix times its gathered rows a block, on
    the MXU, which idles at 97% in this model: products with 0 and 1 are
    exact and the sums are float32 (precision ``highest``: a float32
    cotangent is not rounded to bfloat16 on the way in), rounded once to
    ``ct``'s dtype. Each total is the sum of its own run's rows and of
    nothing else: no scatter, and no prefix sum and difference either. On
    the v5e at ``mp.train``'s 25,512 entries the sums take 38 us (20 at
    ``force.train``'s 9,712 in float32) and the tier with its two row
    gathers 130 (45), where four shifted masked adds (a segmented scan)
    made it 248 (59) and the sorted scatter-add behind ``segment_sum``
    308 (100) (PERF.md section 6, PR 33).
    """
    rows = gather(ct, o_win.reshape(-1)).reshape(*o_win.shape, ct.shape[-1])
    totals = jnp.einsum(
        "bij,bjf->bif", o_same.astype(ct.dtype), rows,
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )
    return totals.astype(ct.dtype).reshape(-1, ct.shape[-1])


def _transpose_cotangent(ct, slots, msk, o_win, o_same, o_last,
                         degree_axis: int = 1):
    """The gather's cotangent transpose ([E, F] -> [N, F]), scatter-free.

    The autodiff transpose of a row gather is a scatter-add of the [E, F]
    cotangent into [N, F], the XLA scatter the dense layout removed from
    the forward (~50x below HBM bandwidth on TPU). Through the packer's
    mapping (pack_graphs ``in_cap`` / ``over_cap``) it is gather(ct,
    slots) and a masked sum over the in-degree axis: a row gather and a
    dense reduction. Two tiers: tier 1 is [N, M] with no in-degree
    padding (a single [N, 2M] tier was the step's largest op at mean
    in-degree M, half of it padding), and the ~7% of edges of rank >= M
    arrive through the node-sorted overflow list (``o_win``, ``o_same``,
    ``o_last``; below). The ``segment_sum`` that used to close that tier
    was the conv's last XLA scatter: ~10 ns a row where a gathered row
    costs ~1.3-2 (PERF.md section 5).

    ``slots`` is flat and ``msk`` says how the gathered rows are viewed:
    [N, In] (node-major, ``degree_axis`` 1) or [In, N] (slot-major,
    ``degree_axis`` 0, ``gather_slot_major``'s: the sum is In slab adds,
    no sublane reduce).
    in_slots arrives pre-flattened (pack_graphs): a device-side
    [N, In] -> [N*In] flatten of the *gathered rows* is a tiled->linear
    relayout that measured 0.75 ms/step under the epoch scan.
    Accumulation stays in the cotangent dtype: matches the scatter-add's
    accumulation precision, and an f32 upcast doubles the [N, In, F]
    intermediate's bytes for no measured accuracy gain (full-step bf16:
    16.0 ms vs f32-acc 17.5 ms vs scatter 18.8 ms).
    """
    contrib = gather(ct, slots).reshape(*msk.shape, ct.shape[-1])
    grad = (contrib * msk[..., None].astype(ct.dtype)).sum(axis=degree_axis)
    if o_win is not None:
        # the overflow tier (~7% of ct's rows, a run of 1..run_cap of them
        # for ~40% of the nodes): gather the rows, sum each node's run in
        # place, and gather every node's total through its pointer. A node
        # that owns no run points at entry O, a row of zeros; the list's
        # own padding rows (whatever slot 0 holds) come after every real
        # entry, and a run is summed forwards only, so no pointer's total
        # holds one. Row gathers cost ~1.3-1.8 ns a row on the v5e where
        # the sorted scatter-add behind ``segment_sum`` cost ~10.
        grad = grad + gather(_run_totals(ct, o_win, o_same), o_last)
    return grad


# ``fwd(res, x)``, linear in ``x``, with the declared transpose
# ``trans(res, ct)``. A linear op with a declared transpose composes with
# forward-mode AD and with repeated differentiation, which the force task
# runs in every step (grad-over-grad: the outer parameter gradient
# linearizes the inner position gradient; a ``custom_vjp`` rejects that jvp).
_linear = jax.custom_derivatives.linear_call


def _gather_rows(res, x):
    """The forward of the transposable gather: ``x[res[0]]``."""
    return gather(x, res[0])


def gather(values: jax.Array, indices: jax.Array) -> jax.Array:
    """values[indices] — the edge-endpoint gather ([N, F] + [E] -> [E, F]).

    ``mode="clip"``: the packers emit only in-range indices (padding slots
    are self-loops or slot 0; data/invariants.py, pinned by
    tests/test_batching.py), and ``jnp.take``'s default ``mode="fill"``
    pays for the case that cannot happen with a ``select_n`` that re-reads
    and re-writes the whole gathered [E, F] (171 us of an 881 us forward
    gather phase at E = 287k on v5e; PERF.md §5). Clip is XLA's own clamp
    of the start index: identical values for every in-range index.
    """
    return jnp.take(values, indices, axis=0, mode="clip")


def gather_slot_major(
    nodes: jax.Array,  # [N, F]
    neighbors: jax.Array,  # [N*M] i32, dense layout: node n owns [n*M, (n+1)*M)
    dense_m: int,
    in_slots: jax.Array | None = None,  # [N*In] i32 FLAT: edge slots
    #   grouped by neighbour; None -> plain AD
    in_mask: jax.Array | None = None,  # [N, In]: 1 where the entry is an edge
    over_slots: jax.Array | None = None,  # [O] i32 overflow edge slots
    over_nodes: jax.Array | None = None,  # [O] i32 (non-decreasing)
    over_last: jax.Array | None = None,  # [N] i32 end of the node's run
    over_runs: jax.Array | None = None,  # [K]: K is the longest run allowed
) -> jax.Array:
    """``nodes[neighbors]`` as [N, M, F], gathered in SLOT-MAJOR row order.

    Every [N, M, .] tensor of the dense conv is laid out slot-major by the
    TPU compiler (M outermost: fc_full's matmul, BN1 and the gate read
    [M][N][F] slabs), while the flat node-major gather (``e = n*M + m``)
    hands it [N*M, F] rows: each direction then pays a reshape that is a
    real relayout and a transposing copy around the gather. Here the
    *indices* are transposed instead (an [E] s32 transpose, identical for
    every conv of a step, so XLA computes it once): the gather's
    [M*N, F] output already is the [M][N][F] block, its view as
    [M, N, F] is a bitcast, and the ``moveaxis`` back to the model's
    logical [N, M, F] is a choice of layout, not a pass.

    The transpose (given ``in_slots``, the host-precomputed mapping of
    pack_graphs ``in_cap`` / ``over_cap``) mirrors it and holds no scatter
    (``_transpose_cotangent``): the cotangent arrives flattened
    slot-major, ``in_slots``/``over_slots`` are renumbered on the device
    (flat slot ``s = n*M + m`` sits at ``m*N + n``), the gathered
    [In*N, F] is viewed [In, N, F] and the masked sum runs over the OUTER
    axis. With ``over_*`` the mapping has TWO TIERS: the edges of rank
    >= In come through the node-sorted overflow list, a row gather of the
    list's rows of the cotangent, a sum over each node's run of at most K
    adjacent rows (``_run_windows``, ``_run_totals``: a 0/1 matrix times
    the rows, a block of 128 entries at a time; K is ``over_runs``'
    length, a shape) and a row gather of every node's total through
    ``over_last``. The forward is bit-identical to ``gather(nodes,
    neighbors)`` (the same rows); the backward sums the plain gather's
    terms in another association.

    Equivalence to the plain gather's VJP requires the cotangent to be
    ZERO on edge slots missing from the mapping (padding slots). CGConv
    guarantees this: messages are multiplied by ``edge_mask`` and masked
    BatchNorm statistics exclude padding, so no gradient path reaches a
    padded slot's gathered row (the row is a term of ``z``, so its
    cotangent is ``dz`` itself).

    The gather is linear in ``nodes`` and is declared so (``_linear``),
    which is what lets the force task differentiate it twice.

    This is NOT the round-3 "slot-space variant" that measured 19% slower
    (17.2 vs 14.5 ms/step, r3 trace5): that one gathered with
    two-dimensional (node, slot) indices, which changed the gather's
    lowering. This keeps the flat one-dimensional row gather and changes
    only the order of its indices. The COO conv, which holds the flat
    [E, F] form, keeps ``gather``.
    """
    n, m = nodes.shape[0], dense_m

    def to_slot_major(slots):  # flat node-major slot ids -> slot-major
        return (slots % m) * n + slots // m

    def view(flat):  # [M*N, F] -> logical [N, M, F]
        return jnp.moveaxis(flat.reshape(m, n, flat.shape[-1]), 0, 1)

    nbrs_t = neighbors.reshape(n, m).T.reshape(-1)
    if in_slots is None:  # forward-only batches carry no transpose mapping
        return view(gather(nodes, nbrs_t))
    # tier-1 entries reordered [N, In] -> [In, N] as well as renumbered
    slots_t = to_slot_major(in_slots.reshape(in_mask.shape).T.reshape(-1))
    o_win, o_same = (None, None) if over_slots is None else _run_windows(
        to_slot_major(over_slots), over_nodes, over_runs.shape[-1])
    # the [In, N] mask is ``rank < in-degree``: a row's real entries are a
    # prefix (graph.transpose_slots; data/invariants.py checks it). Not
    # ``in_mask.T``: for that XLA relayouts the whole STACKED u8 mask of a
    # scan program once a launch (0.63 ms at [594, 23944, 12] on v5e,
    # PERF.md §6 PR 25), where this reads it as staged.
    in_degree = in_mask.astype(jnp.int32).sum(axis=1)
    mask_t = jnp.arange(in_mask.shape[1])[:, None] < in_degree[None, :]

    def trans(res, ct):  # ct: [M*N, F] slot-major -> [N, F]
        return _transpose_cotangent(ct, *res[1:], degree_axis=0)

    res = (nbrs_t, slots_t, mask_t, o_win, o_same, over_last)
    return view(_linear(_gather_rows, trans, res, nodes))


def _segment_totals(cols, segment_ids: jax.Array, num_segments: int):
    """Each segment's sum of the rows of ``cols`` (arrays [N, F_i] of one
    dtype) -> arrays [G, F_i] in ``promote_types(dtype, float32)``: one
    pass for all of them. Ids outside [0, G) belong to no segment.

    The sum is a matmul by the [N, G] 0/1 matrix ``segment_ids[:, None] ==
    arange(G)``, which XLA builds inside the matmul's fusion (no [N, G]
    array is written), not the scatter-add of ``jax.ops.segment_sum``: a
    scatter costs ~10 ns a row on the v5e whatever it adds and sums in the
    data's own dtype (PERF.md section 6, PR 50). Products with 0 and 1 are
    exact and the sums are float32 or wider (precision ``highest``:
    float32 rows are not rounded to bfloat16 on the way in, bfloat16 rows
    take one pass of the MXU either way), as ``_run_totals``' are.

    The transpose is declared (``_linear``), so it can be taken twice, as
    the force task does: every row takes its segment's row, a row gather
    by ``segment_ids``, a group of columns at a time: a group whose
    cotangent nobody reads (a mean's denominator) is then dead code and
    not a wider row.

    The work grows as N * G * sum(F_i), whatever the order of the ids. On
    the v5e at ``mp.train``'s N = 23,944, G = 576, F = 64 in bfloat16 the
    mean's pass takes 17 us (1.9 GFLOP, 10 us of the MXU at its peak; 1.2
    ps a row and slot) where the two scatters took 466, and nothing is
    measurable at ``ocp.train``'s 5,008 x 40 x 384 (PERF.md section 6,
    PR 50). The packers emit a graph's rows together, so a block of 128
    rows touches a few slots and a banded form (as ``_run_totals``: a
    block's rows times the 0/1 matrix of the slots it touches, at ~5 ns a
    row with its gathers) is due where G x 1.2 ps passes that: a few
    thousand graph slots a batch at F = 64 (the cells' largest has 576).
    """
    rows = jnp.concatenate(cols, axis=1)
    splits = np.cumsum([c.shape[1] for c in cols])[:-1]

    def total(ids, x):
        member = ids[:, None] == jnp.arange(num_segments, dtype=ids.dtype)
        return jnp.einsum(
            "ng,nf->gf", member.astype(x.dtype), x,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.promote_types(x.dtype, jnp.float32),
        )

    def spread(ids, ct):
        inside = ((ids >= 0) & (ids < num_segments))[:, None]
        return jnp.concatenate(
            [jnp.where(inside, gather(c, ids), 0)
             for c in jnp.split(ct.astype(rows.dtype), splits, axis=1)],
            axis=1)

    return jnp.split(_linear(total, spread, segment_ids, rows), splits, axis=1)


def segment_sum(data: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    """Sum ``data``'s rows ([N] or [N, F]) into ``num_segments`` buckets,
    in any order of the ids; rows whose id is out of range are dropped.
    Summed in float32 (or wider) and rounded once to ``data``'s dtype
    (``_segment_totals``)."""
    total, = _segment_totals(
        [data.reshape(data.shape[0], -1)], segment_ids, num_segments)
    return total.astype(data.dtype).reshape(num_segments, *data.shape[1:])


def segment_mean(
    data: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    weights: jax.Array | None = None,
) -> jax.Array:
    """Masked segment mean: sum(w*x)/sum(w); empty segments return 0.

    ``weights`` (e.g. a node mask) keeps padding rows out of both numerator
    and denominator — this is the masked pooling from SURVEY.md §7 "hard
    parts" #3. The denominator is one more column of the numerator's pass
    (``_segment_totals``); the division is float32's (or wider), rounded
    once to ``data``'s dtype.
    """
    if weights is None:
        weights = jnp.ones(data.shape[0], data.dtype)
    else:
        data = data * weights[..., None]
    total, denom = _segment_totals(
        [data, weights[:, None].astype(data.dtype)], segment_ids,
        num_segments)
    return (total / jnp.maximum(denom, 1.0)).astype(data.dtype)


def aggregate_edge_messages(
    messages: jax.Array, centers: jax.Array, num_nodes: int
) -> jax.Array:
    """Sum per-edge messages into per-node accumulators (the COO conv).

    The packers emit edges sorted by centre node (data/invariants.py), so
    XLA is told ``indices_are_sorted`` and no device sort runs.
    """
    return jax.ops.segment_sum(
        messages, centers, num_segments=num_nodes, indices_are_sorted=True
    )
