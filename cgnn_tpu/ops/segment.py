"""Gathers, their declared transposes, and segment reductions.

The reference's hottest device loop is the per-edge gather + per-node
sum inside its conv layer (SURVEY.md §3.3): on GPU it is ATen
``index_select`` + ``sum(dim=1)``. Here the dense conv gathers through
``gather_slot_major`` (a row gather whose transpose is a second row
gather through the packer's mapping; the sum over M needs no op of its
own; the rows it moves are the nodes' fc_full projections, 2F wide, and
the force model's positions: models/cgcnn.py _SplitFcFull),
the node-strip sharded conv through ``gather_transpose``, and the
flat COO conv through ``gather`` and ``aggregate_edge_messages``, a
``segment_sum`` over the sorted centres the packers emit. XLA compiles
all of it; there is no hand-written kernel.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _transpose_cotangent(ct, slots, msk, o_slots, o_nodes, o_mask,
                         num_nodes: int, degree_axis: int = 1):
    """The shared cotangent transpose ([E, F] -> [N, F]) — ONE body for
    both row orders.

    ``slots`` is flat and ``msk`` says how the gathered rows are viewed:
    [N, In] (node-major, ``degree_axis`` 1) or [In, N] (slot-major,
    ``degree_axis`` 0: the sum is In slab adds, no sublane reduce).
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
    if o_slots is not None:
        rows = gather(ct, o_slots) * o_mask[:, None].astype(ct.dtype)
        grad = grad + jax.ops.segment_sum(
            rows, o_nodes, num_segments=num_nodes, indices_are_sorted=True,
        )
    return grad


# ``fwd(res, x)``, linear in ``x``, with the declared transpose
# ``trans(res, ct)``. A linear op with a declared transpose composes with
# forward-mode AD and with repeated differentiation, which the force task
# runs in every step (grad-over-grad: the outer parameter gradient
# linearizes the inner position gradient; a ``custom_vjp`` rejects that jvp).
_linear = jax.custom_derivatives.linear_call


def _gather_rows(res, x):
    """The forward of both transposable gathers: ``x[res[0]]``."""
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


def gather_transpose(
    nodes: jax.Array,  # [N, F]
    neighbors: jax.Array,  # [E] i32
    in_slots: jax.Array,  # [N*In] i32 FLAT — edge slots grouped by neighbor
    in_mask: jax.Array,  # [N, In] — 1 where the slot entry is a real edge
    over_slots: jax.Array | None = None,  # [O] i32 overflow edge slots
    over_nodes: jax.Array | None = None,  # [O] i32 (non-decreasing)
    over_mask: jax.Array | None = None,  # [O]
) -> jax.Array:
    """``nodes[neighbors]`` with a SCATTER-FREE (or scatter-light) backward.

    The forward is the plain neighbor gather. Its autodiff backward is a
    scatter-add of the [E, F] cotangent into [N, F] — the same XLA scatter
    the dense edge-slot layout removed from the forward aggregation (it
    runs ~50x below HBM bandwidth on TPU). Given the host-precomputed
    transpose mapping ``in_slots`` (pack_graphs ``in_cap``/``over_cap``),
    the backward becomes gather(ct, in_slots) + masked sum over the
    in-degree axis — a row gather plus a dense reduction, both
    full-bandwidth ops.

    TWO-TIER mode (``over_*`` given; pack_graphs ``over_cap``): tier 1 is
    [N, M] (no in-degree padding — the [N, 2M] single-tier gather was the
    step's largest single op at mean in-degree M, half padding bytes), and
    the ~7% of edges with rank >= M arrive via a node-sorted segment-sum
    over the small overflow list — a scatter 15x smaller than the one this
    path replaces.

    Equivalence to the plain gather's VJP requires the cotangent to be
    zero on edge slots missing from the mapping (padding slots). CGConv
    guarantees this: messages are multiplied by ``edge_mask`` and masked
    BatchNorm statistics exclude padding, so no gradient path reaches a
    padded slot's gathered row (the row is a term of ``z``, so its
    cotangent is ``dz`` itself).

    The gather is linear in ``nodes`` and is declared so (``_linear``),
    which is what lets the force task differentiate it twice.
    """
    num_nodes = nodes.shape[0]

    def trans(res, ct):  # ct: [E, F] -> [N, F]
        return _transpose_cotangent(ct, *res[1:], num_nodes)

    res = (neighbors, in_slots, in_mask, over_slots, over_nodes, over_mask)
    return _linear(_gather_rows, trans, res, nodes)


def gather_slot_major(
    nodes: jax.Array,  # [N, F]
    neighbors: jax.Array,  # [N*M] i32, dense layout: node n owns [n*M, (n+1)*M)
    dense_m: int,
    in_slots: jax.Array | None = None,  # as gather_transpose; None -> plain AD
    in_mask: jax.Array | None = None,
    over_slots: jax.Array | None = None,
    over_nodes: jax.Array | None = None,
    over_mask: jax.Array | None = None,
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

    The transpose (given ``in_slots``; same contract and two-tier mapping
    as ``gather_transpose``) mirrors it: the cotangent arrives flattened
    slot-major, ``in_slots``/``over_slots`` are renumbered on the device
    (flat slot ``s = n*M + m`` sits at ``m*N + n``), the gathered
    [In*N, F] is viewed [In, N, F] and the masked sum runs over the OUTER
    axis. The forward is bit-identical to ``gather_transpose`` (the same
    rows); the backward sums the same terms in another association.

    This is NOT the round-3 "slot-space variant" that measured 19% slower
    (17.2 vs 14.5 ms/step, r3 trace5): that one gathered with
    two-dimensional (node, slot) indices, which changed the gather's
    lowering. This keeps the flat one-dimensional row gather and changes
    only the order of its indices.

    The callers that hold the flat [E, F] form keep ``gather_transpose``
    (the node-strip sharded dense conv) and ``gather`` (the COO conv).
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
    o_slots_t = None if over_slots is None else to_slot_major(over_slots)
    # the [In, N] mask is ``rank < in-degree``: a row's real entries are a
    # prefix (graph.transpose_slots; data/invariants.py checks it). Not
    # ``in_mask.T``: for that XLA relayouts the whole STACKED u8 mask of a
    # scan program once a launch (0.63 ms at [594, 23944, 12] on v5e,
    # PERF.md §6 PR 25), where this reads it as staged.
    in_degree = in_mask.astype(jnp.int32).sum(axis=1)
    mask_t = jnp.arange(in_mask.shape[1])[:, None] < in_degree[None, :]

    def trans(res, ct):  # ct: [M*N, F] slot-major -> [N, F]
        return _transpose_cotangent(ct, *res[1:], n, degree_axis=0)

    res = (nbrs_t, slots_t, mask_t, o_slots_t, over_nodes, over_mask)
    return view(_linear(_gather_rows, trans, res, nodes))


def segment_sum(data: jax.Array, segment_ids: jax.Array, num_segments: int) -> jax.Array:
    """Sum ``data`` rows into ``num_segments`` buckets (deterministic on TPU)."""
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def segment_mean(
    data: jax.Array,
    segment_ids: jax.Array,
    num_segments: int,
    weights: jax.Array | None = None,
) -> jax.Array:
    """Masked segment mean: sum(w*x)/sum(w); empty segments return 0.

    ``weights`` (e.g. a node mask) keeps padding rows out of both numerator
    and denominator — this is the masked pooling from SURVEY.md §7 "hard
    parts" #3.
    """
    if weights is not None:
        data = data * weights[..., None]
        denom = segment_sum(weights, segment_ids, num_segments)
    else:
        denom = segment_sum(jnp.ones(data.shape[0], data.dtype), segment_ids, num_segments)
    total = segment_sum(data, segment_ids, num_segments)
    return total / jnp.maximum(denom, 1.0)[..., None]


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
