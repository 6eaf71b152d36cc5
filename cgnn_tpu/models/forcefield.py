"""Differentiable force field: positions -> energy -> forces by autodiff.

BASELINE.json config #5 (MD17 per-atom force head) requires forces. The
reference lineage's data path precomputes distances on the host, which cuts
the autodiff graph at the geometry — so this model recomputes displacement
vectors *inside* the forward pass from positions + neighbor indices +
periodic image offsets (SURVEY.md §7 phase 7). Forces are then exactly
``F = -dE/dr`` and automatically rotation-equivariant, because E depends on
positions only through interatomic distances.

The conv trunk reuses CGConv; only the edge featurization moves in-model.
"""

from __future__ import annotations

import contextlib
from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from cgnn_tpu.data.graph import GraphBatch
from cgnn_tpu.models.cgcnn import CGConv, masked_atom_features
from cgnn_tpu.models.heads import ForceHead
from cgnn_tpu.observe import phases
from cgnn_tpu.ops.segment import gather_slot_major, segment_sum


def gaussian_expand(d: jax.Array, dmin: float, dmax: float, step: float) -> jax.Array:
    """jnp twin of data/featurize.py GaussianDistance (differentiable)."""
    mu = jnp.arange(dmin, dmax + step, step, dtype=d.dtype)
    return jnp.exp(-((d[..., None] - mu) ** 2) / step**2)


def edge_distances(
    batch: GraphBatch, positions: jax.Array, dense_m: int | None = None
) -> jax.Array:
    """Periodic edge distances recomputed from positions (differentiable):
    [E] for a flat COO batch, [N, M] for a dense one (``dense_m``).

    ``positions`` is passed explicitly (not read from the batch) so callers
    can take gradients with respect to it.

    The dense edge-slot layout (pack_graphs ``dense_m``) fixes the centre
    of slot ``e`` as ``e // M`` (data/invariants.py checks it), so the
    geometry is read off that structure in [N, M, .] form instead of off
    the flat index vectors: the centre's position is a broadcast (reverse
    pass: a sum over M), the lattice is looked up once an atom ([N, 3, 3],
    never [E, 3, 3]), and the neighbours' positions go through the conv's
    own transposable gather, whose reverse pass is a row gather by
    ``in_slots`` plus the overflow tier (its rows gathered, each node's run
    of them summed by a 0/1 matmul, the totals gathered through
    ``over_last``: no scatter; ops/segment.py _run_totals). The flat
    form's three [E]-indexed gathers each reverse into an [E]-row scatter
    at 12-byte rows; those and the s32[E] gather ``node_graph[centers]``
    were a fifth of the force step on the chip (PERF.md section 6, PR 28).

    The gather's transpose needs a zero cotangent on padded slots, as in
    the conv: the Gaussians are multiplied by ``edge_mask``, so ``d``'s
    cotangent is zero there in both reverse passes.
    """
    if dense_m is None:
        lat_e = batch.lattices[batch.node_graph[batch.centers]]  # [E, 3, 3]
        shift = jnp.einsum("ek,ekj->ej", batch.edge_offsets, lat_e)
        rel = positions[batch.neighbors] + shift - positions[batch.centers]
    else:
        offsets = batch.edge_offsets.reshape(-1, dense_m, 3)
        shift = jnp.einsum(
            "nmk,nkj->nmj", offsets, batch.lattices[batch.node_graph]
        )
        nbr_pos = gather_slot_major(
            positions, batch.neighbors, dense_m, batch.in_slots,
            batch.in_mask, over_slots=batch.over_slots,
            over_nodes=batch.over_nodes, over_last=batch.over_last,
            over_runs=batch.over_runs,
        )
        rel = nbr_pos + shift - positions[:, None, :]
    # epsilon under the sqrt keeps the gradient finite on masked padding
    # edges (rel == 0); real edges have d >> eps so values are unaffected
    return jnp.sqrt(jnp.sum(rel * rel, axis=-1) + 1e-12)


class ForceFieldCGCNN(nn.Module):
    """CGCNN trunk + per-atom energy readout over in-model edge features."""

    atom_fea_len: int = 64
    n_conv: int = 3
    h_fea_len: int = 64
    dmin: float = 0.0
    dmax: float = 8.0
    step: float = 0.2
    dtype: Any = jnp.float32
    # dense edge-slot layout (data/graph.py pack_graphs dense_m): the
    # scatter-free aggregation applies to the force task too, and the
    # in-model geometry relies on the layout's structure, not only on its
    # order: edge slot e belongs to atom e // M (so ``centers`` is never
    # read), ``edge_offsets`` and ``neighbors`` view as [N, M, .], and the
    # batch's transpose mapping (in_slots / over_*) serves the position
    # gather as it serves the conv's (edge_distances). Requires batches
    # packed with the same dense_m (invariants.check_batch).
    dense_m: int | None = None

    @nn.compact
    def __call__(
        self,
        batch: GraphBatch,
        positions: jax.Array | None = None,
        train: bool = False,
    ) -> jax.Array:
        """-> per-graph total energies [G] (padding slots zero).

        ``positions`` defaults to ``batch.positions``; the force path passes
        it explicitly so it can differentiate with respect to it.
        """
        if positions is None:
            positions = batch.positions
        # float32 means float32: left to its default the TPU rounds the
        # operands of a float32 matmul to bfloat16, and the forces, a
        # derivative of the energies, and the gradient through them, a
        # second one, then read like the bfloat16 trunk's (0.5-0.9% off the
        # float32 forces against 0.8-1.6%; PERF.md section 2, PR 27). So too
        # the image shifts of edge_distances: a 17 A lattice vector rounded
        # to bfloat16 is 0.03 A off. Every dot traced in here, and the
        # reverse passes' transposes of it, carry the precision.
        precise = (
            jax.default_matmul_precision("highest")
            if jnp.dtype(self.dtype) == jnp.float32
            else contextlib.nullcontext()
        )
        with precise:
            with jax.named_scope(phases.EDGE_GEOM):
                # geometry in the positions' own float32, the cast to the
                # trunk's dtype last: a distance rounded to bfloat16 first
                # (1/32 A apart between 4 and 8 A, against filters 0.2 A
                # wide) puts every Gaussian, and the force read off its
                # slope, at another distance than the frame's
                d = edge_distances(batch, positions, self.dense_m)
                edge_mask = batch.edge_mask
                if self.dense_m is not None:
                    # the conv reads [N, M, K], the shape the dense
                    # distances come out in
                    edge_mask = edge_mask.reshape(d.shape)
                edge_fea = gaussian_expand(d, self.dmin, self.dmax, self.step)
                edge_fea = (edge_fea * edge_mask[..., None]).astype(self.dtype)
            with jax.named_scope(phases.EMBED):
                nodes = nn.Dense(
                    self.atom_fea_len, dtype=self.dtype, name="embedding"
                )(masked_atom_features(batch, self.dtype))
                nodes = nodes * batch.node_mask[:, None].astype(nodes.dtype)
            for i in range(self.n_conv):
                nodes = CGConv(
                    features=self.atom_fea_len,
                    dtype=self.dtype,
                    # BatchNorm breaks train/eval force consistency (see
                    # CGConv)
                    use_batchnorm=False,
                    node_norm="none",
                    dense_m=self.dense_m,
                    name=f"conv_{i}",
                )(
                    nodes,
                    edge_fea,
                    batch.centers,
                    batch.neighbors,
                    batch.edge_mask,
                    batch.node_mask,
                    train=train,
                    # dense two-tier transpose slots (None on COO / in_cap=0
                    # batches -> CGConv falls back to the plain gather)
                    in_slots=batch.in_slots,
                    in_mask=batch.in_mask,
                    over_slots=batch.over_slots,
                    over_nodes=batch.over_nodes,
                    over_last=batch.over_last,
                    over_runs=batch.over_runs,
                )
            with jax.named_scope(phases.FORCE_READOUT):
                atom_energy = ForceHead(
                    h_fea_len=self.h_fea_len, dtype=self.dtype
                )(nodes, batch.node_mask)
                per_graph = segment_sum(
                    atom_energy.astype(jnp.float32), batch.node_graph,
                    batch.graph_capacity,
                )
                return per_graph * batch.graph_mask


def energy_and_forces(
    model: ForceFieldCGCNN, variables, batch: GraphBatch, train: bool = False
):
    """(energies [G], forces [N, 3], new_batch_stats) with F = -dE/dr.

    ``new_batch_stats`` is None in eval mode; in train mode it carries the
    updated BatchNorm running statistics for the caller's state update.
    """

    def total_energy(pos):
        if train:
            e, mutated = model.apply(
                variables, batch, pos, train=True, mutable=["batch_stats"]
            )
            # the trunk is BatchNorm-free (see CGConv.use_batchnorm), so the
            # mutated collection is typically empty
            return jnp.sum(e), (e, mutated.get("batch_stats", {}))
        e = model.apply(variables, batch, pos, train=False)
        return jnp.sum(e), (e, None)

    (_, (energies, new_stats)), grad_pos = jax.value_and_grad(
        total_energy, has_aux=True
    )(batch.positions)
    forces = -grad_pos * batch.node_mask[:, None]
    return energies, forces, new_stats
