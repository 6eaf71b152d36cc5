"""CGCNN in Flax: edge-gated graph convolution over dense edge slots.

Reference semantics (SURVEY.md §2 component 6, §3.3) per conv layer:

    z      = cat(v_i, v_j, e_ij)           # per edge
    z      = BatchNorm(Linear(z))          # 2F+G -> 2F, BN over edges
    gate, core = split(z)
    msg    = sigmoid(gate) * softplus(core)
    agg_i  = sum_j msg_ij                  # per-node sum
    v_i'   = softplus(v_i + BatchNorm(agg_i))   # or LayerNorm, or none

and the full model: Linear(92->F) embedding, n_conv such layers, per-crystal
mean pooling, softplus MLP head (LogSoftmax head for classification).

TPU-first design choices:
- dense edge-slot layout (``dense_m``: node n owns slots [n*M, (n+1)*M)),
  as the reference's [N, M] neighbour tensors but over bucketed, padded
  batches: the aggregation is a sum over M and the v_i term a broadcast, so
  the forward has no scatter. fc_full is linear, so both of its node terms
  are matmuls over [N, F], not over edges: the neighbour term is projected
  first (nodes @ K_j, [N, 2F]) and THEN gathered, in the slot-major row
  order the compiler lays [N, M, .] tensors out in; the gathered [N, M, 2F]
  block is a term of z, and the gather's transpose is a second gather, of
  dz's rows, through a mapping the packer precomputes (_SplitFcFull;
  ops/segment.py gather_slot_major): M rows a node summed over M, and for
  the ~7% of edges beyond a node's first M incoming ones a sorted list
  whose runs are summed a block at a time on the MXU and read back by one
  row gather a node. No direction of the conv holds a scatter. The only
  per-edge matmul is the edge term e @ K_e. This is the body every
  benchmark cell runs;
- a flat COO body (gather + segment-sum over sorted centres) for batches
  packed without ``dense_m``: the reference the tests hold the dense body
  to (``--layout coo``);
- masked BatchNorm / pooling so static-shape padding never leaks into
  statistics (SURVEY.md §7 hard parts #1, #3);
- optional bfloat16 compute for the MXU, float32 params and statistics.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from flax import linen as nn

from cgnn_tpu.data.graph import GraphBatch
from cgnn_tpu.observe import phases
from cgnn_tpu.ops.norm import MaskedBatchNorm, MaskedLayerNorm
from cgnn_tpu.ops.segment import (
    aggregate_edge_messages,
    gather,
    gather_slot_major,
    segment_mean,
)


class _SplitFcFull(nn.Module):
    """``fc_full`` (Linear 2F+G -> 2F) with both node terms computed per NODE.

    Parameter shapes/names are EXACTLY nn.Dense(2F) on the concatenated
    [v_i, v_j, e] input — checkpoints and oracle weight transplants are
    unchanged — but the [N, M, 2F+G] concat is never materialized and
    neither node slice of the kernel meets a gathered row:

        z = (v_i @ K_i)[:, None, :] + gather_rows(nodes @ K_j) + e @ K_e + b

    The centre term broadcasts over M. The neighbour term is projected
    BEFORE the gather, ``nodes[nbr] @ K_j == (nodes @ K_j)[nbr]`` row for
    row: what is gathered is the projected [N, 2F] (rows of 2F, not F),
    the gathered [N, M, 2F] block is a term of ``z``, and the same kernel
    is applied once a node instead of once for each of the ~M edges that
    point at it. In the reverse passes ``dz`` itself goes through the
    gather's transpose (no residual: the add keeps none, and the [N, M, F]
    ``v_j`` that the weight gradient contracted over E is never built);
    the matmuls left, ``dp @ K_j^T`` and ``nodes^T @ dp``, are per node.
    The only per-edge matmul is the edge term (contraction G). Measured:
    the concat write + read was the largest single HBM cost of the step
    (trace r3); a row gather costs by the row, not by the byte, up to
    512-byte rows (PERF.md section 5, PR 30).
    """

    features: int
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, v_i, nodes, e, gather_rows):
        # v_i [N', F]: the centres' rows (``nodes``, or this shard's strip
        # of them); nodes [N, F]: the rows neighbours are read from;
        # e [N', M, G]; gather_rows: [N, 2F] -> [N', M, 2F], linear
        f, g = v_i.shape[-1], e.shape[-1]
        kernel = self.param(
            "kernel",
            nn.initializers.lecun_normal(),
            (2 * f + g, self.features),
            jnp.float32,
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.features,), jnp.float32
        )
        k = kernel.astype(self.dtype)
        z = (
            (v_i.astype(self.dtype) @ k[:f])[:, None, :]
            + gather_rows(nodes.astype(self.dtype) @ k[f : 2 * f])
            + e.astype(self.dtype) @ k[2 * f :]
        )
        return z + bias.astype(self.dtype)


# CGConv.node_norm -> the phase of the conv's tail (the normalisation's module
# name says its own; the residual and its softplus are scoped by this)
_TAIL_PHASE = {"batch": phases.CONV_BN2, "layer": phases.CONV_LN,
               "none": phases.CONV_AGGREGATE}


class CGConv(nn.Module):
    """One edge-gated crystal-graph convolution (reference ``ConvLayer``)."""

    features: int
    dtype: Any = jnp.float32
    # bn1, the BatchNorm over a batch's edges. BatchNorm makes per-edge
    # outputs depend on batch statistics; for energy models that's the
    # reference semantics, but a force field must NOT use it: F = -dE/dr
    # picks up gradient terms through the batch moments in train mode that
    # vanish under running stats at eval, so the learned forces disagree
    # between modes (measured: eval force MAE ~5x worse).
    use_batchnorm: bool = True
    # the normalisation of the per-node sum, independent of bn1's: 'batch'
    # (bn2, the lineage's), 'layer' (LayerNorm over a node's own F features,
    # the Open Catalyst baseline's: no batch statistic, nothing to mask but
    # the padded rows' output) or 'none' (the force model)
    node_norm: str = "batch"
    # dense slot layout (pack_graphs dense_m): node n owns edge slots
    # [n*M, (n+1)*M). Aggregation becomes a plain sum over M — no scatter
    # in the forward, and its transpose is a broadcast — and the per-edge
    # v_i gather becomes a broadcast. On v5e this path removes the XLA
    # scatter that runs ~50x below HBM bandwidth (the CUDA atomicAdd
    # analog of SURVEY.md §2 N2, solved the TPU way: layout, not atomics).
    dense_m: int | None = None

    @nn.compact
    def __call__(
        self,
        nodes: jax.Array,  # [N, F]
        edges: jax.Array,  # [E, G]
        centers: jax.Array,  # [E]
        neighbors: jax.Array,  # [E]
        edge_mask: jax.Array,  # [E]
        node_mask: jax.Array,  # [N]
        train: bool = False,
        in_slots: jax.Array | None = None,  # [N*In] i32 flat transpose of
        #   neighbors (pack_graphs stores it flat; gather_slot_major wants
        #   flat indices — the on-device 2-D->1-D reshape costs a relayout)
        in_mask: jax.Array | None = None,  # [N, In]
        over_slots: jax.Array | None = None,  # [O] two-tier overflow
        over_nodes: jax.Array | None = None,  # [O]
        over_last: jax.Array | None = None,  # [N] end of each node's run
        over_runs: jax.Array | None = None,  # [K] (its length: run cap)
    ) -> jax.Array:
        f = self.features
        if self.dense_m is not None:
            m = self.dense_m
            n = nodes.shape[0]

            def gather_rows(p):  # [N, 2F], projected -> [N, M, 2F]
                # rows gathered in the slot-major order z, BN1 and the gate
                # are laid out in, so no reshape or relayout pass over
                # [E, 2F] surrounds the gather in either direction. With the
                # packed transpose mapping the backward is scatter-free
                # (two-tier when the batch carries overflow slots); eval
                # batches carry none and take the same path without one.
                # (The round-3 "slot-space variant", 19% slower, was another
                # thing: 2-D index gathers — ops/segment.gather_slot_major.)
                with jax.named_scope(phases.CONV_GATHER):
                    return gather_slot_major(
                        p, neighbors, m, in_slots, in_mask,
                        over_slots=over_slots, over_nodes=over_nodes,
                        over_last=over_last, over_runs=over_runs,
                    )

            with jax.named_scope(phases.CONV_FC_FULL):
                # dense batches carry edges pre-shaped [N, M, G] (pack_graphs)
                e = edges.astype(nodes.dtype)
                if e.ndim == 2:  # direct pack_graphs callers with flat edges
                    e = e.reshape(n, m, -1)
                # both node terms per node; the neighbour term is gathered
                # already projected (the innermost scope names the phase)
                z = _SplitFcFull(2 * f, dtype=self.dtype, name="fc_full")(
                    nodes, nodes, e, gather_rows
                )
            if self.use_batchnorm:
                # 3-D BN: statistics over the (N, M) slot axes directly —
                # flattening to [N*M, 2F] costs a real layout-change copy
                z = MaskedBatchNorm(dtype=self.dtype, name="bn1")(
                    z, mask=edge_mask.reshape(n, m),
                    use_running_average=not train,
                )
            with jax.named_scope(phases.CONV_GATE):
                gate, core = jnp.split(z, 2, axis=-1)
                msg = nn.sigmoid(gate) * nn.softplus(core)
                # LOAD-BEARING for gradients, not just values:
                # gather_slot_major's scatter-free VJP assumes zero
                # cotangent on padding edge slots, which THIS mask
                # (together with masked BN statistics) guarantees.
                # Removing it would silently corrupt node gradients
                # (ops/segment.py gather_slot_major docstring; parity
                # test: tests/test_batching.py two-tier backward).
                msg = msg * edge_mask.reshape(n, m, 1).astype(msg.dtype)
            with jax.named_scope(phases.CONV_AGGREGATE):
                agg = msg.sum(axis=1)
        else:
            with jax.named_scope(phases.CONV_GATHER):
                v_i = gather(nodes, centers)
                v_j = gather(nodes, neighbors)
                z = jnp.concatenate(
                    [v_i, v_j, edges.astype(nodes.dtype)], axis=-1)
            z = nn.Dense(2 * f, dtype=self.dtype, name="fc_full")(z)
            if self.use_batchnorm:
                z = MaskedBatchNorm(dtype=self.dtype, name="bn1")(
                    z, mask=edge_mask, use_running_average=not train
                )
            with jax.named_scope(phases.CONV_GATE):
                gate, core = jnp.split(z, 2, axis=-1)
                msg = nn.sigmoid(gate) * nn.softplus(core)
                msg = msg * edge_mask[:, None].astype(msg.dtype)
            with jax.named_scope(phases.CONV_AGGREGATE):
                agg = aggregate_edge_messages(msg, centers, nodes.shape[0])
        if self.node_norm not in _TAIL_PHASE:
            raise ValueError(f"node_norm must be one of "
                             f"{sorted(_TAIL_PHASE)}, got {self.node_norm!r}")
        if self.node_norm == "batch":
            agg = MaskedBatchNorm(dtype=self.dtype, name="bn2")(
                agg, mask=node_mask, use_running_average=not train
            )
        elif self.node_norm == "layer":
            agg = MaskedLayerNorm(dtype=self.dtype, name="ln")(
                agg, mask=node_mask
            )
        # the residual and its softplus belong to the normalisation's
        # phase: they read its output once more and nothing else. Without
        # one (the force model) what they read is the aggregate's
        with jax.named_scope(_TAIL_PHASE[self.node_norm]):
            out = nn.softplus(nodes + agg)
            return out * node_mask[:, None].astype(out.dtype)


def masked_atom_features(batch: GraphBatch, dtype) -> jax.Array:
    """This step's atom features in the trunk's dtype, masked BEFORE the cast.

    Under full staging the epoch scan slices one batch a step off the
    resident stack. A bare ``batch.nodes.astype(dtype)`` (or, in float32,
    the TPU compiler's own conversion of a matmul's operands) is moved
    before that slice, found loop-invariant and hoisted: a convert of the
    whole stack once a launch (bf16[832, 5400, 92], 1.9 ms a step, 23% of
    the force step on the v5e; PERF.md section 6, PR 27). The multiply by
    this step's mask depends on the slice, so the cast stays behind it.
    That is the compiler's behaviour, not a contract:
    tests/test_tpu_compile.py compiles for the described chip and fails if
    anything the size of the stack is computed.
    """
    return (batch.nodes * batch.node_mask[:, None]).astype(dtype)


class CrystalGraphConvNet(nn.Module):
    """Full CGCNN (reference ``CrystalGraphConvNet``, SURVEY.md §2 component 7).

    Returns [G, num_targets] regression outputs (or [G, num_classes] log-probs
    when ``classification``), one row per graph slot; padding slots are
    zeroed. Use ``target_mask``/``graph_mask`` in the loss.
    """

    atom_fea_len: int = 64
    n_conv: int = 3
    h_fea_len: int = 128
    n_h: int = 1
    num_targets: int = 1
    classification: bool = False
    num_classes: int = 2
    dropout_rate: float = 0.0  # reference applies dropout for classification
    dtype: Any = jnp.float32
    head: nn.Module | None = None  # e.g. MultiTaskHead; replaces fc stack
    dense_m: int | None = None  # dense slot layout (see CGConv.dense_m)
    node_norm: str = "batch"  # after each conv's sum (see CGConv.node_norm)
    # softplus on the pooled vector before conv_to_fc (txie-93/cgcnn has
    # one; the Open Catalyst baseline does not)
    pool_softplus: bool = True

    @nn.compact
    def __call__(
        self, batch: GraphBatch, train: bool = False, return_node_features: bool = False
    ):
        with jax.named_scope(phases.EMBED):
            nodes = nn.Dense(
                self.atom_fea_len, dtype=self.dtype, name="embedding"
            )(masked_atom_features(batch, self.dtype))
            nodes = nodes * batch.node_mask[:, None].astype(nodes.dtype)
        for i in range(self.n_conv):
            nodes = CGConv(
                features=self.atom_fea_len,
                dtype=self.dtype,
                dense_m=self.dense_m,
                node_norm=self.node_norm,
                name=f"conv_{i}",
            )(
                nodes,
                batch.edges,
                batch.centers,
                batch.neighbors,
                batch.edge_mask,
                batch.node_mask,
                train=train,
                in_slots=batch.in_slots,
                in_mask=batch.in_mask,
                over_slots=batch.over_slots,
                over_nodes=batch.over_nodes,
                over_last=batch.over_last,
                over_runs=batch.over_runs,
            )
        # per-crystal masked mean pooling (reference `pooling`)
        with jax.named_scope(phases.POOL_HEAD):
            crys = segment_mean(
                nodes,
                batch.node_graph,
                batch.graph_capacity,
                weights=batch.node_mask.astype(nodes.dtype),
            )
            if self.pool_softplus:
                crys = nn.softplus(crys)
            crys = nn.Dense(
                self.h_fea_len, dtype=self.dtype, name="conv_to_fc"
            )(crys)
            crys = nn.softplus(crys)
            if self.classification and self.dropout_rate > 0:
                crys = nn.Dropout(
                    self.dropout_rate, deterministic=not train)(crys)
            if self.head is not None:
                out = self.head(crys)
            else:
                for i in range(self.n_h - 1):
                    crys = nn.softplus(nn.Dense(
                        self.h_fea_len, dtype=self.dtype, name=f"fc_{i}"
                    )(crys))
                out_dim = (self.num_classes if self.classification
                           else self.num_targets)
                out = nn.Dense(out_dim, dtype=self.dtype, name="fc_out")(crys)
                if self.classification:
                    out = nn.log_softmax(out, axis=-1)
            out = out * batch.graph_mask[:, None].astype(out.dtype)
            # promote low-precision (bf16) compute back to f32; keep f64 as-is
            out = out.astype(jnp.promote_types(jnp.float32, out.dtype))
        if return_node_features:
            return out, nodes
        return out
