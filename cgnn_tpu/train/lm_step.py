"""The language-model steps (models/sdar.py, models/afmoe.py,
models/lfm2.py, models/nemotron_h.py), with the same carry as every task's:
``(state, batch) -> (state, metrics)``, metrics as sums so that an epoch's
are exact.

The causal loss (models/afmoe.py) is next-token cross-entropy, ``-(1 / L)
sum_i w_i log p(x^{i+1} | x^{<=i})`` a sequence with ``w_i`` 0 where the next
token is another document's or there is none; after the optimizer's step the
routers' selection biases move by the step's counts (``balanced_biases``):
state that no gradient reaches, carried in ``TrainState.batch_stats`` as the
conv models carry BatchNorm's statistics (models/lfm2.py's and
models/nemotron_h.py's biases are fixed: their configs have no
``load_balance_coeff`` and their steps return them as they were). The
block-diffusion loss is BD3-LM's, read at the noised half only:

    -(1 / L) sum_i 1[x_t^i = MASK] (1 / t_b(i)) log p(x_0^i | x_t, x_0)

a sequence, the mean over a step's sequences; ``1[..] / t`` is staged with
the batch (``loss_weight``). The softmax is over the vocabulary slice held.
The model's ``apply`` returns each sequence's loss (it computes it a sequence
at a time); the step differentiates their mean.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from cgnn_tpu.data.tokens import TokenBatch
from cgnn_tpu.models import lm_blocks
from cgnn_tpu.observe import phases
from cgnn_tpu.ops import moe
from cgnn_tpu.ops.masked_attention import kept_bytes
from cgnn_tpu.ops.short_conv import taps_cut
from cgnn_tpu.ops.ssd import ssd_counts
from cgnn_tpu.train.state import TrainState


def routing_metrics(group_sizes, rungs, experts_held: tuple, k: int,
                    positions: int) -> dict:
    """What the routers did in one step, as sums (``group_sizes [layers,
    E]``, ``rungs [layers, S]``): rows that landed on the experts held, the
    rows the rungs that carried them hold (ops/moe.py ``ladder``), the calls
    (a layer and sequence each) that took the last rung, all ``T x k`` rows,
    the rows a balanced router would send the experts held, and the worst
    layer's largest load over the mean."""
    first, count = experts_held
    n_layers, n_experts = group_sizes.shape
    here = jax.lax.dynamic_slice_in_dim(group_sizes, first, count, axis=1)
    load = group_sizes.astype(jnp.float32)
    pairs = positions // rungs.shape[1] * k  # a sequence's (token, choice)s
    capacities = jnp.asarray(moe.ladder(pairs, count, n_experts),
                             jnp.float32)
    return {
        "moe_rows_here_sum": here.sum().astype(jnp.float32),
        "moe_rows_capacity_sum": capacities[rungs].sum(),
        "moe_calls_full_rung_sum": (
            rungs == capacities.shape[0] - 1).sum().astype(jnp.float32),
        "moe_rows_balanced_sum": jnp.float32(
            n_layers * positions * k * count / n_experts),
        "expert_load_max_over_mean_sum": (
            load.max(axis=1) / load.mean(axis=1)).max(),
    }


def balanced_biases(bias, group_sizes, coeff: float):
    """The routers' selection biases after a step (models/afmoe.py): with
    ``n_e`` the step's tokens that chose expert ``e`` of a layer, ``d = coeff
    * sign(mean(n) - n)`` and ``b += d - mean(d)``: an overloaded expert's
    bias falls, and the biases of a layer keep their mean. ``bias [..., E]``
    float32, ``group_sizes`` as many counts."""
    n = group_sizes.astype(jnp.float32).reshape(bias.shape)
    d = coeff * jnp.sign(n.mean(axis=-1, keepdims=True) - n)
    return bias + d - d.mean(axis=-1, keepdims=True)


def _balance_coeff(cfg):
    """The step size of the routers' selection biases, or None for a model
    that keeps none (its config has no ``load_balance_coeff``)."""
    return getattr(cfg, "load_balance_coeff", None)


def make_lm_train_step(cfg, tiles=None) -> Callable:
    """``cfg`` is the model's and says what the step is: its ``objective``
    (``models.sdar.SdarConfig``: ``blockdiff``, BD3-LM's weighted loss;
    ``models.afmoe.AfmoeConfig``: ``causal``, next-token cross-entropy) and,
    where it has a ``load_balance_coeff``, that the step moves the routers'
    biases, which no gradient reaches, after the optimizer's; ``tiles`` the
    attention's tile counts (the model's ``attention_tiles``), counted into
    the metrics where given."""
    coeff = _balance_coeff(cfg)

    def train_step(state: TrainState, batch: TokenBatch):
        def loss_with_aux(params):
            losses, *routed = state.apply_fn(
                {"params": params, "batch_stats": state.batch_stats}, batch,
                train=True)
            return losses.mean(), (losses.sum(), routed)

        (_, (loss_sum, routed)), grads = jax.value_and_grad(
            loss_with_aux, has_aux=True)(state.params)
        with jax.named_scope(phases.OPTIMIZER):
            stats = state.batch_stats
            if coeff is not None:
                stats = {"router_bias": balanced_biases(
                    stats["router_bias"], routed[0], coeff)}
            new_state = state.apply_gradients(grads, stats)
        return new_state, step_metrics(cfg, batch, loss_sum, routed, tiles,
                                       new_state.batch_stats)

    return train_step


def make_lm_eval_step(cfg, tiles=None) -> Callable:
    def eval_step(state: TrainState, batch: TokenBatch):
        losses, *routed = state.apply_fn(state.variables(), batch,
                                         train=False)
        return step_metrics(cfg, batch, losses.sum(), routed, tiles,
                            state.batch_stats)

    return eval_step


def step_metrics(cfg, batch: TokenBatch, loss_sum, routed, tiles,
                 stats=None) -> dict:
    """``routed``: the model's ``(group_sizes, rungs)``; ``stats`` the
    state's ``batch_stats`` (the routers' biases where the model has them).
    By ``cfg.objective``: a ``blockdiff`` step counts its masked tokens and
    the mask's tiles (``tiles`` = (live, grid) a head and a sequence, every
    layer alike); a ``causal`` one its weighted tokens and the tiles of each
    kind of layer (``tiles`` = {kind: (live, grid, layers)}). Of ``tiles``
    the grid is counted; the live tiles are the step's own, those that the
    mask and the batch's documents leave the kernel (``cfg.live_tiles``;
    ``ops/masked_attention.py``). Either counts
    the bytes its layers' checkpoints keep of the attention
    (``lm_blocks.by_sequence``) and the (sequence, layer, operand) calls of
    ``lm_blocks.prepare_heads``, all and those that took the kernel, from
    the shapes, over the layers that are attention by kind
    (``cfg.n_attention_layers``; a model whose attention layers prepare no
    heads says so, ``heads_prepared_a_layer``); a model with convolution
    layers (``cfg.n_conv_layers``) counts their positions and, of these
    positions' two earlier taps each, the taps a document's or the
    sequence's start cut (``ops/short_conv.py``); one with state-space
    layers (``cfg.n_ssm_layers``) their positions, the document starts at
    which a state and a filter start empty, the scan's chunks and those a
    document's start falls inside (``ops/ssd.py`` ``ssd_counts``)."""
    with jax.named_scope(phases.LM_HEAD):
        s, n = batch.tokens.shape
        causal = cfg.objective == "causal"
        weighted = (batch.loss_weight > 0).sum().astype(jnp.float32)
        metrics = {
            "loss_sum": loss_sum, "count": jnp.float32(s),
            ("weighted_tokens_sum" if causal else "masked_tokens_sum"):
                weighted,
            **routing_metrics(*routed, cfg.experts_held,
                              cfg.num_experts_per_tok, s * n),
        }
        if _balance_coeff(cfg) is not None:
            metrics["expert_bias_abs_max_sum"] = jnp.abs(
                stats["router_bias"]).max()
        attn_layers = cfg.n_attention_layers
        metrics["attn_kept_bytes_sum"] = jnp.float32(
            attn_layers * s * kept_bytes(
                cfg.num_attention_heads, n, cfg.head_dim, cfg.compute_dtype))
        # q and k, where the model's attention prepares its heads
        prepared = cfg.heads_prepared_a_layer * attn_layers * s
        metrics["heads_prepared_sum"] = jnp.float32(prepared)
        metrics["heads_prepared_fused_sum"] = jnp.float32(
            prepared * lm_blocks.heads_fused(n, cfg.head_dim))
        if cfg.n_conv_layers:
            metrics["sconv_positions_sum"] = jnp.float32(
                cfg.n_conv_layers * s * n)
            metrics["sconv_taps_cut_sum"] = cfg.n_conv_layers * taps_cut(
                batch.segment_ids).astype(jnp.float32)
        if cfg.n_ssm_layers:
            met = ssd_counts(batch.segment_ids, cfg.chunk_size)
            metrics["ssm_positions_sum"] = jnp.float32(
                cfg.n_ssm_layers * s * n)
            for name in ("resets", "chunks", "chunks_cut"):
                metrics[f"ssm_{name}_sum"] = cfg.n_ssm_layers * met[
                    name].astype(jnp.float32)
        if tiles is not None:
            for kind, (live, layers) in cfg.live_tiles(
                    batch.segment_ids).items():
                stem, grid = ((f"attn_{kind}_tiles", tiles[kind][1]) if causal
                              else ("bd_tiles", tiles[1]))
                calls = cfg.num_attention_heads * layers  # a sequence
                metrics[stem + "_live_sum"] = calls * live.sum().astype(
                    jnp.float32)
                metrics[stem + "_grid_sum"] = jnp.float32(calls * s * grid)
        # each of these is a step's own number, not a sequence's: its mean
        # over an epoch divides by the steps
        for name in [m for m in metrics if m.endswith("_sum")
                     and m != "loss_sum"]:
            metrics[name[: -len("_sum")] + "_count"] = jnp.float32(1.0)
        return metrics
