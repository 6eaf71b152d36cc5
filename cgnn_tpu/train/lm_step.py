"""The block-diffusion language-model step (models/sdar.py), with the same
carry as every task's: ``(state, batch) -> (state, metrics)``, metrics as
sums so that an epoch's are exact.

The loss is BD3-LM's, read at the noised half only:

    -(1 / L) sum_i 1[x_t^i = MASK] (1 / t_b(i)) log p(x_0^i | x_t, x_0)

a sequence, the mean over a step's sequences; ``1[..] / t`` is staged with
the batch (``loss_weight``). The softmax is over the vocabulary slice held.
The model's ``apply`` returns each sequence's loss (it computes it a sequence
at a time, models/sdar.py); the step differentiates their mean.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from cgnn_tpu.data.tokens import TokenBatch
from cgnn_tpu.observe import phases
from cgnn_tpu.ops import moe
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


def make_lm_train_step(cfg, tiles: tuple[int, int] | None = None) -> Callable:
    """``cfg`` is the model's (``models.sdar.SdarConfig``); ``tiles`` the
    attention's (live, grid) tiles a head and a sequence, counted into the
    metrics where given."""

    def train_step(state: TrainState, batch: TokenBatch):
        def loss_with_aux(params):
            losses, *routed = state.apply_fn(
                {"params": params}, batch, train=True)
            return losses.mean(), (losses.sum(), routed)

        (_, (loss_sum, routed)), grads = jax.value_and_grad(
            loss_with_aux, has_aux=True)(state.params)
        with jax.named_scope(phases.OPTIMIZER):
            new_state = state.apply_gradients(grads, state.batch_stats)
        return new_state, step_metrics(cfg, batch, loss_sum, routed, tiles)

    return train_step


def make_lm_eval_step(cfg, tiles: tuple[int, int] | None = None) -> Callable:
    def eval_step(state: TrainState, batch: TokenBatch):
        losses, *routed = state.apply_fn(state.variables(), batch,
                                         train=False)
        return step_metrics(cfg, batch, losses.sum(), routed, tiles)

    return eval_step


def step_metrics(cfg, batch: TokenBatch, loss_sum, routed, tiles) -> dict:
    """``routed``: the model's ``(group_sizes, rungs)``."""
    with jax.named_scope(phases.LM_HEAD):
        s, n = batch.tokens.shape
        metrics = {
            "loss_sum": loss_sum, "count": jnp.float32(s),
            "masked_tokens_sum": (batch.loss_weight > 0).sum().astype(
                jnp.float32),
            **routing_metrics(*routed, cfg.experts_held,
                              cfg.num_experts_per_tok, s * n),
        }
        if tiles is not None:
            heads = cfg.num_attention_heads * cfg.num_hidden_layers * s
            metrics["bd_tiles_live_sum"] = jnp.float32(heads * tiles[0])
            metrics["bd_tiles_grid_sum"] = jnp.float32(heads * tiles[1])
        # each of these is a step's own number, not a sequence's: its mean
        # over an epoch divides by the steps
        for name in [m for m in metrics if m.endswith("_sum")
                     and m != "loss_sum"]:
            metrics[name[: -len("_sum")] + "_count"] = jnp.float32(1.0)
        return metrics
