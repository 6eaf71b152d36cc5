"""Jitted train/eval steps for the force-field task (BASELINE config #5).

The loss is the standard energy+force composite used for ML force fields:

    L = w_e * MSE(E_norm) + w_f * MSE(F / std)

Energies are normalized with the target Normalizer (mean/std over training
energies); force labels are scaled by 1/std so predicted forces — which are
``-d(E_norm)/dr`` up to the same 1/std factor — live on a matching scale.
Metrics report both MAEs in ORIGINAL units.

The step differentiates twice: an inner ``jax.grad`` over positions produces
forces inside the loss, and the outer ``value_and_grad`` over params
backpropagates through that force computation (second-order mixed
derivatives, handled natively by JAX). The reference lineage cannot express
this — its data path precomputes distances on the host, severing the
autodiff graph at the geometry (SURVEY.md §7 phase 7).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from cgnn_tpu.data.graph import GraphBatch
from cgnn_tpu.observe import phases
from cgnn_tpu.train.state import TrainState


def force_loss(
    energies: jax.Array,
    forces: jax.Array,
    batch: GraphBatch,
    normalizer,
    w_energy: float = 1.0,
    w_force: float = 10.0,
):
    """Composite masked loss; metrics as (sum, count) pairs in original units."""
    std = normalizer.std[0]
    e_norm_target = normalizer.norm(batch.targets)[:, 0]
    gw = batch.graph_mask
    n_g = jnp.maximum(gw.sum(), 1.0)
    e_se = (energies - e_norm_target) ** 2 * gw
    e_loss = e_se.sum() / n_g

    f_target_scaled = batch.node_targets / std
    nw = batch.node_mask[:, None]
    f_se = ((forces - f_target_scaled) ** 2) * nw
    n_f = jnp.maximum(nw.sum() * 3.0, 1.0)
    f_loss = f_se.sum() / n_f

    loss = w_energy * e_loss + w_force * f_loss
    e_ae = jnp.abs(normalizer.denorm(energies[:, None])[:, 0] - batch.targets[:, 0]) * gw
    f_ae = jnp.abs(forces * std - batch.node_targets) * nw
    metrics = {
        "loss_sum": loss * n_g,  # so loss averages like the other tasks
        "mae_sum": e_ae.sum(),
        "count": gw.sum(),
        "force_mae_sum": f_ae.sum(),
        "force_mae_count": nw.sum() * 3.0,
    }
    return loss, metrics


def _energy_and_grad_pos(apply_fn, variables, batch, train: bool):
    """(energies [G], dE/dpos [N,3], new_batch_stats) — differentiable in params."""

    def total_energy(pos):
        if train:
            e, mutated = apply_fn(
                variables, batch, pos, train=True, mutable=["batch_stats"]
            )
            return jnp.sum(e), (e, mutated.get("batch_stats", {}))
        e = apply_fn(variables, batch, pos, train=False)
        return jnp.sum(e), (e, None)

    (_, (energies, new_stats)), grad_pos = jax.value_and_grad(
        total_energy, has_aux=True
    )(batch.positions)
    return energies, grad_pos, new_stats


def make_force_train_step(
    w_energy: float = 1.0,
    w_force: float = 10.0,
    axis_name: str | None = None,
    grad_health: bool = False,
) -> Callable:
    """(state, batch) -> (state, metrics); energy+force composite objective.

    ``grad_health`` adds in-graph grad/update-norm and NaN/Inf-count
    metrics (observe.health) — extra outputs only; the update and hence
    the trajectory are identical with it on or off. Especially relevant
    here: the force task's second-order differentiation is the likeliest
    NaN source in the codebase, and under the epoch scan its onset used
    to be invisible until the epoch aggregate came back.
    """

    def train_step(state: TrainState, batch: GraphBatch):
        def loss_with_aux(params):
            variables = {"params": params, "batch_stats": state.batch_stats}
            energies, grad_pos, new_stats = _energy_and_grad_pos(
                state.apply_fn, variables, batch, train=True
            )
            with jax.named_scope(phases.LOSS):
                forces = -grad_pos * batch.node_mask[:, None]
                loss, metrics = force_loss(
                    energies, forces, batch, state.normalizer, w_energy,
                    w_force,
                )
                return loss, (metrics, new_stats)

        (loss, (metrics, new_stats)), grads = jax.value_and_grad(
            loss_with_aux, has_aux=True
        )(state.params)
        if axis_name is not None:
            grads = lax.pmean(grads, axis_name)
            new_stats = lax.pmean(new_stats, axis_name)
            metrics = lax.psum(metrics, axis_name)
        with jax.named_scope(phases.OPTIMIZER):
            new_state = state.apply_gradients(grads, new_stats)
        if grad_health:
            from cgnn_tpu.observe.health import grad_health_metrics

            # per-shard loss under axis_name: reduce before the NaN
            # check (see train.step.make_train_step) — a NaN on any
            # shard must be visible, not just shard 0's value
            health_loss = (
                loss if axis_name is None else lax.pmean(loss, axis_name)
            )
            metrics = metrics | grad_health_metrics(
                grads, state.params, new_state.params, loss=health_loss
            )
        return new_state, metrics

    return train_step


def make_force_eval_step(
    w_energy: float = 1.0,
    w_force: float = 10.0,
    axis_name: str | None = None,
) -> Callable:
    """(state, batch) -> metrics using running BatchNorm statistics."""

    def eval_step(state: TrainState, batch: GraphBatch):
        energies, grad_pos, _ = _energy_and_grad_pos(
            state.apply_fn, state.variables(), batch, train=False
        )
        with jax.named_scope(phases.LOSS):
            forces = -grad_pos * batch.node_mask[:, None]
            _, metrics = force_loss(
                energies, forces, batch, state.normalizer, w_energy, w_force
            )
        if axis_name is not None:
            metrics = lax.psum(metrics, axis_name)
        return metrics

    return eval_step


def make_force_predict_step() -> Callable:
    """(state, batch) -> (energies [G] denormalized, forces [N,3] orig units)."""

    def predict_step(state: TrainState, batch: GraphBatch):
        energies, grad_pos, _ = _energy_and_grad_pos(
            state.apply_fn, state.variables(), batch, train=False
        )
        std = state.normalizer.std[0]
        forces = -grad_pos * batch.node_mask[:, None] * std
        e = state.normalizer.denorm(energies[:, None])[:, 0] * batch.graph_mask
        return e, forces

    return predict_step
