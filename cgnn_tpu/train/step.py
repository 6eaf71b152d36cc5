"""Jitted train/eval steps (SURVEY.md §3.1 hot loop, rebuilt for XLA).

One traced function per (model, task): forward, masked loss, grads, optimizer
update, BatchNorm stat update — all fused by XLA into a single device
program. The same step body runs single-device (plain ``jit``) or
data-parallel (inside ``shard_map`` with ``axis_name='data'`` — grads and
stats are ``pmean``-ed over ICI, metrics ``psum``-ed; cgnn_tpu.parallel).

Metrics are returned as (sum, count) pairs, never means, so cross-device and
cross-batch accumulation is exact.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from cgnn_tpu.data.graph import GraphBatch
from cgnn_tpu.data.rawbatch import RawBatch
from cgnn_tpu.observe import phases
from cgnn_tpu.train.state import TrainState


def _masked_regression(out, batch: GraphBatch, normalizer, penalty):
    """Mean over the real labels of ``penalty(out - normalized target)``;
    metrics in original units.

    Multi-task outputs (T > 1, BASELINE config #3) additionally report one
    MAE per task column, each averaged over its own label count (labels can
    be missing per task via target_mask).
    """
    t_norm = normalizer.norm(batch.targets)
    w = batch.target_mask * batch.graph_mask[:, None]
    cost = penalty(out - t_norm) * w
    n = jnp.maximum(w.sum(), 1.0)
    loss = cost.sum() / n
    ae = jnp.abs(normalizer.denorm(out) - batch.targets) * w
    metrics = {"loss_sum": cost.sum(), "mae_sum": ae.sum(), "count": w.sum()}
    if out.shape[-1] > 1:
        for t in range(out.shape[-1]):
            metrics[f"mae_task{t}_sum"] = ae[:, t].sum()
            metrics[f"mae_task{t}_count"] = w[:, t].sum()
    return loss, metrics


def regression_loss(out, batch: GraphBatch, normalizer):
    """Masked MSE on normalized targets (the lineage's ``nn.MSELoss``)."""
    return _masked_regression(out, batch, normalizer, lambda d: d ** 2)


def l1_regression_loss(out, batch: GraphBatch, normalizer):
    """Masked L1 on normalized targets (the Open Catalyst baselines train
    on the mean absolute error); ``loss_sum`` is then the sum of |d|."""
    return _masked_regression(out, batch, normalizer, jnp.abs)


# train.py --loss -> the regression task's loss
REGRESSION_LOSSES = {"mse": regression_loss, "l1": l1_regression_loss}


def classification_loss(out, batch: GraphBatch, normalizer):
    """NLL over log-probs (reference: NLLLoss after LogSoftmax) + accuracy."""
    labels = batch.targets[:, 0].astype(jnp.int32)
    w = batch.graph_mask
    nll = -jnp.take_along_axis(out, labels[:, None], axis=1)[:, 0] * w
    n = jnp.maximum(w.sum(), 1.0)
    loss = nll.sum() / n
    correct = (jnp.argmax(out, axis=-1) == labels).astype(jnp.float32) * w
    metrics = {"loss_sum": nll.sum(), "correct_sum": correct.sum(), "count": w.sum()}
    return loss, metrics


def make_train_step(
    classification: bool = False,
    axis_name: str | tuple[str, ...] | None = None,
    loss_fn: Callable | None = None,
    loss_scale: float = 1.0,
    pmean_grads: bool = True,
    grad_health: bool = False,
) -> Callable:
    """Build the (state, batch) -> (state, metrics) step body.

    ``axis_name`` activates cross-device reductions (a tuple reduces over
    several mesh axes at once — hierarchical multi-host DP over
    ('dcn', 'data')); only set it when the step runs inside shard_map/vmap
    with those axes bound.

    ``loss_scale`` multiplies the loss before differentiation (metrics are
    unscaled) and ``pmean_grads=False`` skips the explicit grad allreduce —
    both are for a step running under shard_map with replication checking
    ON, where the transpose already psums parameter cotangents over every
    mesh axis: scaling by 1/axis_size turns that sum into the DDP mean. No
    step of the package is built that way (parallel/data_parallel.py runs
    with ``check_vma=False``); ``pmean_grads=False`` is the step without an
    all-reduce that tests/benchmark/test_dp_cell.py holds the cell's check
    against.

    ``grad_health`` adds in-graph grad-norm / update-norm / NaN-Inf-count
    metrics (observe.health) — extra metric OUTPUTS only, computed from
    the applied (post-``pmean``) grads; the update itself is untouched,
    so the training trajectory is identical with it on or off. Not psum-ed
    under ``axis_name``: post-pmean grads are replicated, so the values
    (and their per-step counts of 1) are already consistent across shards.
    """
    compute_loss = loss_fn or (classification_loss if classification else regression_loss)

    def train_step(state: TrainState, batch: GraphBatch):
        rngs = {"dropout": jax.random.fold_in(state.rng, state.step)}

        def loss_with_aux(params):
            out, mutated = state.apply_fn(
                {"params": params, "batch_stats": state.batch_stats},
                batch,
                train=True,
                mutable=["batch_stats"],
                rngs=rngs,
            )
            with jax.named_scope(phases.LOSS):
                loss, metrics = compute_loss(out, batch, state.normalizer)
                return loss * loss_scale, (metrics, mutated["batch_stats"])

        (loss, (metrics, new_stats)), grads = jax.value_and_grad(
            loss_with_aux, has_aux=True
        )(state.params)
        if axis_name is not None:
            # DDP-equivalent: average grads across replicas; running stats are
            # also averaged (stronger than torch DDP, which keeps rank-0's);
            # metric sums add up exactly (the guard reads its loss off them).
            # One scope for every collective of the step, so a device trace
            # can say what the mesh costs (phases.DP_ALLREDUCE)
            with jax.named_scope(phases.DP_ALLREDUCE):
                if pmean_grads:
                    grads = lax.pmean(grads, axis_name)
                new_stats = lax.pmean(new_stats, axis_name)
                metrics = lax.psum(metrics, axis_name)
        with jax.named_scope(phases.OPTIMIZER):
            new_state = state.apply_gradients(grads, new_stats)
        if grad_health:
            from cgnn_tpu.observe.health import grad_health_metrics

            # the raw loss is per-shard under axis_name (unlike the
            # post-pmean grads): reduce it first so a NaN on ANY shard is
            # visible everywhere instead of shard 0's value escaping the
            # shard_map as the replicated output
            health_loss = loss
            if axis_name is not None:
                with jax.named_scope(phases.DP_ALLREDUCE):
                    health_loss = lax.pmean(loss, axis_name)
            metrics = metrics | grad_health_metrics(
                grads, state.params, new_state.params, loss=health_loss
            )
        return new_state, metrics

    return train_step


def make_eval_step(
    classification: bool = False,
    axis_name: str | tuple[str, ...] | None = None,
    loss_fn: Callable | None = None,
) -> Callable:
    """(state, batch) -> metrics, using running BatchNorm statistics."""
    compute_loss = loss_fn or (classification_loss if classification else regression_loss)

    def eval_step(state: TrainState, batch: GraphBatch):
        out = state.apply_fn(state.variables(), batch, train=False)
        with jax.named_scope(phases.LOSS):
            _, metrics = compute_loss(out, batch, state.normalizer)
        if axis_name is not None:
            with jax.named_scope(phases.DP_ALLREDUCE):
                metrics = lax.psum(metrics, axis_name)
        return metrics

    return eval_step


# the train step donates its state carry (argument 0): XLA reuses the
# parameter/optimizer buffers in place instead of allocating a second
# copy per step. TRAIN_STEP_DONATE is the ONE declaration of WHICH
# argument is donated — consumed by jit_train_step (single-device
# bodies), the scan driver, and the data-parallel wrappers in
# parallel/ — and the graftaudit GA-DONATION check verifies XLA
# actually applied the aliasing (analysis/program_audit).
TRAIN_STEP_DONATE = (0,)


def jit_train_step(body: Callable):
    """The canonical jit wrapper for single-device (state, batch) ->
    (state, metrics) train-step bodies.

    ``body`` may be the raw step, guard-wrapped (resilience.guard), or
    telemetry-wrapped (observe) — anything with the train-step carry
    signature. Used by train/loop.py, scripts/hlo_dump.py, and the
    program auditor, so a single-device train step reaches XLA exactly
    one way; the shard_map wrappers in parallel/ go through
    ``jit_sharded_train_step`` and share the TRAIN_STEP_DONATE contract."""
    return jax.jit(body, donate_argnums=TRAIN_STEP_DONATE)


def jit_sharded_train_step(body: Callable, mesh):
    """The jit wrapper for shard_map-wrapped (state, batch) -> (state,
    metrics) steps whose results are replicated over ``mesh``.

    The result shardings are STATED, not left to propagation: in a
    program with more than one partition jax writes the donation into
    the module (``tf.aliasing_output``, what GA-DONATION audits) only
    for results whose sharding it knows; for the rest it emits
    ``jax.buffer_donor`` and defers the aliasing decision to XLA."""
    from jax.sharding import NamedSharding, PartitionSpec

    replicated = NamedSharding(mesh, PartitionSpec())
    return jax.jit(body, donate_argnums=TRAIN_STEP_DONATE,
                   out_shardings=(replicated, replicated))


def make_predict_step(expander: Callable | None = None,
                      raw_expander: Callable | None = None) -> Callable:
    """(state, batch) -> denormalized predictions [G, T].

    ``expander`` (``data.compact.make_expander``) lets the step accept
    compact-staged batches: a ``CompactBatch`` argument is rebuilt into
    the exact ``GraphBatch`` INSIDE the compiled program (table gather +
    ``exp`` fuse into the forward pass), so only the ~12x smaller raw
    form crosses the host->device link. The type dispatch happens at
    trace time, so ONE jitted callable serves both staging modes — a
    full-fidelity ``GraphBatch`` traces its own cache entry and runs
    unchanged (the serving fallback for non-compactable requests).

    ``raw_expander`` (``ops.neighbor_search.make_raw_expander``) adds
    the third staging form (ISSUE 11): a ``RawBatch`` of wire-form
    structures is turned into a GraphBatch by the IN-PROGRAM periodic
    neighbor search + featurization, and the step returns the tuple
    ``(predictions [G, T], cap_overflow [G] bool, n_edges [G] i32)`` —
    the overflow flag is part of the program's contract (a flagged
    structure's row must never be served; INVARIANTS.md), and the edge
    counts feed the per-rung edge-occupancy gauges.
    """

    def predict_step(state: TrainState, batch):
        if raw_expander is not None and isinstance(batch, RawBatch):
            gb, overflow, n_edges = raw_expander(batch)
            out = state.apply_fn(state.variables(), gb, train=False)
            preds = state.normalizer.denorm(out) * gb.graph_mask[:, None]
            return preds, overflow, n_edges
        if expander is not None and not isinstance(batch, GraphBatch):
            batch = expander(batch)
        out = state.apply_fn(state.variables(), batch, train=False)
        return state.normalizer.denorm(out) * batch.graph_mask[:, None]

    return predict_step
