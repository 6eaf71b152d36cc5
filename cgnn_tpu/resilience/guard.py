"""Divergence recovery: skip bad updates on device, roll back on blowup.

Two layers, built on the in-graph grad-health indicators of
``observe.health``:

**In-graph skip** (``guard_step``): wraps any ``(state, batch) ->
(state, metrics)`` train body. After the inner update it counts
non-finite elements across the new params/batch-stats (plus the step's
loss) and selects old-vs-new state with ``jnp.where`` — a pure in-graph
select, so it works inside the donated-carry whole-epoch scans and
under ``shard_map`` (the inputs to the check are replicated post-pmean
values, so every shard takes the same branch). When no fault fires the
select is the identity: nothing is skipped and the update is the inner
body's. The guarded step is a separate XLA program from the unguarded
one, so the two may round that update differently (1 ulp per step on
jax 0.9.0's CPU backend); tests/test_resilience.py pins the losses to
1e-3 across the two and the skip itself bit-exact. A skipped step leaves ``state.step`` unchanged and
zeroes its metric contributions (count included), and reports
``guard_skipped_sum``/``_count`` through the normal metric plumbing —
visible per-step at ``--telemetry step`` and in every epoch aggregate.

**Host rollback** (``DivergenceMonitor``): watches the per-epoch skip
count; when the guard keeps firing (K or more skipped steps in one
epoch — repeated divergence, not a transient bad batch) it restores the
last good checkpoint through the manager's fallback chain, cuts the
learning rate (``scale_updates`` — wraps ``tx.update`` without touching
the optimizer *state* structure, so checkpoints stay structurally
compatible across rollbacks at the cost of one retrace), and retries,
bounded by ``max_rollbacks``.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import jax.numpy as jnp
import optax

from cgnn_tpu.observe import phases
from cgnn_tpu.observe.health import nonfinite_count


class DivergenceError(RuntimeError):
    """Training diverged and the bounded rollback retries are exhausted."""


def guard_step(body: Callable) -> Callable:
    """Wrap a train body so non-finite updates are skipped on device."""

    def guarded(state, batch):
        new_state, metrics = body(state, batch)
        # the check and the select ride with the update they guard
        with jax.named_scope(phases.OPTIMIZER):
            bad = nonfinite_count(new_state.params)
            bad = bad + nonfinite_count(new_state.batch_stats)
            if "loss_sum" in metrics:
                loss_sum = jnp.asarray(metrics["loss_sum"], jnp.float32)
                bad = bad + (~jnp.isfinite(loss_sum)).astype(jnp.float32)
            ok = bad == 0

            def keep(new, old):
                return jnp.where(ok, new, old)

            def select(new, old):
                return jax.tree_util.tree_map(keep, new, old)

            out_state = new_state.replace(
                # step stays put on a skip: the retried-batch rng fold_in and
                # the lr schedule see a trajectory without the bad step
                step=keep(new_state.step, state.step),
                params=select(new_state.params, state.params),
                batch_stats=select(new_state.batch_stats, state.batch_stats),
                opt_state=select(new_state.opt_state, state.opt_state),
            )
            okf = ok.astype(jnp.float32)
            # zero the skipped step's metric sums AND counts (a NaN loss
            # must not poison the epoch aggregate; where, not multiply —
            # NaN*0=NaN)
            metrics = {k: jnp.where(ok, v, jnp.zeros_like(v))
                       for k, v in metrics.items()}
            metrics["guard_skipped_sum"] = 1.0 - okf
            metrics["guard_skipped_count"] = jnp.float32(1.0)
        return out_state, metrics

    return guarded


def scale_updates(tx: optax.GradientTransformation,
                  factor: float) -> optax.GradientTransformation:
    """``tx`` with its emitted updates scaled by ``factor``.

    Unlike ``optax.chain(tx, optax.scale(f))`` this leaves the optimizer
    STATE structure untouched — checkpoints saved before and after an LR
    cut stay mutually restorable (the fallback chain depends on that).
    The factor is baked into the closure: swapping it retraces the step,
    which is fine for an event as rare as a rollback.
    """

    def update(updates, opt_state, params=None):
        updates, opt_state = tx.update(updates, opt_state, params)
        return (
            jax.tree_util.tree_map(lambda u: u * factor, updates),
            opt_state,
        )

    return optax.GradientTransformation(tx.init, update)


class DivergenceMonitor:
    """Epoch-level watchdog: rollback-with-LR-cut on sustained divergence.

    ``observe(state, epoch, train_m) -> (state, rolled_back)`` is called
    once per epoch by the fit loops with the epoch's aggregated train
    metrics. An epoch is *bad* when its training loss is non-finite
    (guard off or overwhelmed) or when ``max_skips`` or more steps were
    skipped by the in-graph guard. ``post_restore`` re-places restored
    state for the caller's topology (data-parallel loops pass a
    replicate function).
    """

    def __init__(self, ckpt, max_skips: int = 3, lr_cut: float = 0.5,
                 max_rollbacks: int = 3, log_fn: Callable = print,
                 post_restore: Callable | None = None):
        if max_skips < 1:
            raise ValueError(f"max_skips must be >= 1, got {max_skips}")
        if not 0.0 < lr_cut < 1.0:
            raise ValueError(f"lr_cut must be in (0, 1), got {lr_cut}")
        self.ckpt = ckpt
        self.max_skips = max_skips
        self.lr_cut = lr_cut
        self.max_rollbacks = max_rollbacks
        self.rollbacks = 0
        self.lr_scale = 1.0
        self.post_restore = post_restore
        self._log = log_fn
        self._base_tx = None
        # incident hook (ISSUE 15): called with a reason string after
        # every rollback — wire a FlightRecorder.trigger here and the
        # postmortem bundle (metrics window + recent telemetry) dumps
        # at the exact epoch training went off the rails
        self.on_rollback: Callable | None = None

    def _is_bad(self, train_m: dict) -> tuple[bool, str]:
        loss = train_m.get("loss", float("nan"))
        if not math.isfinite(loss):
            return True, f"non-finite train loss {loss}"
        skipped = round(
            train_m.get("guard_skipped", 0.0) * train_m.get("steps", 0)
        )
        if skipped >= self.max_skips:
            return True, (
                f"{skipped} steps skipped by the divergence guard "
                f"(threshold {self.max_skips})"
            )
        return False, ""

    def meta(self) -> dict:
        """Progress to persist in every checkpoint meta: the LR cut and
        retry budget must survive a preemption requeue, or a resumed run
        restarts at the full-strength LR that caused the divergence and
        the rollback budget resets on every requeue (an unbounded
        diverge -> rollback -> preempt loop)."""
        return {
            "guard_lr_scale": self.lr_scale,
            "guard_rollbacks": self.rollbacks,
        }

    def resume_from_meta(self, state, meta: dict):
        """Reapply persisted rollback progress after a resume -> state
        (with the LR cut re-baked into ``state.tx`` when one was active).
        The inverse of ``meta()``; train.py calls this on --resume."""
        self.rollbacks = int(meta.get("guard_rollbacks", 0))
        scale = float(meta.get("guard_lr_scale", 1.0))
        if scale >= 1.0:
            return state
        self._base_tx = state.tx
        self.lr_scale = scale
        self._log(
            f"divergence guard: resumed with lr x{scale:g} and "
            f"{self.rollbacks}/{self.max_rollbacks} rollbacks spent"
        )
        return state.replace(tx=scale_updates(self._base_tx, scale))

    def observe(self, state, epoch: int, train_m: dict):
        bad, why = self._is_bad(train_m)
        if not bad:
            return state, False
        if self._base_tx is None:
            self._base_tx = state.tx
        if self.rollbacks >= self.max_rollbacks:
            raise DivergenceError(
                f"epoch {epoch}: {why}; {self.rollbacks} rollbacks already "
                f"spent (max {self.max_rollbacks}) — giving up"
            )
        if not self.ckpt.exists("latest"):
            self._log(
                f"divergence guard: epoch {epoch} diverged ({why}) but no "
                f"checkpoint exists yet to roll back to — continuing"
            )
            return state, False
        restored, meta = self.ckpt.restore(state)
        self.rollbacks += 1
        self.lr_scale *= self.lr_cut
        restored = restored.replace(
            tx=scale_updates(self._base_tx, self.lr_scale)
        )
        if self.post_restore is not None:
            restored = self.post_restore(restored)
        self._log(
            f"divergence guard: epoch {epoch} diverged ({why}) — rolled "
            f"back to checkpoint epoch {meta.get('epoch', '?')} with lr x"
            f"{self.lr_scale:g} (rollback {self.rollbacks}/"
            f"{self.max_rollbacks})"
        )
        if self.on_rollback is not None:
            try:
                self.on_rollback(f"epoch {epoch}: {why}")
            except Exception:  # noqa: BLE001 — an incident hook must
                pass           # never break the recovery it records
        return restored, True
