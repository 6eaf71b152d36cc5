"""Train state + optimizer factory (SURVEY.md §2 components 10-11).

One pytree holds everything the jitted step mutates — params, BatchNorm
running stats, optimizer state, step counter, and the target Normalizer —
so checkpointing is a single pytree save and the step can donate the whole
state buffer (XLA reuses the memory in place).
"""

from __future__ import annotations

from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import optax
from flax import struct

from cgnn_tpu.train.normalizer import Normalizer


class TrainState(struct.PyTreeNode):
    step: jnp.ndarray  # scalar int32
    params: Any
    batch_stats: Any
    opt_state: Any
    normalizer: Normalizer
    rng: jax.Array  # base key; per-step keys are fold_in(rng, step)
    apply_fn: Callable = struct.field(pytree_node=False)
    tx: optax.GradientTransformation = struct.field(pytree_node=False)

    def variables(self) -> dict:
        return {"params": self.params, "batch_stats": self.batch_stats}

    def apply_gradients(self, grads, new_batch_stats):
        updates, new_opt_state = self.tx.update(grads, self.opt_state, self.params)
        return self.replace(
            step=self.step + 1,
            params=optax.apply_updates(self.params, updates),
            batch_stats=new_batch_stats,
            opt_state=new_opt_state,
        )


def multistep_lr(
    base_lr: float, milestones: Sequence[int], gamma: float = 0.1
) -> optax.Schedule:
    """torch MultiStepLR twin: multiply lr by gamma at each milestone step."""
    if not milestones:
        return optax.constant_schedule(base_lr)
    return optax.piecewise_constant_schedule(
        base_lr, {int(m): gamma for m in milestones}
    )


def make_optimizer(
    optim: str = "sgd",
    lr: float = 0.01,
    momentum: float = 0.9,
    weight_decay: float = 0.0,
    lr_milestones: Sequence[int] = (),
    lr_gamma: float = 0.1,
    grad_clip: float = 0.0,
    b1: float = 0.9,
    b2: float = 0.999,
) -> optax.GradientTransformation:
    """SGD+momentum or Adam with a MultiStepLR schedule (reference defaults).
    ``b1``/``b2`` are Adam's and AdamW's decay rates (optax's defaults)."""
    schedule = multistep_lr(lr, lr_milestones, lr_gamma)
    if optim.lower() == "sgd":
        core = optax.sgd(schedule, momentum=momentum)
    elif optim.lower() == "adam":
        core = optax.adam(schedule, b1=b1, b2=b2)
    elif optim.lower() == "adamw":
        core = optax.adamw(schedule, b1=b1, b2=b2,
                           weight_decay=weight_decay)
    else:
        raise ValueError(f"unknown optimizer {optim!r} (sgd|adam|adamw)")
    parts = []
    if grad_clip > 0:
        parts.append(optax.clip_by_global_norm(grad_clip))
    if weight_decay > 0 and optim.lower() == "sgd":
        # torch SGD couples weight decay into the gradient
        parts.append(optax.add_decayed_weights(weight_decay))
    parts.append(core)
    return optax.chain(*parts)


def create_train_state(
    model,
    example_batch,
    tx: optax.GradientTransformation,
    normalizer: Normalizer,
    rng: jax.Array | None = None,
) -> TrainState:
    rng = rng if rng is not None else jax.random.key(0)
    init_rng, state_rng = jax.random.split(rng)
    variables = model.init(init_rng, example_batch)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=tx.init(params),
        normalizer=normalizer,
        rng=state_rng,
        apply_fn=model.apply,
        tx=tx,
    )
