"""Seeded weights and states for the hybrid short-convolution / attention
mixture-of-experts decoder, made by the benchmark: one jitted call a state,
on the device.

The tree has the program's parameter names and layout (``models/lfm2.py``
``Lfm2Config.shapes``: ``embed``, which is the head too, ``dense/*`` the
leading dense layers stacked, ``periods/run<j>/*`` the expert layers by runs
of one kind, a leaf ``[periods, layers of the run, ...]``, ``final_norm``),
float32; the program's state and the plain reference
(``reference/lfm2_ref.py``) are both handed this tree.
normal(``std``) with the output projections (``w_out``, ``wo`` and every
down projection) at ``std / sqrt(2 x the published depth)``; the norms'
scales are 1 + 0.1 normal, so that a path that drops one shows in the
comparison.

The routers' selection biases ride in ``batch_stats`` (``router_bias``),
uniform in ``+-bias_scale`` and fixed: no step moves them. 0.01 in the
window's state, 0.1 in the state ``correct`` is decided on, so that a path
that drops the bias shows.

Two states are made of one configuration (``kinds/lfm2_train.py``): from
``--seed``, the weights on which ``correct`` is decided; from the
configuration's ``model.weights_seed``, the weights the window trains, so
that the rows routed to the experts held are the same in every run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

OUTPUT_PROJECTIONS = ("w_out", "wo", "w_down", "mlp_down")


def _params(key, shapes: dict, std: float, out_std: float):
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        z = jax.random.normal(jax.random.fold_in(key, i), shape, jnp.float32)
        if name.endswith("norm"):
            leaves.append(1.0 + 0.1 * z)
        else:
            leaves.append((out_std if name in OUTPUT_PROJECTIONS else std)
                          * z)
    return jax.tree_util.tree_unflatten(tree, leaves)


class StateMaker:
    """``make(seed, bias_scale)`` -> a ``TrainState`` around weights from
    ``seed`` with ``tx``'s fresh optimizer state and biases uniform in
    ``+-bias_scale``, built whole in ONE jitted call (compiled once for all
    seeds and scales) and committed to the first device. ``model_cfg`` is
    the program's ``Lfm2Config``; ``init`` the configuration's ``init``
    block (``std``, ``published_layers``)."""

    def __init__(self, model_cfg, init: dict, tx, apply_fn):
        from cgnn_tpu.train import Normalizer
        from cgnn_tpu.train.state import TrainState

        std = float(init["std"])
        out_std = std / math.sqrt(2.0 * int(init["published_layers"]))
        shapes, stats = model_cfg.shapes(), model_cfg.stats_shapes()

        def build(key, bias_scale):
            params = _params(key, shapes, std, out_std)
            bias = {name: bias_scale * jax.random.uniform(
                jax.random.fold_in(key, 1_000_003), shape, jnp.float32,
                -1.0, 1.0) for name, shape in stats.items()}
            return TrainState(
                step=jnp.zeros((), jnp.int32), params=params,
                batch_stats=bias, opt_state=tx.init(params),
                normalizer=Normalizer.identity(1), rng=jax.random.key(0),
                apply_fn=apply_fn, tx=tx)

        self._build = jax.jit(build)

    def make(self, seed: int, bias_scale: float):
        # committed, as warm()'s scratch copy is: an uncommitted state would
        # miss every jit cache entry that warm filled (kinds/train.py)
        return jax.device_put(
            self._build(seed_key(seed), jnp.float32(bias_scale)),
            jax.devices()[0])
