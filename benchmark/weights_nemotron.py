"""Seeded weights and states for the hybrid Mamba-2 / attention
mixture-of-experts decoder, made by the benchmark: one jitted call a state,
on the device.

The tree has the program's parameter names and layout (``models/
nemotron_h.py`` ``NemotronHConfig.shapes``: ``embed``, ``periods/run<j>`` the
layers by repeated groups of kinds, a group of several kinds a dict of them
a kind (``periods/run0/moe/*``, ``periods/run0/mamba/*``), a leaf ``[periods,
repeats, ...]``, ``final_norm``, ``head``), float32; the program's state and
the plain reference (``reference/nemotron_ref.py``) are both handed this
tree. normal(``std``) with the output projections (``w_out``, ``wo`` and
every down projection) at ``std / sqrt(the published depth)`` (one addition
to the stream a layer); the norms' scales are 1 + 0.1 normal, so that a path
that drops one shows in the comparison. The Mamba layers' own: ``dt_bias``
the inverse softplus of a log-uniform step in 0.001-0.1 (floor 1e-4),
``a_log`` the log of uniform(1, 16), ``d_skip`` 1 + 0.1 normal (a dropped
skip shows; the published initialiser's is 1), the filter and its bias
uniform in ``+-1 / sqrt(taps)``.

The routers' selection biases ride in ``batch_stats`` (``router_bias``),
uniform in ``+-bias_scale`` and fixed: no step moves them. 0.01 in the
window's state, 0.1 in the state ``correct`` is decided on, so that a path
that drops the bias shows.

Two states are made of one configuration (``kinds/nemotron_train.py``): from
``--seed``, the weights on which ``correct`` is decided; from the
configuration's ``model.weights_seed``, the weights the window trains, so
that the rows routed to the experts held are the same in every run.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

OUTPUT_PROJECTIONS = ("w_out", "wo", "w_down", "shared_down")
TIME_STEP_MIN, TIME_STEP_MAX, TIME_STEP_FLOOR = 0.001, 0.1, 1e-4
A_MIN, A_MAX = 1.0, 16.0


def _leaf(name: str, key, shape, std: float, out_std: float, taps: int):
    if name in ("conv_w", "conv_b"):
        bound = 1.0 / math.sqrt(taps)
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    if name == "dt_bias":
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(TIME_STEP_MIN),
            math.log(TIME_STEP_MAX))), TIME_STEP_FLOOR)
        return step + jnp.log(-jnp.expm1(-step))
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, A_MIN,
                                          A_MAX))
    z = jax.random.normal(key, shape, jnp.float32)
    if name.endswith("norm") or name == "d_skip":
        return 1.0 + 0.1 * z
    return (out_std if name in OUTPUT_PROJECTIONS else std) * z


def _params(key, shapes: dict, std: float, out_std: float, taps: int):
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    return jax.tree_util.tree_unflatten(tree, [
        _leaf(str(getattr(path[-1], "key", path[-1])),
              jax.random.fold_in(key, i), shape, std, out_std, taps)
        for i, (path, shape) in enumerate(flat)])


class StateMaker:
    """``make(seed, bias_scale)`` -> a ``TrainState`` around weights from
    ``seed`` with ``tx``'s fresh optimizer state and biases uniform in
    ``+-bias_scale``, built whole in ONE jitted call (compiled once for all
    seeds and scales) and committed to the first device. ``model_cfg`` is
    the program's ``NemotronHConfig``; ``init`` the configuration's ``init``
    block (``std``, ``published_layers``)."""

    def __init__(self, model_cfg, init: dict, tx, apply_fn):
        from cgnn_tpu.train import Normalizer
        from cgnn_tpu.train.state import TrainState

        std = float(init["std"])
        out_std = std / math.sqrt(int(init["published_layers"]))
        shapes, stats = model_cfg.shapes(), model_cfg.stats_shapes()
        taps = model_cfg.conv_kernel

        def build(key, bias_scale):
            params = _params(key, shapes, std, out_std, taps)
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
