"""Seeded weights for the Open Catalyst CGCNN, made by the benchmark: one
jitted call on the device.

The tree has the system's parameter names (``models/cgcnn.py`` with
``node_norm='layer'``: ``embedding``, ``conv_{c}/{fc_full,bn1,ln}``,
``conv_to_fc``, ``fc_0 .. fc_{n_h-2}``, ``fc_out``; running statistics for
``bn1`` alone, LayerNorm keeps none) and its float32 storage type; the
system's state and the plain reference (``reference/ocp_ref.py``) are both
handed this same tree. Every leaf is non-trivial (no scale is all one, no
bias zero, no running statistic (0, 1)), so a path that drops a leaf shows
in the comparison. ``weights.py`` covers the lineage's model (BatchNorm
after the sum, ``n_h`` 1) and refuses any other.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key

# The output layer is kept small around a bias well above every standardised
# label (the pool's lie in -2.5 .. 1.9). The loss is L1: its gradient is
# sign(output - label) a structure, and an output within rounding of its
# label flips a whole structure's share of the gradient, 2/32 of a batch of
# 32, between one precision and the next. With every first residual positive
# by a unit or more, the first gradient reads the precision it was computed
# in and not the luck of a sign. The price: that gradient does not depend on
# the labels or their normaliser, and nor do the next two (Adam's first step
# moves every weight by the learning rate and the outputs by far more than
# the labels' spread, so a later step's residuals share a sign too: the
# reference fed raw labels ends on the same parameters). The labels enter
# the comparison through the loss rows (kinds/ocp_train.py CONTROLS
# 'raw_targets'; PERF.md section 2).
OUT_BIAS = 3.0


@functools.partial(jax.jit, static_argnames=(
    "atom_dim", "gauss_dim", "f", "h", "n_conv", "n_h", "num_targets"))
def _make(key, *, atom_dim, gauss_dim, f, h, n_conv, n_h, num_targets):
    keys = iter(jax.random.split(key, 8 * n_conv + 2 * n_h + 8))

    def dense(fan_in, fan_out):
        return {
            "kernel": jax.random.normal(next(keys), (fan_in, fan_out))
            * (1.0 / fan_in) ** 0.5,
            "bias": 0.05 * jax.random.normal(next(keys), (fan_out,)),
        }

    def affine(width):
        return {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (width,)),
                "bias": 0.1 * jax.random.normal(next(keys), (width,))}

    params = {"embedding": dense(atom_dim, f)}
    stats = {}
    for c in range(n_conv):
        k1, k2 = jax.random.split(next(keys))
        params[f"conv_{c}"] = {"fc_full": dense(2 * f + gauss_dim, 2 * f),
                               "bn1": affine(2 * f), "ln": affine(f)}
        stats[f"conv_{c}"] = {"bn1": {
            "mean": 0.1 * jax.random.normal(k1, (2 * f,)),
            "var": jax.random.uniform(k2, (2 * f,), minval=0.5, maxval=1.5)}}
    params["conv_to_fc"] = dense(f, h)
    for k in range(n_h - 1):
        params[f"fc_{k}"] = dense(h, h)
    out = dense(h, num_targets)
    params["fc_out"] = {"kernel": 0.25 * out["kernel"],
                        "bias": OUT_BIAS + out["bias"]}
    as_f32 = functools.partial(jax.tree_util.tree_map,
                               lambda x: x.astype(jnp.float32))
    return as_f32(params), as_f32(stats)


def make_weights(seed: int, model: dict, atom_dim: int, gauss_dim: int):
    """-> (params, batch_stats) for the configuration's ``model`` block."""
    return _make(seed_key(seed), atom_dim=int(atom_dim),
                 gauss_dim=int(gauss_dim), f=int(model["atom_fea_len"]),
                 h=int(model["h_fea_len"]), n_conv=int(model["n_conv"]),
                 n_h=int(model["n_h"]),
                 num_targets=int(model.get("num_targets", 1)))
