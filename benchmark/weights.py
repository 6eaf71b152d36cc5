"""Seeded weights, made by the benchmark: one jitted call on the device.

The tree has the system's parameter names (``models/cgcnn.py``) and its
float32 storage type; the system's state and the plain reference are both
handed this same tree, so neither takes anything the other made. Every leaf
is non-trivial (BatchNorm scales are not all one, running statistics are not
(0, 1)), so a path that drops a leaf shows in the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def seed_key(seed: int, stream: int = 0):
    """A PRNG key from any whole-number seed (they run past 2**31)."""
    seed = int(seed)
    key = jax.random.key(seed & 0x7FFFFFFF)
    key = jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)
    return jax.random.fold_in(key, stream)


@functools.partial(jax.jit, static_argnames=(
    "atom_dim", "gauss_dim", "f", "h", "n_conv", "num_targets"))
def _make(key, *, atom_dim, gauss_dim, f, h, n_conv, num_targets):
    keys = iter(jax.random.split(key, 8 * n_conv + 8))

    def dense(fan_in, fan_out):
        return {
            "kernel": jax.random.normal(next(keys), (fan_in, fan_out))
            * (1.0 / fan_in) ** 0.5,
            "bias": 0.05 * jax.random.normal(next(keys), (fan_out,)),
        }

    def bn(width):
        p = {"scale": 1.0 + 0.1 * jax.random.normal(next(keys), (width,)),
             "bias": 0.1 * jax.random.normal(next(keys), (width,))}
        k1, k2 = jax.random.split(next(keys))
        s = {"mean": 0.1 * jax.random.normal(k1, (width,)),
             "var": jax.random.uniform(k2, (width,), minval=0.5, maxval=1.5)}
        return p, s

    params = {"embedding": dense(atom_dim, f)}
    stats = {}
    for c in range(n_conv):
        bn1, st1 = bn(2 * f)
        bn2, st2 = bn(f)
        params[f"conv_{c}"] = {"fc_full": dense(2 * f + gauss_dim, 2 * f),
                               "bn1": bn1, "bn2": bn2}
        stats[f"conv_{c}"] = {"bn1": st1, "bn2": st2}
    params["conv_to_fc"] = dense(f, h)
    # the output layer is kept small around a bias of one standardised unit.
    # With a free-scale output layer the batch mean of (output - target) is
    # near zero for about one seed in twenty, the whole gradient is then
    # proportional to that small number, and every precision reads several
    # times its usual error (seed 10 of PERF.md's first readings): the
    # comparison would measure the seed's conditioning, not the program.
    out = dense(h, num_targets)
    params["fc_out"] = {"kernel": 0.25 * out["kernel"],
                        "bias": 1.0 + out["bias"]}
    as_f32 = functools.partial(jax.tree_util.tree_map,
                               lambda x: x.astype(jnp.float32))
    return as_f32(params), as_f32(stats)


def make_weights(seed: int, model: dict, atom_dim: int, gauss_dim: int):
    """-> (params, batch_stats) for a configuration's ``model`` block."""
    if int(model["n_h"]) != 1:
        raise ValueError("seeded weights cover n_h = 1 (no hidden fc stack)")
    return _make(seed_key(seed), atom_dim=int(atom_dim),
                 gauss_dim=int(gauss_dim), f=int(model["atom_fea_len"]),
                 h=int(model["h_fea_len"]), n_conv=int(model["n_conv"]),
                 num_targets=int(model.get("num_targets", 1)))
