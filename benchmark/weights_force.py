"""Seeded weights for the energy-and-force model, made by the benchmark: one
jitted call on the device.

The tree has the system's parameter names (``models/forcefield.py``:
``embedding``, ``conv_{c}/fc_full``, ``ForceHead_0/{fc,out}``; no BatchNorm,
so no running statistics) and its float32 storage type; the system's state
and the plain reference are both handed this same tree (after the kind has
set the output bias, ``kinds/force_train.py``). Every leaf is non-trivial (no
bias is zero), so a path that drops a leaf shows in the comparison.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from benchmark.weights import seed_key


@functools.partial(jax.jit, static_argnames=(
    "atom_dim", "gauss_dim", "f", "h", "n_conv"))
def _make(key, *, atom_dim, gauss_dim, f, h, n_conv):
    keys = iter(jax.random.split(key, 2 * n_conv + 6))

    def dense(fan_in, fan_out, scale=1.0, shift=0.0):
        return {
            "kernel": scale * jax.random.normal(next(keys), (fan_in, fan_out))
            * (1.0 / fan_in) ** 0.5,
            "bias": shift + 0.05 * jax.random.normal(next(keys), (fan_out,)),
        }

    # The trunk has no BatchNorm and the readout sums over a frame's atoms,
    # so at unit scale node features grow conv by conv and a frame's energy
    # is hundreds of standardised units: the first loss is 1e5, all of it
    # the energy term, and the path through the forces a rounding error of
    # the gradient (PERF.md section 4). These scales keep every activation
    # of order one and the energy's slope in the positions alive: the core
    # half of each fc_full (the softplus half) starts at -2, the readout's
    # hidden layer at half scale around -1, its output layer at a tenth.
    # The kind then sets the output bias from the reference's energies.
    params = {"embedding": dense(atom_dim, f)}
    for c in range(n_conv):
        fc = dense(2 * f + gauss_dim, 2 * f)
        fc["bias"] = fc["bias"] + jnp.concatenate(
            [jnp.zeros(f), jnp.full((f,), -2.0)])
        params[f"conv_{c}"] = {"fc_full": fc}
    params["ForceHead_0"] = {"fc": dense(f, h, 0.5, -1.0),
                             "out": dense(h, 1, 0.1, 0.05)}
    return jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), params)


def make_weights(seed: int, model: dict, atom_dim: int, gauss_dim: int):
    """-> params for a force configuration's ``model`` block."""
    return _make(seed_key(seed), atom_dim=int(atom_dim),
                 gauss_dim=int(gauss_dim), f=int(model["atom_fea_len"]),
                 h=int(model["h_fea_len"]), n_conv=int(model["n_conv"]))
