"""Plain reference for the energy-and-force model: energies, forces as
-dE/dx, the composite loss, its parameter gradient (a reverse pass over a
reverse pass) and three Adam steps.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. Flat COO edge list, no padding,
no dense slot layout, no transposed gather, no kernels; its own distances and
Gaussians from positions, lattices and the neighbour list's image offsets. It
imports nothing of the program (``cgnn_tpu``) and is handed only what the
benchmark made from the seed (parameters), the pool's energy mean/std, and
frames.

Equations (CGCNN, Xie & Grossman, PRL 120, 145301, ``txie-93/cgcnn``
``model.py``, with the per-atom readout of BASELINE.json configuration 5;
``x`` positions, ``L`` lattices as rows, ``(i, j, o_ij)`` the neighbour list):

    d_ij  = sqrt(|x_j + o_ij L - x_i|^2 + 1e-12)
    e_ij  = exp(-(d_ij - mu_k)^2 / step^2),  mu_k = dmin + k step
    v_i   = W_emb a_i + b_emb                               embedding
    z_ij  = W_f [v_i ; v_j ; e_ij] + b_f                    fc_full, 2F+K -> 2F
    m_ij  = sigmoid(z_ij[:F]) * softplus(z_ij[F:])          gate * core
    v_i'  = softplus(v_i + sum_j m_ij)                      per conv
    eps_i = w_2 . softplus(W_1 v_i + b_1) + b_2             per-atom energy
    E_g   = sum_{i in g} eps_i
    F_i   = -d(sum_g E_g) / d x_i
    loss  = w_e mean_g (E_g - (E^_g - mean) / std)^2
            + w_f mean_{i,c} (F_ic - F^_ic / std)^2

Departures from the published CGCNN, as the system defines the force model
(``cgnn_tpu/models/forcefield.py``, ``train/force_step.py``):
- no BatchNorm anywhere: its batch moments would put terms into -dE/dx in
  train mode that running statistics do not have at eval.
- the readout is per atom and summed (published: mean pooling, then an MLP
  per crystal), so that forces exist for every atom.
- distances are recomputed from positions inside the model, with 1e-12 under
  the root; the published data path expands precomputed distances.
- energies are standardised by the pool's mean/std and force labels divided
  by the same std, so predicted forces are -d(E/std)/dx.

Parameter names are the system's pytree (kernels stored [in, out]):
``embedding``, ``conv_{c}/fc_full``, ``ForceHead_0/{fc,out}``.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

# the float32 matmul and the leaf norms are the first-order reference's own
# (same package, nothing of the program)
from benchmark.reference.cgcnn_ref import (  # noqa: F401
    _mm_f32,
    as_jnp,
    leaf_norms,
)

SQRT_EPS = 1e-12
# optax.adam's defaults, which train.py --optim Adam runs
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


def _fake_bf16(x):
    """Rounding to bfloat16 (8 exponent bits, 7 of mantissa) with a
    straight-through gradient. ``reduce_precision`` and not a cast there and
    back: the TPU compiler is allowed excess precision and takes such a pair
    of converts out, and the control then reads 0 on every row (it did, on
    the chip, PR 27)."""
    return x + jax.lax.stop_gradient(jax.lax.reduce_precision(x, 8, 7) - x)


def mm_bf16(x, w):
    """The control's matmul: both operands rounded to bfloat16, the precision
    below the configuration's float32 (and what the TPU makes of a float32
    matmul that asks for nothing), accumulated exactly."""
    return _mm_f32(_fake_bf16(x), _fake_bf16(w))


def coo_batch(frames: list) -> dict:
    """Concatenate frames (dicts with atom_fea [N, A], positions [N, 3],
    lattice [3, 3], centers, neighbors [E], offsets [E, 3], energy, forces
    [N, 3]) into one unpadded COO batch."""
    node_off = np.cumsum([0] + [len(s["atom_fea"]) for s in frames])
    n_edges = [len(s["centers"]) for s in frames]

    def cat(key, dtype, shift=False):
        return jnp.asarray(np.concatenate([
            np.asarray(s[key], dtype) + (node_off[k] if shift else 0)
            for k, s in enumerate(frames)]))

    return {
        "atom_fea": cat("atom_fea", np.float32),
        "positions": cat("positions", np.float32),
        "lattices": jnp.asarray(np.stack(
            [np.asarray(s["lattice"], np.float32) for s in frames])),
        "centers": cat("centers", np.int32, shift=True),
        "neighbors": cat("neighbors", np.int32, shift=True),
        "offsets": cat("offsets", np.float32),
        "edge_graph": jnp.asarray(np.repeat(
            np.arange(len(frames), dtype=np.int32), n_edges)),
        "node_graph": jnp.asarray(np.repeat(
            np.arange(len(frames), dtype=np.int32), np.diff(node_off))),
        "energies": jnp.asarray(np.array(
            [float(np.ravel(s["energy"])[0]) for s in frames], np.float32)),
        "forces": cat("forces", np.float32),
    }


# ---- the model --------------------------------------------------------


def distances(batch, positions):
    shift = jnp.einsum("ek,ekj->ej", batch["offsets"],
                       batch["lattices"][batch["edge_graph"]],
                       precision=jax.lax.Precision.HIGHEST)
    rel = positions[batch["neighbors"]] + shift - positions[batch["centers"]]
    return jnp.sqrt(jnp.sum(rel * rel, axis=-1) + SQRT_EPS)


def gaussians(d, featurize: dict):
    k = int(round((featurize["radius"] - featurize["dmin"])
                  / featurize["step"])) + 1
    mu = featurize["dmin"] + featurize["step"] * jnp.arange(k,
                                                            dtype=d.dtype)
    return jnp.exp(-((d[:, None] - mu) ** 2) / featurize["step"] ** 2)


def energies(params, batch, positions, featurize: dict, mm=_mm_f32):
    """-> [G] standardised total energies of an unpadded COO batch."""
    n_graphs = batch["energies"].shape[0]
    e = gaussians(distances(batch, positions), featurize)
    v = mm(batch["atom_fea"], params["embedding"]["kernel"]) \
        + params["embedding"]["bias"]
    n = v.shape[0]
    i, j = batch["centers"], batch["neighbors"]
    n_conv = sum(1 for k in params if k.startswith("conv_"))
    for c in range(n_conv):
        p = params[f"conv_{c}"]["fc_full"]
        z = mm(jnp.concatenate([v[i], v[j], e], axis=-1), p["kernel"]) \
            + p["bias"]
        f = z.shape[-1] // 2
        msg = jax.nn.sigmoid(z[:, :f]) * jax.nn.softplus(z[:, f:])
        v = jax.nn.softplus(v + jax.ops.segment_sum(msg, i, num_segments=n))
    head = params["ForceHead_0"]
    h = jax.nn.softplus(mm(v, head["fc"]["kernel"]) + head["fc"]["bias"])
    eps = (mm(h, head["out"]["kernel"]) + head["out"]["bias"])[:, 0]
    return jax.ops.segment_sum(eps, batch["node_graph"], n_graphs)


def energies_and_forces(params, batch, featurize: dict, mm=_mm_f32):
    """(E [G], F [N, 3]), both standardised: F = -d(sum E)/dx."""
    def total(pos):
        e = energies(params, batch, pos, featurize, mm)
        return jnp.sum(e), e

    (_, e), grad_pos = jax.value_and_grad(total, has_aux=True)(
        batch["positions"])
    return e, -grad_pos


def loss_fn(params, batch, t_mean, t_std, featurize: dict, w_e: float,
            w_f: float, mm=_mm_f32):
    e, f = energies_and_forces(params, batch, featurize, mm)
    e_loss = jnp.mean((e - (batch["energies"] - t_mean) / t_std) ** 2)
    f_loss = jnp.mean((f - batch["forces"] / t_std) ** 2)
    return w_e * e_loss + w_f * f_loss, f


def adam_steps(params, batches: list, t_mean, t_std, *, featurize: dict,
               w_e: float, w_f: float, lr: float, mm=_mm_f32) -> dict:
    """Follow the first ``len(batches)`` training steps of Adam (m = b1 m +
    (1 - b1) g; v = b2 v + (1 - b2) g^2; p -= lr m^ / (sqrt(v^) + eps), m^
    and v^ the moments over 1 - b^t), one batch a step.

    -> {"loss": [per step], "forces": step 1's standardised forces [N, 3],
        "grad": step 1's gradient (host arrays), "grad_norm": {leaf: norm},
        "energy_dir": d(mean energy)/d(parameters) at step 1,
        "delta_norm": {leaf: norm of the parameters' change after all steps}}
    """
    featurize = dict(featurize)

    def value_and_grad(p, b):
        return jax.value_and_grad(
            lambda q: loss_fn(q, b, t_mean, t_std, featurize, w_e, w_f, mm),
            has_aux=True)(p)

    grad = jax.jit(value_and_grad)
    # d(mean energy)/d(parameters): the one direction along which every
    # precision's own rounding of the mean energy moves the gradient
    # (``off_energy_diff``)
    mean_energy_grad = jax.jit(jax.grad(lambda q, b: jnp.mean(
        energies(q, b, b["positions"], featurize, mm))))
    tmap = jax.tree_util.tree_map
    start = params
    m = tmap(jnp.zeros_like, params)
    v = tmap(jnp.zeros_like, params)
    losses, first_grad, first_forces = [], None, None
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, 1):
            (loss, forces), g = grad(params, batch)
            if first_grad is None:
                first_grad, first_forces = g, forces
                energy_dir = mean_energy_grad(params, batch)
            m = tmap(lambda a, gg: ADAM_B1 * a + (1 - ADAM_B1) * gg, m, g)
            v = tmap(lambda a, gg: ADAM_B2 * a + (1 - ADAM_B2) * gg * gg,
                     v, g)
            params = tmap(
                lambda p, a, b: p - lr * (a / (1 - ADAM_B1 ** t)) / (
                    jnp.sqrt(b / (1 - ADAM_B2 ** t)) + ADAM_EPS),
                params, m, v)
            losses.append(float(loss))
        delta = tmap(lambda a, b: a - b, params, start)
    return {"loss": losses, "forces": np.asarray(first_forces),
            "grad": tmap(np.asarray, first_grad),
            "energy_dir": tmap(np.asarray, energy_dir),
            "grad_norm": leaf_norms(first_grad),
            "delta_norm": leaf_norms(delta)}


def rel_diff(got, want) -> float:
    """||got - want|| / ||want|| over whole arrays."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want)
                 / max(np.linalg.norm(want), 1e-30))


def off_energy_diff(got, want, energy_dir) -> float:
    """Median over the leaves of ||P(got - want)|| / ||P want||, P taking out
    of a leaf its component along ``energy_dir``.

    The energy term's gradient is 2 mean(E - label) d(mean E)/d(parameters)
    up to the little that frames differ, so an error of the mean energy (the
    weights' own rounding to bfloat16 moves it by ~0.02 standardised units,
    the same in every frame) moves the whole gradient along that one
    direction, by as much as the path through the forces contributes in all.
    What is left after P is mostly that path, the second derivative, and
    reads the precision it was computed in, not the offset's.
    """
    ratios = []
    for g, w, u in zip(*(jax.tree_util.tree_leaves(t)
                         for t in (got, want, energy_dir))):
        g, w, u = (np.asarray(x, np.float64).ravel() for x in (g, w, u))
        uu = max(float(u @ u), 1e-300)
        d = (g - w) - u * (u @ (g - w)) / uu
        w = w - u * (u @ w) / uu
        ratios.append(float(np.linalg.norm(d)
                            / max(np.linalg.norm(w), 1e-300)))
    return float(np.median(ratios))
