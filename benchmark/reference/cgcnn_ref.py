"""Plain reference CGCNN: forward, loss, gradients and three SGD steps.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU a float32 matmul is
otherwise computed in bf16 passes). Flat COO edge list, no padding, no dense
slot layout, no compact staging, no kernels. It imports nothing of the program
(``cgnn_tpu``) and is handed only what the benchmark itself made from the
seed: parameters, running statistics, the target mean/std, and structures.

Equations (Xie & Grossman, PRL 120, 145301; ``txie-93/cgcnn`` ``model.py``):

    v0     = W_emb a_i + b_emb                              embedding
    z_ij   = W_f [v_i ; v_j ; e_ij] + b_f                   fc_full, 2F+K -> 2F
    z_ij   = BN1(z_ij)                                      over all edges
    m_ij   = sigmoid(z_ij[:F]) * softplus(z_ij[F:])         gate * core
    v_i'   = softplus(v_i + BN2(sum_j m_ij))                over all atoms
    c      = mean_i v_i                                     per crystal
    c      = softplus(W_c softplus(c) + b_c)                conv_to_fc
    y      = W_o c + b_o                                    fc_out (n_h = 1)

Departures from the published description, as the system defines them:
- BatchNorm placement, gate order (first half = sigmoid gate) and softplus are
  as published. BatchNorm normalises with the biased batch variance, eps 1e-5,
  in train mode, and with the running statistics in eval mode.
- Pooling is the mean over a crystal's atoms (published: the same).
- The loss is the mean squared error on targets standardised by the training
  pool's mean/std (published ``Normalizer``); predictions are de-standardised.
- Edge features: Gaussian expansion exp(-(d - mu)^2 / var^2) with var = step
  (published ``GaussianDistance``), mu = 0, 0.2, ..., 8.0 (41 filters).
- ``n_h`` = 1, so there is no hidden ``fcs`` stack between conv_to_fc and
  fc_out.

Parameter names are the system's pytree, which maps onto the published
``state_dict`` as PARAM_MAP says (kernels are stored [in, out], i.e. the
transpose of a torch ``weight``).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

BN_EPS = 1e-5

# system pytree path -> published (torch) state_dict key
PARAM_MAP = {
    "embedding/kernel": "embedding.weight^T",
    "embedding/bias": "embedding.bias",
    "conv_{i}/fc_full/kernel": "convs.{i}.fc_full.weight^T",
    "conv_{i}/fc_full/bias": "convs.{i}.fc_full.bias",
    "conv_{i}/bn1/scale": "convs.{i}.bn1.weight",
    "conv_{i}/bn1/bias": "convs.{i}.bn1.bias",
    "conv_{i}/bn2/scale": "convs.{i}.bn2.weight",
    "conv_{i}/bn2/bias": "convs.{i}.bn2.bias",
    "conv_to_fc/kernel": "conv_to_fc.weight^T",
    "conv_to_fc/bias": "conv_to_fc.bias",
    "fc_out/kernel": "fc_out.weight^T",
    "fc_out/bias": "fc_out.bias",
}


# ---- featurisation from the wire form (plain numpy, float64) ----------


def gaussian_expand(distances, radius: float, step: float,
                    dmin: float = 0.0) -> np.ndarray:
    mu = np.arange(dmin, radius + step, step, dtype=np.float64)
    d = np.asarray(distances, np.float64)[..., None]
    return np.exp(-((d - mu) ** 2) / step**2).astype(np.float32)


def neighbor_list(lattice, frac, radius: float, max_nbr: int):
    """Brute-force periodic neighbour search -> (centers, neighbors,
    distances), each atom's ``max_nbr`` nearest within ``radius``, self image
    excluded. Rows of ``lattice`` are the cell vectors."""
    lat = np.asarray(lattice, np.float64)
    frac = np.asarray(frac, np.float64)
    frac = frac - np.floor(frac)
    n = len(frac)
    inv = np.linalg.inv(lat)
    # images needed along axis k: radius over the spacing of its planes
    reach = [int(np.ceil(radius * np.linalg.norm(inv[:, k]) - 1e-12))
             for k in range(3)]
    grid = np.mgrid[-reach[0]:reach[0] + 1, -reach[1]:reach[1] + 1,
                    -reach[2]:reach[2] + 1].reshape(3, -1).T
    cart = frac @ lat
    shifts = grid.astype(np.float64) @ lat
    centers, neighbors, dists = [], [], []
    home = np.all(grid == 0, axis=1)
    for i in range(n):
        diff = cart[None, :, :] + shifts[:, None, :] - cart[i]  # [K, N, 3]
        d = np.sqrt((diff * diff).sum(-1))
        ok = d <= radius
        ok[home, i] = False
        kk, jj = np.nonzero(ok)
        order = np.argsort(d[kk, jj], kind="stable")[:max_nbr]
        centers.append(np.full(len(order), i, np.int32))
        neighbors.append(jj[order].astype(np.int32))
        dists.append(d[kk, jj][order])
    return (np.concatenate(centers), np.concatenate(neighbors),
            np.concatenate(dists))


def from_wire(lattice, frac, atom_fea, target, featurize: dict) -> dict:
    """One structure as the model reads it, featurized here from its wire
    record (``featurize``: radius, max_num_nbr, dmin, step); the atom feature
    rows are the data set's."""
    c, nb, d = neighbor_list(lattice, frac, featurize["radius"],
                             featurize["max_num_nbr"])
    return {"atom_fea": atom_fea, "centers": c, "neighbors": nb,
            "edge_fea": gaussian_expand(d, featurize["radius"],
                                        featurize["step"],
                                        featurize["dmin"]),
            "target": target}


def as_jnp(tree):
    return jax.tree_util.tree_map(jnp.asarray, tree)


def coo_batch(structures: list) -> dict:
    """Concatenate structures (dicts with atom_fea [N, A], edge_fea [E, K],
    centers, neighbors [E], target [T]) into one unpadded COO batch."""
    node_off = np.cumsum([0] + [len(s["atom_fea"]) for s in structures])
    return {
        "atom_fea": jnp.asarray(np.concatenate(
            [np.asarray(s["atom_fea"], np.float32) for s in structures])),
        "edge_fea": jnp.asarray(np.concatenate(
            [np.asarray(s["edge_fea"], np.float32) for s in structures])),
        "centers": jnp.asarray(np.concatenate(
            [np.asarray(s["centers"], np.int32) + node_off[k]
             for k, s in enumerate(structures)])),
        "neighbors": jnp.asarray(np.concatenate(
            [np.asarray(s["neighbors"], np.int32) + node_off[k]
             for k, s in enumerate(structures)])),
        "node_graph": jnp.asarray(np.repeat(
            np.arange(len(structures), dtype=np.int32),
            np.diff(node_off))),
        "targets": jnp.asarray(np.stack(
            [np.atleast_1d(np.asarray(s["target"], np.float32))
             for s in structures])),
    }


# ---- the model --------------------------------------------------------


def _mm_f32(x, w):
    return jnp.dot(x, w, precision=jax.lax.Precision.HIGHEST)


def _fake_fp8(x):
    """Rounding to float8 e4m3 (one scale per tensor, so that its largest
    entry sits at the format's largest, 448) with a straight-through
    gradient."""
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(x.dtype) * scale
    return x + jax.lax.stop_gradient(q - x)


def mm_fp8(x, w):
    """The control's matmul: both operands rounded to float8 e4m3, the
    precision below the configurations' bfloat16, accumulated exactly."""
    return _mm_f32(_fake_fp8(x), _fake_fp8(w))


def _bn(x, p, stats, train: bool):
    if train:
        mean = x.mean(axis=0)
        var = ((x - mean) ** 2).mean(axis=0)
    else:
        mean, var = stats["mean"], stats["var"]
    return (x - mean) / jnp.sqrt(var + BN_EPS) * p["scale"] + p["bias"]


def forward(params, batch_stats, batch, *, train: bool, mm=_mm_f32):
    """-> [G, T] standardised outputs for an unpadded COO batch."""
    n_graphs = batch["targets"].shape[0]
    v = mm(batch["atom_fea"], params["embedding"]["kernel"]) \
        + params["embedding"]["bias"]
    n = v.shape[0]
    i, j = batch["centers"], batch["neighbors"]
    n_conv = sum(1 for k in params if k.startswith("conv_")
                 and k != "conv_to_fc")
    for c in range(n_conv):
        p = params[f"conv_{c}"]
        st = batch_stats[f"conv_{c}"]
        z = jnp.concatenate([v[i], v[j], batch["edge_fea"]], axis=-1)
        z = mm(z, p["fc_full"]["kernel"]) + p["fc_full"]["bias"]
        z = _bn(z, p["bn1"], st["bn1"], train)
        f = z.shape[-1] // 2
        msg = jax.nn.sigmoid(z[:, :f]) * jax.nn.softplus(z[:, f:])
        agg = jax.ops.segment_sum(msg, i, num_segments=n)
        agg = _bn(agg, p["bn2"], st["bn2"], train)
        v = jax.nn.softplus(v + agg)
    ones = jnp.ones((n,), v.dtype)
    count = jax.ops.segment_sum(ones, batch["node_graph"], n_graphs)
    crys = jax.ops.segment_sum(v, batch["node_graph"], n_graphs) \
        / count[:, None]
    crys = mm(jax.nn.softplus(crys), params["conv_to_fc"]["kernel"]) \
        + params["conv_to_fc"]["bias"]
    crys = jax.nn.softplus(crys)
    return mm(crys, params["fc_out"]["kernel"]) + params["fc_out"]["bias"]


def loss_fn(params, batch_stats, batch, t_mean, t_std, mm=_mm_f32):
    out = forward(params, batch_stats, batch, train=True, mm=mm)
    return jnp.mean((out - (batch["targets"] - t_mean) / t_std) ** 2)


def predict(params, batch_stats, batch, t_mean, t_std, mm=_mm_f32):
    """Eval-mode predictions in the targets' own units, [G, T]."""
    with jax.default_matmul_precision("highest"):
        fwd = jax.jit(lambda p, s, b: forward(p, s, b, train=False, mm=mm))
        return np.asarray(fwd(params, batch_stats, batch) * t_std + t_mean)


def sgd_steps(params, batch_stats, batches: list, t_mean, t_std, *,
              lr: float, momentum: float, mm=_mm_f32) -> dict:
    """Follow the first ``len(batches)`` training steps of SGD with momentum
    (trace = g + momentum * trace; p -= lr * trace), one batch a step.

    -> {"loss": [per step], "grad": step 1's gradient (host arrays),
        "grad_norm": {leaf: its norm},
        "delta_norm": {leaf: norm of the parameters' change after all steps}}
    """
    grad = jax.jit(jax.value_and_grad(loss_fn), static_argnums=5)
    start = params
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, first_grad = [], None
    with jax.default_matmul_precision("highest"):
        for batch in batches:
            loss, g = grad(params, batch_stats, batch, t_mean, t_std, mm)
            if first_grad is None:
                first_grad = g
            trace = jax.tree_util.tree_map(
                lambda t, gg: gg + momentum * t, trace, g)
            params = jax.tree_util.tree_map(
                lambda p, t: p - lr * t, params, trace)
            losses.append(float(loss))
        delta = jax.tree_util.tree_map(lambda a, b: a - b, params, start)
    return {"loss": losses,
            "grad": jax.tree_util.tree_map(np.asarray, first_grad),
            "grad_norm": leaf_norms(first_grad),
            "delta_norm": leaf_norms(delta)}


def leaf_norms(tree) -> dict:
    """{"a/b/c": l2 norm} over a nested dict of arrays."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {
        "/".join(str(getattr(k, "key", k)) for k in path):
            float(jnp.sqrt(jnp.sum(jnp.square(
                jnp.asarray(leaf, jnp.float32)))))
        for path, leaf in flat
    }


# a leaf whose reference norm is under this share of the median leaf's is
# zero by construction (the fc_full bias, which BatchNorm removes): whatever
# a program reads there is the rounding of a sum that cancels, not a gradient
ZERO_LEAF = 1e-4


def median_leaf_diff(got, want) -> float:
    """Median over the leaves of two gradient trees of the norm of
    (got - want) against the reference leaf's own norm; leaves that are zero
    by construction are left out. Unlike a gap between two norms this reads
    rounding noise at first order; it sums over every entry of a leaf and
    takes the middle leaf, so it is steady from seed to seed (PERF.md
    section 2 has the readings that chose it)."""
    norms = leaf_norms(want)
    diff = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32),
        got, want))
    floor = float(np.median(list(norms.values())))
    return float(np.median([diff[k] / norms[k] for k in norms
                            if norms[k] >= ZERO_LEAF * floor]))


def leaf_gaps(got: dict, want: dict) -> list:
    """|got - want| of every leaf's norm, each against the reference's norm
    of that leaf or of the median leaf, whichever is larger (some gradients
    are small). Leaves that are zero by construction are left out."""
    floor = float(np.median(list(want.values())))
    return [abs(got[k] - want[k]) / max(want[k], floor, 1e-30)
            for k in want if want[k] >= ZERO_LEAF * floor]
