"""Plain reference for the Open Catalyst Project's baseline CGCNN: forward,
L1 loss, gradients and three Adam steps.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. Flat COO edge list, no padding,
no dense slot layout, no projected-then-gathered neighbour term, no kernels;
its own BatchNorm, LayerNorm and Adam. It imports nothing of the program
(``cgnn_tpu``) and is handed only what the benchmark itself made from the
seed: parameters, running statistics, the target mean/std, and structures.

Equations (Chanussot et al., ACS Catal. 11, 6059, arXiv:2010.09990;
``Open-Catalyst-Project/ocp`` ``ocpmodels/models/cgcnn.py``, ``CGCNNConv``
with ``aggr="add"``, sized by ``configs/is2re/all/cgcnn/cgcnn.yml``), for
atom i with neighbours j (at most 50, nearest first, within 6 A, periodic):

    e_ij   = exp(-1/2 (d_ij - mu_k)^2 / D^2)                GaussianSmearing:
             mu = linspace(0, 6, 100), D = 6/99             100 filters
    v0     = W_emb a_i + b_emb                              embedding_fc
    z_ij   = W_1 [v_i ; v_j ; e_ij] + b_1                   lin1, 2F+K -> 2F
    z_ij   = BN1(z_ij)                                      over all edges
    m_ij   = sigmoid(z_ij[:F]) * softplus(z_ij[F:])         gate * core
    v_i'   = softplus(LN1(sum_j m_ij) + v_i)                LayerNorm over F
    c      = mean_i v_i                                     global_mean_pool
    h      = softplus(W_c c + b_c)                          conv_to_fc
    h      = softplus(W_k h + b_k),  k = 0 .. n_h - 2       fcs
    y      = W_o h + b_o                                    fc_out
    loss   = mean_g |y_g - (E_g - mean) / std|              L1 ("mae")

against ``cgcnn_ref`` (txie-93/cgcnn): LayerNorm in BN2's place, no softplus
on the pooled vector before ``conv_to_fc``, a hidden stack, L1, Adam.

Departures from the source, as the system defines them:
- the atom input is the system's own 92-wide table (``data/elements.py``),
  not the source's ``khot`` embeddings; both feed ``Linear(92 -> F)``.
- BatchNorm normalises with the biased batch variance, eps 1e-5 (torch's);
  LayerNorm with the biased variance over a node's F features, eps 1e-5,
  affine (``torch.nn.LayerNorm``'s).
- edge features are the data set's, expanded on the host by the system's
  ``exp(-(d - mu)^2 / var^2)`` with var = sqrt(2) D: the same numbers as
  ``gaussian_smearing`` below (``tests/test_ocp_ref.py`` holds them to it).
- the optimizer is Adam (b1 0.9, b2 0.999, eps 1e-8) at a constant rate: the
  source's warm-up and milestones lie beyond a window of a few epochs.

Each conv is a ``jax.checkpoint``: at the published widths a batch of 32
slabs has ~270k edges, one conv's [E, 868] and [E, 768] float32
intermediates are ~4 GB, and six convs' kept for the reverse pass would not
fit a 16 GB chip; kept is what a conv is handed, the rest is computed again.
The arithmetic is the same.

Parameter names are the system's pytree, which maps onto the source's
``state_dict`` as PARAM_MAP says (kernels are stored [in, out], i.e. the
transpose of a torch ``weight``).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

# the float32 matmul, the float8 control's, BatchNorm, the COO batch and the
# leaf statistics are the first-order reference's own (same package, nothing
# of the program)
from benchmark.reference.cgcnn_ref import (  # noqa: F401
    _bn,
    _mm_f32,
    as_jnp,
    coo_batch,
    leaf_norms,
    mm_fp8,
)

LN_EPS = 1e-5
# torch.optim.Adam's and optax.adam's defaults
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8

# system pytree path -> source (torch) state_dict key
PARAM_MAP = {
    "embedding/kernel": "embedding_fc.weight^T",
    "embedding/bias": "embedding_fc.bias",
    "conv_{i}/fc_full/kernel": "convs.{i}.lin1.weight^T",
    "conv_{i}/fc_full/bias": "convs.{i}.lin1.bias",
    "conv_{i}/bn1/scale": "convs.{i}.bn1.weight",
    "conv_{i}/bn1/bias": "convs.{i}.bn1.bias",
    "conv_{i}/ln/scale": "convs.{i}.ln1.weight",
    "conv_{i}/ln/bias": "convs.{i}.ln1.bias",
    "conv_to_fc/kernel": "conv_to_fc.0.weight^T",
    "conv_to_fc/bias": "conv_to_fc.0.bias",
    "fc_{k}/kernel": "fcs.{2k}.weight^T",
    "fc_{k}/bias": "fcs.{2k}.bias",
    "fc_out/kernel": "fc_out.weight^T",
    "fc_out/bias": "fc_out.bias",
}


def gaussian_smearing(distances, start: float = 0.0, stop: float = 6.0,
                      num_gaussians: int = 100) -> np.ndarray:
    """The source's ``GaussianSmearing``: ``exp(coeff (d - mu)^2)`` with
    ``mu = linspace(start, stop, n)`` and ``coeff = -0.5 / (mu_1 - mu_0)^2``
    (plain numpy, float64)."""
    mu = np.linspace(start, stop, num_gaussians, dtype=np.float64)
    coeff = -0.5 / (mu[1] - mu[0]) ** 2
    d = np.asarray(distances, np.float64)[..., None]
    return np.exp(coeff * (d - mu) ** 2)


# ---- the model --------------------------------------------------------


def _ln(x, p):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["scale"] + p["bias"]


@functools.partial(jax.checkpoint, static_argnums=(6, 7))
def _conv(p, stats, v, edge_fea, i, j, train: bool, mm):
    z = jnp.concatenate([v[i], v[j], edge_fea], axis=-1)
    z = mm(z, p["fc_full"]["kernel"]) + p["fc_full"]["bias"]
    z = _bn(z, p["bn1"], stats["bn1"], train)
    f = z.shape[-1] // 2
    msg = jax.nn.sigmoid(z[:, :f]) * jax.nn.softplus(z[:, f:])
    agg = jax.ops.segment_sum(msg, i, num_segments=v.shape[0])
    return jax.nn.softplus(_ln(agg, p["ln"]) + v)


def n_hidden(params) -> int:
    """Hidden layers between ``conv_to_fc`` and ``fc_out`` (``fc_0`` ..)."""
    return sum(1 for k in params if k.startswith("fc_") and k != "fc_out")


def forward(params, batch_stats, batch, *, train: bool, mm=_mm_f32):
    """-> [G, 1] standardised outputs for an unpadded COO batch."""
    n_graphs = batch["targets"].shape[0]
    v = mm(batch["atom_fea"], params["embedding"]["kernel"]) \
        + params["embedding"]["bias"]
    n = v.shape[0]
    n_conv = sum(1 for k in params if k.startswith("conv_")
                 and k != "conv_to_fc")
    for c in range(n_conv):
        v = _conv(params[f"conv_{c}"], batch_stats[f"conv_{c}"], v,
                  batch["edge_fea"], batch["centers"], batch["neighbors"],
                  train, mm)
    count = jax.ops.segment_sum(jnp.ones((n,), v.dtype), batch["node_graph"],
                                n_graphs)
    h = jax.ops.segment_sum(v, batch["node_graph"], n_graphs) \
        / count[:, None]
    for name in ["conv_to_fc"] + [f"fc_{k}" for k in range(n_hidden(params))]:
        h = jax.nn.softplus(
            mm(h, params[name]["kernel"]) + params[name]["bias"])
    return mm(h, params["fc_out"]["kernel"]) + params["fc_out"]["bias"]


def loss_and_out(params, batch_stats, batch, t_mean, t_std, mm=_mm_f32):
    """-> (L1 loss on the standardised targets, the outputs [G, 1])."""
    out = forward(params, batch_stats, batch, train=True, mm=mm)
    return jnp.mean(jnp.abs(out - (batch["targets"] - t_mean) / t_std)), out


def adam_steps(params, batch_stats, batches: list, t_mean, t_std, *,
               lr: float, mm=_mm_f32) -> dict:
    """Follow the first ``len(batches)`` training steps of Adam (m = b1 m +
    (1 - b1) g; v = b2 v + (1 - b2) g^2; p -= lr m^ / (sqrt(v^) + eps), m^
    and v^ the moments over 1 - b^t), one batch a step.

    -> {"loss": [per step], "out": step 1's standardised outputs [G, 1],
        "grad": step 1's gradient (host arrays), "grad_norm": {leaf: norm},
        "delta_norm": {leaf: norm of the parameters' change after all steps},
        "params": the parameters after all steps (host arrays)}
    """
    grad = jax.jit(lambda p, b: jax.value_and_grad(
        loss_and_out, has_aux=True)(p, batch_stats, b, t_mean, t_std, mm))
    tmap = jax.tree_util.tree_map
    start = params
    m = tmap(jnp.zeros_like, params)
    v = tmap(jnp.zeros_like, params)
    losses, first_grad, first_out = [], None, None
    with jax.default_matmul_precision("highest"):
        for t, batch in enumerate(batches, 1):
            (loss, out), g = grad(params, batch)
            if first_grad is None:
                first_grad, first_out = g, out
            m = tmap(lambda a, gg: ADAM_B1 * a + (1 - ADAM_B1) * gg, m, g)
            v = tmap(lambda a, gg: ADAM_B2 * a + (1 - ADAM_B2) * gg * gg,
                     v, g)
            params = tmap(
                lambda p, a, b: p - lr * (a / (1 - ADAM_B1 ** t)) / (
                    jnp.sqrt(b / (1 - ADAM_B2 ** t)) + ADAM_EPS),
                params, m, v)
            losses.append(float(loss))
        delta = tmap(lambda a, b: a - b, params, start)
    return {"loss": losses, "out": np.asarray(first_out),
            "grad": tmap(np.asarray, first_grad),
            "grad_norm": leaf_norms(first_grad),
            "delta_norm": leaf_norms(delta),
            "params": tmap(np.asarray, params)}
