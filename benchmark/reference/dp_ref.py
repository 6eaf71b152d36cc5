"""Plain reference for the data-parallel (DDP) training step.

The step of ``cgcnn_ref`` run the way ``torch.nn.parallel.DistributedDataParallel``
runs ``txie-93/cgcnn``'s ``main.py`` on D devices, one shard at a time in a
Python loop: no mesh, no ``shard_map``, no collective, nothing imported from
the program (``cgnn_tpu``). float32, matmul precision ``highest``.

A step takes D batches ``B_0..B_{D-1}``. For shard d, from the same
parameters and running statistics:

    y_d      = f(theta; B_d)       BatchNorm normalises with B_d's OWN moments
    L_d      = mean over B_d's structures of (y - (t - mu) / sigma)^2
    g_d      = dL_d / dtheta
    s_d      = 0.9 * stats + 0.1 * (B_d's moments; the variance unbiased)

    g        = (1/D) sum_d g_d     the all-reduce
    stats'   = (1/D) sum_d s_d
    theta'   = SGD(theta, g)       trace = g + momentum * trace; -= lr * trace

Shards may hold different numbers of structures (snug packing fills
capacities), so the mean of per-shard means is not the mean over the global
batch: the reference follows the program, and DDP does the same.

Departures from torch DDP, as the system defines them:
- the running statistics are AVERAGED over the shards; torch DDP keeps
  rank 0's (``broadcast_buffers`` sends them to the others);
- the reported loss of a step is the sum of squared errors over all shards
  over the count of all structures (metric sums are all-reduced exactly),
  where ``main.py`` prints each rank's own mean.

``variant`` computes the step wrongly in one of the ways a broken collective
would (the controls of the comparison; a sound run must differ from each):
``"grad_unaveraged"`` applies shard 0's gradient alone, ``"sync_bn"``
normalises with the moments of all shards' rows together (SyncBatchNorm),
``"stats_unaveraged"`` keeps shard 0's running statistics.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmark.reference import cgcnn_ref as ref

BN_MOMENTUM = 0.1
VARIANTS = (None, "grad_unaveraged", "sync_bn", "stats_unaveraged")


def _bn_train(x, p, moments: list):
    """``cgcnn_ref._bn`` in train mode, noting the batch's moments and its
    number of rows for the running statistics."""
    mean = x.mean(axis=0)
    var = ((x - mean) ** 2).mean(axis=0)
    moments.append((mean, var, x.shape[0]))
    return (x - mean) / jnp.sqrt(var + ref.BN_EPS) * p["scale"] + p["bias"]


def forward(params, batch, mm=ref._mm_f32):
    """``cgcnn_ref.forward(train=True)`` line for line, returning the
    moments of every BatchNorm as well -> ([G, T] outputs, moments in the
    order bn1, bn2 of conv 0, 1, ...)."""
    moments: list = []
    n_graphs = batch["targets"].shape[0]
    v = mm(batch["atom_fea"], params["embedding"]["kernel"]) \
        + params["embedding"]["bias"]
    n = v.shape[0]
    i, j = batch["centers"], batch["neighbors"]
    n_conv = sum(1 for k in params if k.startswith("conv_")
                 and k != "conv_to_fc")
    for c in range(n_conv):
        p = params[f"conv_{c}"]
        z = jnp.concatenate([v[i], v[j], batch["edge_fea"]], axis=-1)
        z = mm(z, p["fc_full"]["kernel"]) + p["fc_full"]["bias"]
        z = _bn_train(z, p["bn1"], moments)
        f = z.shape[-1] // 2
        msg = jax.nn.sigmoid(z[:, :f]) * jax.nn.softplus(z[:, f:])
        agg = jax.ops.segment_sum(msg, i, num_segments=n)
        agg = _bn_train(agg, p["bn2"], moments)
        v = jax.nn.softplus(v + agg)
    ones = jnp.ones((n,), v.dtype)
    count = jax.ops.segment_sum(ones, batch["node_graph"], n_graphs)
    crys = jax.ops.segment_sum(v, batch["node_graph"], n_graphs) \
        / count[:, None]
    crys = mm(jax.nn.softplus(crys), params["conv_to_fc"]["kernel"]) \
        + params["conv_to_fc"]["bias"]
    crys = jax.nn.softplus(crys)
    out = mm(crys, params["fc_out"]["kernel"]) + params["fc_out"]["bias"]
    return out, moments


def _shard_loss(params, batch, weights, t_mean, t_std, mm):
    """sum_g w_g * (squared error of structure g), with what the running
    statistics and the metric sums need -> (loss, (moments, sum of squared
    errors))."""
    out, moments = forward(params, batch, mm)
    se = ((out - (batch["targets"] - t_mean) / t_std) ** 2).sum(axis=-1)
    return (se * weights).sum(), (moments, se.sum())


# one jitted function for every call: a shard's shapes recur from step to step
# and from seed to seed, and the limits' readings follow a dozen seeds
_GRAD = jax.jit(jax.value_and_grad(_shard_loss, has_aux=True),
                static_argnums=5)


def _running(batch_stats, moments: list):
    """The running statistics after one batch with these moments."""
    new, k = {}, iter(moments)
    for c in sorted(batch_stats):  # conv_0, conv_1, ...: forward's order
        new[c] = {}
        for name in ("bn1", "bn2"):
            mean, var, rows = next(k)
            unbiased = var * rows / max(rows - 1.0, 1.0)
            old = batch_stats[c][name]
            new[c][name] = {
                "mean": (1 - BN_MOMENTUM) * old["mean"] + BN_MOMENTUM * mean,
                "var": (1 - BN_MOMENTUM) * old["var"]
                + BN_MOMENTUM * unbiased,
            }
    return new


def _tree_mean(trees: list):
    return jax.tree_util.tree_map(lambda *xs: sum(xs) / len(xs), *trees)


def ddp_steps(params, batch_stats, groups: list, t_mean, t_std, *,
              lr: float, momentum: float, mm=ref._mm_f32,
              variant: str | None = None) -> dict:
    """Follow ``len(groups)`` data-parallel SGD steps. ``groups[s][d]`` is
    the list of structures (as ``cgcnn_ref.coo_batch`` takes them) of shard
    d in step s.

    -> what ``cgcnn_ref.sgd_steps`` returns (``loss`` a step as the program
    reports it, ``grad`` the first step's applied gradient, ``grad_norm``,
    ``delta_norm``) and ``params``, ``batch_stats`` after the last step,
    ``loss_sum`` and ``count`` a step.
    """
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r} (known: {VARIANTS})")
    grad = _GRAD
    start = params
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    out: dict = {"loss": [], "loss_sum": [], "count": []}
    first_grad = None
    with jax.default_matmul_precision("highest"):
        for shards in groups:
            n_dev = len(shards)
            if variant == "sync_bn":
                # one forward over every shard's rows: the moments are then
                # those of the global batch, and the loss is still the mean
                # of the per-shard means
                batch = ref.coo_batch([s for sh in shards for s in sh])
                w = jnp.asarray(np.concatenate(
                    [np.full(len(sh), 1.0 / (n_dev * len(sh)), np.float32)
                     for sh in shards]))
                (_l, (moments, se)), g = grad(params, batch, w, t_mean,
                                              t_std, mm)
                new_stats = _running(batch_stats, moments)
            else:
                gs, stats_d, se = [], [], 0.0
                for sh in shards:  # one shard at a time: no collective
                    batch = ref.coo_batch(sh)
                    w = jnp.full((len(sh),), 1.0 / len(sh), jnp.float32)
                    (_l, (moments, se_d)), g_d = grad(
                        params, batch, w, t_mean, t_std, mm)
                    gs.append(g_d)
                    stats_d.append(_running(batch_stats, moments))
                    se = se + se_d
                g = gs[0] if variant == "grad_unaveraged" else _tree_mean(gs)
                new_stats = (stats_d[0] if variant == "stats_unaveraged"
                             else _tree_mean(stats_d))
            count = sum(len(sh) for sh in shards)
            out["loss_sum"].append(float(se))
            out["count"].append(count)
            out["loss"].append(float(se) / count)
            if first_grad is None:
                first_grad = g
            trace = jax.tree_util.tree_map(
                lambda t, gg: gg + momentum * t, trace, g)
            params = jax.tree_util.tree_map(
                lambda p, t: p - lr * t, params, trace)
            batch_stats = new_stats
        delta = jax.tree_util.tree_map(lambda a, b: a - b, params, start)
    as_np = lambda tree: jax.tree_util.tree_map(np.asarray, tree)  # noqa: E731
    out.update(grad=as_np(first_grad), grad_norm=ref.leaf_norms(first_grad),
               delta_norm=ref.leaf_norms(delta), params=as_np(params),
               batch_stats=as_np(batch_stats))
    return out
