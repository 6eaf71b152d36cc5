"""The plain reference of the block-diffusion mixture-of-experts decoder
(SDAR-30B-A3B-Chat's block, Qwen3-MoE's; BD3-LM's objective), as one chip of
an expert-parallel layer holds it. It imports nothing of the program.

float32 throughout, ``jax.default_matmul_precision("highest")`` around every
call; the mask is built dense from the equations; attention is one head and
one sequence at a time over the whole ``[2L, 2L]`` score array; every held
expert is a plain loop over its own rows; AdamW is written out. Computed in
blocks so that it fits beside its own 12 bytes a parameter: a
``jax.checkpoint`` a layer, one a head.

The equations, on the residual stream ``x [2L, H]`` of one sequence ``x_t (+)
x_0`` (``L`` noised positions, then the ``L`` clean ones; position ``i`` of
either half has RoPE position ``i`` and block ``b(i) = i // B``):

    h = rms(x) * g_a;   q, k, v = h W_q, h W_k, h W_v
    q, k = rope(rms_head(q) * g_q), rope(rms_head(k) * g_k)
    s_ij = q_i . k_j / sqrt(d)      where query i sees key j:
        noised i, noised j:  b(j) == b(i)
        noised i, clean  j:  b(j) <  b(i)
        clean  i, clean  j:  b(j) <= b(i)
        clean  i, noised j:  never
        and, under packing, only where i and j are of one document
    x += softmax_j(s) v W_o         (8 query heads share a key-value head)
    h = rms(x) * g_m;   p = softmax(h W_r) over ALL experts
    the 8 largest p kept, renormalised to sum 1
    x += sum over the kept experts e THAT ARE HELD HERE of
         p_e (silu(h W_g^e) * (h W_u^e)) W_d^e
    logits = (rms(x_noised) * g_f) W_head       over the vocabulary slice
    loss = -(1 / L) sum_i w_i log softmax(logits_i)[x_0^i],  w_i = 1[x_t^i
           = MASK] / t_b(i); the mean over a step's sequences

The parameter tree is the program's (``embed``, ``layers/*`` stacked on a
leading layer axis with ``w_gate_up = [W_g | W_u]``, ``final_norm``,
``head``); what it means is written here.

Departures: the experts not held add nothing (the share; the deployment's
other seven chips add the rest), and the vocabulary is the slice held. Both
are the configuration's cut, the same in the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

ADAM_EPS = 1e-8
_MASKED = -1e30


def _mm_f32(a, b):
    return jnp.matmul(a, b)


def _fake_fp8(x):
    q = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(q - x)


def mm_fp8(a, b):
    """The control's matmul: both operands rounded to float8 e4m3, the
    precision below the configuration's bfloat16, accumulated exactly."""
    return _mm_f32(_fake_fp8(a), _fake_fp8(b))


def dense_mask(seq_len: int, block: int, segment_ids, causal: bool = False):
    """``[2L, 2L]`` bool from the equations; ``segment_ids [L]``. ``causal``
    is the control: the plain causal mask over the 2L positions."""
    n = 2 * seq_len
    i = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]
    if causal:
        shown = j <= i
    else:
        i_clean, j_clean = i >= seq_len, j >= seq_len
        bi, bj = (i % seq_len) // block, (j % seq_len) // block
        shown = jnp.where(
            i_clean,
            j_clean & (bj <= bi),
            jnp.where(j_clean, bj < bi, bj == bi))
    seg = jnp.concatenate([segment_ids, segment_ids])
    return shown & (seg[:, None] == seg[None, :])


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """``x [N, heads, d]``; position ``i mod (N / 2)``; rotate-half."""
    n, _, d = x.shape
    pos = (jnp.arange(n) % (n // 2)).astype(jnp.float32)
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    half = jnp.concatenate([-x[..., d // 2:], x[..., : d // 2]], axis=-1)
    return x * cos + half * sin


def _attention(q, k, v, mask, mm):
    """``q [Hq, N, d]``, ``k, v [Hkv, N, d]``: one head at a time, the
    whole score array of a head, a checkpoint a head."""
    group = q.shape[0] // k.shape[0]
    scale = 1.0 / math.sqrt(q.shape[-1])

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args
        s = jnp.where(mask, mm(qh, kh.T) * scale, _MASKED)
        return mm(jax.nn.softmax(s, axis=-1), vh)

    return jax.lax.map(one_head, (q, jnp.repeat(k, group, axis=0),
                                  jnp.repeat(v, group, axis=0)))


# rows of one expert computed at a time (its run is read in chunks of this
# many rows; a chunk past the run's end is skipped, not computed)
EXPERT_CHUNK = 1024


def _experts(h, p, cfg, mm, dropped_rows):
    """The held experts' part for ``h [T, H]`` -> (``[T, H]``, the largest
    number of rows any held expert got). Every held expert is a loop over
    its own rows: the (token, choice) pairs are sorted by expert, and expert
    ``e`` reads its run ``EXPERT_CHUNK`` rows at a time (the rows past the
    run's end are masked in its last chunk; the chunks after it are skipped
    by a ``lax.cond``, so an expert costs what its rows cost, whatever the
    load). ``dropped_rows`` is the control: each expert computes only the
    first ``dropped_rows`` of its rows."""
    first, count = cfg["experts_held"]
    k = cfg["num_experts_per_tok"]
    t = h.shape[0]
    inter = p["w_down"].shape[1]
    probs = jax.nn.softmax(mm(h, p["router"]), axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    top = top / top.sum(axis=-1, keepdims=True)
    flat_e = chosen.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sw = top.reshape(-1)[order]
    st = jnp.repeat(jnp.arange(t), k)[order]
    sizes = (flat_e[:, None] == jnp.arange(p["router"].shape[1])).sum(0)
    starts = jnp.cumsum(sizes) - sizes
    chunk = min(EXPERT_CHUNK, t)
    n_chunks = -(-t // chunk)  # an expert gets a token at most once
    # room past the end so that a slice never runs off the arrays
    st = jnp.concatenate([st, jnp.zeros((n_chunks * chunk,), st.dtype)])
    sw = jnp.concatenate([sw, jnp.zeros((n_chunks * chunk,), sw.dtype)])
    out = jnp.zeros_like(h)
    for e in range(first, first + count):
        w_gu, w_d = p["w_gate_up"][e - first], p["w_down"][e - first]
        n_rows = sizes[e] if dropped_rows is None else jnp.minimum(
            sizes[e], dropped_rows)

        def rows_of(out, c, e=e, w_gu=w_gu, w_d=w_d, n_rows=n_rows):
            def work(out):
                at = starts[e] + c * chunk
                rows = jax.lax.dynamic_slice_in_dim(st, at, chunk)
                w = jax.lax.dynamic_slice_in_dim(sw, at, chunk)
                w = jnp.where(c * chunk + jnp.arange(chunk) < n_rows, w, 0.0)
                gu = mm(h[rows], w_gu)
                y = mm(jax.nn.silu(gu[:, :inter]) * gu[:, inter:], w_d)
                return out.at[rows].add(y * w[:, None])

            return jax.lax.cond(c * chunk < n_rows, work, lambda o: o,
                                out), None

        out, _ = jax.lax.scan(rows_of, out, jnp.arange(n_chunks))
    held = jax.lax.dynamic_slice_in_dim(sizes, first, count)
    return out, held.max()


def _layer(cfg, mm, dropped_rows, mask, x, p):
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    n = x.shape[0]
    h = _rms(x, p["attn_norm"], eps)
    q = mm(h, p["wq"]).reshape(n, hq, d)
    k = mm(h, p["wk"]).reshape(n, hkv, d)
    v = mm(h, p["wv"]).reshape(n, hkv, d)
    q = _rope(_rms(q, p["q_norm"], eps), theta)
    k = _rope(_rms(k, p["k_norm"], eps), theta)
    a = _attention(q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
                   mask, mm)
    x = x + mm(a.swapaxes(0, 1).reshape(n, hq * d), p["wo"])
    out, most = _experts(_rms(x, p["moe_norm"], eps), p, cfg, mm,
                         dropped_rows)
    return x + out, most


def sequence_loss(params, tokens, segment_ids, loss_weight, cfg, mm=_mm_f32,
                  causal_mask=False, unweighted=False, dropped_rows=None):
    """One sequence (``tokens [2L]``, ``segment_ids [L]``, ``loss_weight
    [L]``) -> (its loss, the most rows a held expert got in any layer)."""
    length = segment_ids.shape[0]
    mask = dense_mask(length, cfg["block_length"], segment_ids, causal_mask)
    x = jnp.take(params["embed"], tokens, axis=0)
    layer = jax.checkpoint(functools.partial(
        _layer, cfg, mm, dropped_rows, mask))
    x, most = jax.lax.scan(layer, x, params["layers"])
    h = _rms(x[:length], params["final_norm"], cfg["rms_norm_eps"])
    logp = jax.nn.log_softmax(mm(h, params["head"]), axis=-1)
    picked = jnp.take_along_axis(logp, tokens[length:, None], axis=-1)[:, 0]
    w = (loss_weight > 0).astype(jnp.float32) if unweighted else loss_weight
    return -(w * picked).sum() / length, most.max()


def batch_loss(params, batch: dict, cfg, **kw):
    """The mean over the batch's sequences, one sequence at a time."""
    losses, most = jax.lax.map(
        lambda row: sequence_loss(params, *row, cfg, **kw),
        (batch["tokens"], batch["segment_ids"], batch["loss_weight"]))
    return losses.mean(), most.max()


def adamw_steps(params, batches: list, cfg, *, lr, b1, b2, weight_decay,
                mm=_mm_f32, **fault) -> dict:
    """Follow the first ``len(batches)`` steps of AdamW (m = b1 m + (1 - b1)
    g; v = b2 v + (1 - b2) g^2; p -= lr (m^ / (sqrt(v^) + eps) + wd p), m^
    and v^ the moments over 1 - b^t; the decay on every leaf), one batch a
    step. ``params`` are host arrays; they go to the device here.

    While a gradient is computed the device holds the parameters alone:
    both moments wait on the host (the gradient's temporaries and 16 bytes
    a parameter do not fit the chip together).

    -> {"loss": [per step], "grad": step 1's gradient (host arrays),
        "grad_norm": {leaf: norm}, "delta_norm": {leaf: norm of the change
        after all steps}, "expert_rows_most": the most rows a held expert got
        in any layer of any step}
    """
    tmap = jax.tree_util.tree_map
    grad = jax.jit(jax.value_and_grad(
        lambda p, b: batch_loss(p, b, cfg, mm=mm, **fault), has_aux=True))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, m, v, g, t):
        m = tmap(lambda a, gg: b1 * a + (1 - b1) * gg, m, g)
        v = tmap(lambda a, gg: b2 * a + (1 - b2) * gg * gg, v, g)
        p = tmap(lambda w, a, b: w - lr * (
            (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + ADAM_EPS)
            + weight_decay * w), p, m, v)
        return p, m, v

    start = params
    losses, first_grad, most = [], None, 0
    with jax.default_matmul_precision("highest"):
        p = tmap(jnp.asarray, start)
        m = v = None  # zero before the first step; on the host between steps
        for t, batch in enumerate(batches, 1):
            (loss, rows), g = grad(p, tmap(jnp.asarray, batch))
            losses.append(float(loss))
            most = max(most, int(rows))
            if first_grad is None:
                first_grad = tmap(np.array, g)
            m, v = (tmap(jnp.zeros_like, p) if x is None
                    else tmap(jnp.asarray, x) for x in (m, v))
            p, m, v = update(p, m, v, g, jnp.float32(t))
            del g
            if t < len(batches):
                m, v = tmap(np.array, m), tmap(np.array, v)
        after = tmap(np.array, p)
    del p, m, v
    return {"loss": losses, "grad": first_grad,
            "grad_norm": leaf_norms(first_grad),
            "delta_norm": leaf_norms(tmap(lambda a, b: a - np.asarray(b),
                                          after, start)),
            "expert_rows_most": most}


def leaf_norms(tree) -> dict:
    """{"a/b": l2 norm} over a nested dict of host arrays."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", k)) for k in path):
            float(np.sqrt(np.sum(np.square(np.asarray(leaf, np.float64)))))
            for path, leaf in flat}


def median_leaf_diff(got, want) -> float:
    """Median over the leaves of ||got - want|| / ||want||."""
    norms = leaf_norms(want)
    diff = leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a, np.float32) - np.asarray(b, np.float32),
        got, want))
    return float(np.median([diff[k] / max(norms[k], 1e-30) for k in norms]))


def leaf_gaps(got: dict, want: dict) -> list:
    """|got - want| of every leaf's norm against the reference's norm of
    that leaf or of the median leaf, whichever is larger."""
    floor = float(np.median(list(want.values())))
    return [abs(got[k] - want[k]) / max(want[k], floor, 1e-30) for k in want]


# ---- the uncut layer: what the shares add up to (the share test) --------

def full_expert_layer(h, router, w_gate_up_all, w_down_all, k: int,
                      mm=_mm_f32):
    """All experts held: ``sum_k p_k e_k(h)`` for ``h [T, H]``, computed
    densely (every expert on every row, weighted by its p or 0)."""
    probs = jax.nn.softmax(mm(h, router), axis=-1)
    top, chosen = jax.lax.top_k(probs, k)
    top = top / top.sum(axis=-1, keepdims=True)
    inter = w_down_all.shape[1]
    out = jnp.zeros_like(h)
    for e in range(router.shape[1]):
        w = jnp.where(chosen == e, top, 0.0).sum(axis=-1)
        gu = mm(h, w_gate_up_all[e])
        y = mm(jax.nn.silu(gu[:, :inter]) * gu[:, inter:], w_down_all[e])
        out = out + y * w[:, None]
    return out
