"""The plain reference of the window-and-full-attention mixture-of-experts
decoder (arcee-ai's ``afmoe`` block: Trinity-Mini), trained on next-token
cross-entropy, as one chip of an expert-parallel layer holds it. It imports
nothing of the program (the float8 matmul and the norms the comparison reads
are ``reference/sdar_ref.py``'s).

float32 throughout, ``jax.default_matmul_precision("highest")`` around every
call; the masks are built dense from the equations; attention is one head and
one sequence at a time over the whole ``[L, L]`` score array; every held
expert is a plain loop over its own rows; AdamW and the biases' update are
written out. Computed in blocks so that it fits beside its own 12 bytes a
parameter: a ``jax.checkpoint`` a layer, one a head, one a chunk of the head's
positions.

The equations, on the residual stream ``x [L, H]`` of one packed sequence
(``x0 = embed[tokens] * sqrt(H)``; position ``i`` has RoPE position ``i``):

    h = rms(x) * g_in;   q, k, v, g = h W_q, h W_k, h W_v, h W_g
    q, k = rms_head(q) * g_q, rms_head(k) * g_k
    a sliding_attention layer: q, k = rope(q), rope(k); query i sees key j
        where doc(j) = doc(i) and i - window < j <= i
    a full_attention layer: no rope; where doc(j) = doc(i) and j <= i
    a = softmax_j(q_i . k_j / sqrt(d)) v    (8 query heads share a kv head)
    x += rms((a * sigmoid(g)) W_o) * g_post_attn
    h = rms(x) * g_pre_mlp
    a dense layer:   m = (silu(h W_gate) * (h W_up)) W_down
    an expert layer: s = sigmoid(h W_r) over ALL experts
        E = the 8 largest of s + b            (b: the selection bias)
        p_e = route_scale * s_e / (sum over E of s + 1e-20)
        m = shared(h) + sum over e in E THAT ARE HELD HERE of
            p_e (silu(h W_g^e) * (h W_u^e)) W_d^e
    x += rms(m) * g_post_mlp
    logits = (rms(x) * g_f) W_head            over the vocabulary slice
    loss = -(1 / L) sum_i w_i log softmax(logits_i)[token_{i+1}], w_i = 0
           where token i + 1 is another document's or there is none; the
           mean over a step's sequences
    after the optimizer's step, an expert layer's biases, with n_e the
    step's tokens that chose e: d = coeff * sign(mean(n) - n); b += d - mean(d)

The parameter tree is the program's (``embed``; ``dense/*`` the leading dense
layers stacked; ``periods/run<j>/*`` the expert layers: a period is cut into
runs of layers of one kind, and a leaf is ``[periods, layers of the run,
...]``; ``final_norm``; ``head``; ``w_gate_up = [W_g | W_u]`` everywhere);
what it means is written here.

Departures: the experts not held add nothing (the share; the deployment's
other chips add the rest), and the vocabulary is the slice held. Both are
the configuration's cut, the same in the program.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# what the comparison's rows read and the float8 control, the sibling
# reference's (as force_ref.py and ocp_ref.py take cgcnn_ref.py's)
from benchmark.reference.sdar_ref import (  # noqa: F401
    ADAM_EPS, _mm_f32, leaf_gaps, leaf_norms, median_leaf_diff, mm_fp8,
)

_MASKED = -1e30
SLIDING = "sliding_attention"
# the ways the reference can be computed wrongly (the controls)
FAULTS = ("no_window", "rope_everywhere", "bias_unused", "softmax_scores",
          "no_shared", "ungated")


def dense_mask(segment_ids, window: int = 0):
    """``[L, L]`` bool from the equations; ``segment_ids [L]``; ``window`` 0
    is the full layer's mask."""
    n = segment_ids.shape[0]
    i = jnp.arange(n)[:, None]
    j = jnp.arange(n)[None, :]
    shown = j <= i
    if window:
        shown = shown & (j > i - window)
    return shown & (segment_ids[:, None] == segment_ids[None, :])


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g


def _rope(x, theta):
    """``x [N, heads, d]``; position ``i``; rotate-half."""
    n, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv[None, :]
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


def _swiglu_mlp(h, w_gate_up, w_down, mm):
    inter = w_down.shape[0]
    gu = mm(h, w_gate_up)
    return mm(jax.nn.silu(gu[:, :inter]) * gu[:, inter:], w_down)


def route(h, router, bias, cfg, mm, faults=()):
    """-> (``p [T, k]``, ``chosen [T, k]``) over all experts."""
    logits = mm(h, router)
    if "softmax_scores" in faults:
        s = jax.nn.softmax(logits, axis=-1)
    else:
        s = jax.nn.sigmoid(logits)
    pick = s if "bias_unused" in faults else s + bias
    _, chosen = jax.lax.top_k(pick, cfg["num_experts_per_tok"])
    p = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["route_norm"]:
        p = p / (p.sum(axis=-1, keepdims=True) + 1e-20)
    return cfg["route_scale"] * p, chosen


# rows of one expert computed at a time (its run is read in chunks of this
# many rows; a chunk past the run's end is skipped, not computed)
EXPERT_CHUNK = 1024


def _experts(h, p, bias, cfg, mm, faults):
    """The held experts' part for ``h [T, H]`` -> (``[T, H]``, ``counts
    [E]``: the tokens that chose each of ALL experts). Every held expert is
    a loop over its own rows: the (token, choice) pairs are sorted by
    expert, and expert ``e`` reads its run ``EXPERT_CHUNK`` rows at a time
    (the rows past the run's end are masked in its last chunk; the chunks
    after it are skipped by a ``lax.cond``)."""
    first, count = cfg["experts_held"]
    k = cfg["num_experts_per_tok"]
    t = h.shape[0]
    top, chosen = route(h, p["router"], bias, cfg, mm, faults)
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

    def one_expert(out, held):
        e, w_gu, w_d = held

        def rows_of(out, c):
            def work(out):
                at = starts[e] + c * chunk
                rows = jax.lax.dynamic_slice_in_dim(st, at, chunk)
                w = jax.lax.dynamic_slice_in_dim(sw, at, chunk)
                w = jnp.where(c * chunk + jnp.arange(chunk) < sizes[e], w,
                              0.0)
                y = _swiglu_mlp(h[rows], w_gu, w_d, mm)
                return out.at[rows].add(y * w[:, None])

            return jax.lax.cond(c * chunk < sizes[e], work, lambda o: o,
                                out), None

        return jax.lax.scan(rows_of, out, jnp.arange(n_chunks))[0], None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(first, first + count), p["w_gate_up"], p["w_down"]))
    return out, sizes


def _layer(cfg, mm, faults, kind, x, p, bias, segment_ids):
    """One layer -> (x, counts [E] or None for a dense layer)."""
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, n = cfg["rms_norm_eps"], x.shape[0]
    sliding = kind == SLIDING
    h = _rms(x, p["attn_norm"], eps)
    q = _rms(mm(h, p["wq"]).reshape(n, hq, d), p["q_norm"], eps)
    k = _rms(mm(h, p["wk"]).reshape(n, hkv, d), p["k_norm"], eps)
    v = mm(h, p["wv"]).reshape(n, hkv, d)
    if sliding or "rope_everywhere" in faults:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    window = cfg["sliding_window"] if sliding and "no_window" not in faults \
        else 0
    a = _attention(q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
                   dense_mask(segment_ids, window), mm)
    a = a.swapaxes(0, 1).reshape(n, hq * d)
    if "ungated" not in faults:
        a = a * jax.nn.sigmoid(mm(h, p["wg"]))
    x = x + _rms(mm(a, p["wo"]), p["post_attn_norm"], eps)
    h = _rms(x, p["mlp_norm"], eps)
    if "mlp_gate_up" in p:
        m, counts = _swiglu_mlp(h, p["mlp_gate_up"], p["mlp_down"], mm), None
    else:
        m, counts = _experts(h, p, bias, cfg, mm, faults)
        if "no_shared" not in faults:
            m = m + _swiglu_mlp(h, p["shared_gate_up"], p["shared_down"], mm)
    return x + _rms(m, p["post_mlp_norm"], eps), counts


def layers_of(params, cfg):
    """The stack in order: [(kind, the layer's own weights, (period, place)
    of its biases or None)]."""
    types, nd = list(cfg["layer_types"]), cfg["num_dense_layers"]
    runs = [params["periods"][f"run{j}"]
            for j in range(len(params["periods"]))]
    in_run = [jax.tree_util.tree_leaves(r)[0].shape[1] for r in runs]
    # place in the period -> (run, place in the run)
    places = [(j, o) for j, n in enumerate(in_run) for o in range(n)]
    out = []
    for i, kind in enumerate(types):
        if i < nd:
            p, at = {k: v[i] for k, v in params["dense"].items()}, None
        else:
            at = divmod(i - nd, len(places))
            j, o = places[at[1]]
            p = {k: v[at[0], o] for k, v in runs[j].items()}
        out.append((kind, p, at))
    return out


# positions of a sequence whose logits are held at once
HEAD_CHUNK = 2048


def sequence_loss(params, bias, tokens, segment_ids, loss_weight, cfg,
                  mm=_mm_f32, faults=()):
    """One sequence (``tokens, segment_ids, loss_weight [L]``; ``bias
    [periods, layers a period, E]``) -> (its loss, ``counts [expert layers,
    E]``)."""
    length = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg["mup_enabled"]:
        x = x * math.sqrt(cfg["hidden_size"])
    counts = []
    for kind, p, at in layers_of(params, cfg):
        b = None if at is None else bias[at]
        x, n = jax.checkpoint(functools.partial(
            _layer, cfg, mm, faults, kind))(x, p, b, segment_ids)
        if n is not None:
            counts.append(n)
    targets = jnp.roll(tokens, -1)
    chunk = HEAD_CHUNK if length % HEAD_CHUNK == 0 else length

    @jax.checkpoint
    def picked(rows):
        x_rows, tgt = rows
        h = _rms(x_rows, params["final_norm"], cfg["rms_norm_eps"])
        logp = jax.nn.log_softmax(mm(h, params["head"]), axis=-1)
        return jnp.take_along_axis(logp, tgt[:, None], axis=-1)[:, 0]

    logp = jax.lax.map(picked, (x.reshape(-1, chunk, x.shape[-1]),
                                targets.reshape(-1, chunk))).reshape(-1)
    return -(loss_weight * logp).sum() / length, jnp.stack(counts)


def batch_loss(params, bias, batch: dict, cfg, **kw):
    """The mean over the batch's sequences, one sequence at a time -> (loss,
    the step's ``counts [expert layers, E]``)."""
    losses, counts = jax.lax.map(
        lambda row: sequence_loss(params, bias, *row, cfg, **kw),
        (batch["tokens"], batch["segment_ids"], batch["loss_weight"]))
    return losses.mean(), counts.sum(axis=0)


def bias_update(bias, counts, coeff: float):
    """The biases after a step: ``counts`` as many as ``bias``, layer by
    layer. Centred: a layer's biases keep their mean."""
    n = np.asarray(counts, np.float32).reshape(np.shape(bias))
    d = np.float32(coeff) * np.sign(n.mean(axis=-1, keepdims=True) - n)
    return (np.asarray(bias, np.float32) + d
            - d.mean(axis=-1, keepdims=True, dtype=np.float32))


def adamw_steps(params, bias, batches: list, cfg, *, lr, b1, b2,
                weight_decay, mm=_mm_f32, faults=()) -> dict:
    """Follow the first ``len(batches)`` steps of AdamW (m = b1 m + (1 - b1)
    g; v = b2 v + (1 - b2) g^2; p -= lr (m^ / (sqrt(v^) + eps) + wd p), m^
    and v^ the moments over 1 - b^t; the decay on every leaf) and of the
    biases (``bias_update``, after the optimizer's step, from the counts of
    the step's forward pass), one batch a step. ``params`` and ``bias`` are
    host arrays; they go to the device here.

    While a gradient is computed the device holds the parameters alone:
    both moments wait on the host.

    -> {"loss": [per step], "grad": step 1's gradient (host arrays),
        "grad_norm": {leaf: norm}, "delta_norm": {leaf: norm of the change
        after all steps}, "bias": [the biases after each step], "counts":
        [each step's counts [expert layers, E]]}
    """
    tmap = jax.tree_util.tree_map
    grad = jax.jit(jax.value_and_grad(
        lambda p, b, batch: batch_loss(p, b, batch, cfg, mm=mm,
                                       faults=tuple(faults)), has_aux=True))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def update(p, m, v, g, t):
        m = tmap(lambda a, gg: b1 * a + (1 - b1) * gg, m, g)
        v = tmap(lambda a, gg: b2 * a + (1 - b2) * gg * gg, v, g)
        p = tmap(lambda w, a, b: w - lr * (
            (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + ADAM_EPS)
            + weight_decay * w), p, m, v)
        return p, m, v

    start = params
    bias = np.asarray(bias, np.float32)
    losses, first_grad, biases, all_counts = [], None, [], []
    with jax.default_matmul_precision("highest"):
        p = tmap(jnp.asarray, start)
        m = v = None  # zero before the first step; on the host between steps
        for t, batch in enumerate(batches, 1):
            (loss, counts), g = grad(p, jnp.asarray(bias),
                                     tmap(jnp.asarray, batch))
            losses.append(float(loss))
            all_counts.append(np.array(counts))
            if first_grad is None:
                first_grad = tmap(np.array, g)
            m, v = (tmap(jnp.zeros_like, p) if x is None
                    else tmap(jnp.asarray, x) for x in (m, v))
            p, m, v = update(p, m, v, g, jnp.float32(t))
            del g
            bias = bias_update(bias, all_counts[-1],
                               cfg["load_balance_coeff"])
            biases.append(bias)
            if t < len(batches):
                m, v = tmap(np.array, m), tmap(np.array, v)
        after = tmap(np.array, p)
    del p, m, v
    return {"loss": losses, "grad": first_grad,
            "grad_norm": leaf_norms(first_grad),
            "delta_norm": leaf_norms(tmap(lambda a, b: a - np.asarray(b),
                                          after, start)),
            "bias": biases, "counts": all_counts}


def bias_diff_share(got: list, want: list, coeff: float) -> float:
    """The share of the biases (every expert layer's, after each step) that
    are not the reference's: further off than half of the ``coeff`` one
    step moves them by."""
    off = [np.abs(np.asarray(g) - np.asarray(w)) > 0.5 * coeff
           for g, w in zip(got, want)]
    return float(np.mean(off))


# ---- the uncut layer: what the shares add up to (the share test) --------

def full_expert_layer(h, router, bias, w_gate_up_all, w_down_all,
                      shared_gate_up, shared_down, cfg, mm=_mm_f32):
    """All experts held, and the shared one: ``shared(h) + sum_k p_k
    e_k(h)`` for ``h [T, H]``, computed densely (every expert on every row,
    weighted by its p or 0)."""
    p, chosen = route(h, router, bias, cfg, mm)
    out = _swiglu_mlp(h, shared_gate_up, shared_down, mm)
    for e in range(router.shape[1]):
        w = jnp.where(chosen == e, p, 0.0).sum(axis=-1)
        out = out + _swiglu_mlp(h, w_gate_up_all[e], w_down_all[e], mm) \
            * w[:, None]
    return out
