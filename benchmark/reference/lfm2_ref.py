"""The plain reference of the hybrid short-convolution / attention
mixture-of-experts decoder (LiquidAI's ``lfm2_moe`` block: LFM2-24B-A2B),
trained on next-token cross-entropy, as one chip of an expert-parallel layer
holds it. It imports nothing of the program (the norm, RoPE, the dense mask,
a head's attention, the SwiGLU and the walk over the stacked tree are
``reference/afmoe_ref.py``'s; the float8 matmul and the norms the comparison
reads ``reference/sdar_ref.py``'s).

float32 throughout, ``jax.default_matmul_precision("highest")`` around every
call; the convolution is an explicit loop over its three taps with the
document test written from the equation; the mask is built dense; attention
is one head and one sequence at a time over the whole ``[L, L]`` score array;
every held expert is a plain loop over its own rows; AdamW is written out.
Computed in blocks so that it fits beside its own 12 bytes a parameter: a
``jax.checkpoint`` a layer, one a head, one a chunk of the head's positions.

The equations, on the residual stream ``x [L, H]`` of one packed sequence
(``x0 = embed[tokens]``; position ``i`` has RoPE position ``i``):

    a layer: x += mixer(rms(x) * g_op);  x += ffn(rms(x) * g_ffn)
    a conv layer:  B, C, u = h W_in          (thirds of W_in's output)
        z_i = B_i * u_i
        c_i = sum_{t = 0..2} w[:, t] * z_{i - 2 + t}, a tap whose position
              i - 2 + t is before the sequence or in another document adds 0
        mixer = (C * c) W_out
    a full_attention layer: q, k, v = h W_q, h W_k, h W_v
        q, k = rope(rms_head(q) * g_q), rope(rms_head(k) * g_k)
        query i sees key j where doc(j) = doc(i) and j <= i
        mixer = (softmax_j(q_i . k_j / sqrt(d)) v) W_o   (4 query heads share
                a kv head)
    a dense layer:   ffn = (silu(h W_1) * (h W_3)) W_2
    an expert layer: s = sigmoid(h W_r) over ALL experts
        E = the 4 largest of s + b            (b: the selection bias, fixed)
        p_e = s_e / (sum over E of s + 1e-6)
        ffn = sum over e in E THAT ARE HELD HERE of
              p_e (silu(h W_1^e) * (h W_3^e)) W_2^e
    logits = (rms(x) * g_f) embed^T           over the vocabulary slice
    loss = -(1 / L) sum_i w_i log softmax(logits_i)[token_{i+1}], w_i = 0
           where token i + 1 is another document's or there is none; the
           mean over a step's sequences

The parameter tree is the program's (``embed``; ``dense/*`` the leading dense
layers stacked; ``periods/run<j>/*`` the expert layers: a period is cut into
runs of layers of one kind, and a leaf is ``[periods, layers of the run,
...]``; ``final_norm``; ``w_gate_up = [W_1 | W_3]`` everywhere; no head: the
embedding is the head); what it means is written here.

Departures: the experts not held add nothing (the share; the deployment's
other chips add the rest), and the vocabulary is the slice held. Both are
the configuration's cut, the same in the program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.reference.afmoe_ref import (  # noqa: F401
    _attention, _rms, _rope, _swiglu_mlp, dense_mask, layers_of,
)
from benchmark.reference.sdar_ref import (  # noqa: F401
    ADAM_EPS, _mm_f32, leaf_gaps, leaf_norms, median_leaf_diff, mm_fp8,
)

CONV = "conv"
TAPS = 3
# the ways the reference can be computed wrongly (the controls)
FAULTS = ("conv_crosses_documents", "conv_ungated", "conv_two_taps",
          "bias_unused", "softmax_scores", "untied_head", "no_head_norms")


def short_conv(bcu, w, segment_ids, faults=()):
    """``bcu [L, 3H]`` = ``[B | C | u]``, ``w [H, 3]``, ``segment_ids [L]``
    -> ``C * c [L, H]``: tap ``t`` reads position ``i - 2 + t``."""
    n, h = bcu.shape[0], bcu.shape[1] // 3
    b, c, u = bcu[:, :h], bcu[:, h:2 * h], bcu[:, 2 * h:]
    ungated = "conv_ungated" in faults
    z = u if ungated else b * u
    i = jnp.arange(n)
    out = jnp.zeros_like(z)
    for t in range(TAPS):
        if t == 0 and "conv_two_taps" in faults:
            continue
        j = i - (TAPS - 1) + t
        reads = j >= 0
        at = jnp.maximum(j, 0)
        if "conv_crosses_documents" not in faults:
            reads = reads & (segment_ids[at] == segment_ids)
        out = out + w[:, t][None, :] * jnp.where(reads[:, None], z[at], 0.0)
    return out if ungated else c * out


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
    if cfg["norm_topk_prob"]:
        p = p / (p.sum(axis=-1, keepdims=True) + 1e-6)
    return cfg["routed_scaling_factor"] * p, chosen


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


def _mixer(cfg, mm, faults, kind, h, p, segment_ids):
    if kind == CONV:
        y = short_conv(mm(h, p["w_in"]), p["conv_w"], segment_ids, faults)
        return mm(y, p["w_out"])
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, n = cfg["norm_eps"], h.shape[0]
    q = mm(h, p["wq"]).reshape(n, hq, d)
    k = mm(h, p["wk"]).reshape(n, hkv, d)
    v = mm(h, p["wv"]).reshape(n, hkv, d)
    if "no_head_norms" not in faults:
        q, k = _rms(q, p["q_norm"], eps), _rms(k, p["k_norm"], eps)
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    a = _attention(q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
                   dense_mask(segment_ids), mm)
    return mm(a.swapaxes(0, 1).reshape(n, hq * d), p["wo"])


def _layer(cfg, mm, faults, kind, x, p, bias, segment_ids):
    """One layer -> (x, counts [E] or None for a dense layer)."""
    eps = cfg["norm_eps"]
    x = x + _mixer(cfg, mm, faults, kind, _rms(x, p["op_norm"], eps), p,
                   segment_ids)
    h = _rms(x, p["ffn_norm"], eps)
    if "mlp_gate_up" in p:
        m, counts = _swiglu_mlp(h, p["mlp_gate_up"], p["mlp_down"], mm), None
    else:
        m, counts = _experts(h, p, bias, cfg, mm, faults)
    return x + m, counts


# positions of a sequence whose logits are held at once
HEAD_CHUNK = 2048
# the seed of the fault ``untied_head``'s own head
_UNTIED_SEED = 7


def sequence_loss(params, bias, tokens, segment_ids, loss_weight, cfg,
                  mm=_mm_f32, faults=()):
    """One sequence (``tokens, segment_ids, loss_weight [L]``; ``bias
    [periods, layers a period, E]``) -> (its loss, ``counts [expert layers,
    E]``)."""
    length = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)
    counts = []
    for kind, p, at in layers_of(params, cfg):
        b = None if at is None else bias[at]
        x, n = jax.checkpoint(functools.partial(
            _layer, cfg, mm, faults, kind))(x, p, b, segment_ids)
        if n is not None:
            counts.append(n)
    head = params["embed"].T
    if "untied_head" in faults:
        head = 0.02 * jax.random.normal(jax.random.key(_UNTIED_SEED),
                                        head.shape, jnp.float32)
    targets = jnp.roll(tokens, -1)
    chunk = HEAD_CHUNK if length % HEAD_CHUNK == 0 else length

    @jax.checkpoint
    def picked(rows):
        x_rows, tgt = rows
        h = _rms(x_rows, params["final_norm"], cfg["norm_eps"])
        logp = jax.nn.log_softmax(mm(h, head), axis=-1)
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


def adamw_steps(params, bias, batches: list, cfg, *, lr, b1, b2,
                weight_decay, mm=_mm_f32, faults=()) -> dict:
    """Follow the first ``len(batches)`` steps of AdamW (m = b1 m + (1 - b1)
    g; v = b2 v + (1 - b2) g^2; p -= lr (m^ / (sqrt(v^) + eps) + wd p), m^
    and v^ the moments over 1 - b^t; the decay on every leaf), one batch a
    step. The biases are no parameters and no step moves them. ``params``
    and ``bias`` are host arrays; they go to the device here.

    While a gradient is computed the device holds the parameters alone:
    both moments wait on the host.

    -> {"loss": [per step], "grad": step 1's gradient (host arrays),
        "grad_norm": {leaf: norm}, "delta_norm": {leaf: norm of the change
        after all steps}, "counts": [each step's counts [expert layers, E]]}
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
    losses, first_grad, all_counts = [], None, []
    with jax.default_matmul_precision("highest"):
        p = tmap(jnp.asarray, start)
        bias = jnp.asarray(bias, jnp.float32)
        m = v = None  # zero before the first step; on the host between steps
        for t, batch in enumerate(batches, 1):
            (loss, counts), g = grad(p, bias, tmap(jnp.asarray, batch))
            losses.append(float(loss))
            all_counts.append(np.array(counts))
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
            "counts": all_counts}


# ---- the uncut layer: what the shares add up to (the share test) --------

def full_expert_layer(h, router, bias, w_gate_up_all, w_down_all, cfg,
                      mm=_mm_f32):
    """All 64 experts held: ``sum_k p_k e_k(h)`` for ``h [T, H]``, computed
    densely (every expert on every row, weighted by its p or 0)."""
    p, chosen = route(h, router, bias, cfg, mm)
    out = jnp.zeros_like(h)
    for e in range(router.shape[1]):
        w = jnp.where(chosen == e, p, 0.0).sum(axis=-1)
        out = out + _swiglu_mlp(h, w_gate_up_all[e], w_down_all[e], mm) \
            * w[:, None]
    return out
