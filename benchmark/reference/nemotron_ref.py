"""The plain reference of the hybrid Mamba-2 / attention mixture-of-experts
decoder (NVIDIA's ``nemotron_h`` block: Nemotron-3-Nano-30B-A3B), trained on
next-token cross-entropy, as one chip of an expert-parallel layer holds it.
It imports nothing of the program (the norm, RoPE, the dense mask and a
head's attention are ``reference/afmoe_ref.py``'s; the float8 matmul and the
norms the comparison reads ``reference/sdar_ref.py``'s).

float32 throughout, ``jax.default_matmul_precision("highest")`` around every
call. The Mamba layer is computed POSITION BY POSITION: the filter an
explicit loop over its four taps with the document test written from the
equation, the state a ``lax.scan`` over the ``L`` positions that sets it to 0
at a document's first; no chunks anywhere. The mask is built dense; attention
is one head and one sequence at a time over the whole ``[L, L]`` score array;
every held expert is a plain loop over its own rows; AdamW is written out.
Computed in blocks so that it fits beside its own 12 bytes a parameter: a
``jax.checkpoint`` a layer, one every 64 positions of the Mamba layer's walk
(which keeps the state there and walks the positions between again in the
reverse pass: what is computed is the recurrence), one a head of the
attention, one a chunk of the head's positions.

The equations, on the residual stream ``x [L, H]`` of one packed sequence
(``x0 = embed[tokens]``), every layer ONE block under ONE norm by its letter
in ``hybrid_override_pattern``:

    a layer: x += f(rms(x) * g)
    M: z, xBC, dt = h W_in                 (split in this order)
       c_i = b_conv + sum_{t = 0..3} w[:, t] * xBC_{i - 3 + t}, a tap whose
             position i - 3 + t is before the sequence or in another
             document adds 0;   xBC = silu(c)
       x, B, C = xBC                       (64 heads of 64; 8 groups of 128;
                                            head h reads group h // 8)
       d_t = softplus(dt_t + dt_bias);  a_t = exp(-exp(A_log) d_t)
       S_t = a_t S_{t-1} + d_t x_t (x) B_t, S_{t-1} = 0 at a document's
             first position;   y_t = S_t C_t + D x_t
       g = y * silu(z);  g = g / sqrt(mean over each group of 512 channels
           (g^2) + eps) * w_norm;   f = g W_out
    *: q, k, v = h W_q, h W_k, h W_v       (no norm, nothing rotated)
       query i sees key j where doc(j) = doc(i) and j <= i
       f = (softmax_j(q_i . k_j / sqrt(d)) v) W_o   (16 query heads share a
           kv head)
    E: s = sigmoid(h W_r) over ALL experts
       K = the 6 largest of s + b          (b: the selection bias, fixed)
       p_e = 2.5 s_e / (sum over K of s + 1e-20)
       f = relu(h W_up^s)^2 W_down^s + sum over e in K THAT ARE HELD HERE
           of p_e relu(h W_up^e)^2 W_down^e
    logits = (rms(x) * g_f) W_head          over the vocabulary slice
    loss = -(1 / L) sum_i w_i log softmax(logits_i)[token_{i+1}], w_i = 0
           where token i + 1 is another document's or there is none; the
           mean over a step's sequences

The parameter tree is the program's (``embed``; ``periods/run<j>`` the
layers, a period cut into repeated groups of kinds: a group of one kind holds
its leaves ``[periods, layers of the run, ...]``, a group of several a dict
of them a kind, a leaf ``[periods, repeats, ...]``; ``final_norm``;
``head``); what it means is written here.

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

from benchmark.reference.afmoe_ref import (  # noqa: F401
    _attention, _rms, _rope, dense_mask,
)
from benchmark.reference.sdar_ref import (  # noqa: F401
    ADAM_EPS, _mm_f32, leaf_gaps, leaf_norms, median_leaf_diff, mm_fp8,
)

KINDS = {"M": "mamba", "E": "moe", "*": "attention"}
# the ways the reference can be computed wrongly (the controls)
FAULTS = ("state_crosses_documents", "conv_crosses_documents",
          "conv_three_taps", "no_conv_bias", "dt_not_softplus", "no_dt_bias",
          "no_skip_D", "one_group", "norm_before_gate", "norm_ungrouped",
          "attention_rotated", "silu_experts", "no_shared_expert",
          "bias_unused", "weights_unscaled")


# ---- the Mamba layer, position by position ------------------------------

def conv_silu(xbc, w, b, segment_ids, faults=()):
    """``xbc [L, C]``, ``w [C, taps]``, ``b [C]``, ``segment_ids [L]`` ->
    ``silu(conv + b) [L, C]``: tap ``t`` reads position ``i - (taps - 1) +
    t``."""
    n, taps = xbc.shape[0], w.shape[1]
    i = jnp.arange(n)
    out = jnp.zeros_like(xbc)
    for t in range(taps):
        if t == 0 and "conv_three_taps" in faults:
            continue
        j = i - (taps - 1) + t
        reads = j >= 0
        at = jnp.maximum(j, 0)
        if "conv_crosses_documents" not in faults:
            reads = reads & (segment_ids[at] == segment_ids)
        out = out + w[:, t][None, :] * jnp.where(reads[:, None], xbc[at], 0.0)
    if "no_conv_bias" not in faults:
        out = out + b[None, :]
    return jax.nn.silu(out)


# positions between two kept states: the reverse pass keeps the state at
# every ``KEEP``-th position and walks the positions between them again (a
# state is ``[64, 64, 128]`` floats, 2 MB: all 4,096 of a sequence would be
# 8.6 GB). What is computed is the recurrence, a position at a time.
KEEP = 64


def _recurrence(x, d, a, b, c, skip, first, state_dtype):
    """``x [L, heads, P]``, ``d, a [L, heads]``, ``b, c [L, heads, N]``
    (each head's group's), ``skip [heads]``, ``first [L]`` bool (a
    document's first position) -> ``y [L, heads, P]``. The state ``[heads,
    P, N]`` is carried in ``state_dtype`` and computed in float32."""
    def step(state, at):
        xt, dt, a_t, bt, ct, starts = at
        state = jnp.where(starts, 0.0, a_t[:, None, None]
                          * state.astype(jnp.float32))
        state = state + dt[:, None, None] * xt[:, :, None] * bt[:, None, :]
        state = state.astype(state_dtype)
        y = (state.astype(jnp.float32) * ct[:, None, :]).sum(axis=-1)
        return state, y + skip[:, None] * xt

    @jax.checkpoint
    def walk(state, rows):
        return jax.lax.scan(step, state, rows)

    n = x.shape[0]
    keep = math.gcd(n, KEEP)
    zero = jnp.zeros((x.shape[1], x.shape[2], b.shape[2]), state_dtype)
    y = jax.lax.scan(walk, zero, tuple(
        v.reshape(n // keep, keep, *v.shape[1:])
        for v in (x, d, a, b, c, first)))[1]
    return y.reshape(x.shape)


def _gate_norm(y, z, scale, groups, eps, faults):
    """``y * silu(z)``, then the RMSNorm over each group's channels."""
    def norm(g):
        if "norm_ungrouped" in faults:
            by = g[:, None, :]
        else:
            by = g.reshape(g.shape[0], groups, -1)
        by = by * jax.lax.rsqrt(jnp.mean(by * by, axis=-1, keepdims=True)
                                + eps)
        return by.reshape(g.shape)

    if "norm_before_gate" in faults:
        return norm(y) * scale * jax.nn.silu(z)
    return norm(y * jax.nn.silu(z)) * scale


def mamba_mixer(h, p, segment_ids, cfg, mm=_mm_f32, faults=(),
                state_dtype="float32"):
    """``h [L, H]`` (normed) -> the layer's ``f [L, H]``."""
    n = h.shape[0]
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, ns = cfg["n_groups"], cfg["ssm_state_size"]
    inner, per = heads * hd, heads // groups
    conv = inner + 2 * groups * ns
    zxd = mm(h, p["w_in"])
    z, xbc, dt = zxd[:, :inner], zxd[:, inner:inner + conv], \
        zxd[:, inner + conv:]
    xbc = conv_silu(xbc, p["conv_w"], p["conv_b"], segment_ids, faults)
    x = xbc[:, :inner].reshape(n, groups, per, hd)
    b = xbc[:, inner:inner + groups * ns].reshape(n, groups, ns)
    c = xbc[:, inner + groups * ns:].reshape(n, groups, ns)
    if "one_group" in faults:
        b, c = (jnp.broadcast_to(v[:, :1], v.shape) for v in (b, c))
    if "no_dt_bias" not in faults:
        dt = dt + p["dt_bias"][None, :]
    d = jnp.maximum(dt, 0.0) if "dt_not_softplus" in faults \
        else jax.nn.softplus(dt)
    a = jnp.exp(-jnp.exp(p["a_log"])[None, :] * d)
    first = jnp.concatenate([jnp.ones((1,), bool),
                             segment_ids[1:] != segment_ids[:-1]])
    if "state_crosses_documents" in faults:
        first = jnp.arange(n) == 0
    skip = jnp.zeros_like(p["d_skip"]) if "no_skip_D" in faults \
        else p["d_skip"]
    # head h reads group h // (heads a group)
    y = _recurrence(x.reshape(n, heads, hd), d, a,
                    jnp.repeat(b, per, axis=1), jnp.repeat(c, per, axis=1),
                    skip, first, jnp.dtype(state_dtype)).reshape(n, inner)
    g = _gate_norm(y, z, p["gate_norm"], groups, cfg["layer_norm_epsilon"],
                   faults)
    return mm(g, p["w_out"])


# ---- attention, experts --------------------------------------------------

def attention_mixer(h, p, segment_ids, cfg, mm=_mm_f32, faults=()):
    hq, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    n = h.shape[0]
    q = mm(h, p["wq"]).reshape(n, hq, d)
    k = mm(h, p["wk"]).reshape(n, hkv, d)
    v = mm(h, p["wv"]).reshape(n, hkv, d)
    if "attention_rotated" in faults:
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    a = _attention(q.swapaxes(0, 1), k.swapaxes(0, 1), v.swapaxes(0, 1),
                   dense_mask(segment_ids), mm)
    return mm(a.swapaxes(0, 1).reshape(n, hq * d), p["wo"])


def _expert_mlp(h, w_up, w_down, mm, faults=()):
    up = mm(h, w_up)
    if "silu_experts" in faults:
        return mm(jax.nn.silu(up), w_down)
    r = jnp.maximum(up, 0.0)
    return mm(r * r, w_down)


def route(h, router, bias, cfg, mm, faults=()):
    """-> (``p [T, k]``, ``chosen [T, k]``) over all experts."""
    s = jax.nn.sigmoid(mm(h, router))
    pick = s if "bias_unused" in faults else s + bias
    _, chosen = jax.lax.top_k(pick, cfg["num_experts_per_tok"])
    p = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["norm_topk_prob"]:
        p = p / (p.sum(axis=-1, keepdims=True) + 1e-20)
    if "weights_unscaled" not in faults:
        p = cfg["routed_scaling_factor"] * p
    return p, chosen


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
        e, w_up, w_down = held

        def rows_of(out, c):
            def work(out):
                at = starts[e] + c * chunk
                rows = jax.lax.dynamic_slice_in_dim(st, at, chunk)
                w = jax.lax.dynamic_slice_in_dim(sw, at, chunk)
                w = jnp.where(c * chunk + jnp.arange(chunk) < sizes[e], w,
                              0.0)
                y = _expert_mlp(h[rows], w_up, w_down, mm, faults)
                return out.at[rows].add(y * w[:, None])

            return jax.lax.cond(c * chunk < sizes[e], work, lambda o: o,
                                out), None

        return jax.lax.scan(rows_of, out, jnp.arange(n_chunks))[0], None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(first, first + count), p["w_up"], p["w_down"]))
    return out, sizes


def _layer(cfg, mm, faults, state_dtype, kind, x, p, bias, segment_ids):
    """One layer -> (x, counts [E] or None for a layer that routes
    nothing)."""
    h = _rms(x, p["norm"], cfg["layer_norm_epsilon"])
    if kind == "mamba":
        return x + mamba_mixer(h, p, segment_ids, cfg, mm, faults,
                               state_dtype), None
    if kind == "attention":
        return x + attention_mixer(h, p, segment_ids, cfg, mm, faults), None
    m, counts = _experts(h, p, bias, cfg, mm, faults)
    if "no_shared_expert" not in faults:
        m = m + _expert_mlp(h, p["shared_up"], p["shared_down"], mm, faults)
    return x + m, counts


def layers_of(params, cfg):
    """The stack in order: [(kind, the layer's own weights, (period, place
    among the period's expert layers) of its biases or None)]. A run of the
    tree holds ``repeats`` layers of each of its kinds; a layer takes the
    next free place of its kind in the first run that is not full."""
    pattern = cfg["hybrid_override_pattern"]
    runs = [params["periods"][f"run{j}"]
            for j in range(len(params["periods"]))]

    def of_kinds(run):  # {kind or None: leaves}
        nested = all(isinstance(v, dict) for v in run.values())
        return run if nested else {None: run}

    leading = jax.tree_util.tree_leaves(runs[0])[0].shape
    n_periods = leading[0]
    per_period = len(pattern) // n_periods
    out = []
    for period in range(n_periods):
        j, taken, routed = 0, {}, 0
        for letter in pattern[period * per_period:(period + 1) * per_period]:
            kind = KINDS[letter]
            kinds = of_kinds(runs[j])
            name = kind if kind in kinds else None
            leaves = kinds[name]
            repeats = jax.tree_util.tree_leaves(leaves)[0].shape[1]
            place = taken.get(name, 0)
            p = {k: v[period, place] for k, v in leaves.items()}
            taken[name] = place + 1
            at = None
            if kind == "moe":
                at, routed = (period, routed), routed + 1
            out.append((kind, p, at))
            if all(taken.get(k, 0) == repeats for k in kinds):
                j, taken = j + 1, {}
    return out


# positions of a sequence whose logits are held at once
HEAD_CHUNK = 2048


def sequence_loss(params, bias, tokens, segment_ids, loss_weight, cfg,
                  mm=_mm_f32, faults=(), state_dtype="float32"):
    """One sequence (``tokens, segment_ids, loss_weight [L]``; ``bias
    [periods, expert layers a period, E]``) -> (its loss, ``counts [expert
    layers, E]``)."""
    length = tokens.shape[0]
    x = jnp.take(params["embed"], tokens, axis=0)
    counts = []
    for kind, p, at in layers_of(params, cfg):
        b = None if at is None else bias[at]
        x, n = jax.checkpoint(functools.partial(
            _layer, cfg, mm, faults, state_dtype, kind))(x, p, b,
                                                         segment_ids)
        if n is not None:
            counts.append(n)
    targets = jnp.roll(tokens, -1)
    chunk = HEAD_CHUNK if length % HEAD_CHUNK == 0 else length

    @jax.checkpoint
    def picked(rows):
        x_rows, tgt = rows
        h = _rms(x_rows, params["final_norm"], cfg["layer_norm_epsilon"])
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


def adamw_steps(params, bias, batches: list, cfg, *, lr, b1, b2,
                weight_decay, mm=_mm_f32, faults=(),
                state_dtype="float32") -> dict:
    """Follow the first ``len(batches)`` steps of AdamW (m = b1 m + (1 - b1)
    g; v = b2 v + (1 - b2) g^2; p -= lr (m^ / (sqrt(v^) + eps) + wd p), m^
    and v^ the moments over 1 - b^t; the decay on every leaf, the Mamba
    layers' ``a_log``, ``dt_bias``, ``d_skip`` and filter bias too, as the
    program's optimizer decays every leaf), one batch a step. The biases are
    no parameters and no step moves them. ``params`` and ``bias`` are host
    arrays; they go to the device here.

    While a gradient is computed the device holds the parameters alone:
    both moments wait on the host.

    -> {"loss": [per step], "grad": step 1's gradient (host arrays),
        "grad_norm": {leaf: norm}, "delta_norm": {leaf: norm of the change
        after all steps}, "counts": [each step's counts [expert layers, E]]}
    """
    tmap = jax.tree_util.tree_map
    grad = jax.jit(jax.value_and_grad(
        lambda p, b, batch: batch_loss(
            p, b, batch, cfg, mm=mm, faults=tuple(faults),
            state_dtype=state_dtype), has_aux=True))

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

def full_expert_layer(h, router, bias, w_up_all, w_down_all, shared_up,
                      shared_down, cfg, mm=_mm_f32):
    """All 128 experts held, and the shared one: ``e_shared(h) + sum_k p_k
    e_k(h)`` for ``h [T, H]``, computed densely (every expert on every row,
    weighted by its p or 0)."""
    p, chosen = route(h, router, bias, cfg, mm)
    out = _expert_mlp(h, shared_up, shared_down, mm)
    for e in range(router.shape[1]):
        w = jnp.where(chosen == e, p, 0.0).sum(axis=-1)
        out = out + _expert_mlp(h, w_up_all[e], w_down_all[e], mm) \
            * w[:, None]
    return out
