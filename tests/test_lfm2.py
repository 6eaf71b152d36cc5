"""The hybrid short-convolution / attention mixture-of-experts decoder
(models/lfm2.py, ops/short_conv.py, the stack of models/lm_blocks.py,
ops/moe.py's router with a fixed bias, train/lm_step.py's causal step over a
tied head) at a tiny size on the CPU, against the plain reference
(benchmark/reference/lfm2_ref.py), which imports nothing of the program.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.kinds import bd_train  # noqa: E402
from benchmark.reference import lfm2_ref as ref  # noqa: E402
from cgnn_tpu.data import tokens  # noqa: E402
from cgnn_tpu.models import lfm2, lm_blocks  # noqa: E402
from cgnn_tpu.ops import moe  # noqa: E402
from cgnn_tpu.ops.masked_attention import (  # noqa: E402
    StaticMask, masked_attention,
)
from cgnn_tpu.ops.short_conv import short_conv, taps_cut  # noqa: E402
from cgnn_tpu.train import Normalizer, make_optimizer  # noqa: E402
from cgnn_tpu.train.lm_step import (  # noqa: E402
    make_lm_train_step, step_metrics,
)
from cgnn_tpu.train.state import TrainState  # noqa: E402

L = 32
C, F = lfm2.CONV, lfm2.FULL
# one dense layer, then two periods of (full, conv, conv)
TYPES = (C, F, C, C, F, C, C)
CFG = lfm2.Lfm2Config(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_hidden_layers=7, num_dense_layers=1, layer_types=TYPES,
    intermediate_size=96, moe_intermediate_size=32, n_experts=16,
    num_experts_per_tok=4, experts_held=(4, 4), vocab_size=128,
    dtype="float32")
REF_CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "layer_types": list(TYPES), "num_dense_layers": 1,
    "num_experts_per_tok": 4, "experts_held": (4, 4), "rope_theta": 1e6,
    "norm_eps": 1e-5, "norm_topk_prob": True, "routed_scaling_factor": 1.0}
ADAMW = dict(lr=1e-3, b1=0.9, b2=0.95, weight_decay=0.1)


def _pool(seed=0, n=6):
    return tokens.make_pool(n, L, vocab_size=CFG.vocab_size, seed=seed,
                            doc_median=12, doc_min=2, doc_max=L,
                            kind="causal")


def _params(seed, cfg=CFG):
    p = lfm2.init_params(cfg, jax.random.key(seed), std=0.3)
    # norm scales off 1, so that a dropped scale shows
    return jax.tree_util.tree_map_with_path(
        lambda path, x: (x * (1.0 + 0.1 * jnp.cos(jnp.arange(
            x.size, dtype=jnp.float32).reshape(x.shape)))).astype(jnp.float32)
        if "norm" in str(path[-1]) else x, p)


def _bias(seed, cfg=CFG):
    """Biases off 0, so that a dropped bias shows."""
    shape = cfg.stats_shapes()["router_bias"]
    return jax.random.uniform(jax.random.key(100 + seed), shape, jnp.float32,
                              -0.1, 0.1)


def _state(params, bias, cfg=CFG):
    tx = make_optimizer("adamw", lr=ADAMW["lr"], b1=ADAMW["b1"],
                        b2=ADAMW["b2"], weight_decay=ADAMW["weight_decay"],
                        lr_milestones=[])
    return TrainState(
        step=jnp.zeros((), jnp.int32), params=params,
        batch_stats={"router_bias": bias}, opt_state=tx.init(params),
        normalizer=Normalizer.identity(1), rng=jax.random.key(0),
        apply_fn=functools.partial(lfm2.apply, cfg), tx=tx)


def _as_ref(b):
    return {"tokens": b.tokens, "segment_ids": b.segment_ids,
            "loss_weight": b.loss_weight}


# ---- the convolution --------------------------------------------------

# documents of 3, 1, 2 and 4 tokens: a document of one token, and every
# kind of start (the sequence's, a document's first and second position)
SEG = np.asarray([0, 0, 0, 1, 2, 2, 3, 3, 3, 3], np.int32)


def _conv_inputs(seed=0, n=len(SEG), h=5):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (n, 3 * h)),
            jax.random.normal(ks[1], (h, 3)),
            jax.random.normal(ks[2], (n, h)))


def _by_hand(bcu, w, seg):
    """The equation, a position and a tap at a time, in numpy."""
    bcu, w = np.asarray(bcu, np.float64), np.asarray(w, np.float64)
    n, h = bcu.shape[0], bcu.shape[1] // 3
    z = bcu[:, :h] * bcu[:, 2 * h:]
    out = np.zeros((n, h))
    for i in range(n):
        for t in range(3):
            j = i - 2 + t
            if j >= 0 and seg[j] == seg[i]:
                out[i] += w[:, t] * z[j]
    return bcu[:, h:2 * h] * out


# position -> the taps (t = 0 two earlier, 1 one earlier, 2 itself) it reads
EDGES = [(0, {2}), (1, {1, 2}), (2, {0, 1, 2}), (3, {2}), (4, {2}),
         (5, {1, 2}), (6, {2}), (7, {1, 2}), (8, {0, 1, 2}), (9, {0, 1, 2})]


@pytest.mark.parametrize("i,taps", EDGES)
def test_every_edge_of_the_convolution(i, taps):
    """Position ``i`` of ``SEG`` reads exactly ``taps``: with B = C = 1 and
    ``u`` one-hot at position ``j``, the output at ``i`` is the tap that
    reads ``j``, or 0."""
    h = 2
    w = jnp.asarray([[0.25, 0.5, 1.0]] * h)
    for t in range(3):
        j = i - 2 + t
        if j < 0:
            assert t not in taps
            continue
        u = np.zeros((len(SEG), h), np.float32)
        u[j] = 1.0
        bcu = jnp.concatenate([jnp.ones((len(SEG), 2 * h)), u], axis=-1)
        for fn in (lambda: short_conv(bcu[None], w, SEG[None])[0],
                   lambda: ref.short_conv(bcu, w, jnp.asarray(SEG))):
            got = float(fn()[i, 0])
            assert got == (float(w[0, t]) if t in taps else 0.0), (t, got)


@pytest.mark.parametrize("which", ["program", "reference"])
def test_the_convolution_is_its_equation_by_hand(which):
    bcu, w, _ = _conv_inputs()
    want = _by_hand(bcu, w, SEG)
    got = (short_conv(bcu[None], w, SEG[None])[0] if which == "program"
           else ref.short_conv(bcu, w, jnp.asarray(SEG)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    # 1 tap cut at a second position, 2 at a first: 4 documents, and the
    # one-token document has no second position
    assert int(taps_cut(SEG[None])) == 4 * 2 + 3 * 1
    with pytest.raises(ValueError, match="taps"):
        short_conv(bcu[None], w[:, :2], SEG[None])


def test_the_convolution_s_gradient_is_the_loop_s():
    bcu, w, ct = _conv_inputs(1)
    seg = jnp.asarray(SEG)
    got = jax.grad(lambda a, b: (short_conv(a[None], b, seg[None])[0]
                                 * ct).sum(), argnums=(0, 1))(bcu, w)
    want = jax.grad(lambda a, b: (ref.short_conv(a, b, seg) * ct).sum(),
                    argnums=(0, 1))(bcu, w)
    for g, v in zip(got, want):
        assert float(jnp.abs(v).max()) > 0.1
        np.testing.assert_allclose(g, v, rtol=2e-5, atol=2e-6)


def test_the_convolution_rounds_once_in_the_compute_dtype():
    bcu, w, _ = _conv_inputs(2)
    got = short_conv(bcu.astype(jnp.bfloat16)[None], w, SEG[None])[0]
    assert got.dtype == jnp.bfloat16
    want = _by_hand(bcu.astype(jnp.bfloat16).astype(jnp.float32), w, SEG)
    np.testing.assert_allclose(got.astype(jnp.float32), want, rtol=1e-2,
                               atol=1e-2)


# ---- nothing crosses a document ---------------------------------------

@pytest.mark.parametrize("kind", [C, F])
def test_nothing_crosses_a_document(kind):
    """A mixer's output at the positions of other documents does not move
    when one position's input does; later positions of its own do."""
    params = _params(0)
    run = "run0" if kind == F else "run1"
    p = jax.tree_util.tree_map(lambda a: a[0, 0], params["periods"][run])
    seg = jnp.asarray(_pool(2).segment_ids[:1])
    x = jax.random.normal(jax.random.key(3), (1, L, 64))
    j = 3
    own = np.asarray(seg[0]) == int(seg[0, j])
    assert own[j + 1] and not own.all()
    a = lfm2._mixer(CFG, kind, x, p, seg)
    b = lfm2._mixer(CFG, kind, x.at[0, j].add(1.0), p, seg)
    moved = np.abs(np.asarray(a - b)[0]).max(axis=-1) > 0
    assert not moved[~own].any() and moved[j] and moved[j + 1]
    assert not moved[:j].any()  # causal


# ---- heads of 64 ------------------------------------------------------

def _dense_attention(q, k, v, seg):
    group = q.shape[1] // k.shape[1]
    out = []
    for s in range(q.shape[0]):
        mask = ref.dense_mask(seg[s])
        ks, vs = (jnp.repeat(t[s], group, axis=0) for t in (k, v))
        scores = jnp.where(mask, jnp.einsum("hqd,hkd->hqk", q[s], ks), -1e30)
        out.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1),
                              vs))
    return jnp.stack(out)


def test_heads_of_64_through_the_composition_and_the_attention():
    """``prepare_heads`` at 64 lanes takes the composition (its kernel takes
    whole 128-lane tiles) and agrees with the reference's norm and RoPE;
    ``masked_attention`` at 64 lanes agrees with the dense mask."""
    n, d, hq, hkv = L, 64, 4, 2
    assert not lm_blocks.fused.supported(8192, d)
    ks = jax.random.split(jax.random.key(5), 5)
    xq = jax.random.normal(ks[0], (2, n, hq * d))
    xk = jax.random.normal(ks[1], (2, n, hkv * d))
    g = 1.0 + 0.1 * jax.random.normal(ks[2], (d,))
    v = jax.random.normal(ks[3], (2, hkv, n, d))
    pos = jnp.arange(n, dtype=jnp.int32)
    q = lm_blocks.prepare_heads(xq, g, pos, theta=1e6, eps=1e-5,
                                scale=1.0 / 8.0)
    k = lm_blocks.prepare_heads(xk, g, pos, theta=1e6, eps=1e-5)
    for got, x, heads, scale in ((q, xq, hq, 1.0 / 8.0), (k, xk, hkv, 1.0)):
        want = jnp.stack([ref._rope(ref._rms(
            x[s].reshape(n, heads, d), g, 1e-5), 1e6) for s in range(2)])
        np.testing.assert_allclose(got, jnp.swapaxes(want, 1, 2) * scale,
                                   rtol=2e-5, atol=2e-6)
    seg = jnp.asarray(_pool(1).segment_ids[:2])
    got = masked_attention(q, k, v, seg, StaticMask("causal", n),
                           impl="blocked")
    np.testing.assert_allclose(got, _dense_attention(q, k, v, seg),
                               rtol=2e-5, atol=2e-6)


# ---- the shares against the uncut layer -------------------------------

def _expert_weights(seed, t=24, h=16, e=64, i=8):
    ks = jax.random.split(jax.random.key(seed), 5)
    return (jax.random.normal(ks[0], (t, h)),
            jax.random.normal(ks[1], (h, e)),
            0.3 * jax.random.normal(ks[2], (e, h, 2 * i)),
            0.3 * jax.random.normal(ks[3], (e, i, h)),
            0.5 * jax.random.normal(ks[4], (e,)))


def test_the_eight_shares_add_up_to_the_uncut_layer():
    """Eight shares of eight experts each add up to the reference's uncut
    layer of 64, and the rows they were routed to every (token, choice)."""
    x, router, w_gu, w_d, bias = _expert_weights(0)
    total, rows = 0.0, 0
    for first in range(0, 64, 8):
        out, sizes, _ = moe.expert_share(
            x, router, w_gu[first:first + 8], w_d[first:first + 8],
            experts_held=(first, 8), k=4, impl="ragged",
            routing=CFG.routing, bias=bias)
        assert int(sizes.sum()) == 24 * 4
        rows += int(sizes[first:first + 8].sum())
        total = total + out
    assert rows == 24 * 4
    want = ref.full_expert_layer(x, router, bias, w_gu, w_d, REF_CFG)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # one share alone is not the layer, nor is the layer without its bias
    assert float(jnp.abs(out - want).max()) > 1e-2
    unbiased = ref.full_expert_layer(x, router, 0 * bias, w_gu, w_d, REF_CFG)
    assert float(jnp.abs(unbiased - want).max()) > 1e-2


def test_the_weights_norm_has_its_1e_6():
    logits = jnp.log(jnp.asarray([[0.8, 0.6, 0.5, 0.2]]) /
                     (1 - jnp.asarray([[0.8, 0.6, 0.5, 0.2]])))
    assert CFG.routing == moe.Router("sigmoid", True, 1e-6, 1.0)
    w, e = moe.route(logits, 2, CFG.routing, jnp.asarray([0, 0, 0, 0.5]))
    assert e.tolist() == [[0, 3]]
    np.testing.assert_allclose(w, [[0.8 / (1.0 + 1e-6), 0.2 / (1.0 + 1e-6)]],
                               rtol=1e-6)
    # the reference's router, from the equation, is the same function
    p, chosen = ref.route(logits, jnp.eye(4), jnp.asarray([0, 0, 0, 0.5]),
                          {**REF_CFG, "num_experts_per_tok": 2}, ref._mm_f32)
    assert chosen.tolist() == [[0, 3]]
    np.testing.assert_allclose(p, w, rtol=1e-6)


# ---- the whole step against the reference -----------------------------

@pytest.fixture(scope="module")
def followed():
    """The reference's three steps from seed 0's weights and biases."""
    batches = tokens.split_batches(_pool(0), 2)
    return ref.adamw_steps(
        jax.tree_util.tree_map(np.asarray, _params(0)),
        np.asarray(_bias(0)), [_as_ref(b) for b in batches], REF_CFG,
        **ADAMW)


def test_three_adamw_steps_agree_with_the_reference(followed):
    """Loss, the first gradient leaf by leaf, the parameters' change after
    three steps, from seeded weights and biases; the biases untouched."""
    params, bias, want = _params(0), _bias(0), followed
    batches = tokens.split_batches(_pool(0), 2)
    step = jax.jit(make_lm_train_step(CFG, lfm2.attention_tiles(CFG, L)))
    state = _state(params, bias)
    losses = []
    for t, b in enumerate(batches):
        state, m = step(state, b)
        losses.append(float(m["loss_sum"]) / float(m["count"]))
        np.testing.assert_array_equal(state.batch_stats["router_bias"], bias)
        if t == 0:
            grad = bd_train.first_gradient(state.opt_state, ADAMW["b1"])
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    assert "expert_bias_abs_max_sum" not in m
    # six expert layers x two sequences, the one rung at this size
    assert float(m["moe_calls_full_rung_sum"]) == 12.0
    assert float(m["weighted_tokens_sum"]) == float(
        (batches[-1].loss_weight > 0).sum())
    # two attention layers of four heads, two sequences; five conv layers
    assert float(m["attn_full_tiles_live_sum"]) == 4 * 2 * 2
    assert "attn_window_tiles_live_sum" not in m
    assert float(m["heads_prepared_sum"]) == 2 * 2 * 2
    assert float(m["attn_kept_bytes_sum"]) == 2 * 2 * 4 * L * (16 * 4 + 4)
    assert float(m["sconv_positions_sum"]) == 5 * 2 * L
    assert float(m["sconv_taps_cut_sum"]) == 5 * int(
        taps_cut(batches[-1].segment_ids))
    flat_got = jax.tree_util.tree_leaves_with_path(grad)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want["grad"]))
    # embed, final_norm; 7 the dense conv layer's; 11 an attention expert
    # layer's, 8 a conv one's
    assert len(flat_got) == 2 + 7 + 11 + 8
    for path, g in flat_got:
        w = flat_want[path]
        assert np.abs(w).max() > 0, path  # every leaf gets a gradient
        np.testing.assert_allclose(g, w, rtol=2e-3,
                                   atol=2e-5 * np.abs(w).max(),
                                   err_msg=str(path))
    delta = ref.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: np.asarray(a) - np.asarray(b), state.params, params))
    for k, v in want["delta_norm"].items():
        assert delta[k] == pytest.approx(v, rel=2e-3), k
    assert ref.median_leaf_diff(grad, want["grad"]) < 1e-4


@pytest.mark.parametrize("packed", [True, False])
def test_a_step_counts_the_tiles_its_documents_leave(monkeypatch, packed):
    """``attn_full_tiles_live``: what ``ops/masked_attention.py`` visits of
    the step's sequences, tiles of 8 here; with one document a sequence the
    static count of ``attention_tiles``; the grid either way."""
    from cgnn_tpu.ops import masked_attention as op

    monkeypatch.setattr(op, "TILE_Q", 8)
    monkeypatch.setattr(op, "TILE_KV", 8)
    # boundaries on the tiles' edges: a tile the kernel visits holds a pair
    # that the dense mask shows (off them it may visit one that holds none)
    documents = ([8, 24], [16, 8, 8]) if packed else ([L], [L])
    batch = tokens.split_batches(_pool(0), 2)[0]._replace(
        segment_ids=np.stack([np.repeat(np.arange(len(d)), d)
                              for d in documents]).astype(np.int32))
    tiles = lfm2.attention_tiles(CFG, L)
    assert tiles == {"full": (10, 16, 2)}
    m = step_metrics(CFG, batch, jnp.float32(0.0), (
        jnp.ones((6, 16), jnp.int32), jnp.zeros((6, 2), jnp.int32)), tiles)
    dense = StaticMask("causal", L).dense()
    left = sum(int((dense & (row[:, None] == row[None, :])).reshape(
        4, 8, 4, 8).any(axis=(1, 3)).sum()) for row in batch.segment_ids)
    assert (left < 2 * 10) is packed
    # two attention layers of four heads
    assert float(m["attn_full_tiles_live_sum"]) == 4 * 2 * left
    assert float(m["attn_full_tiles_grid_sum"]) == 4 * 2 * 2 * 16


def test_the_tied_embedding_s_gradient_is_the_sum_of_both_uses():
    """The embedding's gradient is its gradient as the table plus its
    gradient as the head, each taken with the other use held fixed."""
    params, bias = _params(0), _bias(0)
    batch = tokens.split_batches(_pool(0), 2)[0]

    def loss(embed_in, embed_out):
        p = {**params, "embed": embed_in}
        x, *_ = lfm2.hidden_states(CFG, p, bias, batch.tokens,
                                   batch.segment_ids)
        return lm_blocks.chunked_loss_sums(
            x, jnp.roll(batch.tokens, -1, axis=1), batch.loss_weight,
            params["final_norm"], embed_out.T, eps=CFG.norm_eps,
            dtype=CFG.compute_dtype).mean()

    as_table, as_head = jax.grad(loss, argnums=(0, 1))(
        params["embed"], params["embed"])
    whole = jax.grad(lambda p: lfm2.apply(
        CFG, {"params": p, "batch_stats": {"router_bias": bias}},
        batch)[0].mean())(params)["embed"]
    assert float(jnp.abs(as_table).max()) > 1e-4
    assert float(jnp.abs(as_head).max()) > 1e-4
    np.testing.assert_allclose(whole, as_table + as_head, rtol=1e-4,
                               atol=1e-7)


@pytest.mark.parametrize("fault", list(ref.FAULTS) + ["float8"])
def test_each_fault_of_the_reference_is_another_function(fault):
    params = jax.tree_util.tree_map(np.asarray, _params(0))
    bias = np.asarray(_bias(0))
    batch = _as_ref(tokens.split_batches(_pool(0), 2)[0])
    sound, counts = ref.batch_loss(params, bias, batch, REF_CFG)
    kw = {"mm": ref.mm_fp8} if fault == "float8" else {"faults": (fault,)}
    broken, other = ref.batch_loss(params, bias, batch, REF_CFG, **kw)
    assert abs(float(broken) - float(sound)) > 1e-4 * abs(float(sound))
    assert counts.shape == other.shape == (6, 16)
    assert int(counts.sum()) == 6 * 2 * L * 4


def test_bfloat16_compute_stays_near_float32(followed):
    cfg16 = dataclasses.replace(CFG, dtype="bfloat16")
    params, bias = _params(0), _bias(0)
    batch = tokens.split_batches(_pool(0), 2)[0]
    variables = {"params": params, "batch_stats": {"router_bias": bias}}
    a, *_ = lfm2.apply(CFG, variables, batch)
    b, sizes, rungs = lfm2.apply(cfg16, variables, batch)
    assert a.shape == b.shape == (2,) and sizes.shape == (6, 16)
    assert rungs.shape == (6, 2)  # an expert layer and sequence each
    np.testing.assert_allclose(a, b, rtol=0.05)
    assert int(sizes.sum()) == 6 * (2 * L) * 4
    batches, want = tokens.split_batches(_pool(0), 2), followed
    step = jax.jit(make_lm_train_step(cfg16))
    state = _state(params, bias, cfg16)
    for x, w in zip(batches, want["loss"]):
        state, m = step(state, x)
        assert float(m["loss_sum"]) / 2 == pytest.approx(w, rel=0.05)
    np.testing.assert_array_equal(state.batch_stats["router_bias"], bias)


# ---- the stack and the count ------------------------------------------

def test_parameter_count_and_the_stack():
    real = lfm2.Lfm2Config()
    conv = 2048 * 6144 + 2048 * 3 + 2048 * 2048
    attn = 2 * 2048 * 2048 + 2 * 2048 * 512 + 128
    expert = 3 * 2048 * 1536
    assert (conv, attn, expert) == (16_783_360, 10_485_888, 9_437_184)
    dense_layer = conv + 3 * 2048 * 11776 + 4096
    conv_layer = conv + 8 * expert + 2048 * 64 + 4096
    attn_layer = attn + 8 * expert + 2048 * 64 + 4096
    assert (dense_layer, conv_layer, attn_layer) == (
        89_139_200, 92_416_000, 86_118_528)
    assert real.n_params() == (dense_layer + 3 * conv_layer + attn_layer
                               + 8192 * 2048 + 2048) == 469_284_992
    assert "head" not in real.shapes()  # tied: counted once
    assert real.period == (F, C, C, C) and real.n_periods == 1
    assert real.runs == ((F, 1), (C, 3)) and CFG.runs == ((F, 1), (C, 2))
    assert (real.n_attention_layers, real.n_conv_layers) == (1, 4)
    assert real.stats_shapes() == {"router_bias": (1, 4, 64)}
    shapes = real.shapes()
    assert set(shapes["dense"]) == {"op_norm", "ffn_norm", "w_in", "conv_w",
                                    "w_out", "mlp_gate_up", "mlp_down"}
    assert shapes["periods"]["run0"]["wq"] == (1, 1, 2048, 2048)
    assert shapes["periods"]["run0"]["wk"] == (1, 1, 2048, 512)
    assert shapes["periods"]["run1"]["w_in"] == (1, 3, 2048, 6144)
    assert shapes["periods"]["run1"]["w_gate_up"] == (1, 3, 8, 2048, 3072)
    deeper = dataclasses.replace(
        real, num_hidden_layers=9, layer_types=(C,) + (F, C, C, C) * 2)
    assert deeper.n_periods == 2 and deeper.period == real.period
    assert deeper.shapes()["periods"]["run1"]["router"] == (2, 3, 2048, 64)
    # the published stack: two dense conv layers, then 38 = 9.5 periods
    published = (C, C) + (F, C, C, C) * 9 + (F, C)
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(real, num_hidden_layers=4)
    with pytest.raises(ValueError, match="one kind"):
        dataclasses.replace(real, num_dense_layers=2,
                            layer_types=(C, F, C, C, C))
    # the filter's length is the op's constant, no field of the config
    assert "conv_L_cache" not in {f.name for f in dataclasses.fields(real)}
    assert shapes["dense"]["conv_w"] == (1, 2048, 3)
    whole = dataclasses.replace(real, num_hidden_layers=40,
                                num_dense_layers=2, layer_types=published)
    assert whole.period == published[2:]  # no shorter period fits 38 layers
    p = lfm2.init_params(CFG, jax.random.key(0), n_layers_published=40)
    assert float(p["periods"]["run1"]["op_norm"].min()) == 1.0
    for name, leaf in (("w_out", p["periods"]["run1"]["w_out"]),
                       ("wo", p["periods"]["run0"]["wo"]),
                       ("mlp_down", p["dense"]["mlp_down"])):
        assert float(jnp.std(leaf)) == pytest.approx(
            0.02 / np.sqrt(80.0), rel=0.1), name
    assert float(jnp.std(p["embed"])) == pytest.approx(0.02, rel=0.1)
    assert not np.asarray(lfm2.init_stats(CFG)["router_bias"]).any()


def test_the_stack_is_lm_blocks_and_both_models_share_it(monkeypatch):
    """``hidden_states`` of this model and of models/afmoe.py run the one
    ``scan_stack``; an expert layer's checkpoint keeps the routed output."""
    from cgnn_tpu.models import afmoe

    calls, seen = [], []
    real = lm_blocks.scan_stack

    def recorded(cfg, *a, **kw):
        calls.append(type(cfg).__name__)
        return real(cfg, *a, **kw)

    def kept(layer, x, segment_ids, keep=()):
        seen.append(keep)
        return lm_blocks.by_sequence(layer, x, segment_ids, keep)

    monkeypatch.setattr(lm_blocks, "scan_stack", recorded)
    monkeypatch.setattr(lfm2, "by_sequence", kept)
    batch = tokens.split_batches(_pool(0), 2)[0]
    jax.eval_shape(functools.partial(lfm2.hidden_states, CFG), _params(0),
                   _bias(0), batch.tokens, batch.segment_ids)
    tiny = afmoe.AfmoeConfig(
        hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, num_hidden_layers=3, num_dense_layers=1,
        layer_types=(afmoe.SLIDING,) * 2 + (afmoe.FULL,), sliding_window=8,
        intermediate_size=96, moe_intermediate_size=32, n_experts=16,
        num_experts_per_tok=4, experts_held=(0, 4), vocab_size=128,
        dtype="float32")
    jax.eval_shape(
        functools.partial(afmoe.hidden_states, tiny),
        afmoe.init_params(tiny, jax.random.key(0)),
        afmoe.init_stats(tiny)["router_bias"], batch.tokens,
        batch.segment_ids)
    assert calls == ["Lfm2Config", "AfmoeConfig"]
    assert seen == [(), (lfm2.ROUTED,), (lfm2.ROUTED,)]
    assert issubclass(afmoe.AfmoeConfig, lm_blocks.Stack)
    assert afmoe.AfmoeConfig.period is lm_blocks.Stack.period


# ---- the normal path --------------------------------------------------

def test_train_py_trains_the_tiny_preset_through_fit_and_the_scan_driver(
        capsys, tmp_path):
    import train

    code = train.main([
        "--device", "cpu", "--task", "lm", "--lm-model", "lfm2-tiny",
        "--synthetic", "24", "-b", "2", "--epochs", "3", "--optim", "AdamW",
        "--lr", "3e-3", "--weight-decay", "0.1", "--ckpt-dir", str(tmp_path),
        "--check-invariants", "--no-preempt-handler"])
    out = capsys.readouterr().out
    assert code == 0
    losses = [float(ln.split("train loss ")[1].split()[0])
              for ln in out.splitlines() if ln.startswith("Epoch ")]
    assert len(losses) == 3 and losses[-1] < losses[0]
    assert "lm: " in out and "largest bias" not in out


@pytest.mark.parametrize("task,preset,model", [
    ("lm", "lfm2-tiny", "lfm2"), ("lm", "lfm2-24b-a2b-ep8", "lfm2"),
    ("lm", "tiny", "afmoe"), ("lm", "trinity-mini-ep16", "afmoe"),
    ("blockdiff", "tiny", "sdar"), ("blockdiff", "sdar-ep8", "sdar")])
def test_a_preset_names_its_model(task, preset, model):
    from cgnn_tpu.train import blockdiff

    module, cfg = blockdiff.model_config(task, preset, bf16=False)
    assert module.__name__ == f"cgnn_tpu.models.{model}"
    assert type(cfg).__name__ == blockdiff.CONFIGS[model]
    if preset == "lfm2-24b-a2b-ep8":
        assert cfg == lfm2.Lfm2Config() and cfg.n_params() == 469_284_992
    other = "blockdiff" if task == "lm" else "lm"
    if preset not in blockdiff.PRESETS[other]:
        with pytest.raises(ValueError, match="no preset"):
            blockdiff.model_config(other, preset, bf16=False)


def test_a_json_file_is_read_as_the_task_s_first_model(tmp_path):
    """The presets name the second family; a JSON file of fields is the
    task's first model's, as before there was a second."""
    import json

    from cgnn_tpu.train import blockdiff

    path = tmp_path / "m.json"
    path.write_text(json.dumps(dict(blockdiff.PRESETS["lm"]["tiny"][1])))
    module, cfg = blockdiff.model_config("lm", str(path), bf16=True)
    assert module.__name__ == "cgnn_tpu.models.afmoe"
    assert cfg.dtype == "bfloat16" and cfg.experts_held == (0, 4)
    path.write_text(json.dumps(dict(blockdiff.PRESETS["lm"]["lfm2-tiny"][1])))
    with pytest.raises(ValueError, match="layer_types"):
        blockdiff.model_config("lm", str(path), bf16=False)
