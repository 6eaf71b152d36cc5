"""The window-and-full-attention mixture-of-experts decoder (models/afmoe.py,
ops/masked_attention.py, ops/moe.py's router, train/lm_step.py's causal step
and bias update, data/tokens.py's causal batch) at a tiny size on the CPU,
against the plain reference (benchmark/reference/afmoe_ref.py), which imports
nothing of the program.
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
from benchmark.reference import afmoe_ref as ref  # noqa: E402
from cgnn_tpu.data import invariants, tokens  # noqa: E402
from cgnn_tpu.models import afmoe, lm_blocks  # noqa: E402
from cgnn_tpu.ops import moe  # noqa: E402
from cgnn_tpu.ops.masked_attention import (  # noqa: E402
    StaticMask, mask_tiles, masked_attention,
)
from cgnn_tpu.train import Normalizer, make_optimizer  # noqa: E402
from cgnn_tpu.train.lm_step import (  # noqa: E402
    balanced_biases, make_lm_train_step, step_metrics,
)
from cgnn_tpu.train.state import TrainState  # noqa: E402

L, WINDOW = 32, 8
S, F = afmoe.SLIDING, afmoe.FULL
# one dense layer, then two periods of (window, window, full)
TYPES = (S, S, S, F, S, S, F)
CFG = afmoe.AfmoeConfig(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_hidden_layers=7, num_dense_layers=1, layer_types=TYPES,
    sliding_window=WINDOW, intermediate_size=96, moe_intermediate_size=32,
    n_experts=16, num_experts_per_tok=4, experts_held=(4, 4),
    vocab_size=128, dtype="float32")
REF_CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "layer_types": list(TYPES), "num_dense_layers": 1,
    "sliding_window": WINDOW, "num_experts_per_tok": 4,
    "experts_held": (4, 4), "rope_theta": 1e4, "rms_norm_eps": 1e-5,
    "route_norm": True, "route_scale": 2.826, "load_balance_coeff": 0.001,
    "mup_enabled": True}
ADAMW = dict(lr=1e-3, b1=0.9, b2=0.95, weight_decay=0.1)


def _pool(seed=0, n=6):
    return tokens.make_pool(n, L, vocab_size=CFG.vocab_size, seed=seed,
                            doc_median=12, doc_min=2, doc_max=L,
                            kind="causal")


def _params(seed):
    p = afmoe.init_params(CFG, jax.random.key(seed), std=0.3)
    # norm scales off 1, so that a dropped scale shows
    return jax.tree_util.tree_map_with_path(
        lambda path, x: (x * (1.0 + 0.1 * jnp.cos(jnp.arange(
            x.size, dtype=jnp.float32).reshape(x.shape)))).astype(jnp.float32)
        if "norm" in str(path[-1]) else x, p)


def _bias(seed):
    """Biases off 0, so that a dropped bias shows."""
    shape = CFG.stats_shapes()["router_bias"]
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
        apply_fn=functools.partial(afmoe.apply, cfg), tx=tx)


def _as_ref(b):
    return {"tokens": b.tokens, "segment_ids": b.segment_ids,
            "loss_weight": b.loss_weight}


# ---- the causal batch -------------------------------------------------

def test_the_causal_pool_weighs_what_has_a_next_token_of_its_document():
    pool = _pool(3, n=64)
    assert pool.tokens.shape == pool.segment_ids.shape == (64, L)
    assert pool.tokens.dtype == np.int32 and pool.tokens.max() == 127
    w, seg = pool.loss_weight, pool.segment_ids
    assert w.dtype == np.float32 and set(np.unique(w)) == {0.0, 1.0}
    assert (w[:, -1] == 0).all()
    assert ((seg[:, 1:] == seg[:, :-1]) == (w[:, :-1] == 1)).all()
    # packed to exactly L with several documents a sequence, none padded
    assert (seg[:, 0] == 0).all() and (np.diff(seg, axis=1) >= 0).all()
    assert seg.max() >= 2
    again = _pool(3, n=64)
    assert all((a == b).all() for a, b in zip(pool, again))
    assert (pool.tokens != _pool(4, n=64).tokens).any()
    with pytest.raises(ValueError, match="kind"):
        tokens.make_pool(2, L, vocab_size=8, seed=0, kind="prefix")


def test_the_block_diffusion_pool_is_what_it_was():
    """The causal kind draws nothing of the other kind's stream."""
    a = tokens.make_pool(4, L, vocab_size=64, block=4, seed=5, doc_median=12,
                         doc_min=4, doc_max=L)
    assert a.tokens.shape == (4, 2 * L) and a.tokens.max() == 63
    assert (a.tokens[:, L:] < 63).all()  # [MASK] is never a clean id


def test_invariants_and_shape_key_take_a_causal_batch():
    from cgnn_tpu.data.graph import batch_shape_key
    from cgnn_tpu.train import loop

    batches = tokens.split_batches(_pool(), 2)
    key = batch_shape_key(batches[0])
    assert key == ("tokens", (2, L))
    assert loop.program_name((key, 2), True) == f"scan_train_n{2 * L}_l2"
    invariants.check_any(batches[0], train=True)
    bad = batches[0]._replace(loss_weight=np.ones_like(
        batches[0].loss_weight))
    with pytest.raises(invariants.BatchInvariantError, match="next token"):
        invariants.check_any(bad)
    bad = batches[0]._replace(tokens=batches[0].tokens[:, :-1])
    with pytest.raises(invariants.BatchInvariantError, match="shapes"):
        invariants.check_any(bad)


# ---- the attention op -------------------------------------------------

def _dense_attention(q, k, v, seg, window):
    """Plain softmax attention under the reference's dense mask."""
    group = q.shape[1] // k.shape[1]
    out = []
    for s in range(q.shape[0]):
        mask = ref.dense_mask(seg[s], window)
        ks, vs = (jnp.repeat(t[s], group, axis=0) for t in (k, v))
        scores = jnp.where(mask, jnp.einsum("hqd,hkd->hqk", q[s], ks), -1e30)
        out.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1),
                              vs))
    return jnp.stack(out)


def _qkv(seed, n=L):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (2, 4, n, 16))
    k = jax.random.normal(ks[1], (2, 2, n, 16))
    v = jax.random.normal(ks[2], (2, 2, n, 16))
    return q * 0.25, k, v, jax.random.normal(ks[3], (2, 4, n, 16))


@pytest.mark.parametrize("window", [0, WINDOW, 1])
@pytest.mark.parametrize("packed", [False, True])
def test_attention_agrees_with_the_dense_mask(packed, window):
    q, k, v, w = _qkv(0)
    seg = (jnp.asarray(_pool(1).segment_ids[:2]) if packed
           else jnp.zeros((2, L), jnp.int32))
    mask = StaticMask("causal", L, window=window)
    got = masked_attention(q, k, v, seg, mask, impl="blocked")
    want = _dense_attention(q, k, v, seg, window)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)
    f = lambda fn: jax.grad(  # noqa: E731
        lambda q, k, v: (fn(q, k, v) * w).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(
            f(lambda q, k, v: masked_attention(q, k, v, seg, mask,
                                               impl="blocked")),
            f(lambda q, k, v: _dense_attention(q, k, v, seg, window))):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-6)


def _documents(lengths, doubled):
    """One row of ``segment_ids``; ``doubled``: the block-diffusion call's,
    the documents of ``x_t`` and again those of ``x_0``."""
    row = np.repeat(np.arange(len(lengths)), lengths)
    return np.concatenate([row, row]) if doubled else row


# id: (kind of mask, its window or block, the documents of two unlike
# sequences, the tiles of 128 a head visits of each)
_SPLASH_CASES = {
    "causal-several-documents": (
        "causal", 0, ([100, 200, 12, 200], [128, 256, 128]), (8, 5)),
    "window-several-documents": (
        "causal", 160, ([100, 200, 12, 200], [128, 256, 128]), (8, 5)),
    "bd-several-documents": (
        "bd", 4, ([128, 256, 128], [256, 100, 156]), (14, 16)),
    "causal-one-document": ("causal", 0, ([512], [512]), (10, 10)),
    "window-one-document": ("causal", 160, ([512], [512]), (9, 9)),
    "bd-one-document": ("bd", 4, ([512], [512]), (24, 24)),
    "causal-boundary-off-an-edge": (
        "causal", 0, ([130, 382], [254, 258]), (8, 8)),
    "bd-boundary-off-an-edge": ("bd", 4, ([132, 380], [252, 260]), (20, 20)),
}


@pytest.mark.parametrize("case", list(_SPLASH_CASES))
def test_the_splash_kernel_agrees_with_the_dense_mask(monkeypatch, case):
    """``impl="splash"``, the kernel interpreted on the CPU, several tiles a
    side and two sequences of unlike documents: the output and the gradients
    of q, k and v against ``impl="blocked"``, and bit for bit those of the
    kernel whose tables the documents did not refine; each of the three
    refined tables holds the tiles that the dense mask leaves, and a step
    it skips fetches a live step's tile."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
    )

    from cgnn_tpu.ops import masked_attention as op

    kind, arg, lengths, live = _SPLASH_CASES[case]
    bd = kind == "bd"
    seg = np.stack([_documents(x, bd) for x in lengths]).astype(np.int32)
    n = seg.shape[1]
    mask = (StaticMask("bd", n, block=arg) if bd
            else StaticMask("causal", n, window=arg))
    monkeypatch.setattr(op, "TILE_Q", 128)
    monkeypatch.setattr(op, "TILE_KV", 128)
    monkeypatch.setattr(
        splash, "make_splash_mqa_single_device", functools.partial(
            splash.make_splash_mqa_single_device, interpret=True))
    op._splash_kernel.cache_clear()

    def outputs(impl):
        q, k, v, w = _qkv(2, n)
        f = lambda q, k, v: masked_attention(  # noqa: E731
            q, k, v, jnp.asarray(seg), mask, impl=impl)
        return (f(q, k, v), *jax.grad(
            lambda *qkv: (f(*qkv) * w).sum(), argnums=(0, 1, 2))(q, k, v))

    try:
        # the suite's x64 is no entry point's, and the kernel is float32's
        with jax.enable_x64(False):
            static = op._splash_kernel(mask, 2)
            grid = (n // 128, n // 128)
            shown = mask.dense().reshape(grid[0], 128, grid[1], 128)
            for row, count in zip(seg, live):
                want = (shown & (row[:, None] == row[None, :]).reshape(
                    shown.shape)).any(axis=(1, 3))
                assert want.sum() == count
                kernel = op._refined(static, op.dead_tiles(jnp.asarray(row)))
                for name in ("fwd_mask_info", "dq_mask_info",
                             "dkv_mask_info"):
                    was, now = getattr(static, name), getattr(kernel, name)
                    assert now.block_mask.dtype == was.block_mask.dtype
                    steps = np.asarray(now.block_mask)[0] > 0
                    there = np.asarray(now.data_next)[0]
                    tiles = np.zeros(grid, bool)
                    if name == "dkv_mask_info":
                        tiles[there[steps], np.nonzero(steps)[1]] = True
                    else:
                        tiles[np.nonzero(steps)[0], there[steps]] = True
                    np.testing.assert_array_equal(tiles, want)
                    assert steps.sum() == count  # no tile twice
                    assert now.data_next.dtype == was.data_next.dtype
                    # a skipped step fetches what a live step of its row of
                    # steps reads; a live step reads as built
                    axis = 0 if name == "dkv_mask_info" else 1
                    np.testing.assert_array_equal(
                        there[steps], np.asarray(was.data_next)[0][steps])
                    for at in np.argwhere(~steps):
                        row = [slice(None), slice(None)]
                        row[1 - axis] = at[1 - axis]
                        assert there[tuple(at)] in there[tuple(row)][
                            steps[tuple(row)]]
                    if "one-document" in case:
                        np.testing.assert_array_equal(now.block_mask,
                                                      was.block_mask)
                    for other in ("mask_next", "partial_mask_blocks"):
                        assert getattr(now, other) is getattr(was, other)
            assert "one-document" in case or min(live) < mask_tiles(mask)[0]
            got = outputs("splash")
            for a, b in zip(got, outputs("blocked")):
                np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4)
            monkeypatch.setattr(op, "_refined", lambda kernel, dead: kernel)
            for a, b in zip(got, outputs("splash")):
                np.testing.assert_array_equal(a, b)
    finally:
        op._splash_kernel.cache_clear()


@pytest.mark.parametrize("kind,i,j,visible", [
    ("window", 20, 20, True), ("window", 20, 13, True),
    ("window", 20, 12, False), ("window", 20, 21, False),
    ("full", 20, 0, True), ("full", 20, 21, False), ("full", 0, 0, True)])
def test_every_edge_of_the_masks(kind, i, j, visible):
    """The window counts the query itself: i - window < j <= i."""
    mask = StaticMask("causal", L, window=WINDOW if kind == "window" else 0)
    assert bool(mask.dense()[i, j]) is visible
    seg = jnp.zeros((L,), jnp.int32)
    assert bool(ref.dense_mask(seg, mask.window)[i, j]) is visible


def test_nothing_crosses_a_document():
    q, k, v, _ = _qkv(1)
    seg = jnp.asarray(_pool(2).segment_ids[:2])
    mask = StaticMask("causal", L)
    a = masked_attention(q, k, v, seg, mask, impl="blocked")
    j = 3  # a key of the first document of sequence 0
    later = np.asarray(seg[0]) != int(seg[0, j])
    assert later.any()
    b = masked_attention(q, k, v.at[0, :, j].add(1.0), seg, mask,
                         impl="blocked")
    moved = np.abs(np.asarray(a - b)[0]).max(axis=(0, 2)) > 0
    assert not moved[later].any() and moved[j]


def test_tiles():
    """The counters against the dense mask: at the cell's sizes the window
    layers visit 70 of 256 tiles of 512, the full ones 136."""
    full = StaticMask("causal", 8192)
    window = StaticMask("causal", 8192, window=2048)
    assert mask_tiles(full) == (16 * 17 // 2, 256)
    # a query tile sees its own, the four before it whole or in part
    assert mask_tiles(window) == (sum(min(q + 1, 5) for q in range(16)), 256)
    assert mask_tiles(StaticMask("causal", L, window=WINDOW)) == (1, 1)
    cfg = dataclasses.replace(CFG, sliding_window=2048)
    tiles = afmoe.attention_tiles(cfg, 8192)
    assert tiles == {"window": (70, 256, 5), "full": (136, 256, 2)}
    dense = np.asarray(ref.dense_mask(jnp.zeros((1024,), jnp.int32), 300))
    live = dense.reshape(2, 512, 2, 512).any(axis=(1, 3)).sum()
    assert mask_tiles(StaticMask("causal", 1024, window=300)) == (live, 4)
    with pytest.raises(ValueError, match="positions"):
        masked_attention(*_qkv(0)[:3], jnp.zeros((2, L), jnp.int32),
                         StaticMask("causal", 2 * L))
    with pytest.raises(ValueError, match="static mask"):
        StaticMask("prefix", L).dense()


# ---- the router -------------------------------------------------------

def test_route_by_hand_the_bias_changes_the_choice_and_not_the_weight():
    logits = jnp.log(jnp.asarray([[0.8, 0.6, 0.5, 0.2]]) /
                     (1 - jnp.asarray([[0.8, 0.6, 0.5, 0.2]])))
    router = moe.Router(score_func="sigmoid", norm=True, norm_eps=1e-20,
                        scale=2.0)
    w, e = moe.route(logits, 2, router)
    assert e.tolist() == [[0, 1]]
    np.testing.assert_allclose(w, [[2 * 0.8 / 1.4, 2 * 0.6 / 1.4]],
                               rtol=1e-6)
    # a bias lifts expert 3 over expert 1: it is chosen, and weighs by its
    # own score 0.2, not by 0.2 + 0.5
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.5])
    w, e = moe.route(logits, 2, router, bias)
    assert e.tolist() == [[0, 3]]
    np.testing.assert_allclose(w, [[2 * 0.8 / 1.0, 2 * 0.2 / 1.0]],
                               rtol=1e-6)
    # no norm: the scores themselves, scaled
    w, _ = moe.route(logits, 2, dataclasses.replace(router, norm=False), bias)
    np.testing.assert_allclose(w, [[1.6, 0.4]], rtol=1e-6)
    # no gradient reaches the bias; the logits get theirs
    g = jax.grad(lambda b: moe.route(logits, 2, router, b)[0].sum())(bias)
    assert not np.asarray(g).any()
    g = jax.grad(lambda x: moe.route(x, 2, router, bias)[0][0, 1])(logits)
    assert np.asarray(g)[0, 3] != 0
    # the default is what it was: softmax, renormalised to 1
    w, e = moe.route(logits, 2)
    p = jax.nn.softmax(logits)[0]
    np.testing.assert_allclose(w[0], p[:2] / p[:2].sum(), rtol=1e-6)
    with pytest.raises(ValueError, match="score function"):
        moe.route(logits, 2, moe.Router(score_func="tanh"))


def test_the_bias_update_by_hand_centred():
    bias = jnp.asarray([[0.1, -0.1, 0.0, 0.0]])
    counts = jnp.asarray([[10, 2, 4, 0]])  # mean 4: over, under, at, under
    got = balanced_biases(bias, counts, 0.01)
    d = np.asarray([-0.01, 0.01, 0.0, 0.01])
    np.testing.assert_allclose(got[0], np.asarray(bias[0]) + d - d.mean(),
                               rtol=1e-6)
    assert float(got.sum()) == pytest.approx(float(bias.sum()), abs=1e-7)
    np.testing.assert_allclose(
        ref.bias_update(np.asarray(bias), np.asarray(counts), 0.01), got,
        rtol=1e-6)
    # layer by layer: another layer's counts do not move this one's
    two = balanced_biases(jnp.zeros((1, 2, 4)),
                          jnp.asarray([[10, 2, 4, 0], [1, 1, 1, 1]]), 0.01)
    np.testing.assert_allclose(two[0, 0], d - d.mean(), atol=1e-9)
    assert not np.asarray(two[0, 1]).any()


# ---- the shares against the uncut layer -------------------------------

def _expert_weights(seed, t=24, h=16, e=16, i=8):
    ks = jax.random.split(jax.random.key(seed), 7)
    return (jax.random.normal(ks[0], (t, h)),
            jax.random.normal(ks[1], (h, e)),
            0.3 * jax.random.normal(ks[2], (e, h, 2 * i)),
            0.3 * jax.random.normal(ks[3], (e, i, h)),
            0.3 * jax.random.normal(ks[4], (h, 2 * i)),
            0.3 * jax.random.normal(ks[5], (i, h)),
            0.5 * jax.random.normal(ks[6], (e,)))


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen shares of eight experts each, the shared expert counted once:
    they add up to the reference's uncut layer, and the rows they were
    routed add up to every (token, choice) pair."""
    x, router, w_gu, w_d, s_gu, s_d, bias = _expert_weights(0, e=128)
    cfg = {**REF_CFG, "num_experts_per_tok": 8}
    routing = CFG.routing
    total, rows = afmoe._mlp(x, s_gu, s_d), 0
    for first in range(0, 128, 8):
        out, sizes, _ = moe.expert_share(
            x, router, w_gu[first:first + 8], w_d[first:first + 8],
            experts_held=(first, 8), k=8, impl="ragged", routing=routing,
            bias=bias)
        assert int(sizes.sum()) == 24 * 8
        rows += int(sizes[first:first + 8].sum())
        total = total + out
    assert rows == 24 * 8
    want = ref.full_expert_layer(x, router, bias, w_gu, w_d, s_gu, s_d, cfg)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # one share alone is not the layer, nor is the layer without its bias
    assert float(jnp.abs(out - want).max()) > 1e-2
    unbiased = ref.full_expert_layer(x, router, 0 * bias, w_gu, w_d, s_gu,
                                     s_d, cfg)
    assert float(jnp.abs(unbiased - want).max()) > 1e-2


def test_one_share_is_the_reference_s():
    """A share alone against the reference's loop over its experts' rows
    (every leaf's gradient is compared by the whole step's test)."""
    x, router, w_gu, w_d, _, _, bias = _expert_weights(2)
    out, sizes, _ = moe.expert_share(
        x, router, w_gu[4:8], w_d[4:8], experts_held=(4, 4), k=4,
        impl="ragged", routing=CFG.routing, bias=bias)
    want, counts = ref._experts(
        x, {"router": router, "w_gate_up": w_gu[4:8], "w_down": w_d[4:8]},
        bias, REF_CFG, ref._mm_f32, ())
    assert (np.asarray(sizes) == np.asarray(counts)).all()
    assert float(jnp.abs(want).max()) > 1e-2
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)


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
    """Loss, the first gradient leaf by leaf, the parameters' change and the
    biases after each of three steps, from seeded weights and biases."""
    params, bias, want = _params(0), _bias(0), followed
    batches = tokens.split_batches(_pool(0), 2)
    step = jax.jit(make_lm_train_step(CFG, afmoe.attention_tiles(CFG, L)))
    state = _state(params, bias)
    losses, biases = [], []
    for t, b in enumerate(batches):
        state, m = step(state, b)
        losses.append(float(m["loss_sum"]) / float(m["count"]))
        biases.append(np.asarray(state.batch_stats["router_bias"]))
        if t == 0:
            grad = bd_train.first_gradient(state.opt_state, ADAMW["b1"])
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    for got, ref_bias in zip(biases, want["bias"]):
        np.testing.assert_allclose(got, ref_bias, rtol=0, atol=2e-7)
    assert ref.bias_diff_share(biases, want["bias"], 0.001) == 0.0
    # every step moved every layer's biases, and kept their mean
    moved = np.abs(biases[0] - np.asarray(bias))
    assert moved.max() <= 0.002 and (moved > 0).mean() > 0.9
    np.testing.assert_allclose(biases[-1].mean(-1), np.asarray(bias).mean(-1),
                               atol=1e-6)
    assert float(m["expert_bias_abs_max_sum"]) == pytest.approx(
        np.abs(biases[-1]).max())
    # six expert layers x two sequences, the one rung at this size
    assert float(m["moe_calls_full_rung_sum"]) == 12.0
    assert float(m["weighted_tokens_sum"]) == float(
        (batches[-1].loss_weight > 0).sum())
    assert float(m["attn_window_tiles_live_sum"]) == 4 * 2 * 5
    assert float(m["attn_full_tiles_grid_sum"]) == 4 * 2 * 2
    flat_got = jax.tree_util.tree_leaves_with_path(grad)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want["grad"]))
    assert len(flat_got) == 3 + 13 + 2 * 16
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


def test_the_reference_reads_an_expert_s_run_in_chunks(monkeypatch):
    """Whatever the chunk, the same function: a chunk past a run's end is
    skipped, the last one masked."""
    x, router, w_gu, w_d, _, _, bias = _expert_weights(3)
    p = {"router": router, "w_gate_up": w_gu[4:8], "w_down": w_d[4:8]}
    f = lambda: jax.value_and_grad(  # noqa: E731
        lambda p: (ref._experts(x, p, bias, REF_CFG, ref._mm_f32, ())[0]
                   ** 2).sum())(p)
    a, ga = f()
    assert ref.EXPERT_CHUNK == 1024
    monkeypatch.setattr(ref, "EXPERT_CHUNK", 5)
    b, gb = f()
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for u, w in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(u, w, rtol=1e-4, atol=1e-6)


def test_bfloat16_compute_stays_near_float32(followed):
    cfg16 = dataclasses.replace(CFG, dtype="bfloat16")
    params, bias = _params(0), _bias(0)
    batch = tokens.split_batches(_pool(0), 2)[0]
    variables = {"params": params, "batch_stats": {"router_bias": bias}}
    a, *_ = afmoe.apply(CFG, variables, batch)
    b, sizes, rungs = afmoe.apply(cfg16, variables, batch)
    assert a.shape == b.shape == (2,) and sizes.shape == (6, 16)
    assert rungs.shape == (6, 2)  # an expert layer and sequence each
    np.testing.assert_allclose(a, b, rtol=0.05)
    assert int(sizes.sum()) == 6 * (2 * L) * 4
    # three bfloat16 steps: the loss near the reference's, the biases the
    # reference's but where a count lay at the mean
    batches, want = tokens.split_batches(_pool(0), 2), followed
    step = jax.jit(make_lm_train_step(cfg16))
    state, got = _state(params, bias, cfg16), []
    for x, w in zip(batches, want["loss"]):
        state, m = step(state, x)
        assert float(m["loss_sum"]) / 2 == pytest.approx(w, rel=0.05)
        got.append(np.asarray(state.batch_stats["router_bias"]))
    assert ref.bias_diff_share(got, want["bias"], 0.001) < 0.25


# ---- what a layer's checkpoint keeps ----------------------------------

@pytest.mark.parametrize("dtype,rounding", [("float32", 1e-5),
                                            ("bfloat16", 0.0)])
def test_keeping_the_attention_s_output_changes_no_number(monkeypatch,
                                                          dtype, rounding):
    """One training step with the attention's output kept for the reverse
    pass and with a ``by_sequence`` that keeps it under no name (the expert
    layers' routed output is kept either way): the loss and the biases are
    equal, and every leaf's gradient is, in float32 to the rounding of the
    CPU's compiler (tests/test_sdar.py has why)."""
    cfg = dataclasses.replace(CFG, dtype=dtype)
    batch = tokens.split_batches(_pool(0), 2)[0]
    state = _state(_params(0), _bias(0), cfg)

    def step():
        new, m = jax.jit(make_lm_train_step(cfg))(state, batch)
        return (float(m["loss_sum"]),
                np.asarray(new.batch_stats["router_bias"]),
                bd_train.first_gradient(new.opt_state, ADAMW["b1"]))

    loss, bias, grad = step()
    monkeypatch.setattr(lm_blocks, "KEPT", "nothing.by.this.name")
    loss_alone, bias_alone, grad_alone = step()
    assert loss == loss_alone
    np.testing.assert_array_equal(bias, bias_alone)
    flat = jax.tree_util.tree_leaves_with_path(grad)
    assert len(flat) == 3 + 13 + 2 * 16
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(grad_alone)):
        assert np.abs(w).max() > 0, path
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=rounding * np.abs(w).max(),
                                   err_msg=str(path))


@pytest.mark.parametrize("layer", ["dense", "expert"])
def test_the_layer_s_checkpoint_keeps_the_attention_s_output(monkeypatch,
                                                             capsys, layer):
    """What the reverse pass of a layer under ``by_sequence`` keeps beside
    the layer's arguments, every sequence's stacked by the scan over them:
    the attention's named output and, of an expert layer as
    ``hidden_states`` calls it, the routed experts' output still."""
    params, bias = _params(0), _bias(0)
    batch = tokens.split_batches(_pool(0), 2)[0]
    x = params["embed"][batch.tokens]
    if layer == "dense":
        p0 = jax.tree_util.tree_map(lambda a: a[0], params["dense"])
        keep = ()

        def one(p, x_seq, seg):
            return afmoe._dense_layer(CFG, S, x_seq, p, seg)
    else:
        p0 = jax.tree_util.tree_map(lambda a: a[0, 0],
                                    params["periods"]["run1"])
        keep = (afmoe.ROUTED,)

        def one(p, x_seq, seg):
            return afmoe._expert_layer(CFG, F, x_seq, p, bias[0, 2], seg)

    def kept():
        jax.ad_checkpoint.print_saved_residuals(
            lambda x, p: lm_blocks.by_sequence(
                functools.partial(one, p), x, batch.segment_ids,
                keep=keep)[0].sum(), x, p0)
        return sorted(ln.split()[0] for ln in capsys.readouterr().out
                      .splitlines() if "output of scan" in ln
                      and not ln.startswith("i32"))  # the documents

    # [S, 1, Hkv, G, L, D], the op's output before it is reshaped; the
    # routed rows [S, L, H]
    attn, routed = f"f32[2,1,2,2,{L},16]", f"f32[2,{L},64]"
    assert kept() == ([attn] if layer == "dense" else [attn, routed])
    monkeypatch.setattr(lm_blocks, "KEPT", "nothing.by.this.name")
    assert kept() == ([] if layer == "dense" else [routed])


def test_hidden_states_has_the_expert_layers_keep_the_routed_output(
        monkeypatch):
    """The names ``hidden_states`` hands ``by_sequence``: none of a dense
    layer's own, ``ROUTED`` of an expert layer's, each run of the period."""
    seen = []

    def recorded(layer, x, segment_ids, keep=()):
        seen.append(keep)
        return lm_blocks.by_sequence(layer, x, segment_ids, keep)

    monkeypatch.setattr(afmoe, "by_sequence", recorded)
    batch = tokens.split_batches(_pool(0), 2)[0]
    jax.eval_shape(functools.partial(afmoe.hidden_states, CFG), _params(0),
                   _bias(0), batch.tokens, batch.segment_ids)
    assert seen == [(), (afmoe.ROUTED,), (afmoe.ROUTED,)]


def test_what_a_step_counts_of_the_kept_bytes():
    batch = tokens.split_batches(_pool(0), 2)[0]
    m = step_metrics(CFG, batch, jnp.float32(0.0), (
        jnp.ones((6, 16), jnp.int32), jnp.zeros((6, 2), jnp.int32)), None,
        {"router_bias": _bias(0)})
    # 7 layers x 2 sequences x 4 heads x L x (16 float32 + a float32)
    assert float(m["attn_kept_bytes_sum"]) == 7 * 2 * 4 * L * (16 * 4 + 4)


def _tiles_left(mask, segment_ids, tile):
    """The tiles a head visits of the sequences, by the dense mask."""
    g = mask.n // tile
    return sum(int((mask.dense() & (row[:, None] == row[None, :])).reshape(
        g, tile, g, tile).any(axis=(1, 3)).sum()) for row in segment_ids)


@pytest.mark.parametrize("packed", [True, False])
def test_a_step_counts_the_tiles_its_documents_leave(monkeypatch, packed):
    """``attn_<kind>_tiles_live``: what ``ops/masked_attention.py`` visits of
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
    tiles = afmoe.attention_tiles(CFG, L)
    assert tiles == {"window": (7, 16, 5), "full": (10, 16, 2)}
    m = step_metrics(CFG, batch, jnp.float32(0.0), (
        jnp.ones((6, 16), jnp.int32), jnp.zeros((6, 2), jnp.int32)), tiles,
        {"router_bias": _bias(0)})
    for kind, (live, grid, layers) in tiles.items():
        mask = StaticMask("causal", L, window=WINDOW * (kind == "window"))
        left = _tiles_left(mask, batch.segment_ids, 8)
        assert (left < 2 * live) is packed
        assert float(m[f"attn_{kind}_tiles_live_sum"]) == 4 * layers * left
        assert float(m[f"attn_{kind}_tiles_grid_sum"]) == (
            4 * layers * 2 * grid)


@pytest.mark.parametrize("counted,lines,digest", [
    (False, 9678,
     "da1636ba0af424ee931054477a891253d4fae03cf387ef5ddf55d038e6031fcc"),
    (True, 9746,
     "58964a3d1c45811fc347736a4f3ce9092b28daffca298c14b2b996f921197f5b")])
def test_the_step_lowers_to_the_text_it_had_before_the_stack_moved(
        counted, lines, digest):
    """PR 49 moved the stack's scans out of models/afmoe.py into
    ``lm_blocks.scan_stack``, which models/lfm2.py shares: the step of this
    file's model lowers to the text PR 48's did (StableHLO without
    locations, under the suite's x64 and eight CPU devices), so no number
    of ``trinity.train`` moved with it. PR 51 refines the splash kernel's
    tables, which the CPU's path does not run: without the tiles' counters
    the step lowers to the text it had on PR 51's parent (and PR 48's less
    the counters' 8 lines); with them it is 68 lines longer, the tiles
    that the batch's documents leave (``cfg.live_tiles``). A change meant
    to reach this model's program fails here: measure ``trinity.train``
    with it, then pin the new text."""
    import hashlib

    batch = tokens.split_batches(_pool(0), 2)[0]
    step = make_lm_train_step(
        CFG, afmoe.attention_tiles(CFG, L) if counted else None)
    text = jax.jit(step).lower(_state(_params(0), _bias(0)), batch).as_text()
    assert len(text.splitlines()) == lines
    assert hashlib.sha256(text.encode()).hexdigest() == digest


def test_parameter_count_and_the_stack():
    real = afmoe.AfmoeConfig()
    # ISSUE 45's table: 65.0 M dense + 4 x 84.1 M + 102.5 M = 504.1 M
    attn = 3 * 2048 * 4096 + 2 * 2048 * 512 + 2 * 128 + 4 * 2048
    expert = 3 * 2048 * 1024
    assert real.n_params() == (
        attn + 3 * 2048 * 6144
        + 4 * (attn + 2048 * 128 + 9 * expert)
        + 2 * 25024 * 2048 + 2048) == 504_147_200
    assert real.period == (S, S, S, F) and real.n_periods == 1
    assert real.stats_shapes() == {"router_bias": (1, 4, 128)}
    deeper = dataclasses.replace(
        real, num_hidden_layers=9, layer_types=(S,) + (S, S, S, F) * 2)
    assert deeper.n_periods == 2 and deeper.period == real.period
    assert real.runs == ((S, 3), (F, 1)) and CFG.runs == ((S, 2), (F, 1))
    assert deeper.shapes()["periods"]["run0"]["router"] == (2, 3, 2048, 128)
    assert deeper.shapes()["periods"]["run1"]["wo"] == (2, 1, 4096, 2048)
    assert CFG.period == (S, S, F) and CFG.n_periods == 2
    with pytest.raises(ValueError, match="layer_types"):
        dataclasses.replace(real, num_hidden_layers=4)
    with pytest.raises(ValueError, match="one kind"):
        dataclasses.replace(real, num_dense_layers=2,
                            layer_types=(S, F, S, S, F))
    p = afmoe.init_params(CFG, jax.random.key(0), n_layers_published=32)
    assert float(p["periods"]["run0"]["attn_norm"].min()) == 1.0
    assert float(jnp.std(p["periods"]["run1"]["wo"])) == pytest.approx(
        0.02 / 8.0, rel=0.1)
    assert float(jnp.std(p["dense"]["mlp_down"])) == pytest.approx(
        0.02 / 8.0, rel=0.1)
    assert float(jnp.std(p["head"])) == pytest.approx(0.02, rel=0.1)
    assert not np.asarray(afmoe.init_stats(CFG)["router_bias"]).any()


# ---- the normal path --------------------------------------------------

def test_train_py_trains_the_tiny_preset_through_fit_and_the_scan_driver(
        capsys, tmp_path):
    import train

    code = train.main([
        "--device", "cpu", "--task", "lm", "--lm-model", "tiny",
        "--synthetic", "24", "-b", "2", "--epochs", "3", "--optim", "AdamW",
        "--lr", "3e-3", "--weight-decay", "0.1", "--ckpt-dir", str(tmp_path),
        "--check-invariants", "--no-preempt-handler"])
    out = capsys.readouterr().out
    assert code == 0
    losses = [float(ln.split("train loss ")[1].split()[0])
              for ln in out.splitlines() if ln.startswith("Epoch ")]
    assert len(losses) == 3 and losses[-1] < losses[0]
    assert "lm: " in out and "largest bias" in out
