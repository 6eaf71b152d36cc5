"""The block-diffusion mixture-of-experts decoder (models/sdar.py,
ops/bd_attention.py, ops/moe.py, train/lm_step.py, data/tokens.py) at a tiny
size on the CPU, against the plain reference
(benchmark/reference/sdar_ref.py), which imports nothing of the program.
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
from benchmark.reference import sdar_ref as ref  # noqa: E402
from cgnn_tpu.data import invariants, tokens  # noqa: E402
from cgnn_tpu.models import lm_blocks, sdar  # noqa: E402
from cgnn_tpu.ops import moe  # noqa: E402
from cgnn_tpu.ops.bd_attention import bd_attention, bd_mask, bd_tiles  # noqa: E402
from cgnn_tpu.ops.masked_attention import kept_bytes  # noqa: E402
from cgnn_tpu.train import Normalizer, make_optimizer  # noqa: E402
from cgnn_tpu.train.lm_step import (  # noqa: E402
    make_lm_train_step, step_metrics,
)
from cgnn_tpu.train.state import TrainState  # noqa: E402

L, BLOCK = 32, 4
CFG = sdar.SdarConfig(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_hidden_layers=2, n_experts=16, num_experts_per_tok=4,
    experts_held=(4, 4), moe_intermediate_size=32, vocab_size=128,
    block_length=BLOCK, dtype="float32")
REF_CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "num_hidden_layers": 2, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "vocab_size": 128, "rms_norm_eps": 1e-6,
    "rope_theta": 1e6, "experts_held": (4, 4), "block_length": BLOCK}
ADAMW = dict(lr=1e-3, b1=0.9, b2=0.95, weight_decay=0.1)


def _pool(seed=0, n=6):
    return tokens.make_pool(n, L, vocab_size=CFG.vocab_size, block=BLOCK,
                            seed=seed, doc_median=12, doc_min=4, doc_max=L)


def _params(seed):
    p = sdar.init_params(CFG, jax.random.key(seed), std=0.3)
    # norm scales off 1, so that a dropped scale shows
    return jax.tree_util.tree_map_with_path(
        lambda path, x: (x * (1.0 + 0.1 * jnp.cos(jnp.arange(
            x.size, dtype=jnp.float32).reshape(x.shape)))).astype(jnp.float32)
        if "norm" in str(path[-1]) else x, p)


def _state(params):
    tx = make_optimizer("adamw", lr=ADAMW["lr"], b1=ADAMW["b1"],
                        b2=ADAMW["b2"], weight_decay=ADAMW["weight_decay"],
                        lr_milestones=[])
    return TrainState(
        step=jnp.zeros((), jnp.int32), params=params, batch_stats={},
        opt_state=tx.init(params), normalizer=Normalizer.identity(1),
        rng=jax.random.key(0), apply_fn=functools.partial(sdar.apply, CFG),
        tx=tx)


def _as_ref(b):
    return {"tokens": b.tokens, "segment_ids": b.segment_ids,
            "loss_weight": b.loss_weight}


# ---- the pool ---------------------------------------------------------

def test_the_pool_is_packed_and_noised_as_the_objective_says():
    pool = _pool(3, n=64)
    assert pool.tokens.shape == (64, 2 * L) and pool.tokens.dtype == np.int32
    noised, clean = pool.tokens[:, :L], pool.tokens[:, L:]
    mask_id = CFG.mask_id
    assert clean.max() < mask_id and clean.min() >= 0
    masked = noised == mask_id
    assert ((noised == clean) | masked).all()
    # a weight exactly where the token was masked, 1/t of its block: the
    # same in every masked token of a block, and at least 1
    assert ((pool.loss_weight > 0) == masked).all()
    blocks = pool.loss_weight.reshape(64, L // BLOCK, BLOCK)
    for blk in blocks.reshape(-1, BLOCK):
        seen = np.unique(blk[blk > 0])
        assert len(seen) <= 1 and (seen >= 1.0).all()
    # no padding; documents are runs from 0 with boundaries on whole blocks
    seg = pool.segment_ids
    assert (seg[:, 0] == 0).all() and (np.diff(seg, axis=1) >= 0).all()
    assert (np.diff(seg, axis=1).reshape(64, -1)[:, BLOCK - 1::BLOCK].sum()
            == np.diff(seg, axis=1).sum())
    assert seg.max() >= 1  # some sequence holds several documents
    # the same seed gives the same pool; another seed another
    again = _pool(3, n=64)
    assert all((a == b).all() for a, b in zip(pool, again))
    assert (pool.tokens != _pool(4, n=64).tokens).any()
    # E[weight] is 1 a token: the loss is an unbiased bound
    assert 0.7 < pool.loss_weight.mean() < 1.4


def test_invariants_and_shape_key_take_a_token_batch():
    from cgnn_tpu.data.graph import batch_shape_key
    from cgnn_tpu.train import loop

    batches = tokens.split_batches(_pool(), 2)
    assert len(batches) == 3
    key = batch_shape_key(batches[0])
    assert key == ("tokens", (2, 2 * L))
    assert loop.program_name((key, 2), True) == f"scan_train_n{4 * L}_l2"
    invariants.check_any(batches[0], train=True)
    bad = batches[0]._replace(loss_weight=np.zeros_like(
        batches[0].loss_weight))
    with pytest.raises(invariants.BatchInvariantError, match="weight"):
        invariants.check_any(bad)
    bad = batches[0]._replace(segment_ids=batches[0].segment_ids[:, ::-1] + 1)
    with pytest.raises(invariants.BatchInvariantError, match="runs"):
        invariants.check_any(bad)
    # the staging counters read it as they read a graph batch
    args = loop._staging_args(batches)
    assert args["groups"] == 1 and args["batches"] == 3
    assert args["bytes"] == sum(x.nbytes for b in batches for x in b)
    assert args["edge_fea_bytes"] == 0
    assert args["transpose_overflow_rows"] == 0


# ---- the attention op -------------------------------------------------

def _dense_attention(q, k, v, seg, causal=False):
    """Plain softmax attention under the reference's dense mask."""
    group = q.shape[1] // k.shape[1]
    out = []
    for s in range(q.shape[0]):
        mask = ref.dense_mask(L, BLOCK, seg[s], causal)
        ks, vs = (jnp.repeat(t[s], group, axis=0) for t in (k, v))
        scores = jnp.where(mask, jnp.einsum("hqd,hkd->hqk", q[s], ks), -1e30)
        out.append(jnp.einsum("hqk,hkd->hqd", jax.nn.softmax(scores, -1),
                              vs))
    return jnp.stack(out)


def _qkv(seed):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (2, 4, 2 * L, 16))
    k = jax.random.normal(ks[1], (2, 2, 2 * L, 16))
    v = jax.random.normal(ks[2], (2, 2, 2 * L, 16))
    return q * 0.25, k, v, jax.random.normal(ks[3], (2, 4, 2 * L, 16))


@pytest.mark.parametrize("packed", [False, True])
def test_attention_agrees_with_the_dense_mask(packed):
    q, k, v, w = _qkv(0)
    seg = (jnp.asarray(_pool(1).segment_ids[:2]) if packed
           else jnp.zeros((2, L), jnp.int32))
    if packed:
        assert int(seg.max()) >= 1
    got = bd_attention(q, k, v, seg, block=BLOCK, impl="blocked")
    want = _dense_attention(q, k, v, seg)
    np.testing.assert_allclose(got, want, atol=2e-6)
    f = lambda fn: jax.grad(lambda *a: (fn(*a) * w).sum(), (0, 1, 2))  # noqa: E731
    g_got = f(lambda q, k, v: bd_attention(q, k, v, seg, block=BLOCK,
                                           impl="blocked"))(q, k, v)
    g_want = f(lambda q, k, v: _dense_attention(q, k, v, seg))(q, k, v)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(a, b, atol=5e-6)
    # the plain causal mask is another function
    assert float(jnp.abs(got - _dense_attention(q, k, v, seg,
                                                causal=True)).max()) > 1e-2


# (query, key, visible) over the doubled sequence of L = 32, blocks of 4:
# position i of the noised half is i, of the clean half L + i
REGIONS = [
    ("noised sees its own block, later tokens too", 5, 7, True),
    ("noised sees its own block, earlier tokens", 6, 4, True),
    ("noised does not see a later noised block", 5, 8, False),
    ("noised does not see an earlier noised block", 9, 5, False),
    ("noised sees the clean keys of earlier blocks", 9, L + 3, True),
    ("noised does not see its own block's clean keys", 9, L + 8, False),
    ("noised does not see later clean keys", 9, L + 12, False),
    ("clean sees its own block, later tokens too", L + 8, L + 11, True),
    ("clean sees earlier clean blocks", L + 8, L + 1, True),
    ("clean does not see later clean blocks", L + 8, L + 12, False),
    ("clean never sees a noised key, its own position's", L + 8, 8, False),
    ("clean never sees a noised key, an earlier block's", L + 8, 1, False),
]


@pytest.mark.parametrize("says,i,j,visible", REGIONS,
                         ids=[r[0] for r in REGIONS])
def test_every_region_of_the_mask(says, i, j, visible):
    """The program's static mask and the reference's dense one agree on
    the region, and the op's output at query ``i`` moves with key ``j``'s
    value if and only if ``j`` is visible."""
    mask = bd_mask(L, BLOCK)
    dense = np.asarray(ref.dense_mask(L, BLOCK, jnp.zeros(L, jnp.int32)))
    assert (mask == dense).all()
    assert bool(mask[i, j]) is visible
    q, k, v, _ = _qkv(2)
    seg = jnp.zeros((2, L), jnp.int32)
    moved = v.at[:, :, j].add(1.0)
    a = bd_attention(q, k, v, seg, block=BLOCK, impl="blocked")
    b = bd_attention(q, k, moved, seg, block=BLOCK, impl="blocked")
    assert bool(jnp.abs(a - b)[:, :, i].max() > 1e-6) is visible


def test_nothing_crosses_a_document():
    q, k, v, _ = _qkv(3)
    seg = jnp.asarray(np.repeat([[0, 1]], 2, 0).repeat(L // 2, axis=1)
                      .astype(np.int32))
    a = bd_attention(q, k, v, seg, block=BLOCK, impl="blocked")
    # a key of the first document, clean half: visible to a later block of
    # its own document, never to the second document's
    j = L + 2
    b = bd_attention(q, k, v.at[:, :, j].add(1.0), seg, block=BLOCK,
                     impl="blocked")
    moved = np.asarray(jnp.abs(a - b).max(axis=(0, 1, 3)) > 1e-6)
    assert moved[L + 9] and moved[9]  # own document, later blocks
    assert not moved[L + L // 2:].any() and not moved[L // 2:L].any()


def test_tiles():
    assert bd_tiles(L, BLOCK) == (1, 1)  # one tile holds this size
    live, grid = bd_tiles(4096, 4)
    assert grid == 256 and live == 80  # 8 + 36 + 0 + 36 tiles of 512
    assert 0.25 < live / grid < 0.36


# ---- the expert layer -------------------------------------------------

def _expert_weights(seed, n_experts=16, h=64, inter=32):
    ks = jax.random.split(jax.random.key(seed), 4)
    return (jax.random.normal(ks[0], (24, h)),
            jax.random.normal(ks[1], (h, n_experts)),
            0.2 * jax.random.normal(ks[2], (n_experts, h, 2 * inter)),
            0.2 * jax.random.normal(ks[3], (n_experts, inter, h)))


def test_the_shares_add_up_to_the_uncut_layer():
    """Four shares of four experts each: their partial outputs add up to
    what the reference's uncut layer gives, and the rows they were routed
    add up to every (token, choice) pair."""
    x, router, w_gu, w_d = _expert_weights(0)
    total, rows = 0.0, 0
    for first in range(0, 16, 4):
        out, sizes, _ = moe.expert_share(
            x, router, w_gu[first:first + 4], w_d[first:first + 4],
            experts_held=(first, 4), k=4, impl="ragged")
        assert int(sizes.sum()) == 24 * 4
        rows += int(sizes[first:first + 4].sum())
        total = total + out
    assert rows == 24 * 4
    want = ref.full_expert_layer(x, router, w_gu, w_d, 4)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # one share alone is not the layer
    assert float(jnp.abs(out - want).max()) > 1e-2


def test_dropless_under_a_router_forced_onto_one_held_expert():
    """Every token's first choice is expert 5, held here: all 24 rows reach
    it, none is dropped, and the output is the reference's."""
    x, router, w_gu, w_d = _expert_weights(1)
    router = router.at[:, 5].set(0.0) * 0.01
    router = router.at[:, 5].set(50.0 * jnp.sign(x.sum(0)))
    x = jnp.abs(x) * jnp.sign(x.sum(0))[None, :]  # x . router[:, 5] >> 0
    out, sizes, _ = moe.expert_share(
        x, router, w_gu[4:8], w_d[4:8], experts_held=(4, 4), k=4,
        impl="ragged")
    assert int(sizes[5]) == 24
    want, most = ref._experts(
        x, {"router": router, "w_gate_up": w_gu[4:8], "w_down": w_d[4:8]},
        REF_CFG, ref._mm_f32, None)
    assert int(most) == 24
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    # the reference's control drops rows, and reads otherwise
    dropped, _ = ref._experts(
        x, {"router": router, "w_gate_up": w_gu[4:8], "w_down": w_d[4:8]},
        REF_CFG, ref._mm_f32, 6)
    assert float(jnp.abs(dropped - want).max()) > 1e-3


def test_routing_gradients_reach_the_router():
    x, router, w_gu, w_d = _expert_weights(2)

    def f(x, router, w_gu, w_d):
        out, *_ = moe.expert_share(x, router, w_gu[4:8], w_d[4:8],
                                   experts_held=(4, 4), k=4, impl="ragged")
        return (out ** 2).sum()

    def g(x, router, w_gu, w_d):
        out, _ = ref._experts(
            x, {"router": router, "w_gate_up": w_gu[4:8],
                "w_down": w_d[4:8]}, REF_CFG, ref._mm_f32, None)
        return (out ** 2).sum()

    got = jax.grad(f, (0, 1, 2, 3))(x, router, w_gu, w_d)
    want = jax.grad(g, (0, 1, 2, 3))(x, router, w_gu, w_d)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(got[1]).max()) > 0
    # the experts not held get no gradient
    assert float(jnp.abs(got[2][:4]).max()) == 0.0


# ---- the held rows travel compact -------------------------------------

def _load(name):
    """(x, router, w_gu, w_d, held) of one load, 24 tokens of 4 choices
    over 16 experts."""
    x, router, w_gu, w_d = _expert_weights(3)
    held = {"first_is_zero": (0, 4), "the_last_experts": (12, 4)}.get(
        name, (4, 4))
    if name == "all_on_one_held_expert":
        router = router.at[:, 5].set(50.0 * jnp.sign(x.sum(0)))
        x = jnp.abs(x) * jnp.sign(x.sum(0))[None, :]
    if name == "no_row_held":
        router = router.at[:, 4:8].set(-50.0 * jnp.sign(x.sum(0))[:, None])
        x = jnp.abs(x) * jnp.sign(x.sum(0))[None, :]
    return x, router, w_gu, w_d, held


@functools.lru_cache(maxsize=None)
def _reference_of(name):
    """The reference's output, its gradients and the held rows' count."""
    x, router, w_gu, w_d, (first, count) = _load(name)
    cfg = {**REF_CFG, "experts_held": (first, count)}

    def g(x, router, a, b):
        out, _ = ref._experts(x, {"router": router, "w_gate_up": a,
                                  "w_down": b}, cfg, ref._mm_f32, None)
        return (out ** 2).sum(), out

    (_, out), grads = jax.value_and_grad(g, (0, 1, 2, 3), has_aux=True)(
        x, router, w_gu[first:first + count], w_d[first:first + count])
    _, experts = moe.route(x @ router, 4)
    n_here = int(((experts >= first) & (experts < first + count)).sum())
    return out, grads, n_here


LOADS = ("first_above_zero", "first_is_zero", "the_last_experts",
         "all_on_one_held_expert", "no_row_held")
# the compact rung's capacity from the held rows' count (24 x 4 = 96 pairs:
# the ladder's own choice at this size is the one rung)
RUNGS = {"rows_under_the_rung": lambda n: n + 5,
         "rows_exactly_at_the_rung": lambda n: max(n, 1),
         "one_row_over_the_rung": lambda n: max(n - 1, 1),
         "the_full_rung_forced": lambda n: 96,
         "the_ladder_s_own_choice": lambda n: None}


@pytest.mark.parametrize("load", LOADS)
@pytest.mark.parametrize("rung", RUNGS)
def test_every_rung_under_every_load_is_the_reference_s_layer(rung, load):
    """Whatever capacity carries the held rows, output and gradients (to
    ``x``, the router and both weight stacks) are the reference's, and the
    rung's index says which capacity it was."""
    x, router, w_gu, w_d, (first, count) = _load(load)
    want, want_grads, n_here = _reference_of(load)
    assert {"all_on_one_held_expert": n_here >= 24,
            "no_row_held": n_here == 0}.get(load, 0 < n_here < 48)
    capacity = RUNGS[rung](n_here)
    # the compact rung where it holds the rows, else the full one behind it
    index = int(capacity is not None and n_here > capacity)
    assert index == (rung == "one_row_over_the_rung" and n_here > 0)

    def f(x, router, a, b):
        out, sizes, got = moe.expert_share(
            x, router, a, b, experts_held=(first, count), k=4,
            impl="ragged", capacity=capacity)
        return (out ** 2).sum(), (out, sizes, got)

    (_, (out, sizes, got)), grads = jax.jit(jax.value_and_grad(
        f, (0, 1, 2, 3), has_aux=True))(
        x, router, w_gu[first:first + count], w_d[first:first + count])
    assert int(sizes[first:first + count].sum()) == n_here
    assert int(got) == index
    np.testing.assert_allclose(out, want, rtol=2e-5, atol=2e-5)
    for a, b in zip(grads, want_grads):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)


def _carried_rows(seed=0, c=300, t=50, k=8, h=16):
    """``c`` carried rows of which 200 are valid: five a token for 40 of
    the 50 tokens, in an order of their own, so that sorted by token the run
    of token 25 (rows 125-129) straddles the first block of 128."""
    rng = np.random.default_rng(seed)
    counts = np.zeros(t, np.int32)
    counts[rng.permutation(t)[:40]] = 5
    tok = np.repeat(np.arange(t), counts)
    assert tok[127] == tok[128]  # a run over a block's edge
    tok = np.concatenate([rng.permutation(tok), rng.integers(0, t, c - 200)])
    valid = np.arange(c) < 200
    plan = moe.row_plan(jnp.asarray(tok, jnp.int32), jnp.asarray(valid),
                        jnp.asarray(counts), k)
    return plan, counts, rng.normal(size=(c, h)).astype(np.float32), \
        rng.normal(size=(t, h)).astype(np.float32)


def test_combine_is_the_segment_sum_of_the_valid_rows():
    plan, counts, y, _ = _carried_rows()
    tok, valid = plan[:2]
    want = jax.ops.segment_sum(jnp.where(valid[:, None], y, 0), tok, 50)
    np.testing.assert_allclose(moe.combine(jnp.asarray(y), plan), want,
                               rtol=1e-5, atol=1e-5)
    assert float(jnp.abs(want[counts == 0]).max()) == 0.0
    # what a row that is not valid holds never arrives
    y = np.where(np.asarray(valid)[:, None], y, np.nan)
    got = moe.combine(jnp.asarray(y), plan)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


def test_spread_and_combine_are_each_other_s_transpose():
    plan, _, y, x = _carried_rows(1)
    tok, valid = plan[:2]
    rows = moe.spread(jnp.asarray(x), plan)
    np.testing.assert_array_equal(
        rows, np.where(np.asarray(valid)[:, None], x[np.asarray(tok)], 0))
    back = moe.combine(jnp.asarray(y), plan)
    assert float((rows * y).sum()) == pytest.approx(
        float((x * back).sum()), rel=1e-5)
    # and each one's declared reverse pass is the other
    np.testing.assert_allclose(
        jax.vjp(lambda x: moe.spread(x, plan),
                jnp.asarray(x))[1](jnp.asarray(y))[0], back, rtol=1e-6)
    np.testing.assert_array_equal(
        jax.vjp(lambda y: moe.combine(y, plan),
                jnp.asarray(y))[1](jnp.asarray(x))[0], rows)


def test_the_ladder_and_what_the_step_counts_of_it():
    """The rungs are the shapes': twice the balanced load, doubled up to
    every pair. The step's sums read the rungs' indices as rows carried and
    as calls that took the last rung."""
    from cgnn_tpu.train.lm_step import routing_metrics

    assert moe.ladder(65536, 16, 128) == (16384, 32768, 65536)
    assert moe.ladder(65536, 64, 128) == (65536,)
    assert moe.ladder(96, 4, 16) == (96,)
    assert moe.ladder(4096 * 2, 4, 64) == (1024, 2048, 4096, 8192)
    sizes = jnp.zeros((2, 128), jnp.int32).at[:, :16].set(1000)
    m = routing_metrics(sizes, jnp.asarray([[0, 1], [2, 0]], jnp.int32),
                        (0, 16), 8, 2 * 8192)
    assert float(m["moe_rows_here_sum"]) == 32000.0
    assert float(m["moe_rows_capacity_sum"]) == 2 * 16384 + 32768 + 65536
    assert float(m["moe_calls_full_rung_sum"]) == 1.0


def test_swiglu_s_reverse_pass():
    gu = jax.random.normal(jax.random.key(0), (7, 10))
    plain = lambda t: jax.nn.silu(t[:, :5]) * t[:, 5:]  # noqa: E731
    np.testing.assert_allclose(moe.swiglu(gu), plain(gu), rtol=1e-6)
    w = jnp.arange(35.0).reshape(7, 5)
    np.testing.assert_allclose(
        jax.grad(lambda t: (moe.swiglu(t) * w).sum())(gu),
        jax.grad(lambda t: (plain(t) * w).sum())(gu), rtol=1e-5, atol=1e-6)


# ---- the whole step against the reference -----------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_three_adamw_steps_agree_with_the_reference(seed):
    """Loss, the first gradient leaf by leaf, and the parameters' change
    after three AdamW steps, from seeded weights."""
    params = _params(seed)
    batches = tokens.split_batches(_pool(seed), 2)
    want = ref.adamw_steps(
        jax.tree_util.tree_map(np.asarray, params),
        [_as_ref(b) for b in batches], REF_CFG, **ADAMW)
    assert want["expert_rows_most"] > 0
    step = jax.jit(make_lm_train_step(CFG))
    state = _state(params)
    losses = []
    for t, b in enumerate(batches):
        state, m = step(state, b)
        losses.append(float(m["loss_sum"]) / float(m["count"]))
        if t == 0:
            grad = bd_train.first_gradient(state.opt_state, ADAMW["b1"])
    np.testing.assert_allclose(losses, want["loss"], rtol=2e-5)
    # at this size the ladder is the one rung: 2 layers x 2 sequences carry
    # all 64 x 4 pairs each
    assert float(m["moe_calls_full_rung_sum"]) == 4.0
    assert float(m["moe_rows_capacity_sum"]) == 4 * 2 * L * 4
    flat_got = jax.tree_util.tree_leaves_with_path(grad)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want["grad"]))
    assert len(flat_got) == 14
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


def test_the_reference_reads_an_expert_s_run_in_chunks(monkeypatch):
    """Whatever the chunk, the same function: a chunk past a run's end is
    skipped, the last one masked."""
    params = jax.tree_util.tree_map(np.asarray, _params(0))
    batch = _as_ref(tokens.split_batches(_pool(0), 2)[0])
    f = lambda: jax.value_and_grad(  # noqa: E731
        lambda p: ref.batch_loss(p, batch, REF_CFG), has_aux=True)(params)
    (a, most), ga = f()
    assert ref.EXPERT_CHUNK == 1024 and 8 < int(most) <= 2 * L
    monkeypatch.setattr(ref, "EXPERT_CHUNK", 8)
    (b, most_b), gb = f()
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    assert int(most_b) == int(most)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(x, y, rtol=1e-4, atol=1e-7)


@pytest.mark.parametrize("fault", [
    {"causal_mask": True}, {"unweighted": True}, {"dropped_rows": 8},
    {"mm": ref.mm_fp8}])
def test_each_fault_of_the_reference_is_another_function(fault):
    params = jax.tree_util.tree_map(np.asarray, _params(0))
    batch = _as_ref(tokens.split_batches(_pool(0), 2)[0])
    sound, _ = ref.batch_loss(params, batch, REF_CFG)
    broken, _ = ref.batch_loss(params, batch, REF_CFG, **fault)
    assert abs(float(broken) - float(sound)) > 1e-3 * abs(float(sound))


def test_bfloat16_compute_stays_near_float32():
    cfg16 = dataclasses.replace(CFG, dtype="bfloat16")
    params = _params(0)
    batch = tokens.split_batches(_pool(0), 2)[0]
    a, *_ = sdar.apply(CFG, {"params": params}, batch)
    b, sizes, rungs = sdar.apply(cfg16, {"params": params}, batch)
    assert a.shape == b.shape == (2,) and sizes.shape == (2, 16)
    assert rungs.shape == (2, 2)  # a layer and sequence each
    np.testing.assert_allclose(a, b, rtol=0.05)
    assert int(sizes.sum()) == 2 * (2 * 2 * L) * 4


# ---- what the layer's checkpoint keeps --------------------------------

@pytest.mark.parametrize("dtype,rounding", [("float32", 1e-5),
                                            ("bfloat16", 0.0)])
def test_keeping_the_attention_s_output_changes_no_number(monkeypatch,
                                                          dtype, rounding):
    """One training step with the attention's output kept for the reverse
    pass and with a ``by_sequence`` that keeps the layer's input alone (no
    value carries the name it keeps): the same operations on the same
    values. The loss is equal, and in bfloat16, the cells' precision, so is
    every leaf's gradient; in float32 to the rounding of the CPU's compiler
    (7e-7 of a leaf's largest here), which fuses the forward pass it
    rebuilds in its own way: run op by op the leaves are equal there too
    (60 s a step)."""
    cfg = dataclasses.replace(CFG, dtype=dtype)
    batch = tokens.split_batches(_pool(0), 2)[0]
    state = dataclasses.replace(
        _state(_params(0)), apply_fn=functools.partial(sdar.apply, cfg))

    def step():
        new, m = jax.jit(make_lm_train_step(cfg))(state, batch)
        return float(m["loss_sum"]), bd_train.first_gradient(
            new.opt_state, ADAMW["b1"])

    loss, grad = step()
    monkeypatch.setattr(lm_blocks, "KEPT", "nothing.by.this.name")
    loss_alone, grad_alone = step()
    assert loss == loss_alone
    flat = jax.tree_util.tree_leaves_with_path(grad)
    assert len(flat) == 14
    for (path, g), w in zip(flat, jax.tree_util.tree_leaves(grad_alone)):
        assert np.abs(w).max() > 0, path
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=rounding * np.abs(w).max(),
                                   err_msg=str(path))


def test_the_layer_s_checkpoint_keeps_the_attention_s_output(monkeypatch,
                                                             capsys):
    """What the reverse pass of a layer under ``by_sequence`` keeps beside
    the layer's arguments: the attention's named output, every sequence's
    stacked by the scan over them; under another name, nothing."""
    params = _params(0)
    batch = tokens.split_batches(_pool(0), 2)[0]
    p0 = jax.tree_util.tree_map(lambda a: a[0], params["layers"])
    x = params["embed"][batch.tokens]

    def kept():
        jax.ad_checkpoint.print_saved_residuals(
            lambda x, p: lm_blocks.by_sequence(
                lambda x_seq, seg: sdar._layer(CFG, x_seq, p, seg), x,
                batch.segment_ids)[0].sum(), x, p0)
        return sorted(ln.split()[0] for ln in capsys.readouterr().out
                      .splitlines() if "output of scan" in ln
                      and not ln.startswith("i32"))  # the documents

    # [S, 1, Hkv, G, 2L, D]: the op's output before it is reshaped
    assert kept() == [f"f32[2,1,2,2,{2 * L},16]"]
    monkeypatch.setattr(lm_blocks, "KEPT", "nothing.by.this.name")
    assert kept() == []


@pytest.mark.parametrize("dtype,width", [("float32", 4), ("bfloat16", 2)])
def test_what_a_step_counts_of_the_kept_bytes(dtype, width):
    """``attn_kept_bytes``: layers x sequences x (the output in the compute
    dtype + the float32 log-sum-exp), over the doubled positions."""
    batch = tokens.split_batches(_pool(0), 2)[0]
    m = step_metrics(dataclasses.replace(CFG, dtype=dtype), batch,
                     jnp.float32(0.0), (jnp.ones((2, 16), jnp.int32),
                                        jnp.zeros((2, 2), jnp.int32)), None)
    assert float(m["attn_kept_bytes_sum"]) == (
        2 * 2 * 4 * (2 * L) * (16 * width + 4))
    assert float(m["attn_kept_bytes_count"]) == 1.0
    # the cell's shapes: 0.55 GB a step
    assert 4 * 2 * kept_bytes(32, 8192, 128, jnp.bfloat16) == 545_259_520


@pytest.mark.parametrize("packed", [True, False])
def test_a_step_counts_the_tiles_its_documents_leave(monkeypatch, packed):
    """``bd_tiles_live``: what ``ops/masked_attention.py`` visits of the
    step's doubled sequences, tiles of 8 here; with one document a sequence
    the static count of ``attention_tiles``; the grid either way."""
    from cgnn_tpu.ops import masked_attention as op

    monkeypatch.setattr(op, "TILE_Q", 8)
    monkeypatch.setattr(op, "TILE_KV", 8)
    # boundaries on the tiles' edges: a tile the kernel visits holds a pair
    # that the dense mask shows (off them it may visit one that holds none)
    documents = ([8, 24], [16, 8, 8]) if packed else ([L], [L])
    batch = tokens.split_batches(_pool(0), 2)[0]._replace(
        segment_ids=np.stack([np.repeat(np.arange(len(d)), d)
                              for d in documents]).astype(np.int32))
    live, grid = tiles = sdar.attention_tiles(CFG, L)
    assert tiles == (24, 64)  # 4 + 10 + 0 + 10 of four quarters of 16
    m = step_metrics(CFG, batch, jnp.float32(0.0), (
        jnp.ones((2, 16), jnp.int32), jnp.zeros((2, 2), jnp.int32)), tiles)
    seg = np.concatenate([batch.segment_ids, batch.segment_ids], axis=1)
    left = sum(int((bd_mask(L, BLOCK) & (row[:, None] == row[None, :]))
                   .reshape(8, 8, 8, 8).any(axis=(1, 3)).sum())
               for row in seg)
    assert (left < 2 * live) is packed
    # two layers of four heads
    assert float(m["bd_tiles_live_sum"]) == 4 * 2 * left
    assert float(m["bd_tiles_grid_sum"]) == 4 * 2 * 2 * grid


def test_parameter_count_and_init():
    real = sdar.SdarConfig()
    layer = (2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 2 * 128 + 2048
             + 2048 + 2048 * 128 + 16 * 3 * 2048 * 768)
    assert layer == 94_638_336
    assert real.n_params() == 6 * layer + 2 * 18992 * 2048 + 2048
    assert real.n_params() == 645_623_296
    assert dataclasses.replace(real, num_hidden_layers=4).n_params() \
        == 4 * layer + 2 * 18992 * 2048 + 2048
    p = sdar.init_params(CFG, jax.random.key(0), n_layers_published=48)
    assert float(p["layers"]["wo"].std()) == pytest.approx(
        0.02 / (96 ** 0.5), rel=0.1)
    assert float(p["layers"]["wq"].std()) == pytest.approx(0.02, rel=0.1)
    assert float(p["final_norm"].min()) == 1.0


# ---- the normal path --------------------------------------------------

def test_train_py_trains_the_tiny_preset_through_fit_and_the_scan_driver(
        capsys, tmp_path):
    import train

    code = train.main([
        "--device", "cpu", "--task", "blockdiff", "--synthetic", "24",
        "-b", "2", "--epochs", "3", "--optim", "AdamW", "--lr", "3e-3",
        "--weight-decay", "0.1", "--ckpt-dir", str(tmp_path),
        "--check-invariants", "--no-preempt-handler"])
    out = capsys.readouterr().out
    assert code == 0
    losses = [float(ln.split("train loss ")[1].split()[0])
              for ln in out.splitlines() if ln.startswith("Epoch ")]
    assert len(losses) == 3 and losses[-1] < losses[0]
    assert "blockdiff: 0.12 M parameters" in out or "blockdiff:" in out
