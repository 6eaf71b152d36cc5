"""The hybrid Mamba-2 / attention mixture-of-experts decoder
(models/nemotron_h.py, ops/ssd.py, ops/short_conv.py's shared taps, the
stack of models/lm_blocks.py with layers that are a mixer OR a feed-forward
part, ops/moe.py's ``relu2`` experts, train/lm_step.py's causal step) at a
tiny size on the CPU, against the plain reference
(benchmark/reference/nemotron_ref.py), which imports nothing of the program
and holds no chunked form.
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
from benchmark.reference import nemotron_ref as ref  # noqa: E402
from cgnn_tpu.data import tokens  # noqa: E402
from cgnn_tpu.models import afmoe, lfm2, lm_blocks  # noqa: E402
from cgnn_tpu.models import nemotron_h as nm  # noqa: E402
from cgnn_tpu.ops import moe  # noqa: E402
from cgnn_tpu.ops.ssd import ssd_counts  # noqa: E402
from cgnn_tpu.train import Normalizer, make_optimizer  # noqa: E402
from cgnn_tpu.train.lm_step import make_lm_train_step  # noqa: E402
from cgnn_tpu.train.state import TrainState  # noqa: E402

L = 32
M, E, A = nm.MAMBA, nm.MOE, nm.ATTENTION
# two periods of (E, M) x 2 and an attention layer; chunks of 8 positions,
# which the documents' starts do and do not fall on
PATTERN = "EMEM*" * 2
CFG = nm.NemotronHConfig(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_hidden_layers=10, hybrid_override_pattern=PATTERN,
    mamba_num_heads=8, mamba_head_dim=8, n_groups=2, ssm_state_size=16,
    chunk_size=8, moe_intermediate_size=24,
    moe_shared_expert_intermediate_size=48, n_experts=16,
    num_experts_per_tok=4, experts_held=(4, 4), vocab_size=128,
    dtype="float32")
REF_CFG = {
    "hidden_size": 64, "num_attention_heads": 4, "num_key_value_heads": 2,
    "head_dim": 16, "hybrid_override_pattern": PATTERN,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "num_experts_per_tok": 4, "experts_held": (4, 4),
    "rope_theta": 1e4, "layer_norm_epsilon": 1e-5, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5}
ADAMW = dict(lr=1e-3, b1=0.9, b2=0.95, weight_decay=0.1)


def _pool(seed=0, n=6):
    return tokens.make_pool(n, L, vocab_size=CFG.vocab_size, seed=seed,
                            doc_median=12, doc_min=2, doc_max=L,
                            kind="causal")


def _params(seed, cfg=CFG):
    p = nm.init_params(cfg, jax.random.key(seed), std=0.3)
    # norm scales and the skips off 1, so that a dropped one shows
    return jax.tree_util.tree_map_with_path(
        lambda path, x: (x * (1.0 + 0.1 * jnp.cos(jnp.arange(
            x.size, dtype=jnp.float32).reshape(x.shape)))).astype(jnp.float32)
        if str(path[-1].key).endswith(("norm", "d_skip")) else x, p)


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
        apply_fn=functools.partial(nm.apply, cfg), tx=tx)


def _as_ref(b):
    return {"tokens": b.tokens, "segment_ids": b.segment_ids,
            "loss_weight": b.loss_weight}


def _layer_params(params, kind, period=0, place=0):
    run = params["periods"]["run1" if kind == A else "run0"]
    leaves = run if kind == A else run[kind]
    return jax.tree_util.tree_map(lambda a: a[period, place], leaves)


# ---- nothing crosses a document ----------------------------------------

@pytest.mark.parametrize("kind", [M, A])
def test_nothing_crosses_a_document(kind):
    """A mixer's output and its input's gradient at the positions of other
    documents are BIT-equal when one document's input changes (the filter,
    the state and the attention alike); later positions of its own move."""
    p = _layer_params(_params(0), kind)
    seg = jnp.asarray(_pool(2).segment_ids[:1])
    x = jax.random.normal(jax.random.key(3), (1, L, 64), jnp.float32)
    layer = nm._mamba_layer if kind == M else nm._attention_layer
    j = 3
    own = np.asarray(seg[0]) == int(seg[0, j])
    assert own[j + 1] and not own.all()
    # a document that starts inside a chunk of 8, and one on its first place
    starts = np.flatnonzero(np.diff(np.asarray(seg[0])) != 0) + 1
    assert (starts % CFG.chunk_size != 0).any()

    def out_and_grad(x):
        f = lambda v: layer(CFG, v, p, seg)[0]  # noqa: E731
        y, pull = jax.vjp(f, x)
        return y, pull(jnp.cos(jnp.arange(y.size, dtype=jnp.float32)
                               ).reshape(y.shape))[0]

    (a, ga), (b, gb) = out_and_grad(x), out_and_grad(
        x.at[0, own].add(jax.random.normal(
            jax.random.key(4), (int(own.sum()), 64), jnp.float32)))
    np.testing.assert_array_equal(np.asarray(a)[0, ~own],
                                  np.asarray(b)[0, ~own])
    np.testing.assert_array_equal(np.asarray(ga)[0, ~own],
                                  np.asarray(gb)[0, ~own])
    moved = np.abs(np.asarray(a - b)[0]).max(axis=-1) > 0
    assert moved[own].all()
    assert np.isfinite(np.asarray(ga)).all()
    # causal: a later position's input moves no earlier output
    c = layer(CFG, x.at[0, j].add(1.0), p, seg)[0]
    moved = np.abs(np.asarray(a - c)[0]).max(axis=-1) > 0
    assert not moved[:j].any() and moved[j] and moved[j + 1]


def test_the_mamba_layer_is_the_reference_s_position_by_position():
    """One Mamba layer of the program (the shared taps at four with a bias
    and a silu, the chunked scan, the gate before the grouped norm) against
    the reference's loop over taps and positions; each fault of the layer
    is another function."""
    p = _layer_params(_params(0), M)
    seg = _pool(2).segment_ids[0]
    x = jax.random.normal(jax.random.key(3), (L, 64), jnp.float32)
    got = nm._mamba_layer(CFG, x[None], p, jnp.asarray(seg)[None])[0][0] - x
    h = ref._rms(x, p["norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        want = ref.mamba_mixer(h, p, jnp.asarray(seg), REF_CFG)
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
        for fault in ref.FAULTS[:10]:
            broken = ref.mamba_mixer(h, p, jnp.asarray(seg), REF_CFG,
                                     faults=(fault,))
            assert float(jnp.abs(broken - want).max()) > 1e-3 * float(
                jnp.abs(want).max()), fault
        rounded = ref.mamba_mixer(h, p, jnp.asarray(seg), REF_CFG,
                                  state_dtype="bfloat16")
    gap = float(jnp.abs(rounded - want).max() / jnp.abs(want).max())
    assert 1e-4 < gap < 0.1, gap


def test_the_attention_takes_no_positions():
    """The attention layer equals the reference's unrotated one and is
    another function under RoPE; a sequence's documents in another order
    give the same outputs in that order (no position reaches the layer)."""
    p = _layer_params(_params(0), A)
    x = jax.random.normal(jax.random.key(3), (L, 64), jnp.float32)
    seg = np.repeat(np.arange(4), L // 4).astype(np.int32)
    got = nm._attention_layer(CFG, x[None], p, jnp.asarray(seg)[None])[0][0]
    h = ref._rms(x, p["norm"], 1e-5)
    with jax.default_matmul_precision("highest"):
        want = ref.attention_mixer(h, p, jnp.asarray(seg), REF_CFG)
        rotated = ref.attention_mixer(h, p, jnp.asarray(seg), REF_CFG,
                                      faults=("attention_rotated",))
    np.testing.assert_allclose(got - x, want, rtol=2e-4, atol=2e-5)
    assert float(jnp.abs(rotated - want).max()) > 1e-2
    order = np.concatenate([np.arange(L // 2, L), np.arange(L // 2)])
    moved = nm._attention_layer(CFG, x[order][None], p,
                                jnp.asarray(seg[order])[None])[0][0]
    np.testing.assert_allclose(moved, got[order], rtol=1e-5, atol=1e-6)


# ---- relu^2 experts, and the sixteen shares -----------------------------

def _expert_weights(seed, t=24, h=16, e=128, i=12):
    ks = jax.random.split(jax.random.key(seed), 7)
    scales = (1.0, 1.0, 0.3, 0.3, 0.5, 0.3, 0.3)
    shapes = ((t, h), (h, e), (e, h, i), (e, i, h), (e,), (h, 2 * i),
              (2 * i, h))
    return tuple(s * jax.random.normal(k, shape, jnp.float32)
                 for s, k, shape in zip(scales, ks, shapes))


ROUTING = nm.NemotronHConfig().routing
SHARE_CFG = {"num_experts_per_tok": 6, "norm_topk_prob": True,
             "routed_scaling_factor": 2.5}


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Sixteen shares of eight experts each (0-7, 8-15, .. 120-127), with
    the shared expert counted once, add up to the reference's uncut layer
    of 128, and the rows they were routed to every (token, choice); at a
    width (12) that is no whole number of tiles, padded to one."""
    x, router, w_up, w_down, bias, s_up, s_down = _expert_weights(0)
    assert ROUTING == moe.Router("sigmoid", True, 1e-20, 2.5)
    total = moe.relu2(x @ s_up) @ s_down
    rows = 0
    for first in range(0, 128, 8):
        up, down = moe.lane_aligned(w_up[first:first + 8],
                                    w_down[first:first + 8])
        assert up.shape == (8, 16, 128) and down.shape == (8, 128, 16)
        out, sizes, _ = moe.expert_share(
            x, router, up, down, experts_held=(first, 8), k=6,
            impl="ragged", routing=ROUTING, bias=bias, form="relu2")
        assert int(sizes.sum()) == 24 * 6
        rows += int(sizes[first:first + 8].sum())
        total = total + out
    assert rows == 24 * 6
    with jax.default_matmul_precision("highest"):
        want = ref.full_expert_layer(x, router, bias, w_up, w_down, s_up,
                                     s_down, SHARE_CFG)
    np.testing.assert_allclose(total, want, rtol=2e-5, atol=2e-5)
    # one share alone is not the layer, nor the layer without its shared
    # expert, its bias, or its scale
    assert float(jnp.abs(out - want).max()) > 1e-2
    for other in (
            ref.full_expert_layer(x, router, bias, w_up, w_down, 0 * s_up,
                                  s_down, SHARE_CFG),
            ref.full_expert_layer(x, router, 0 * bias, w_up, w_down, s_up,
                                  s_down, SHARE_CFG),
            ref.full_expert_layer(x, router, bias, w_up, w_down, s_up,
                                  s_down, {**SHARE_CFG,
                                           "routed_scaling_factor": 1.0})):
        assert float(jnp.abs(other - want).max()) > 1e-2


@pytest.mark.parametrize("capacity", [None, 48])
def test_relu2_experts_through_the_share_against_the_loop(capacity):
    """``relu(h W_up)^2 W_down`` through ``expert_share`` (a compact rung
    and the full one) against the reference's loop over each expert's rows,
    forward and gradient, at a width that is no multiple of the tile."""
    x, router, w_up, w_down, bias, *_ = _expert_weights(1, e=16, i=12)
    held = (4, 4)
    cfg = {**SHARE_CFG, "num_experts_per_tok": 4, "experts_held": held}

    def program(x, w_up, w_down):
        up, down = moe.lane_aligned(w_up, w_down)
        out, sizes, rung = moe.expert_share(
            x, router, up, down, experts_held=held, k=4, impl="ragged",
            capacity=capacity, routing=ROUTING, bias=bias, form="relu2")
        return out, (sizes, rung)

    def loop(x, w_up, w_down):
        p = {"router": router, "w_up": w_up, "w_down": w_down}
        return ref._experts(x, p, bias, cfg, ref._mm_f32, ())

    weigh = jnp.cos(jnp.arange(x.size, dtype=jnp.float32)).reshape(x.shape)
    held_w = (w_up[4:8], w_down[4:8])
    (got, (sizes, rung)), pull = jax.vjp(program, x, *held_w, has_aux=False)
    with jax.default_matmul_precision("highest"):
        (want, counts), pull_ref = jax.vjp(loop, x, *held_w)
        g_want = pull_ref((weigh, jnp.zeros_like(counts)))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(sizes, counts)
    assert int(rung) == (0 if capacity and int(sizes[4:8].sum()) <= capacity
                         else (1 if capacity else 0))
    g_got = pull((weigh, (jnp.zeros_like(sizes), jnp.zeros_like(rung))))
    for a, b in zip(g_got, g_want):
        assert float(jnp.abs(b).max()) > 0
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(jnp.abs(b).max()))
    # swiglu is another function of the same weights' shapes
    assert moe.EXPERT_FORMS.keys() == {"swiglu", "relu2"}
    r = jnp.asarray([-1.0, 0.0, 0.5, 2.0])
    np.testing.assert_allclose(moe.relu2(r), [0, 0, 0.25, 4.0])
    np.testing.assert_allclose(jax.grad(lambda v: moe.relu2(v).sum())(r),
                               [0, 0, 1.0, 4.0])


def test_the_weights_norm_has_its_1e_20_and_its_scale():
    scores = jnp.asarray([[0.8, 0.6, 0.5, 0.2]])
    logits = jnp.log(scores / (1 - scores))
    w, e = moe.route(logits, 2, ROUTING, jnp.asarray([0, 0, 0, 0.5]))
    assert e.tolist() == [[0, 3]]
    np.testing.assert_allclose(w, [[2.5 * 0.8, 2.5 * 0.2]], rtol=1e-6)
    p, chosen = ref.route(logits, jnp.eye(4), jnp.asarray([0, 0, 0, 0.5]),
                          {**SHARE_CFG, "num_experts_per_tok": 2},
                          ref._mm_f32)
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
    step = jax.jit(make_lm_train_step(CFG, nm.attention_tiles(CFG, L)))
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
    # four expert layers x two sequences, the one rung at this size
    assert float(m["moe_calls_full_rung_sum"]) == 8.0
    assert float(m["moe_rows_balanced_sum"]) == 4 * 2 * L * 4 * 4 / 16
    assert float(m["weighted_tokens_sum"]) == float(
        (batches[-1].loss_weight > 0).sum())
    # two attention layers of four heads, two sequences, which prepare no
    # heads; four Mamba layers, no convolution layer
    assert float(m["attn_full_tiles_live_sum"]) == 4 * 2 * 2
    assert float(m["heads_prepared_sum"]) == 0.0
    assert float(m["attn_kept_bytes_sum"]) == 2 * 2 * 4 * L * (16 * 4 + 4)
    assert "sconv_positions_sum" not in m
    seg = batches[-1].segment_ids
    met = ssd_counts(seg, CFG.chunk_size)
    assert float(m["ssm_positions_sum"]) == 4 * 2 * L
    assert float(m["ssm_chunks_sum"]) == 4 * 2 * L // 8
    assert float(m["ssm_resets_sum"]) == 4 * int(met["resets"]) \
        == 4 * (2 + int((np.diff(seg, axis=1) != 0).sum()))
    assert 0 < float(m["ssm_chunks_cut_sum"]) == 4 * int(met["chunks_cut"])
    flat_got = jax.tree_util.tree_leaves_with_path(grad)
    flat_want = dict(jax.tree_util.tree_leaves_with_path(want["grad"]))
    # embed, head, final_norm; 6 an expert layer's, 9 a Mamba layer's, 5
    # the attention layer's
    assert len(flat_got) == 3 + 6 + 9 + 5
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


@pytest.mark.parametrize("fault",
                         list(ref.FAULTS) + ["float8", "state_bfloat16"])
def test_each_fault_of_the_reference_is_another_function(fault):
    params = jax.tree_util.tree_map(np.asarray, _params(0))
    bias = np.asarray(_bias(0))
    batch = _as_ref(tokens.split_batches(_pool(0), 2)[0])
    sound, counts = ref.batch_loss(params, bias, batch, REF_CFG)
    kw = {"float8": {"mm": ref.mm_fp8},
          "state_bfloat16": {"state_dtype": "bfloat16"}}.get(
              fault, {"faults": (fault,)})
    broken, other = ref.batch_loss(params, bias, batch, REF_CFG, **kw)
    # the rounded state moves a float32 loss by a few of its last bits
    least = 1e-6 if fault == "state_bfloat16" else 1e-5
    assert abs(float(broken) - float(sound)) > least * abs(float(sound))
    assert counts.shape == other.shape == (4, 16)
    assert int(counts.sum()) == 4 * 2 * L * 4
    assert len(ref.FAULTS) == 15


def test_bfloat16_compute_stays_near_float32(followed):
    cfg16 = dataclasses.replace(CFG, dtype="bfloat16")
    params, bias = _params(0), _bias(0)
    batch = tokens.split_batches(_pool(0), 2)[0]
    variables = {"params": params, "batch_stats": {"router_bias": bias}}
    a, *_ = nm.apply(CFG, variables, batch)
    b, sizes, rungs = nm.apply(cfg16, variables, batch)
    assert a.shape == b.shape == (2,) and sizes.shape == (4, 16)
    assert rungs.shape == (4, 2)  # an expert layer and sequence each
    np.testing.assert_allclose(a, b, rtol=0.05)
    assert int(sizes.sum()) == 4 * (2 * L) * 4
    batches, want = tokens.split_batches(_pool(0), 2), followed
    step = jax.jit(make_lm_train_step(cfg16))
    state = _state(params, bias, cfg16)
    for x, w in zip(batches, want["loss"]):
        state, m = step(state, x)
        assert float(m["loss_sum"]) / 2 == pytest.approx(w, rel=0.05)
    np.testing.assert_array_equal(state.batch_stats["router_bias"], bias)


# ---- the stack and the count ------------------------------------------

def test_parameter_count_and_the_stack():
    real = nm.NemotronHConfig()
    h = 2688
    expert = 8 * 2 * h * 1856 + h * 128 + 2 * h * 3712 + h
    mamba = (h * 10304 + 6144 * 4 + 6144 + 3 * 64 + 4096 + 4096 * h + h)
    attn = 2 * h * 4096 + 2 * h * 256 + h
    assert (expert, mamba, attn) == (100_125_312, 38_744_896, 23_399_040)
    assert real.n_params() == (3 * expert + 3 * mamba + attn
                               + 2 * 16384 * h + h) == 528_092_736
    assert sum(int(np.prod(s)) for s in jax.tree_util.tree_leaves(
        real.shapes(), is_leaf=lambda x: isinstance(x, tuple))) \
        == real.n_params()
    # one body a kind: (E, M) x 3, then *
    assert real.period == (E, M, E, M, E, M, A) and real.n_periods == 1
    assert real.groups == (((E, M), 3), ((A,), 1))
    assert real.runs == (((E, M), 3), (A, 1))
    assert (real.n_expert_layers, real.n_ssm_layers, real.n_attention_layers,
            real.n_conv_layers) == (3, 3, 1, 0)
    assert real.stats_shapes() == {"router_bias": (1, 3, 128)}
    shapes = real.shapes()["periods"]
    assert set(shapes) == {"run0", "run1"} and set(shapes["run0"]) == {E, M}
    assert shapes["run0"][E]["w_up"] == (1, 3, 8, h, 1856)
    assert shapes["run0"][M]["w_in"] == (1, 3, h, 4096 + 6144 + 64)
    assert shapes["run1"]["wq"] == (1, 1, h, 4096)
    deeper = dataclasses.replace(real, num_hidden_layers=14,
                                 hybrid_override_pattern="EMEMEM*" * 2)
    assert deeper.n_periods == 2 and deeper.groups == real.groups
    assert deeper.stats_shapes() == {"router_bias": (2, 3, 128)}
    # the published pattern whole: a body a run, every kind by its letter
    whole = dataclasses.replace(
        real, num_hidden_layers=52, hybrid_override_pattern=(
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"))
    assert whole.n_periods == 1
    assert (whole.n_expert_layers, whole.n_ssm_layers,
            whole.n_attention_layers) == (23, 23, 6)
    assert whole.groups[:4] == (((M, E), 2), ((M,), 1), ((A,), 1),
                                ((E, M), 3))
    assert sum(len(g) * n for g, n in whole.groups) == 52
    # Trinity's and LFM2's runs are what they were
    S, F, C = afmoe.SLIDING, afmoe.FULL, lfm2.CONV
    assert afmoe.AfmoeConfig().runs == ((S, 3), (F, 1))
    assert lfm2.Lfm2Config().runs == ((F, 1), (C, 3))
    assert afmoe.AfmoeConfig().n_expert_layers == 4
    assert lfm2.Lfm2Config().n_attention_layers == 1
    assert CFG.groups == (((E, M), 2), ((A,), 1)) and CFG.n_periods == 2
    with pytest.raises(ValueError, match="names no layer"):
        dataclasses.replace(real, hybrid_override_pattern="EMEMEMX")
    with pytest.raises(ValueError, match="do not name"):
        dataclasses.replace(real, num_hidden_layers=6)
    with pytest.raises(ValueError, match="at least one expert layer"):
        dataclasses.replace(real, num_hidden_layers=2,
                            hybrid_override_pattern="M*")
    p = nm.init_params(CFG, jax.random.key(0), n_layers_published=52)
    mamba_p = p["periods"]["run0"][M]
    assert float(mamba_p["gate_norm"].min()) == 1.0
    assert float(mamba_p["d_skip"].min()) == 1.0
    assert 0.0 <= float(mamba_p["a_log"].min()) \
        and float(mamba_p["a_log"].max()) <= float(np.log(16.0))
    steps = jax.nn.softplus(mamba_p["dt_bias"])
    assert 0.001 <= float(steps.min()) and float(steps.max()) <= 0.1001
    assert float(jnp.abs(mamba_p["conv_w"]).max()) <= 0.5
    assert float(jnp.std(mamba_p["w_out"])) == pytest.approx(
        0.02 / np.sqrt(52), rel=0.1)
    assert float(jnp.std(p["head"])) == pytest.approx(0.02, rel=0.1)


@pytest.mark.parametrize("pattern,groups", [
    ("M*M*E", (((M, A), 2), ((E,), 1))),      # a repeated group routes nothing
    ("EEM*", (((E,), 2), ((M,), 1), ((A,), 1))),  # runs of one kind alone
    ("MEME*E", (((M, E), 2), ((A,), 1), ((E,), 1)))])
def test_other_patterns_run_through_the_same_stack(pattern, groups):
    """Groups that route nothing, runs of one kind that do or do not route,
    an expert layer after the last group: the counts come out a routing
    layer each, in the stack's order, and the reference walks the same
    tree."""
    cfg = dataclasses.replace(CFG, num_hidden_layers=len(pattern),
                              hybrid_override_pattern=pattern)
    assert cfg.groups == groups
    n_routing = pattern.count("E")
    assert cfg.stats_shapes() == {"router_bias": (1, n_routing, 16)}
    params, bias = _params(1, cfg), _bias(1, cfg)
    batch = tokens.split_batches(_pool(0), 2)[0]
    losses, sizes, rungs = nm.apply(
        cfg, {"params": params, "batch_stats": {"router_bias": bias}}, batch)
    assert sizes.shape == (n_routing, 16) and rungs.shape == (n_routing, 2)
    assert int(sizes.sum()) == n_routing * 2 * L * 4
    with jax.default_matmul_precision("highest"):
        want, counts = ref.batch_loss(
            jax.tree_util.tree_map(np.asarray, params), np.asarray(bias),
            _as_ref(batch), {**REF_CFG, "hybrid_override_pattern": pattern})
    np.testing.assert_allclose(losses.mean(), want, rtol=2e-5)
    np.testing.assert_array_equal(sizes, counts)


def test_the_stack_holds_one_body_a_kind(monkeypatch):
    """The step's program holds each kind's layer body once, forward (the
    period ``E M E M *`` is the group (E, M) twice and ``*``, not five runs
    of one layer), and all three decoders share ``lm_blocks.scan_stack``."""
    calls = {M: 0, E: 0, A: 0}
    for kind, name in ((M, "_mamba_layer"), (A, "_attention_layer"),
                       (E, "_expert_layer")):
        real = getattr(nm, name)

        def counted(*a, _kind=kind, _real=real, **kw):
            calls[_kind] += 1
            return _real(*a, **kw)

        monkeypatch.setattr(nm, name, counted)
    batch = tokens.split_batches(_pool(0), 2)[0]
    variables = {"params": _params(0),
                 "batch_stats": {"router_bias": _bias(0)}}
    jax.make_jaxpr(lambda v: nm.apply(CFG, v, batch))(variables)
    assert calls == {M: 1, E: 1, A: 1}
    assert nm.hidden_states.__code__.co_names.count("scan_stack") == 1
    assert nm.NemotronHConfig.groups is lm_blocks.Stack.groups
    assert afmoe.AfmoeConfig.groups is lm_blocks.Stack.groups
    assert lfm2.Lfm2Config.groups is lm_blocks.Stack.groups


# ---- the normal path --------------------------------------------------

def test_train_py_trains_the_tiny_preset_through_fit_and_the_scan_driver(
        capsys, tmp_path):
    import train

    code = train.main([
        "--device", "cpu", "--task", "lm", "--lm-model", "nemotron-tiny",
        "--synthetic", "24", "-b", "2", "--epochs", "3", "--optim", "AdamW",
        "--lr", "3e-3", "--weight-decay", "0.1", "--ckpt-dir", str(tmp_path),
        "--check-invariants", "--no-preempt-handler"])
    out = capsys.readouterr().out
    assert code == 0
    losses = [float(ln.split("train loss ")[1].split()[0])
              for ln in out.splitlines() if ln.startswith("Epoch ")]
    assert len(losses) == 3 and losses[-1] < losses[0]
    assert "lm: " in out and "largest bias" not in out


@pytest.mark.parametrize("preset", ["nemotron-tiny",
                                    "nemotron-3-nano-30b-a3b-ep16"])
def test_a_preset_names_its_model(preset):
    from cgnn_tpu.train import blockdiff

    module, cfg = blockdiff.model_config("lm", preset, bf16=False)
    assert module is nm and type(cfg) is nm.NemotronHConfig
    assert blockdiff.CONFIGS["nemotron_h"] == "NemotronHConfig"
    if preset != "nemotron-tiny":
        assert cfg == nm.NemotronHConfig() and cfg.n_params() == 528_092_736
    with pytest.raises(ValueError, match="no preset"):
        blockdiff.model_config("blockdiff", preset, bf16=False)
