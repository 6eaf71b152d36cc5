"""The Mamba-2 scan in its chunked form (ops/ssd.py) against the recurrence
written position by position, in value and in gradient, across every kind of
document boundary; and the taps that ops/short_conv.py's two convolutions
share (four with a bias and a silu for the Mamba layer, LFM2's three as
before). float32 on the CPU, seeded inputs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cgnn_tpu.ops import short_conv as sc
from cgnn_tpu.ops.ssd import CHUNK, ssd_counts, ssd_scan

S, L, HEADS, P, GROUPS, N = 2, 24, 4, 4, 2, 8


def _inputs(seed=0, length=L):
    ks = jax.random.split(jax.random.key(seed), 7)
    f32 = jnp.float32
    return (jax.random.normal(ks[0], (S, length, HEADS, P), f32),
            jax.random.normal(ks[1], (S, length, HEADS), f32),
            jax.random.normal(ks[2], (S, length, GROUPS, N), f32),
            jax.random.normal(ks[3], (S, length, GROUPS, N), f32),
            jnp.log(jax.random.uniform(ks[4], (HEADS,), f32, 1.0, 16.0)),
            jax.random.normal(ks[5], (HEADS,), f32) - 2.0,
            1.0 + 0.1 * jax.random.normal(ks[6], (HEADS,), f32))


def recurrence(x, dt, b, c, a_log, dt_bias, d_skip, seg):
    """``S_t = a_t S_{t-1} + d_t x_t (x) B_t``, ``y_t = S_t C_t + D x_t``, a
    position at a time, the state emptied at a document's first."""
    per = x.shape[2] // b.shape[2]
    d = jax.nn.softplus(dt + dt_bias)
    a = jnp.exp(-jnp.exp(a_log) * d)
    first = jnp.concatenate([jnp.ones_like(seg[:, :1], bool),
                             seg[:, 1:] != seg[:, :-1]], axis=1)
    bh, ch = (jnp.repeat(v, per, axis=2) for v in (b, c))

    def one(xs, ds, as_, bs, cs, starts):
        def step(state, at):
            xt, dt_, at_, bt, ct, s0 = at
            state = jnp.where(s0, 0.0, at_[:, None, None] * state) \
                + dt_[:, None, None] * xt[:, :, None] * bt[:, None, :]
            return state, (state * ct[:, None, :]).sum(-1) \
                + d_skip[:, None] * xt
        zero = jnp.zeros((x.shape[2], x.shape[3], b.shape[3]), jnp.float32)
        return jax.lax.scan(step, zero, (xs, ds, as_, bs, cs, starts))[1]

    return jax.vmap(one)(x, d, a, bh, ch, first)


def _segments(lengths_by_row):
    return jnp.asarray([np.repeat(np.arange(len(ls)), ls)
                        for ls in lengths_by_row], jnp.int32)


# row 0's documents, row 1's: chunks of 8 that do and do not divide a
# document; a document of one token; a document that starts on a chunk's
# first position (8, 16) and on its last (7, 15); a sequence one document
# fills
CASES = {
    "chunks_divide_the_documents": ([8, 16], [16, 8]),
    "chunks_do_not_divide_them": ([5, 6, 13], [11, 13]),
    "a_document_of_one_token": ([7, 1, 16], [1, 22, 1]),
    "a_start_on_a_chunk_s_last_position": ([7, 8, 9], [15, 9]),
    "a_start_on_a_chunk_s_first_position": ([8, 8, 8], [16, 8]),
    "one_document_fills_the_sequence": ([24], [24]),
    "every_position_a_document": ([1] * 24, [1] * 24),
}


@pytest.mark.parametrize("chunk", [8, 5, 24, CHUNK])
@pytest.mark.parametrize("case", sorted(CASES))
def test_the_chunked_scan_is_the_recurrence(case, chunk):
    """In value and in every operand's gradient, finite everywhere; chunks
    of 8 and of 24 divide the sequence, 5 does not (it is padded), and a
    chunk longer than the sequence is the sequence."""
    seg = _segments(CASES[case])
    args = _inputs(0)
    got = ssd_scan(*args, seg, chunk=chunk)
    want = recurrence(*args, seg)
    assert got.dtype == jnp.float32 and got.shape == (S, L, HEADS, P)
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6 * scale)
    weigh = jnp.cos(jnp.arange(want.size, dtype=jnp.float32)).reshape(
        want.shape)
    g_got = jax.grad(lambda *a: (ssd_scan(*a, seg, chunk=chunk)
                                 * weigh).sum(), argnums=range(7))(*args)
    g_want = jax.grad(lambda *a: (recurrence(*a, seg) * weigh).sum(),
                      argnums=range(7))(*args)
    for name, a, b in zip(("x", "dt", "B", "C", "A_log", "dt_bias", "D"),
                          g_got, g_want):
        assert bool(jnp.isfinite(a).all()), name
        np.testing.assert_allclose(
            a, b, rtol=2e-4, atol=2e-5 * float(jnp.abs(b).max()),
            err_msg=name)


def test_steps_so_large_that_a_decay_underflows_stay_finite():
    """``exp(cum_i - cum_j)`` underflows to 0 over a chunk of fast heads and
    would be ``inf`` for the hidden pairs ``j > i`` if they reached the
    ``exp``: value and gradient stay finite, and equal the recurrence's."""
    x, dt, b, c, a_log, dt_bias, d_skip = _inputs(1)
    seg = _segments(CASES["chunks_do_not_divide_them"])
    fast = (x, dt + 8.0, b, c, a_log + 3.0, dt_bias, d_skip)
    got, pull = jax.vjp(lambda *a: ssd_scan(*a, seg, chunk=8), *fast)
    want, pull_ref = jax.vjp(lambda *a: recurrence(*a, seg), *fast)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=1e-5)
    for a, w in zip(pull(jnp.ones_like(got)), pull_ref(jnp.ones_like(got))):
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(a, w, rtol=1e-3,
                                   atol=1e-4 * float(jnp.abs(w).max()))


def test_another_document_s_inputs_reach_nothing_bit_for_bit():
    """Change one document's inputs: every other document's outputs and
    gradients are bit-equal, whether it shares a chunk with it or not."""
    seg = _segments(CASES["chunks_do_not_divide_them"])
    args = _inputs(2)
    own = np.asarray(seg[0]) == 1  # row 0's middle document, inside a chunk
    other = _inputs(3)

    def changed(a, o):
        if a.ndim < 2:
            return a
        return a.at[0, own].set(o[0, own])

    moved = tuple(changed(a, o) for a, o in zip(args, other))
    weigh = jnp.sin(jnp.arange(S * L * HEADS * P, dtype=jnp.float32)
                    ).reshape(S, L, HEADS, P)

    def out_and_grads(a):
        y, pull = jax.vjp(lambda *v: ssd_scan(*v, seg, chunk=8), *a)
        return (y, *pull(weigh)[:4])

    keep = ~own
    for a, b in zip(out_and_grads(args), out_and_grads(moved)):
        np.testing.assert_array_equal(np.asarray(a)[0, keep],
                                      np.asarray(b)[0, keep])
        np.testing.assert_array_equal(np.asarray(a)[1], np.asarray(b)[1])
        assert (np.asarray(a)[0, own] != np.asarray(b)[0, own]).any()


def test_the_compute_dtype_outside_and_float32_inside():
    seg = _segments(CASES["chunks_do_not_divide_them"])
    x, dt, b, c, *scalars = _inputs(0)
    want = recurrence(x, dt, b, c, *scalars, seg)
    bf = jnp.bfloat16
    got = ssd_scan(x.astype(bf), dt.astype(bf), b.astype(bf), c.astype(bf),
                   *scalars, seg, chunk=8)
    assert got.dtype == bf
    gap = jnp.abs(got.astype(jnp.float32) - want).max() / jnp.abs(want).max()
    assert 1e-4 < float(gap) < 0.05


def test_the_scan_s_counters():
    seg = _segments(CASES["a_start_on_a_chunk_s_last_position"])
    met = {k: int(v) for k, v in ssd_counts(seg, 8).items()}
    # row 0: starts at 0, 7, 15; row 1: at 0, 15; three chunks a row; the
    # starts at 7 and 15 fall inside a chunk (its last position)
    assert met == {"resets": 5, "chunks": 6, "chunks_cut": 3}
    on_first = _segments(CASES["a_start_on_a_chunk_s_first_position"])
    assert int(ssd_counts(on_first, 8)["chunks_cut"]) == 0
    assert int(ssd_counts(on_first, 5)["chunks"]) == 2 * 5
    assert int(ssd_counts(on_first, CHUNK)["chunks"]) == 2


# ---- the taps both convolutions share -----------------------------------

SEG = np.asarray([0, 0, 0, 1, 2, 2, 3, 3, 3, 3, 3], np.int32)


def _taps_by_hand(z, w, seg):
    z, w = np.asarray(z, np.float64), np.asarray(w, np.float64)
    taps, out = w.shape[1], np.zeros_like(z)
    for i in range(z.shape[0]):
        for t in range(taps):
            j = i - (taps - 1) + t
            if j >= 0 and seg[j] == seg[i]:
                out[i] += w[:, t] * z[j]
    return out


@pytest.mark.parametrize("taps", [4, 3, 1])
def test_the_shared_taps_are_the_equation_by_hand(taps):
    ks = jax.random.split(jax.random.key(taps), 3)
    z = jax.random.normal(ks[0], (len(SEG), 5), jnp.float32)
    w = jax.random.normal(ks[1], (5, taps), jnp.float32)
    bias = jax.random.normal(ks[2], (5,), jnp.float32)
    seg = jnp.asarray(SEG)[None]
    want = _taps_by_hand(z, w, SEG)
    np.testing.assert_allclose(sc.causal_taps(z[None], w, seg)[0], want,
                               rtol=1e-5, atol=1e-6)
    got = sc.silu_conv(z[None], w, bias, seg)[0]
    pre = want + np.asarray(bias, np.float64)
    np.testing.assert_allclose(got, pre / (1.0 + np.exp(-pre)), rtol=1e-5,
                               atol=1e-6)
    # the (position, tap) pairs a start cut: 4 starts, each cuts its later
    # positions' taps that reach back over it
    cut = sum(1 for i in range(len(SEG)) for back in range(1, taps)
              if i - back < 0 or SEG[i - back] != SEG[i])
    assert int(sc.taps_cut(seg, taps)) == cut
    with pytest.raises(ValueError, match="filter"):
        sc.silu_conv(z[None], w[:4], bias, seg)


def test_lfm2_s_three_taps_are_what_they_were():
    """``short_conv`` through the shared taps: LFM2's gated 3-tap filter,
    held to ``TAPS``, and its gradient the loop's."""
    ks = jax.random.split(jax.random.key(0), 2)
    bcu = jax.random.normal(ks[0], (len(SEG), 15), jnp.float32)
    w = jax.random.normal(ks[1], (5, 3), jnp.float32)
    seg = jnp.asarray(SEG)[None]
    assert sc.TAPS == 3
    want = np.asarray(bcu[:, 5:10], np.float64) * _taps_by_hand(
        bcu[:, :5] * bcu[:, 10:], w, SEG)
    np.testing.assert_allclose(sc.short_conv(bcu[None], w, seg)[0], want,
                               rtol=1e-5, atol=1e-6)
    assert int(sc.taps_cut(seg)) == int(sc.taps_cut(seg, 3))
    with pytest.raises(ValueError, match="3 taps"):
        sc.short_conv(bcu[None], jnp.ones((5, 4)), seg)
    dw = jax.grad(lambda w: sc.short_conv(bcu[None], w, seg).sum())(w)
    assert bool(jnp.isfinite(dw).all()) and float(jnp.abs(dw).max()) > 0
