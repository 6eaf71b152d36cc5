"""Unit tests for segment ops and masked BatchNorm (SURVEY.md §4.2)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import torch

from cgnn_tpu.ops.norm import MaskedBatchNorm
from cgnn_tpu.ops.segment import (
    aggregate_edge_messages,
    gather,
    gather_slot_major,
    segment_mean,
    segment_sum,
)


class TestSegmentOps:
    def test_segment_sum_matches_loop(self):
        rng = np.random.default_rng(0)
        data = rng.normal(size=(40, 5)).astype(np.float32)
        ids = rng.integers(0, 7, size=40)
        expected = np.zeros((7, 5), np.float32)
        for row, i in zip(data, ids):
            expected[i] += row
        got = segment_sum(jnp.asarray(data), jnp.asarray(ids), 7)
        np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-5)

    def test_segment_mean_masked(self):
        data = jnp.array([[2.0], [4.0], [100.0], [6.0]])
        ids = jnp.array([0, 0, 0, 1])
        w = jnp.array([1.0, 1.0, 0.0, 1.0])  # row 2 is padding
        got = segment_mean(data, ids, 3, weights=w)
        np.testing.assert_allclose(got, [[3.0], [6.0], [0.0]], atol=1e-6)

    @staticmethod
    def _pooling_case(dtype, rows_a_graph=None):
        """Rows of ``dtype`` over 12 graph slots as a packed batch never
        has them all at once: ids in no order, slot 5 with no rows, ids of
        -1 and 12 and beyond (rows of no slot), the last rows padding of
        weight 0 under a real id; or, with ``rows_a_graph``, that many rows
        in each slot, all of one sign, where a running sum in the rows' own
        dtype loses the most."""
        rng = np.random.default_rng(3)
        g = 12
        if rows_a_graph:
            ids = np.repeat(np.arange(g), rows_a_graph)
            data = rng.uniform(0.5, 1.5, size=(len(ids), 7))
            weights = np.ones(len(ids))
        else:
            ids = rng.integers(0, g, size=300)
            ids[ids == 5] = 6
            ids[::17] = rng.choice([-1, -7, g, g + 30], size=len(ids[::17]))
            data = rng.normal(size=(300, 7))
            weights = rng.uniform(0.5, 2.0, size=300).round(2)
            weights[-40:] = 0.0
        # what the device is handed, and the same numbers for the loop
        data = np.asarray(jnp.asarray(data, dtype).astype(jnp.float64))
        weights = np.asarray(jnp.asarray(weights, dtype).astype(jnp.float64))
        return g, ids.astype(np.int32), data, weights

    @staticmethod
    def _pooling_loop(g, ids, rows, weights=None):
        """The sums of ``rows`` a slot and, given ``weights``, those over
        the weights' sums: float64, one row at a time."""
        total, count = np.zeros((g, rows.shape[1])), np.zeros(g)
        for n, i in enumerate(ids):
            if 0 <= i < g:
                total[i] += rows[n]
                count[i] += 0.0 if weights is None else weights[n]
        if weights is None:
            return total
        return total / np.maximum(count, 1.0)[:, None]

    @pytest.mark.parametrize("weighted", [False, True],
                             ids=["unweighted", "weighted"])
    @pytest.mark.parametrize("op", ["sum", "mean"])
    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "float64"])
    def test_pooling_matches_float64_loop(self, dtype, op, weighted):
        """``segment_sum`` / ``segment_mean`` (a 0/1 matmul since PR 50)
        against a float64 loop over the rows the device was handed (unsorted
        ids, an empty slot, ids out of range, padding rows of weight 0), to
        the dtype's rounding of ONE result: the sums are float32 or wider
        and rounded once. In bfloat16 at 255 rows a graph they are no less
        exact than ``jax.ops.segment_sum``, which adds in bfloat16."""
        eps = float(jnp.finfo(dtype).eps)
        for rows_a_graph in (None, 255):
            g, ids, data, weights = self._pooling_case(dtype, rows_a_graph)
            if not weighted:
                weights = np.ones_like(weights)
            x, w = jnp.asarray(data, dtype), jnp.asarray(weights, dtype)
            # the weighted rows as the dtype rounds them: the sum is what
            # is held to float64, not the product before it
            xw = x * w[:, None] if weighted else x
            rows = np.asarray(xw.astype(jnp.float64))
            if op == "sum":
                got = segment_sum(xw, jnp.asarray(ids), g)
                want = self._pooling_loop(g, ids, rows)
                assert segment_sum(xw[:, 0], jnp.asarray(ids), g).shape == (g,)
            else:
                got = segment_mean(x, jnp.asarray(ids), g,
                                   weights=w if weighted else None)
                want = self._pooling_loop(g, ids, rows, weights)
            assert got.dtype == jnp.dtype(dtype) and got.shape == want.shape
            err = np.abs(np.asarray(got.astype(jnp.float64)) - want)
            # one rounding of the result, and the sum's own in float32
            # (float64's for float64 rows) over a slot's rows
            sum_eps = float(jnp.finfo(jnp.promote_types(dtype, "float32")).eps)
            tol = (eps + (300 if rows_a_graph else 60) * sum_eps
                   ) * np.maximum(np.abs(want), 1.0)
            assert (err <= tol).all(), (err / tol).max()
            if rows_a_graph is None:
                assert not np.asarray(got)[5].any()  # the empty slot
            if dtype == "bfloat16" and rows_a_graph and op == "sum":
                plain = jax.ops.segment_sum(xw, jnp.asarray(ids), g)
                plain_err = np.abs(
                    np.asarray(plain.astype(jnp.float64)) - want)
                assert err.max() <= plain_err.max()
                assert plain_err.max() > 4 * err.max()  # and it shows

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_pooling_derivatives_match_plain_segment_sum(self, dtype):
        """First and second derivatives of a function of the pooled rows,
        through the declared transpose (a row gather by the ids, and its
        transpose the 0/1 matmul again: ``linear_call``) against plain
        autodiff of ``jax.ops.segment_sum``, on the same unsorted ids with
        an empty slot, ids out of range and padding rows."""
        g, ids, data, weights = self._pooling_case(dtype)
        ids = jnp.asarray(ids)
        x, w = jnp.asarray(data, dtype), jnp.asarray(weights, dtype)
        k = jnp.asarray(np.random.default_rng(4).normal(size=(7, 3)), dtype)

        def plain_sum(v, i, n):
            return jax.ops.segment_sum(v, i, num_segments=n)

        def plain_mean(v, i, n, weights):
            return plain_sum(v * weights[:, None], i, n) / jnp.maximum(
                plain_sum(weights, i, n), 1.0)[:, None]

        def energy(total, mean, x, k):
            pooled = jnp.tanh(mean(x, ids, g, weights=w) @ k)
            per_row = jnp.sin(x).sum(-1) * w  # [N]: the force head's shape
            return ((pooled.astype(jnp.float32) ** 2).sum()
                    + (total(per_row.astype(jnp.float32), ids, g) ** 3).sum())

        def on_first_derivative(total, mean, x, k):
            gx = jax.grad(energy, argnums=2)(total, mean, x, k)
            return (gx.astype(jnp.float32) ** 2).sum()

        tol = 2e-5 if dtype == "float32" else 0.06
        for fn in (energy, on_first_derivative):
            got = jax.grad(fn, argnums=(2, 3))(segment_sum, segment_mean, x, k)
            want = jax.grad(fn, argnums=(2, 3))(plain_sum, plain_mean, x, k)
            for a, b in zip(got, want):
                b = np.asarray(b.astype(jnp.float64))
                assert np.abs(b).max() > 0.1
                np.testing.assert_allclose(
                    np.asarray(a.astype(jnp.float64)), b, rtol=0,
                    atol=tol * np.abs(b).max(), err_msg=fn.__name__)

    @pytest.mark.parametrize("shape", ["64x16x8", "1000x300x32",
                                       "2048x513x16", "skew"])
    def test_aggregate_matches_loop(self, shape):
        """The COO conv's aggregation is the per-node sum of its edges'
        messages, at odd shapes and with empty nodes and one hub node of
        huge degree; its gradient is the gather of the cotangent."""
        rng = np.random.default_rng(1)
        if shape == "skew":
            n, f = 260, 8
            centers = np.sort(np.concatenate([
                np.full(700, 5),             # hub: degree 700
                rng.integers(100, 120, 50),  # sparse middle, gaps elsewhere
                np.full(30, n - 1),          # tail node
            ])).astype(np.int32)
        else:
            e, n, f = map(int, shape.split("x"))
            centers = np.sort(rng.integers(0, n, size=e)).astype(np.int32)
        msgs = rng.normal(size=(len(centers), f)).astype(np.float32)
        expected = np.zeros((n, f), np.float64)
        for row, i in zip(msgs, centers):
            expected[i] += row
        got = aggregate_edge_messages(
            jnp.asarray(msgs), jnp.asarray(centers), n)
        np.testing.assert_allclose(got, expected, rtol=1e-5, atol=1e-4)
        w = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))
        grad = jax.grad(lambda x: (aggregate_edge_messages(
            x, jnp.asarray(centers), n) * w).sum())(jnp.asarray(msgs))
        np.testing.assert_array_equal(
            np.asarray(grad), np.asarray(w)[centers])

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("mapped", [False, True])
    def test_gather_slot_major_is_the_plain_gather_bit_for_bit(
            self, dtype, mapped):
        """Slot-major row order changes which row is gathered WHEN, never
        which: the [N, M, F] result is ``nodes[neighbors]`` exactly."""
        n, m, f = 40, 6, 8
        nodes, nbrs, mapping = _dense_gather_case(n, m, f, dtype)
        got = gather_slot_major(nodes, nbrs, m, *(mapping if mapped else ()))
        want = nodes[nbrs].reshape(n, m, f)
        assert got.shape == (n, m, f) and got.dtype == dtype
        np.testing.assert_array_equal(
            np.asarray(got.astype(jnp.float32)),
            np.asarray(want.astype(jnp.float32)))

    def test_gather_slot_major_grad_over_grad(self):
        """The force task differentiates the positions gradient again
        (grad-over-grad through linear_call): second-order AD through
        the slot-major transpose == through the plain gather."""
        n, m, f = 24, 4, 5
        nodes, nbrs, mapping = _dense_gather_case(n, m, f, jnp.float32)
        w = jnp.asarray(np.random.default_rng(7).normal(
            size=(n, m, f)).astype(np.float32))

        def energy(fn, x, scale):
            return (jnp.tanh(fn(x * scale)) * w).sum()

        def second_order(fn):
            inner = jax.grad(lambda x, s: energy(fn, x, s))
            return jax.grad(lambda s: (inner(nodes, s) ** 2).sum())(
                jnp.float32(0.7))

        got = second_order(
            lambda x: gather_slot_major(x, nbrs, m, *mapping))
        want = second_order(lambda x: gather(x, nbrs).reshape(n, m, f))
        np.testing.assert_allclose(float(got), float(want), rtol=1e-5)


def test_linear_gather_survives_forward_over_reverse():
    """``gather_slot_major`` with a two-tier mapping is declared linear
    with its transpose (``_linear``), so forward-mode over reverse-mode
    composes — ``jax.jvp`` of ``jax.grad``, what a ``custom_vjp`` rejects
    — and equals plain autodiff of ``jnp.take``."""
    n, m, f = 24, 4, 5
    nodes, nbrs, mapping = _dense_gather_case(n, m, f, jnp.float32)
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.normal(size=(n, m, f)).astype(np.float32))
    tangent = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32))

    def hvp(fn):
        grad = jax.grad(lambda x: (jnp.tanh(fn(x)) * w).sum())
        return jax.jvp(grad, (nodes,), (tangent,))

    g_got, t_got = hvp(lambda x: gather_slot_major(x, nbrs, m, *mapping))
    g_want, t_want = hvp(
        lambda x: jnp.take(x, nbrs, axis=0).reshape(n, m, f))
    np.testing.assert_allclose(g_got, g_want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(t_got, t_want, rtol=1e-5, atol=1e-6)


def _dense_gather_case(n, m, f, dtype):
    """Random dense-layout neighbours with their exact transpose mapping
    as the packer builds it: tier 1 holds each node's first ``m`` in-edges,
    the rest overflow (node-sorted runs, padded with slot-0 entries that
    name the last node; ``over_last`` ends each run)."""
    from cgnn_tpu.data.graph import transpose_slots

    rng = np.random.default_rng(n * m + f)
    nbrs = rng.integers(0, n, size=n * m).astype(np.int32)
    extra = int(np.maximum(np.bincount(nbrs, minlength=n) - m, 0).sum())
    mapping = transpose_slots(nbrs, np.ones(n * m, bool), n, m, None,
                              over_cap=extra + 3)
    assert extra > 0, "no overflow exercised"
    nodes = jnp.asarray(rng.normal(size=(n, f)).astype(np.float32)).astype(dtype)
    mapping = tuple(jnp.asarray(x) for x in mapping)
    return nodes, jnp.asarray(nbrs), mapping


class TestMaskedBatchNorm:
    """Parity with torch.nn.BatchNorm1d — the oracle's normalizer."""

    def _torch_bn_reference(self, x, train, steps=1):
        bn = torch.nn.BatchNorm1d(x.shape[-1], momentum=0.1, eps=1e-5)
        bn.train(train)
        with torch.no_grad():
            for _ in range(steps):
                out = bn(torch.from_numpy(x))
        return out.numpy(), bn.running_mean.numpy(), bn.running_var.numpy()

    def test_train_mode_matches_torch(self):
        rng = np.random.default_rng(2)
        x = rng.normal(2.0, 3.0, size=(32, 6)).astype(np.float32)
        mod = MaskedBatchNorm()
        variables = mod.init(jax.random.key(0), jnp.asarray(x))
        y, updated = mod.apply(
            variables, jnp.asarray(x), mutable=["batch_stats"],
            use_running_average=False,
        )
        ref_y, ref_mean, ref_var = self._torch_bn_reference(x, train=True)
        np.testing.assert_allclose(y, ref_y, rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(
            updated["batch_stats"]["mean"], ref_mean, rtol=1e-4, atol=1e-5
        )
        np.testing.assert_allclose(
            updated["batch_stats"]["var"], ref_var, rtol=1e-4, atol=1e-5
        )

    def test_eval_mode_uses_running_stats(self):
        rng = np.random.default_rng(3)
        x = rng.normal(size=(16, 4)).astype(np.float32)
        mod = MaskedBatchNorm()
        variables = mod.init(jax.random.key(0), jnp.asarray(x))
        # running stats are (0, 1) at init -> eval output is x (scale=1, bias=0)
        y = mod.apply(variables, jnp.asarray(x), use_running_average=True)
        np.testing.assert_allclose(y, x / np.sqrt(1 + 1e-5), rtol=1e-5, atol=1e-5)

    def test_fully_masked_batch_preserves_running_stats(self):
        """An all-padding batch (empty DP shard) must not decay stats."""
        x = np.zeros((8, 3), np.float32)
        mask = np.zeros(8, np.float32)
        mod = MaskedBatchNorm()
        v = mod.init(jax.random.key(0), jnp.asarray(x))
        before = jax.device_get(v["batch_stats"])
        _, upd = mod.apply(
            v, jnp.asarray(x), mask=jnp.asarray(mask),
            mutable=["batch_stats"], use_running_average=False,
        )
        after = jax.device_get(upd["batch_stats"])
        np.testing.assert_array_equal(after["mean"], before["mean"])
        np.testing.assert_array_equal(after["var"], before["var"])

    def test_masked_equals_unmasked_on_real_rows(self):
        """SURVEY.md §4.2: masked BN over padded data == BN over unpadded."""
        rng = np.random.default_rng(4)
        real = rng.normal(1.0, 2.0, size=(20, 5)).astype(np.float32)
        padded = np.concatenate([real, np.zeros((12, 5), np.float32)])
        mask = np.concatenate([np.ones(20), np.zeros(12)]).astype(np.float32)

        mod = MaskedBatchNorm()
        v1 = mod.init(jax.random.key(0), jnp.asarray(real))
        y_real, s_real = mod.apply(
            v1, jnp.asarray(real), mutable=["batch_stats"],
            use_running_average=False,
        )
        y_pad, s_pad = mod.apply(
            v1, jnp.asarray(padded), mask=jnp.asarray(mask),
            mutable=["batch_stats"], use_running_average=False,
        )
        np.testing.assert_allclose(y_pad[:20], y_real, rtol=1e-5, atol=1e-5)
        np.testing.assert_allclose(
            s_pad["batch_stats"]["mean"], s_real["batch_stats"]["mean"],
            rtol=1e-5, atol=1e-6,
        )
        np.testing.assert_allclose(
            s_pad["batch_stats"]["var"], s_real["batch_stats"]["var"],
            rtol=1e-5, atol=1e-6,
        )


def test_one_pass_bn_matches_two_pass_reference():
    """The f32 one-pass (E[x^2]-E[x]^2) masked moments must match a numpy
    two-pass centered reference at f32-roundoff tolerance — the f64 parity
    suite deliberately routes to the two-pass branch and would not catch a
    one-pass regression (dropped mask in s2, broken psum tuple)."""
    import jax

    from cgnn_tpu.ops.norm import MaskedBatchNorm

    rng = np.random.default_rng(0)
    x = rng.normal(2.0, 3.0, size=(257, 6)).astype(np.float32)
    mask = (rng.random(257) > 0.3).astype(np.float32)

    bn = MaskedBatchNorm()
    variables = bn.init(jax.random.key(0), x, mask=mask)
    y, mutated = bn.apply(
        variables, x, mask=mask, use_running_average=False,
        mutable=["batch_stats"],
    )

    rows = x[mask > 0]
    mean = rows.mean(axis=0)
    var = rows.var(axis=0)  # biased, two-pass centered
    ref = (x - mean) / np.sqrt(var + 1e-5)
    np.testing.assert_allclose(np.asarray(y), ref, rtol=2e-4, atol=2e-4)
    # running stats: unbiased variance update at momentum 0.1
    n = rows.shape[0]
    np.testing.assert_allclose(
        np.asarray(mutated["batch_stats"]["mean"]), 0.1 * mean, rtol=2e-4
    )
    np.testing.assert_allclose(
        np.asarray(mutated["batch_stats"]["var"]),
        0.9 * 1.0 + 0.1 * var * n / (n - 1), rtol=2e-4,
    )


@pytest.mark.parametrize("dims", [(1024, 4), (128, 8, 4)],
                         ids=["RxC", "NxMxC"])
def test_one_pass_bn_high_mean_no_cancellation(dims):
    """|mean| >> std regime: unshifted f32 E[x^2]-E[x]^2 loses all variance
    bits (var clamps to 0 and rsqrt(eps) AMPLIFIES by ~300x); the
    shift-invariant accumulation must keep the output unit-variance, for
    bn2's rows and bn1's [N, M, C], and the variance that reaches the
    running statistics is finite and right: the shift's reason to exist
    (it is read off the leading row-block of x, ops/norm.py).
    Advisor finding r3 (ops/norm.py one-pass cancellation)."""
    import jax

    from cgnn_tpu.ops.norm import MaskedBatchNorm

    rng = np.random.default_rng(1)
    # mean 1e4, std 1: mean^2/var = 1e8 > 2^24 — guaranteed f32
    # cancellation without a shift
    x = (1e4 + rng.normal(0.0, 1.0, size=dims)).astype(np.float32)
    mask = np.ones(dims[:-1], np.float32)
    mask[dims[0] * 7 // 8:] = 0.0

    bn = MaskedBatchNorm()
    variables = bn.init(jax.random.key(0), x, mask=mask)
    y, mutated = bn.apply(
        variables, x, mask=mask, use_running_average=False,
        mutable=["batch_stats"],
    )
    rows = x[mask > 0].astype(np.float64)
    ref = (x.astype(np.float64) - rows.mean(0)) / np.sqrt(rows.var(0) + 1e-5)
    got = np.asarray(y)[mask > 0]
    # unit-scale output, not a 300x blowup; tolerance is loose because the
    # data itself carries only ~3 significant fractional digits in f32
    np.testing.assert_allclose(got, ref[mask > 0], atol=5e-2)
    assert float(np.abs(got).max()) < 10.0
    var = np.asarray(mutated["batch_stats"]["var"])
    assert np.isfinite(var).all()
    np.testing.assert_allclose(var, 0.9 + 0.1 * rows.var(0, ddof=1),
                               rtol=1e-3)


_BN_CASES = pytest.mark.parametrize(
    "dtype,shape,masked,sharded",
    [pytest.param(d, s, m, a, id=f"{d}-{s}-{'masked' if m else 'unmasked'}-"
                  f"{'axis_name' if a else 'one-device'}")
     for d in ("float32", "bfloat16") for s in ("RxC", "NxMxC")
     for m in (True, False) for a in (False, True)])


def _bn_case(dtype, shape, masked, sharded):
    """-> (x, mask or None, weights of a loss, variables, ``run``) for
    train-mode ``MaskedBatchNorm`` over rows [R, C] or the conv's [N, M, C]; sharded:
    two shards on a leading axis, the module under ``axis_name`` inside a
    ``vmap`` of that name (the moments are the global ones, as under a
    mesh). ``run(x)`` -> (output, updated running statistics)."""
    rng = np.random.default_rng(7)
    dims = {"RxC": (96, 8), "NxMxC": (24, 4, 8)}[shape]
    dims = ((2,) if sharded else ()) + dims
    x = jnp.asarray(rng.normal(1.5, 2.0, size=dims).astype(np.float32),
                    jnp.dtype(dtype))
    mask = (jnp.asarray((rng.random(dims[:-1]) > 0.3).astype(np.float32))
            if masked else None)
    weights = jnp.asarray(rng.normal(size=dims).astype(np.float32))
    variables = MaskedBatchNorm().init(jax.random.key(0),
                                       x[0] if sharded else x)
    mod = MaskedBatchNorm(axis_name="data" if sharded else None)

    def one(variables, x, mask):
        return mod.apply(variables, x, mask=mask, use_running_average=False,
                         mutable=["batch_stats"])

    def run(x, variables=variables):
        if not sharded:
            return one(variables, x, mask)
        return jax.vmap(one, in_axes=(None, 0, 0), axis_name="data")(
            variables, x, mask)

    return x, mask, weights, variables, run


def _eqns(jaxpr):
    """(jaxpr, equation) for every equation of a jaxpr and of the jaxprs in
    its equations' parameters."""
    for eqn in jaxpr.eqns:
        yield jaxpr, eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@_BN_CASES
def test_bn_shift_slices_x_before_it_converts(dtype, shape, masked, sharded):
    """The shift of the one-pass moments is the mean of x's leading
    row-block. It must be sliced from x in x's own dtype and converted after
    the slice: a slice of the whole array converted to float32 makes XLA
    hoist that convert into the producer of x, which then writes a float32
    copy of the whole activation beside it (769 MB a conv in ``ocp.train``,
    read back for 150 KB; PR 37, pinned on the compiled program by
    tests/test_tpu_compile.py
    test_no_float32_copy_of_z_is_written_beside_z). Here, on the jaxpr: one
    slice reads x itself, and none reads a whole-array
    ``convert_element_type`` (float32 inputs have no convert to misplace:
    their cases hold the first half)."""
    x, _mask, _w, _variables, run = _bn_case(dtype, shape, masked, sharded)
    block = list(x.shape)
    block[1 if sharded else 0] = 1
    slices_of_x, slices_of_a_convert = 0, []
    for jaxpr, eqn in _eqns(jax.make_jaxpr(run)(x).jaxpr):
        if (eqn.primitive.name not in ("slice", "dynamic_slice", "gather")
                or list(eqn.outvars[0].aval.shape) != block):
            continue
        source = eqn.invars[0]
        assert source.aval.shape == x.shape
        slices_of_x += source.aval.dtype == x.dtype
        made_by = [e for e in jaxpr.eqns if source in e.outvars]
        if made_by and made_by[0].primitive.name == "convert_element_type":
            slices_of_a_convert.append(str(made_by[0]))
    assert slices_of_x == 1
    assert not slices_of_a_convert, slices_of_a_convert


@_BN_CASES
def test_one_pass_bn_agrees_with_the_two_pass_form(dtype, shape, masked,
                                                   sharded):
    """Outputs, updated running statistics and the gradient of a masked loss
    (w.r.t. x, scale and bias) of the one-pass moments with their shift
    against the centered two-pass form (``force_two_pass_stats``), for the
    [R, C] rows of bn2 and the [N, M, C] of bn1, with and without a mask,
    on one device and under ``axis_name`` (where the shift is ``pmean``-ed
    and the sums ``psum``-ed). Tolerances: the file's own (2e-4 in float32,
    3e-2 of the largest reference entry in bfloat16, where both forms round
    the same float32 result to 8 bits)."""
    from cgnn_tpu.ops.norm import force_two_pass_stats

    x, mask, weights, variables, run = _bn_case(dtype, shape, masked,
                                                sharded)
    keep = 1.0 if mask is None else mask[..., None]

    def loss(params, x):
        y, mutated = run(x, {**variables, "params": params})
        y = y.astype(jnp.float32) * keep
        return (y * weights).sum() + ((y * weights) ** 2).sum(), (
            y, mutated["batch_stats"])

    def both():
        (_, (y, stats)), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(variables["params"], x)
        return y, stats, grads

    got = both()
    force_two_pass_stats(True)
    try:
        want = both()
    finally:
        force_two_pass_stats(False)

    def close(a, b, what):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.isfinite(b).all() and float(np.abs(b).max()) > 1e-3, what
        tol = ({"rtol": 2e-4, "atol": 2e-4} if dtype == "float32" else
               {"rtol": 0.0, "atol": 3e-2 * float(np.abs(b).max())})
        np.testing.assert_allclose(a, b, err_msg=what, **tol)

    close(got[0], want[0], "outputs")
    for name in ("mean", "var"):
        close(got[1][name], want[1][name], f"running {name}")
    close(got[2][1], want[2][1], "gradient w.r.t. x")
    for name in ("scale", "bias"):
        close(got[2][0][name], want[2][0][name], f"gradient {name}")


def _conv_case(mapping):
    """One packed dense batch with padding nodes and padding slots, and
    the transpose mapping the case asks for: none (forward-only batches),
    single-tier ([N, In] at the dataset's in-degree cap) or two-tier
    ([N, M] plus a non-empty overflow list at the data set's run capacity;
    ``two-tier-snug-run``: the capacity is the batch's own longest run, so
    a node owns a run of exactly K; ``two-tier-empty``: graphs of which no
    atom has more than M incoming edges, so the list is all padding)."""
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic
    from cgnn_tpu.data.graph import (
        batch_iterator,
        capacities_for,
        in_degree_cap,
        max_in_degree,
        overflow_rows,
    )

    m = 8
    cfg = FeaturizeConfig(radius=4.0, max_num_nbr=m)
    graphs = load_synthetic(14, cfg, seed=2, max_atoms=9)
    nc, ec = capacities_for(graphs, 14, dense_m=m)
    in_cap = {"none": 0, "single-tier": in_degree_cap(graphs)}.get(mapping)
    kw = {}
    if mapping == "two-tier-snug-run":
        kw = {"run_cap": max_in_degree(graphs) - m}
    elif mapping == "two-tier-empty":
        graphs = [g for g in load_synthetic(40, cfg, seed=2, max_atoms=9)
                  if max_in_degree([g]) <= m][:14]
        assert len(graphs) == 14
    batch = next(batch_iterator(graphs, 14, nc, ec, dense_m=m,
                                in_cap=in_cap, **kw))
    node_mask = np.asarray(batch.node_mask)
    edge_mask = np.asarray(batch.edge_mask)
    assert 0 < node_mask.sum() < node_mask.size, "no padding node"
    assert (edge_mask.reshape(nc, m)[node_mask > 0] == 0).any(), \
        "no padding slot on a real node"
    assert (batch.in_slots is None) == (mapping == "none")
    assert (batch.over_slots is not None) == mapping.startswith("two-tier")
    if mapping == "two-tier-empty":
        assert overflow_rows(batch) == 0 and len(batch.over_slots) >= 8
    elif mapping.startswith("two-tier"):
        assert overflow_rows(batch) > 0, "no overflow edge"
        longest = np.nonzero(np.asarray(batch.over_runs))[0].max() + 1
        assert (longest == len(batch.over_runs)) \
            == (mapping == "two-tier-snug-run")
    return batch, m


def _conv_pair(batch, m, jdt, f, norm):
    """The dense conv and the COO conv with ONE set of variables (the COO
    conv's, moved off their (0, 1) initial values so that eval mode and the
    EMA update are not trivial), and the arguments each is called with."""
    from cgnn_tpu.models.cgcnn import CGConv

    rng = np.random.default_rng(5)
    node_mask = jnp.asarray(batch.node_mask)
    nodes = jnp.asarray(
        rng.normal(size=(node_mask.shape[0], f)).astype(np.float32)
    ) * node_mask[:, None]
    # ``norm``: True (bn1 and bn2), False (neither, the force model's conv)
    # or "layernorm" (bn1, and LayerNorm after the sum: the Open Catalyst one)
    kw = dict(use_batchnorm=bool(norm),
              node_norm={True: "batch", False: "none"}.get(norm, "layer"))
    dense = CGConv(features=f, dtype=jdt, dense_m=m, **kw)
    coo = CGConv(features=f, dtype=jdt, **kw)

    def args(edges):
        return (edges, batch.centers, batch.neighbors, batch.edge_mask,
                batch.node_mask)

    mapping_kw = dict(in_slots=batch.in_slots, in_mask=batch.in_mask,
                      over_slots=batch.over_slots,
                      over_nodes=batch.over_nodes,
                      over_last=batch.over_last,
                      over_runs=batch.over_runs)
    variables = coo.init(jax.random.key(0), nodes.astype(jdt),
                         *args(batch.flat_edges))
    v_dense = dense.init(jax.random.key(0), nodes.astype(jdt),
                         *args(batch.edges), **mapping_kw)
    # the parameter tree is the concatenated Linear's, leaf for leaf
    assert (jax.tree_util.tree_map(jnp.shape, variables)
            == jax.tree_util.tree_map(jnp.shape, v_dense))
    leaves, treedef = jax.tree_util.tree_flatten(variables)
    variables = jax.tree_util.tree_unflatten(treedef, [
        x + jnp.asarray(rng.uniform(0.05, 0.3, x.shape).astype(np.float32))
        for x in leaves])
    return nodes, variables, (dense, batch.edges, mapping_kw), \
        (coo, batch.flat_edges, {}), args


_MAPPINGS = ["none", "single-tier", "two-tier", "two-tier-snug-run",
             "two-tier-empty"]


@pytest.mark.parametrize("batchnorm", [True, False, "layernorm"],
                         ids=["batchnorm", "no-batchnorm", "layernorm"])
@pytest.mark.parametrize("mapping", _MAPPINGS)
@pytest.mark.parametrize("mode", ["train", "eval"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_dense_conv_matches_coo_conv(dtype, mode, mapping, batchnorm):
    """The conv every benchmark cell runs (``CGConv`` with ``dense_m``:
    fc_full's neighbour term projected once a node and gathered slot-major
    at width 2F, 3-D BN1, sum over M) against the flat COO body (gather +
    concat + Dense + segment-sum), the in-program reference: same
    parameters, same packed batch. Outputs always; in train mode also the
    gradients w.r.t. the input nodes and every parameter and the updated
    bn1 / bn2 running statistics, with and without the packed transpose
    mapping (and its overflow tier) behind the gather's backward, with
    BatchNorm (``mp.train``, ``oc20.train``), without (the force model's
    conv) and with LayerNorm after the sum in bn2's place (``ocp.train``).

    float32 tolerances are those of the kernel tests this replaces (2e-5
    on values, 5e-4 on gradients). In bfloat16 the two bodies round
    differently (three sliced matmuls against one over the concat, a sum
    over M against a segment-sum, each rounded to 8 bits), so they are
    held to 3e-2 of the largest reference entry (of the module, for a
    parameter gradient): a masked-out term or a wrong neighbour moves
    values by their own size."""
    batch, m = _conv_case(mapping)
    jdt = jnp.dtype(dtype)
    nodes, variables, dense, coo, args = _conv_pair(batch, m, jdt, 16,
                                                   batchnorm)
    node_mask = batch.node_mask
    train = mode == "train"
    stats = variables.get("batch_stats", {})

    def run(conv, edges, kw):
        def loss(params, x):
            out, mut = conv.apply(
                {"params": params, "batch_stats": stats},
                x.astype(jdt), *args(edges), train=train,
                mutable=["batch_stats"], **kw)
            out = out.astype(jnp.float32)
            return (out ** 2).sum(), (out, mut.get("batch_stats", {}))

        if not train:
            return loss(variables["params"], nodes)[1], None
        (_, aux), grads = jax.value_and_grad(
            loss, argnums=(0, 1), has_aux=True)(variables["params"], nodes)
        return aux, grads

    (out_d, stats_d), g_d = run(*dense)
    (out_c, stats_c), g_c = run(*coo)

    def close(got, want, rtol, atol, what, scale=None):
        got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
        if dtype == "bfloat16":
            scale = float(np.abs(want).max()) if scale is None else scale
            rtol, atol = 0.0, 3e-2 * max(scale, 1e-3)
        np.testing.assert_allclose(got, want, rtol=rtol, atol=atol,
                                   err_msg=what)

    assert float(np.abs(np.asarray(out_c)).max()) > 0.1
    close(out_d, out_c, 2e-5, 2e-5, "outputs")
    # padding node rows stay zero
    assert not np.asarray(out_d)[np.asarray(node_mask) == 0].any()
    if not train:
        return
    flat = lambda tree: sorted(  # noqa: E731
        (jax.tree_util.keystr(k), v)
        for k, v in jax.tree_util.tree_leaves_with_path(tree))
    assert len(flat(stats_c)) == {True: 4, False: 0, "layernorm": 2}[
        batchnorm]
    for (ka, a), (kb, b) in zip(flat(stats_d), flat(stats_c)):
        assert ka == kb
        close(a, b, 1e-4, 1e-5, f"running statistics {ka}")
    (gp_d, gx_d), (gp_c, gx_c) = g_d, g_c
    assert float(np.abs(np.asarray(gx_c)).max()) > 0.1
    close(gx_d, gx_c, 5e-4, 5e-5, "gradient w.r.t. nodes")
    # kernel/scale and bias of fc_full, bn1, bn2 (or ln)
    assert len(flat(gp_d)) == (6 if batchnorm else 2)
    assert ("ln" in gp_d) == (batchnorm == "layernorm")
    for module in gp_c:
        # a module's leaves share one bf16 scale: under BatchNorm fc_full's
        # bias gradient is zero in exact arithmetic (BN1 removes what a
        # bias adds), so each body returns its own rounding of a
        # cancelling sum
        scale = max(float(np.abs(np.asarray(v)).max())
                    for v in jax.tree_util.tree_leaves(gp_c[module]))
        assert scale > 0.1, module
        for (ka, a), (kb, b) in zip(flat(gp_d[module]),
                                    flat(gp_c[module])):
            assert ka == kb
            close(a, b, 5e-4, 5e-5, f"gradient {module}{ka}", scale)


def _primitive_names(jaxpr) -> set:
    """Every primitive of ``jaxpr`` and of the jaxprs its equations hold."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for value in eqn.params.values():
            for sub in (value if isinstance(value, (list, tuple))
                        else [value]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    names |= _primitive_names(sub)
    return names


@pytest.mark.parametrize("body", ["dense-two-tier", "dense-no-mapping",
                                  "coo"])
def test_conv_holds_no_collective(body):
    """One mesh axis, and it is outside the conv: neither body of
    ``CGConv``, forward or reverse, names a mesh axis (the replicas' sums
    are the step's, train/step.py). The dense body with the two-tier
    mapping (training), without one (eval batches), and the COO body."""
    batch, m = _conv_case("none" if body == "dense-no-mapping"
                          else "two-tier")
    nodes, variables, dense, coo, args = _conv_pair(
        batch, m, jnp.dtype("float32"), 16, True)
    conv, edges, kw = coo if body == "coo" else dense
    train = body != "dense-no-mapping"

    def loss(params, x):
        out, _ = conv.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            x, *args(edges), train=train, mutable=["batch_stats"], **kw)
        return (out ** 2).sum()

    names = _primitive_names(jax.make_jaxpr(
        jax.grad(loss, argnums=(0, 1)))(variables["params"], nodes).jaxpr)
    assert "gather" in names and "dot_general" in names, names
    across = {"psum", "psum2", "psum_invariant", "pmax", "pmin",
              "all_gather", "all_gather_invariant", "all_to_all",
              "ppermute", "pcast", "pvary", "pbroadcast", "axis_index",
              "reduce_scatter"}
    assert not names & across, names & across


def test_conv_and_model_fields():
    """The conv's options are these five and the model has no mesh axis of
    its own: a sixth is a decision every cell's program would carry."""
    import dataclasses

    from cgnn_tpu.models.cgcnn import CGConv, CrystalGraphConvNet

    own = lambda cls: [f.name for f in dataclasses.fields(cls)  # noqa: E731
                       if f.name not in ("parent", "name")]
    assert own(CGConv) == ["features", "dtype", "use_batchnorm",
                           "node_norm", "dense_m"]
    assert not [n for n in own(CrystalGraphConvNet) if "axis" in n]


@pytest.mark.parametrize("mapping", _MAPPINGS)
def test_dense_conv_second_derivative_matches_coo_conv(mapping):
    """Grad over grad, which the force step runs in every step
    (train/force_step.py): the inner reverse pass gives d(sum out^2)/d(nodes,
    edges) (the path to the forces goes through both), the outer one
    differentiates a loss on that first derivative w.r.t. the parameters
    and the nodes. In the dense body the inner pass sends ``dz`` through the
    gather's declared transpose and the outer pass transposes that again (a
    ``linear_call``; a ``custom_vjp`` would refuse the jvp), at the projected
    width 2F; the COO body is plain autodiff of ``take`` and
    ``segment_sum``. float32, no BatchNorm, as the force model builds its
    convs; held to 5e-4 of each leaf's largest reference entry."""
    batch, m = _conv_case(mapping)
    nodes, variables, dense, coo, args = _conv_pair(
        batch, m, jnp.dtype("float32"), 16, False)

    def run(conv, edges, kw):
        def energy(x, e, params):
            out = conv.apply({"params": params}, x, *args(e), train=True,
                             **kw)
            return (out ** 2).sum()

        def on_first_derivative(params, x):
            gx, ge = jax.grad(energy, argnums=(0, 1))(x, edges, params)
            # per-slot weights, or the loss would be symmetric in the edges
            w = jnp.arange(ge.size, dtype=ge.dtype).reshape(
                -1, ge.shape[-1]) % 7 / 7
            return (gx ** 2).sum() + (w * ge.reshape(w.shape) ** 2).sum()

        return jax.grad(on_first_derivative, argnums=(0, 1))(
            variables["params"], nodes)

    got, want = run(*dense), run(*coo)
    leaves = jax.tree_util.tree_leaves_with_path(want)
    assert len(leaves) == 3  # fc_full's kernel and bias, the nodes
    for (path, b), a in zip(leaves, jax.tree_util.tree_leaves(got)):
        b = np.asarray(b)
        scale = float(np.abs(b).max())
        assert scale > 0.1, jax.tree_util.keystr(path)
        np.testing.assert_allclose(
            np.asarray(a), b, rtol=0, atol=5e-4 * scale,
            err_msg=f"second derivative {jax.tree_util.keystr(path)}")
