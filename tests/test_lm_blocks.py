"""``lm_blocks.prepare_heads`` (models/lm_blocks.py, ops/prepare_heads.py): a
projection's output made the attention's operand. The kernels run here in
Pallas interpret mode, against the plain form, which is the composition the
op replaced.
"""

from __future__ import annotations

import dataclasses
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from cgnn_tpu.data import tokens  # noqa: E402
from cgnn_tpu.models import afmoe, lm_blocks, sdar  # noqa: E402
from cgnn_tpu.ops import prepare_heads as fused  # noqa: E402
from cgnn_tpu.train.lm_step import step_metrics  # noqa: E402

N, D = 64, 128
THETA, EPS = 1e4, 1e-6
POSITIONS = {
    "none": lambda n: None,
    "causal": lambda n: jnp.arange(n, dtype=jnp.int32),
    # the block-diffusion decoder's: the noised copy, then the clean one
    "blockdiff": lambda n: jnp.arange(n, dtype=jnp.int32) % (n // 2),
}


def _operands(s, heads, dtype, n=N, d=D):
    x = jax.random.normal(jax.random.key(0), (s, n, heads * d), jnp.float32)
    w = 1.0 + 0.1 * jax.random.normal(jax.random.key(1), (d,), jnp.float32)
    g = jax.random.normal(jax.random.key(2), (s, heads, n, d), jnp.float32)
    return x.astype(dtype), w, g.astype(dtype)


def _plain(x, w, positions, scale):
    return lm_blocks.prepare_heads(x, w, positions, theta=THETA, eps=EPS,
                                   scale=scale)


def _kernel(x, w, positions, scale):
    return fused.prepare_heads(x, w, positions, THETA, EPS, scale)


@pytest.fixture(autouse=True)
def interpreted():
    """The kernels' bodies run on the CPU, interpreted."""
    with pltpu.force_tpu_interpret_mode():
        yield


def _close(got, want, dtype, what):
    """float32: to rounding; bfloat16: within one place of the result (and
    float32's rounding where a rotation's two terms cancel)."""
    assert got.dtype == want.dtype and got.shape == want.shape, what
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    if dtype == "float32":
        np.testing.assert_allclose(got, want, rtol=2e-5,
                                   atol=2e-6 * np.abs(want).max(),
                                   err_msg=what)
    else:
        assert (np.abs(got - want)
                <= 2.0 ** -7 * np.maximum(np.abs(got), np.abs(want))
                + 2e-6 * np.abs(want).max()).all(), what


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("s", [1, 2])
@pytest.mark.parametrize("heads", [32, 4])
@pytest.mark.parametrize("positions", list(POSITIONS))
def test_the_kernels_agree_with_the_plain_form(positions, heads, s, dtype):
    """Values, and the gradients to ``x`` and to the norm's scale, the
    cotangent passing the scale (``q``'s ``1 / sqrt(D)`` on 32 heads, ``k``'s
    1 on 4): the forward and the reverse kernel against autodiff of
    ``rms_norm`` -> ``rope`` -> ``* scale`` -> cast -> ``swapaxes``."""
    assert not lm_blocks.heads_fused(N, D)  # the CPU: this is the plain form
    x, w, g = _operands(s, heads, dtype)
    pos = POSITIONS[positions](N)
    scale = 1.0 / math.sqrt(D) if heads == 32 else 1.0

    def both(fn):
        out, vjp = jax.vjp(lambda x, w: fn(x, w, pos, scale), x, w)
        return out, *vjp(g)

    (got, dx, dw), (want, dx_want, dw_want) = both(_kernel), both(_plain)
    assert got.shape == (s, heads, N, D)
    _close(got, want, dtype, "values")
    _close(dx, dx_want, dtype, "the gradient to x")
    assert dw.dtype == jnp.float32
    np.testing.assert_allclose(dw, dw_want, rtol=0,
                               atol=1e-5 * np.abs(dw_want).max())


def test_the_scale_reaches_the_values_and_the_gradient():
    """``scale`` multiplies the output, and ``x``'s gradient with it."""
    x, w, g = _operands(1, 4, "float32")
    pos = POSITIONS["causal"](N)

    def both(scale):
        out, vjp = jax.vjp(lambda x: _kernel(x, w, pos, scale), x)
        return out, vjp(g)[0]

    (out, dx), (out_1, dx_1) = both(0.25), both(1.0)
    np.testing.assert_allclose(out, 0.25 * out_1, rtol=1e-6)
    np.testing.assert_allclose(dx, 0.25 * dx_1, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("positions", list(POSITIONS))
def test_the_plain_form_is_the_composition_it_replaced(positions):
    """Bit for bit: what ``models/sdar.py`` and ``models/afmoe.py`` wrote
    out on ``q`` until PR 47."""
    x, w, _ = _operands(2, 4, "bfloat16")
    pos, scale = POSITIONS[positions](N), 1.0 / math.sqrt(D)
    q = lm_blocks.rms_norm(x.reshape(2, N, 4, D), w, EPS)
    if pos is not None:
        q = lm_blocks.rope(q, pos, THETA)
    want = jnp.swapaxes((q * scale).astype(x.dtype), 1, 2)
    np.testing.assert_array_equal(
        np.asarray(_plain(x, w, pos, scale), np.float32),
        np.asarray(want, np.float32))


def test_several_blocks_and_their_partial_sums(monkeypatch):
    """More than one block of rows a sequence: the output's index map puts
    each where it belongs, and the norm scale's gradient is the sum of the
    blocks' partial sums."""
    monkeypatch.setattr(fused, "_BLOCK_BYTES", 16 * 4 * D * 2)
    x, w, g = _operands(2, 4, "bfloat16")
    assert fused._block_rows(N, x.shape[-1] * 2) == 16
    pos = POSITIONS["blockdiff"](N)
    _close(_kernel(x, w, pos, 0.5), _plain(x, w, pos, 0.5), "bfloat16",
           "values")
    (dx, dw), (dx_want, dw_want) = (
        jax.vjp(lambda x, w: fn(x, w, pos, 0.5), x, w)[1](g)
        for fn in (_kernel, _plain))
    _close(dx, dx_want, "bfloat16", "the gradient to x")
    np.testing.assert_allclose(dw, dw_want, rtol=0,
                               atol=1e-5 * np.abs(dw_want).max())


@pytest.mark.parametrize("n,d,fits", [
    (8192, 128, True), (64, 128, True), (64, 256, True),
    (64, 16, False),   # the tiny models of the tests
    (64, 64, False), (24, 128, False)])
def test_the_shape_and_the_backend_choose_the_kernel(monkeypatch, n, d,
                                                     fits):
    """No key and no flag: on the CPU never, on the TPU where a head is whole
    128-lane tiles; and the op takes the kernel exactly there."""
    assert fused.supported(n, d) == fits
    assert not lm_blocks.heads_fused(n, d)
    x, w, _ = _operands(1, 2, "float32", n, d)
    pos = POSITIONS["causal"](n)

    def calls():
        return str(jax.make_jaxpr(
            lambda x, w: _plain(x, w, pos, 1.0))(x, w)).count("pallas_call")

    assert calls() == 0
    monkeypatch.setattr(lm_blocks.jax, "default_backend", lambda: "tpu")
    assert lm_blocks.heads_fused(n, d) == fits
    assert calls() == (1 if fits else 0)


SDAR = sdar.SdarConfig(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_hidden_layers=2, n_experts=16, num_experts_per_tok=4,
    experts_held=(4, 4), moe_intermediate_size=32, vocab_size=128,
    block_length=4, dtype="float32")
AFMOE = afmoe.AfmoeConfig(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
    head_dim=16, num_hidden_layers=7, num_dense_layers=1,
    layer_types=(afmoe.SLIDING,) * 3 + (afmoe.FULL,) + (afmoe.SLIDING,) * 2
    + (afmoe.FULL,), intermediate_size=96, n_experts=16, num_experts_per_tok=4,
    experts_held=(4, 4), moe_intermediate_size=32, vocab_size=128,
    sliding_window=8, dtype="float32")


@pytest.mark.parametrize("cfg,kind,layers", [(SDAR, "blockdiff", 2),
                                             (AFMOE, "causal", 7)])
@pytest.mark.parametrize("head_dim,on_tpu,fused_share", [
    (16, False, 0), (16, True, 0), (128, False, 0), (128, True, 1)])
def test_what_a_step_counts_of_the_prepared_heads(monkeypatch, cfg, kind,
                                                  layers, head_dim, on_tpu,
                                                  fused_share):
    """``heads_prepared``: the (sequence, layer, operand) calls of a step, q
    and k; ``heads_prepared_fused``: those that took the kernel: all of them
    on the TPU at a head of 128, none elsewhere. From the shapes."""
    if on_tpu:
        monkeypatch.setattr(lm_blocks.jax, "default_backend", lambda: "tpu")
    pool = tokens.make_pool(
        4, 32, vocab_size=128, seed=0, doc_median=12, doc_min=4, doc_max=32,
        **({"block": 4} if kind == "blockdiff" else {"kind": "causal"}))
    batch = tokens.split_batches(pool, 2)[0]
    n_expert_layers = layers - getattr(cfg, "num_dense_layers", 0)
    m = step_metrics(
        dataclasses.replace(cfg, head_dim=head_dim), batch, jnp.float32(0.0),
        (jnp.ones((n_expert_layers, 16), jnp.int32),
         jnp.zeros((n_expert_layers, 2), jnp.int32)), None,
        {"router_bias": jnp.zeros((1, 1, 16))})
    assert float(m["heads_prepared_sum"]) == 2 * layers * 2
    assert float(m["heads_prepared_fused_sum"]) == (
        fused_share * 2 * layers * 2)
    assert float(m["heads_prepared_count"]) == 1.0
    assert float(m["heads_prepared_fused_count"]) == 1.0
