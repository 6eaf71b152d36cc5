"""Masked BatchNorm and LayerNorm — padding-aware normalization.

The reference normalizes over all N·M edge slots and all N node slots with
cuDNN/ATen BatchNorm1d (SURVEY.md §2 component 6). On TPU the batch is padded
to static capacity, and padding rows must not pollute the batch statistics
(SURVEY.md §7 "hard parts" #3) — this module computes masked moments.

Semantics mirror ``torch.nn.BatchNorm1d`` for the oracle parity harness
(SURVEY.md §4.3):

- normalization uses the *biased* batch variance (divide by n);
- running-variance updates use the *unbiased* estimate (divide by n-1);
- running stats update as ``running = (1-momentum)*running + momentum*batch``
  with torch's default momentum 0.1;
- eval mode normalizes with running stats.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

# Diagnostic/experiment knob: force the two-pass centered variance even for
# float32 statistics (the f64 oracle path always uses it). Costs one extra
# full read of the activation per BN; exists so accuracy A/Bs can isolate
# the one-pass estimator (scripts/mae_ab.py) and as an escape hatch.
_FORCE_TWO_PASS = False


def force_two_pass_stats(enabled: bool = True) -> None:
    global _FORCE_TWO_PASS
    _FORCE_TWO_PASS = enabled


class MaskedBatchNorm(nn.Module):
    """BatchNorm1d over rows [..., C] with an optional [...] validity mask.

    All leading axes are batch axes (statistics reduce over every axis but
    the last), so callers with a dense edge-slot layout can pass [N, M, C]
    + mask [N, M] directly — numerically identical to flattening to
    [N*M, C] first, but without the reshape, which on TPU is a real
    layout-change copy for (8,128)-tiled 3-D tensors (measured ~16% of
    step time as "data formatting" before this was removed).
    """

    momentum: float = 0.1
    epsilon: float = 1e-5
    use_scale: bool = True
    use_bias: bool = True
    # output dtype; statistics follow promote_types(input, float32), so
    # float64 activations keep float64 running stats (oracle parity)
    dtype: jnp.dtype | None = None
    # when the row axis is sharded across a mesh axis (edge-sharded graph
    # parallelism), moments must be computed over ALL shards: f32-stat
    # mode psums (count, sum, sum-of-squares) once; f64-stat mode (the
    # oracle-parity path) psums count+mean first and the centered
    # variance second, keeping the single-device centered numerics
    axis_name: str | None = None

    @nn.compact
    def __call__(
        self,
        x: jax.Array,
        mask: jax.Array | None = None,
        use_running_average: bool = False,
    ) -> jax.Array:
        features = x.shape[-1]
        # statistics in >= float32 (float64 when the input is float64, for
        # the double-precision oracle parity harness)
        stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros(features, jnp.float32)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones(features, jnp.float32)
        )

        reduce_axes = tuple(range(x.ndim - 1))
        # One-pass moments (E[x^2] - E[x]^2) in float32-stat mode: both
        # sums reduce over a single read of x, where the centered two-pass
        # form costs an extra full pass over the (large) activation per BN
        # per direction. The two-pass form is kept for float64 stats —
        # the double-precision oracle parity harness pins 1e-8 agreement
        # with torch, and one-pass cancellation error would show there.
        one_pass = stat_dtype == jnp.float32 and not _FORCE_TWO_PASS
        if use_running_average:
            mean, var = ra_mean.value, ra_var.value
        else:
            xf = x.astype(stat_dtype)
            if one_pass:
                # Shift-invariant accumulation: var(x) = var(x - c) for any
                # per-feature c, and a c near the data mean prevents the
                # catastrophic cancellation of E[x^2] - E[x]^2 when
                # |mean| >> std (f32 keeps ~7 digits; at mean 1e4, std 1 the
                # unshifted form returns var = 0 and rsqrt AMPLIFIES). The
                # leading row-block is real data (pack_graphs places padding
                # last), and correctness never depends on the choice of c —
                # only the cancellation magnitude does. The subtract fuses
                # into the same single read of x.
                #
                # The slice is taken from x in x's OWN dtype and converted
                # after it (bfloat16 -> float32 is exact and commutes with
                # a slice: the value is the same bit for bit). Slicing the
                # converted whole array instead makes XLA hoist that convert
                # into the producer of x as a second output: the fusion
                # that writes bfloat16 z then writes a float32 [N, M, 2F]
                # copy beside it, 769 MB a conv in ocp.train, read back for
                # its first 150 KB (PERF.md section 6, PR 37). Pinned by
                # tests/test_tpu_compile.py
                # test_no_float32_copy_of_z_is_written_beside_z and
                # tests/test_ops.py test_bn_shift_slices_x_before_it_converts.
                shift = jax.lax.stop_gradient(
                    x[:1].astype(stat_dtype).mean(
                        axis=tuple(range(x.ndim - 1)))
                )
                if self.axis_name is not None:
                    # shards must agree on c or their (s1, s2) can't be
                    # psum-combined
                    shift = jax.lax.pmean(shift, self.axis_name)
                xs = xf - shift
            else:
                xs = xf
            if mask is not None:
                m = mask.astype(stat_dtype)
                n_real = m.sum()
                xm = xs * m[..., None]
                s1 = xm.sum(axis=reduce_axes)
                s2 = (xm * xs).sum(axis=reduce_axes) if one_pass else None
            else:
                m = None
                n_real = jnp.asarray(
                    np.prod([x.shape[a] for a in reduce_axes]), stat_dtype
                )
                s1 = xs.sum(axis=reduce_axes)
                s2 = (xs * xs).sum(axis=reduce_axes) if one_pass else None
            if self.axis_name is not None:
                if one_pass:
                    n_real, s1, s2 = jax.lax.psum(
                        (n_real, s1, s2), self.axis_name)
                else:
                    n_real, s1 = jax.lax.psum((n_real, s1), self.axis_name)
            n = jnp.maximum(n_real, 1.0)
            if one_pass:
                mean_s = s1 / n
                var = jnp.maximum(s2 / n - mean_s * mean_s, 0.0)
                mean = mean_s + shift
            else:
                mean = s1 / n
                centered = (xf - mean) ** 2
                ss = (
                    (centered * m[..., None]).sum(axis=reduce_axes)
                    if m is not None
                    else centered.sum(axis=reduce_axes)
                )
                if self.axis_name is not None:
                    ss = jax.lax.psum(ss, self.axis_name)
                var = ss / n
            if not self.is_initializing():
                # a fully-masked batch (all padding, e.g. an empty DP eval
                # shard) must not decay the running stats toward (0, 0)
                has_rows = n_real > 0
                unbiased = var * n / jnp.maximum(n - 1.0, 1.0)
                ra_mean.value = jnp.where(
                    has_rows,
                    (1.0 - self.momentum) * ra_mean.value + self.momentum * mean,
                    ra_mean.value,
                )
                ra_var.value = jnp.where(
                    has_rows,
                    (1.0 - self.momentum) * ra_var.value + self.momentum * unbiased,
                    ra_var.value,
                )

        y = (x.astype(stat_dtype) - mean) * jax.lax.rsqrt(
            var.astype(stat_dtype) + self.epsilon
        )
        if self.use_scale:
            y = y * self.param("scale", nn.initializers.ones, (features,), jnp.float32)
        if self.use_bias:
            y = y + self.param("bias", nn.initializers.zeros, (features,), jnp.float32)
        return y.astype(self.dtype or x.dtype)


class MaskedLayerNorm(nn.Module):
    """LayerNorm over the last axis of rows [..., C] (``torch.nn.LayerNorm``:
    biased variance, affine), with an optional [...] validity mask.

    A row's moments are its own, so padding cannot pollute anything; the
    mask only zeroes the padded rows' output, which would otherwise read the
    bias. Moments in >= float32, as ``MaskedBatchNorm`` keeps its statistics.
    """

    epsilon: float = 1e-5
    dtype: jnp.dtype | None = None  # output dtype

    @nn.compact
    def __call__(self, x: jax.Array, mask: jax.Array | None = None):
        features = x.shape[-1]
        stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
        xf = x.astype(stat_dtype)
        mean = xf.mean(axis=-1, keepdims=True)
        centered = xf - mean
        var = (centered * centered).mean(axis=-1, keepdims=True)
        y = centered * jax.lax.rsqrt(var + self.epsilon)
        y = y * self.param("scale", nn.initializers.ones, (features,),
                           jnp.float32)
        y = y + self.param("bias", nn.initializers.zeros, (features,),
                           jnp.float32)
        if mask is not None:
            y = y * mask[..., None].astype(stat_dtype)
        return y.astype(self.dtype or x.dtype)
