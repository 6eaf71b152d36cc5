"""The short, depthwise, causal convolutions of the hybrid decoders: the
gated one that is models/lfm2.py's mixer (``short_conv``, 3 taps, no bias),
and the one a Mamba-2 layer passes ``[x | B | C]`` through before its scan
(models/nemotron_h.py: ``silu_conv``, 4 taps, a bias, a ``silu``, no gates).
Both are the same taps (``causal_taps``, as many as the filter ``w`` has).

The gated one: on ``[B | C | u] = h W_in`` (``bcu [S, N, 3H]``, the thirds in
that order), with ``w [H, TAPS]`` a depthwise filter over the ``H`` channels:

    z_i = B_i * u_i
    c_i = sum_t w[:, t] * z_{i - (TAPS - 1) + t}       t = 0 .. TAPS - 1
    out_i = C_i * c_i

causal, and a document's own: a tap whose position lies before the sequence
or in another document contributes 0 (``segment_ids``, as
``ops/masked_attention.py`` takes them), as a server's convolution cache
starts every request empty. Plain shifted multiply-adds: ``z``, ``z`` one and
two positions earlier, each masked by ``segment_ids`` compared with itself
shifted. The arithmetic is float32 inside and the arrays are in the compute
dtype outside; the pass is bound by bandwidth (``3H`` read and ``H`` written
a position) and XLA makes it one fusion a direction.

The other: ``out_i = silu(sum_t w[:, t] * x_{i - (taps - 1) + t} + bias)``
over its channels, under the same document rule.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

# LFM2's filter length (``conv_L_cache``): the position itself and two
# earlier; what ``short_conv`` holds its filter to
TAPS = 3


def _same_document(segment_ids, back: int):
    """``[S, N]`` bool: position ``i - back`` is inside the sequence and of
    position ``i``'s document."""
    n = segment_ids.shape[1]
    earlier = jnp.pad(segment_ids, ((0, 0), (back, 0)),
                      constant_values=-1)[:, :n]
    return earlier == segment_ids


def _earlier(z, segment_ids, back: int):
    """``z_{i - back}`` at ``i``, 0 where that is no position of ``i``'s
    document."""
    n = z.shape[1]
    shifted = jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :n]
    return jnp.where(_same_document(segment_ids, back)[..., None], shifted, 0)


def causal_taps(z, w, segment_ids):
    """``sum_t w[:, t] * z_{i - (taps - 1) + t}`` for ``z [S, N, C]`` and
    ``w [C, taps]``, both float32 (``w[:, taps - 1]`` weighs the position
    itself); a tap before the sequence or in another document adds 0."""
    taps = w.shape[1]
    conv = w[:, taps - 1] * z
    for back in range(1, taps):
        conv = conv + w[:, taps - 1 - back] * _earlier(z, segment_ids, back)
    return conv


def short_conv(bcu, w, segment_ids):
    """``bcu [S, N, 3H]`` in the compute dtype, ``w [H, TAPS]`` float32
    (``w[:, TAPS - 1]`` weighs the position itself), ``segment_ids [S, N]``
    int32 -> ``C * conv(B * u) [S, N, H]`` in ``bcu``'s dtype."""
    h = bcu.shape[-1] // 3
    if w.shape != (h, TAPS):
        raise ValueError(f"a filter of {w.shape} over {h} channels and "
                         f"{TAPS} taps")
    b, c, u = (bcu[..., j * h:(j + 1) * h].astype(jnp.float32)
               for j in range(3))
    w = w.astype(jnp.float32)
    return (c * causal_taps(b * u, w, segment_ids)).astype(bcu.dtype)


def silu_conv(x, w, bias, segment_ids):
    """``x [S, N, C]`` in the compute dtype, ``w [C, taps]`` and ``bias
    [C]`` float32, ``segment_ids [S, N]`` int32 -> ``silu(conv(x) + bias)
    [S, N, C]`` in ``x``'s dtype."""
    if w.shape[0] != x.shape[-1] or bias.shape != w.shape[:1]:
        raise ValueError(f"a filter of {w.shape} and a bias of {bias.shape} "
                         f"over {x.shape[-1]} channels")
    conv = causal_taps(x.astype(jnp.float32), w.astype(jnp.float32),
                       segment_ids)
    return jax.nn.silu(conv + bias.astype(jnp.float32)).astype(x.dtype)


def taps_cut(segment_ids, taps: int = TAPS):
    """The (position, tap) pairs of one layer that contribute 0 because they
    lie before the sequence or in another document -> int32."""
    return sum((~_same_document(segment_ids, back)).sum(dtype=jnp.int32)
               for back in range(1, taps))
