"""The gated short convolution of a hybrid decoder (models/lfm2.py): the
mixer of the layers that are no attention.

On ``[B | C | u] = h W_in`` (``bcu [S, N, 3H]``, the thirds in that order),
with ``w [H, TAPS]`` a depthwise filter over the ``H`` channels:

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
"""

from __future__ import annotations

import jax.numpy as jnp

# the filter's length (``conv_L_cache``): the position itself and two earlier
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
    z = b * u
    conv = w[:, TAPS - 1] * z
    for back in range(1, TAPS):
        conv = conv + w[:, TAPS - 1 - back] * _earlier(z, segment_ids, back)
    return (c * conv).astype(bcu.dtype)


def taps_cut(segment_ids):
    """The (position, tap) pairs of one layer that contribute 0 because they
    lie before the sequence or in another document -> int32."""
    return sum((~_same_document(segment_ids, back)).sum(dtype=jnp.int32)
               for back in range(1, TAPS))
