"""Softmax attention under a static mask over positions and, under packing,
within documents: the one op behind every masked attention of this repo.

The mask over positions is given and static (``StaticMask``: the
block-diffusion mask over a doubled sequence, ``ops/bd_attention.py``;
causal; causal within a window); the documents are data (``segment_ids``): a
query sees a key only where the mask shows it and both are of one document.
On the TPU the op is the splash-attention kernel of
``jax.experimental.pallas`` given that mask: it visits the live tiles only
(``mask_tiles``) and never writes a ``[heads, N, N]`` score array. Elsewhere
it is the same arithmetic in plain ``jnp``, a block of queries at a time.

What the reverse pass needs of the forward one is named ``KEPT``: the
kernel's output and its log-sum-exp, the plain path's output. A
``jax.checkpoint`` whose policy keeps that name (``lm_blocks.by_sequence``)
rebuilds neither: the reverse kernels read what the forward one wrote.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name

# the name of what the reverse pass reads of the forward one
KEPT = "attn.kept"
# one tile of the kernel's grid, queries x keys (forward and backward); the
# counters of ``mask_tiles`` are in these tiles whatever runs the op
TILE_Q = 512
TILE_KV = 512
_MASKED = -0.7 * float(np.finfo(np.float32).max)


@dataclasses.dataclass(frozen=True)
class StaticMask:
    """Which key a query sees, documents aside, over ``n`` positions:

    - ``causal``: key ``j`` iff ``j <= i``; with ``window`` ``w`` iff ``i - w
      < j <= i`` (the window counts the query itself);
    - ``bd``: the block-diffusion mask over ``x_t (+) x_0``, ``n / 2``
      positions a half in blocks of ``block``: with ``b(i)`` a position's
      block within its half, a noised query sees the noised keys of its own
      block and the clean keys of earlier blocks, a clean query the clean
      keys of its own and earlier blocks (``ops/bd_attention.py`` is the
      caller that doubles the documents).
    """
    kind: str
    n: int
    window: int = 0
    block: int = 0

    def dense(self) -> np.ndarray:
        """``[n, n]`` bool: query row sees key column."""
        return _dense(self)


@functools.lru_cache(maxsize=8)
def _dense(mask: StaticMask) -> np.ndarray:
    if mask.kind == "causal":
        i = np.arange(mask.n)[:, None]
        j = np.arange(mask.n)[None, :]
        shown = j <= i
        return shown & (j > i - mask.window) if mask.window else shown
    if mask.kind == "bd":
        half = mask.n // 2
        if mask.n % 2 or half % mask.block:
            raise ValueError(f"a sequence of {half} is no whole number of "
                             f"blocks of {mask.block}")
        b = np.arange(half) // mask.block
        own = b[:, None] == b[None, :]
        earlier = b[None, :] < b[:, None]
        top = np.concatenate([own, earlier], axis=1)  # noised queries
        bottom = np.concatenate([np.zeros_like(own), own | earlier], axis=1)
        return np.concatenate([top, bottom], axis=0)
    raise ValueError(f"no static mask {mask.kind!r}")


def _tile(n: int, tile: int) -> int:
    return tile if n % tile == 0 else n


def mask_tiles(mask: StaticMask) -> tuple[int, int]:
    """(live, grid): tiles of the ``n x n`` grid that hold a visible pair,
    and all of them, a head and a sequence."""
    n = mask.n
    tq, tk = _tile(n, TILE_Q), _tile(n, TILE_KV)
    live = mask.dense().reshape(n // tq, tq, n // tk, tk).any(axis=(1, 3))
    return int(live.sum()), int(live.size)


def kept_bytes(heads: int, n: int, head_dim: int, dtype) -> int:
    """Bytes of ``KEPT`` a sequence: the kernel's output in ``dtype`` and
    its float32 log-sum-exp, ``heads`` query heads over ``n`` positions."""
    return heads * n * (head_dim * jnp.dtype(dtype).itemsize + 4)


def _blocked(q, k, v, segment_ids, mask: np.ndarray):
    """Plain ``jnp``: ``q [S, Hkv, G, N, D]``, ``k, v [S, Hkv, N, D]``."""
    n = q.shape[-2]
    tq = _tile(n, TILE_Q)
    shown = jnp.asarray(mask)
    seg = segment_ids  # [S, N]

    def rows(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, tq, axis=3)
        s = jnp.einsum("shgqd,shkd->shgqk", qb, k,
                       preferred_element_type=jnp.float32)
        ok = jax.lax.dynamic_slice_in_dim(shown, start, tq, axis=0)
        same = (jax.lax.dynamic_slice_in_dim(seg, start, tq, axis=1)
                [:, :, None] == seg[:, None, :])  # [S, tq, N]
        ok = ok[None] & same
        s = jnp.where(ok[:, None, None], s, _MASKED)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("shgqk,shkd->shgqd", p.astype(v.dtype), v,
                          preferred_element_type=jnp.float32).astype(q.dtype)

    out = jax.lax.map(rows, jnp.arange(0, n, tq))  # [n/tq, S, Hkv, G, tq, D]
    out = jnp.moveaxis(out, 0, 3)
    return checkpoint_name(out.reshape(q.shape), KEPT)


@functools.lru_cache(maxsize=8)
def _splash_kernel(mask: StaticMask, group: int):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as mask_lib,
    )

    n = mask.n
    tq, tk = _tile(n, TILE_Q), _tile(n, TILE_KV)
    one = mask_lib.NumpyMask(mask.dense())
    sizes = splash.BlockSizes(
        block_q=tq, block_kv=tk, block_kv_compute=tk,
        block_q_dkv=tq, block_kv_dkv=tk, block_kv_dkv_compute=tk,
        block_q_dq=tq, block_kv_dq=tk)
    # the kernel keeps its mask tables as arrays: made under a trace they
    # would be that trace's tracers, and the kernel outlives it here
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            mask_lib.MultiHeadMask([one] * group), block_sizes=sizes,
            residual_checkpoint_name=KEPT)


def _splash(q, k, v, segment_ids, mask: StaticMask):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
    )

    kernel = _splash_kernel(mask, q.shape[2])

    def one_kv_head(qg, kh, vh, seg):
        return kernel(qg, kh, vh,
                      segment_ids=splash.SegmentIds(q=seg, kv=seg))

    over_heads = jax.vmap(one_kv_head, in_axes=(0, 0, 0, None))
    return jax.vmap(over_heads)(q, k, v, segment_ids)


def masked_attention(q, k, v, segment_ids, mask: StaticMask, *,
                     impl: str = "auto"):
    """``q [S, Hq, N, D]`` (already scaled), ``k, v [S, Hkv, N, D]``,
    ``segment_ids [S, N]`` int32 -> ``[S, Hq, N, D]``. ``impl``: ``splash``
    (the TPU kernel), ``blocked`` (plain ``jnp``) or ``auto`` (the kernel
    where the default backend is a TPU)."""
    s, hq, n, d = q.shape
    hkv = k.shape[1]
    if n != mask.n:
        raise ValueError(f"a mask over {mask.n} positions, {n} given")
    if impl == "auto":
        impl = "splash" if jax.default_backend() == "tpu" else "blocked"
    qg = q.reshape(s, hkv, hq // hkv, n, d)
    if impl == "splash":
        out = _splash(qg, k, v, segment_ids, mask)
    elif impl == "blocked":
        out = _blocked(qg, k, v, segment_ids, mask.dense())
    else:
        raise ValueError(f"no attention implementation {impl!r}")
    return out.reshape(s, hq, n, d)
