"""Block-diffusion attention over the doubled sequence (BD3-LM,
arXiv:2503.09573; SDAR, arXiv:2510.06303).

A training sequence is ``x_t (+) x_0``: ``L`` noised positions followed by
the ``L`` clean ones, both halves at positions ``0..L-1``. With ``b(i) =
i // block`` the block of a position within its half:

- a noised query sees the noised keys of its own block (both directions) and
  the clean keys of earlier blocks (``b(j) < b(i)``);
- a clean query sees the clean keys of its own and earlier blocks
  (``b(j) <= b(i)``) and never a noised key;
- under packing, a query sees only keys of its own document.

The mask over positions is static (``bd_mask``); the documents are data
(``segment_ids``). About a quarter of the ``2L x 2L`` grid is live. On the
TPU the op is the splash-attention kernel of ``jax.experimental.pallas``
given that mask: it visits the live tiles only (``bd_tiles``) and never
writes a ``[heads, 2L, 2L]`` score array. Elsewhere it is the same
arithmetic in plain ``jnp``, a block of queries at a time.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

# one tile of the kernel's grid, queries x keys (forward and backward); the
# counters of ``bd_tiles`` are in these tiles whatever runs the op
TILE_Q = 512
TILE_KV = 512
_MASKED = -0.7 * float(np.finfo(np.float32).max)


@functools.lru_cache(maxsize=8)
def bd_mask(seq_len: int, block: int) -> np.ndarray:
    """``[2L, 2L]`` bool: query row sees key column (documents aside)."""
    if seq_len % block:
        raise ValueError(f"a sequence of {seq_len} is no whole number of "
                         f"blocks of {block}")
    b = np.arange(seq_len) // block
    own = b[:, None] == b[None, :]
    earlier = b[None, :] < b[:, None]
    top = np.concatenate([own, earlier], axis=1)  # noised queries
    bottom = np.concatenate([np.zeros_like(own), own | earlier], axis=1)
    return np.concatenate([top, bottom], axis=0)


def _tile(n: int, tile: int) -> int:
    return tile if n % tile == 0 else n


def bd_tiles(seq_len: int, block: int) -> tuple[int, int]:
    """(live, grid): tiles of the ``2L x 2L`` grid that hold a visible pair,
    and all of them, a head and a sequence."""
    mask = bd_mask(seq_len, block)
    n = 2 * seq_len
    tq, tk = _tile(n, TILE_Q), _tile(n, TILE_KV)
    live = mask.reshape(n // tq, tq, n // tk, tk).any(axis=(1, 3))
    return int(live.sum()), int(live.size)


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
    return out.reshape(q.shape)


@functools.lru_cache(maxsize=8)
def _splash_kernel(seq_len: int, block: int, group: int):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
        splash_attention_mask as mask_lib,
    )

    n = 2 * seq_len
    tq, tk = _tile(n, TILE_Q), _tile(n, TILE_KV)
    one = mask_lib.NumpyMask(bd_mask(seq_len, block))
    sizes = splash.BlockSizes(
        block_q=tq, block_kv=tk, block_kv_compute=tk,
        block_q_dkv=tq, block_kv_dkv=tk, block_kv_dkv_compute=tk,
        block_q_dq=tq, block_kv_dq=tk)
    # the kernel keeps its mask tables as arrays: made under a trace they
    # would be that trace's tracers, and the kernel outlives it here
    with jax.ensure_compile_time_eval():
        return splash.make_splash_mqa_single_device(
            mask_lib.MultiHeadMask([one] * group), block_sizes=sizes)


def _splash(q, k, v, segment_ids, seq_len: int, block: int):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
    )

    kernel = _splash_kernel(seq_len, block, q.shape[2])

    def one_kv_head(qg, kh, vh, seg):
        return kernel(qg, kh, vh,
                      segment_ids=splash.SegmentIds(q=seg, kv=seg))

    over_heads = jax.vmap(one_kv_head, in_axes=(0, 0, 0, None))
    return jax.vmap(over_heads)(q, k, v, segment_ids)


def bd_attention(q, k, v, segment_ids, *, block: int, impl: str = "auto"):
    """``q [S, Hq, 2L, D]`` (already scaled), ``k, v [S, Hkv, 2L, D]``,
    ``segment_ids [S, L]`` int32 -> ``[S, Hq, 2L, D]``. ``impl``: ``splash``
    (the TPU kernel), ``blocked`` (plain ``jnp``) or ``auto`` (the kernel
    where the default backend is a TPU)."""
    s, hq, n, d = q.shape
    hkv = k.shape[1]
    if impl == "auto":
        impl = "splash" if jax.default_backend() == "tpu" else "blocked"
    seg = jnp.concatenate([segment_ids, segment_ids], axis=-1)
    qg = q.reshape(s, hkv, hq // hkv, n, d)
    if impl == "splash":
        out = _splash(qg, k, v, seg, n // 2, block)
    elif impl == "blocked":
        out = _blocked(qg, k, v, seg, bd_mask(n // 2, block))
    else:
        raise ValueError(f"no attention implementation {impl!r}")
    return out.reshape(s, hq, n, d)
