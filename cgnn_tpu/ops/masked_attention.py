"""Softmax attention under a static mask over positions and, under packing,
within documents: the one op behind every masked attention of this repo.

The mask over positions is given and static (``StaticMask``: the
block-diffusion mask over a doubled sequence, ``ops/bd_attention.py``;
causal; causal within a window); the documents are data (``segment_ids``): a
query sees a key only where the mask shows it and both are of one document.
On the TPU the op is the splash-attention kernel of
``jax.experimental.pallas`` given that mask: it visits the live tiles only,
those that the mask leaves (``mask_tiles``) less those that the call's
documents hide (``dead_tiles``: the kernel's tables of the static mask are
refined by ``segment_ids`` before every call), and never writes a ``[heads,
N, N]`` score array. Elsewhere it is the same arithmetic in plain ``jnp``, a
block of queries at a time.

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


def _shown_tiles(mask: StaticMask) -> np.ndarray:
    """``[q tiles, kv tiles]`` bool: the tile holds a pair the mask shows."""
    n = mask.n
    tq, tk = _tile(n, TILE_Q), _tile(n, TILE_KV)
    return mask.dense().reshape(n // tq, tq, n // tk, tk).any(axis=(1, 3))


def mask_tiles(mask: StaticMask) -> tuple[int, int]:
    """(live, grid): tiles of the ``n x n`` grid that hold a visible pair,
    and all of them, a head and a sequence, documents aside."""
    live = _shown_tiles(mask)
    return int(live.sum()), int(live.size)


def dead_tiles(segment_ids):
    """``segment_ids [..., N]`` int -> ``[..., N / TILE_Q, N / TILE_KV]``
    bool: the tiles in which no query is of a key's document, by the range
    of the documents a tile holds: dead where the queries' ``[min, max]`` and
    the keys' do not meet. A pair of one document puts that document in both
    ranges, so a tile that holds a visible pair is never called dead,
    whatever the ids. The test errs the other way only: it calls a tile live
    where the ranges meet and no document is shared, which ids that are
    non-decreasing along the sequence (``TokenBatch.segment_ids``), or two
    such halves that end on tiles' edges (``ops/bd_attention.py``), never
    do; and it knows nothing of the static mask, which may hide a live
    tile's every pair of one document (it does on none of the cells' pools:
    tests/test_masked_attention.py)."""
    *lead, n = segment_ids.shape
    by_q = segment_ids.reshape(*lead, n // _tile(n, TILE_Q), -1)
    by_kv = segment_ids.reshape(*lead, n // _tile(n, TILE_KV), -1)
    q_lo, q_hi = by_q.min(axis=-1), by_q.max(axis=-1)
    kv_lo, kv_hi = by_kv.min(axis=-1), by_kv.max(axis=-1)
    return ((q_hi[..., :, None] < kv_lo[..., None, :])
            | (kv_hi[..., None, :] < q_lo[..., :, None]))


def live_tiles(mask: StaticMask, segment_ids):
    """``segment_ids [..., N]`` -> ``[...]`` int32: the tiles a head visits
    of each sequence, ``mask_tiles``' live ones less ``dead_tiles``."""
    return (_shown_tiles(mask) & ~dead_tiles(segment_ids)).sum(
        axis=(-2, -1), dtype=jnp.int32)


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


def _refined(kernel, dead):
    """``kernel`` with the tiles ``dead [q tiles, kv tiles]`` taken off its
    three tables. The library shrinks a table to the mask's live steps:
    forward and ``dq`` are ``[1, q tile, j-th step]`` with ``data_next`` the
    step's key tile, ``dkv`` is ``[1, i-th step, kv tile]`` with
    ``data_next`` its query tile. A step whose tile is dead gets
    ``block_mask`` 0 (skip), and every step the kernel skips, the mask's own
    padding too, gets for ``data_next`` the tile of the next live step of
    its row of steps (past the last, of the last): what that step fetches
    anyway, so that a skipped step fetches nothing of its own. A live step
    and everything else stay as built."""
    def refine(info, by_kv: bool):
        axis = 1 if by_kv else 2  # the steps of one row of the walk
        there = info.data_next.astype(jnp.int32)
        if by_kv:
            hidden = dead[there, jnp.arange(dead.shape[1])]
        else:
            hidden = dead[jnp.arange(dead.shape[0])[:, None], there]
        # a weak 0: the table keeps its dtype (int8 as the library built it)
        block_mask = jnp.where(hidden, 0, info.block_mask)
        live = block_mask > 0
        steps = jax.lax.broadcasted_iota(jnp.int32, there.shape, axis)
        after = jax.lax.cummin(jnp.where(live, steps, there.shape[axis]),
                               axis=axis, reverse=True)
        before = jax.lax.cummax(jnp.where(live, steps, 0), axis=axis)
        source = jnp.where(after < there.shape[axis], after, before)
        return info._replace(
            block_mask=block_mask,
            data_next=jnp.take_along_axis(info.data_next, source, axis=axis))

    return type(kernel)(
        refine(kernel.fwd_mask_info, False),
        refine(kernel.dq_mask_info, False),
        refine(kernel.dkv_mask_info, True), **kernel.kwargs)


def _splash(q, k, v, segment_ids, mask: StaticMask):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash,
    )

    static = _splash_kernel(mask, q.shape[2])

    def one_sequence(qs, ks, vs, seg):
        kernel = _refined(static, dead_tiles(seg))
        ids = splash.SegmentIds(q=seg, kv=seg)
        return jax.vmap(lambda qg, kh, vh: kernel(qg, kh, vh,
                                                  segment_ids=ids))(qs, ks, vs)

    return jax.vmap(one_sequence)(q, k, v, segment_ids)


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
