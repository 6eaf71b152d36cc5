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
(``segment_ids``). About a quarter of the ``2L x 2L`` grid is live. The op
itself is ``ops/masked_attention.py``'s, given this mask: the splash kernel
over the live tiles (``bd_tiles``, less those that a call's documents hide:
``bd_live_tiles``) on the TPU, plain ``jnp`` elsewhere.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from cgnn_tpu.ops.masked_attention import (
    StaticMask, live_tiles, mask_tiles, masked_attention,
)


def bd_mask(seq_len: int, block: int) -> np.ndarray:
    """``[2L, 2L]`` bool: query row sees key column (documents aside)."""
    return StaticMask("bd", 2 * seq_len, block=block).dense()


def bd_tiles(seq_len: int, block: int) -> tuple[int, int]:
    """(live, grid): tiles of the ``2L x 2L`` grid that hold a visible pair,
    and all of them, a head and a sequence."""
    return mask_tiles(StaticMask("bd", 2 * seq_len, block=block))


def _doubled(segment_ids):
    """The documents of ``x_t (+) x_0``: those of a sequence, twice."""
    return jnp.concatenate([segment_ids, segment_ids], axis=-1)


def bd_live_tiles(segment_ids, block: int):
    """``segment_ids [S, L]`` -> ``[S]`` int32: the tiles a head visits of
    each doubled sequence, ``bd_tiles``' live ones less those that its
    documents hide."""
    seg = _doubled(segment_ids)
    return live_tiles(StaticMask("bd", seg.shape[-1], block=block), seg)


def bd_attention(q, k, v, segment_ids, *, block: int, impl: str = "auto"):
    """``q [S, Hq, 2L, D]`` (already scaled), ``k, v [S, Hkv, 2L, D]``,
    ``segment_ids [S, L]`` int32 -> ``[S, Hq, 2L, D]``; ``impl`` as
    ``masked_attention``'s."""
    return masked_attention(q, k, v, _doubled(segment_ids),
                            StaticMask("bd", q.shape[2], block=block),
                            impl=impl)
