"""Packed token sequences for language-model training.

A ``TokenBatch`` is what one step reads: ``S`` sequences of ``L`` tokens, the
document each position belongs to (``segment_ids [S, L]``) and the weight of
each position in the loss (``loss_weight [S, L]``). Two kinds (``make_pool``):

- ``blockdiff``: each sequence staged as its noised copy followed by the
  clean one (``tokens [S, 2L]``); ``loss_weight`` is ``1 / t`` of its block
  where the token was replaced by ``[MASK]``, else 0. The noise is drawn
  once with the pool, as an offline pipeline pre-noises a shard: a batch is
  data, and the step draws nothing.
- ``causal``: the sequence once (``tokens [S, L]``); position ``i`` predicts
  token ``i + 1``, and ``loss_weight`` is 1 but where the next token is
  another document's or there is none (the last of a sequence), where it
  is 0.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class TokenBatch(NamedTuple):
    tokens: np.ndarray  # int32: [S, 2L] x_t then x_0, or [S, L] (causal)
    segment_ids: np.ndarray  # [S, L] int32, non-decreasing along a row
    loss_weight: np.ndarray  # [S, L] float32


def token_shape_key(batch: TokenBatch) -> tuple:
    """The compiled shape of a token batch (``data.graph.batch_shape_key``)."""
    return ("tokens", tuple(np.shape(batch.tokens)))


def document_lengths(rng, total: int, *, median: float, sigma: float,
                     lo: int, hi: int, block: int) -> list[int]:
    """Lognormal document lengths (clipped to ``lo..hi``, rounded down to
    whole blocks) packed to exactly ``total``: the last document is cut."""
    out, left = [], total
    while left > 0:
        n = int(np.clip(rng.lognormal(np.log(median), sigma), lo, hi))
        n = max(block, n - n % block)
        n = min(n, left)
        out.append(n)
        left -= n
    return out


def make_pool(n_sequences: int, seq_len: int, *, vocab_size: int,
              block: int = 1, seed: int, doc_median: float = 2048.0,
              doc_sigma: float = 1.0, doc_min: int = 64,
              doc_max: int = 4096, kind: str = "blockdiff") -> TokenBatch:
    """``n_sequences`` packed sequences as one ``TokenBatch`` (``S =
    n_sequences``), everything drawn from ``seed``:

    - documents of lognormal length with boundaries on whole blocks, packed
      to exactly ``seq_len``: no padding;
    - ``kind`` ``blockdiff``: ids uniform over ``0 .. vocab_size - 2``
      (``vocab_size - 1`` is ``[MASK]``); one ``t ~ U(0, 1]`` a block; each
      token of the block is replaced by ``[MASK]`` with probability ``t``
      (the linear schedule) and then weighs ``1 / t`` in the loss;
    - ``kind`` ``causal``: ids uniform over all ``vocab_size``; every
      position whose next token is of its own document weighs 1.
    """
    if kind not in ("blockdiff", "causal"):
        raise ValueError(f"no kind of token batch {kind!r}")
    if seq_len % block:
        raise ValueError(f"sequence length {seq_len} is no whole number of "
                         f"blocks of {block}")
    rng = np.random.default_rng(seed)
    mask_id = vocab_size - 1
    clean = rng.integers(0, vocab_size if kind == "causal" else mask_id,
                         size=(n_sequences, seq_len),
                         dtype=np.int64).astype(np.int32)
    segment_ids = np.zeros((n_sequences, seq_len), np.int32)
    for row in segment_ids:
        lengths = document_lengths(
            rng, seq_len, median=doc_median, sigma=doc_sigma,
            lo=min(doc_min, seq_len), hi=min(doc_max, seq_len), block=block)
        row[:] = np.repeat(np.arange(len(lengths), dtype=np.int32), lengths)
    if kind == "causal":
        follows = np.zeros((n_sequences, seq_len), np.float32)
        follows[:, :-1] = segment_ids[:, 1:] == segment_ids[:, :-1]
        return TokenBatch(tokens=clean, segment_ids=segment_ids,
                          loss_weight=follows)
    # U(0, 1]: 1 - U[0, 1)
    t = 1.0 - rng.random((n_sequences, seq_len // block))
    t_tok = np.repeat(t, block, axis=1)
    masked = rng.random((n_sequences, seq_len)) < t_tok
    noised = np.where(masked, mask_id, clean).astype(np.int32)
    return TokenBatch(
        tokens=np.concatenate([noised, clean], axis=1),
        segment_ids=segment_ids,
        loss_weight=np.where(masked, 1.0 / t_tok, 0.0).astype(np.float32),
    )


def split_batches(pool: TokenBatch, per_step: int) -> list[TokenBatch]:
    """The pool as whole steps of ``per_step`` sequences, in pool order."""
    n = pool.tokens.shape[0]
    if n % per_step:
        raise ValueError(f"{n} sequences are no whole number of steps of "
                         f"{per_step}")
    return [TokenBatch(*(x[i:i + per_step] for x in pool))
            for i in range(0, n, per_step)]
