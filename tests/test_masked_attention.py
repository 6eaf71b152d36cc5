"""ops/masked_attention.py's tables, with no kernel in them: the tiles that
a call's documents hide (``dead_tiles``) against the dense mask, on the
pools the three decoder cells train on and at small sizes."""

import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from cgnn_tpu.data import tokens
from cgnn_tpu.ops import masked_attention as op
from cgnn_tpu.ops.masked_attention import (
    StaticMask, dead_tiles, live_tiles, mask_tiles,
)

CONFIGS = os.path.join(os.path.dirname(__file__), "..", "benchmark",
                       "configs")


def _documents(name: str) -> np.ndarray:
    """``segment_ids [S, L]`` of the pool that configuration ``name``'s
    cell trains on (``benchmark/kinds/*_train.py`` make it so)."""
    with open(os.path.join(CONFIGS, name + ".json")) as f:
        cfg = json.load(f)
    data, docs = cfg["data"], cfg["data"]["documents"]
    block = cfg.get("diffusion", {}).get("block_length")
    return tokens.make_pool(
        int(data["n"]), int(data["sequence_length"]),
        vocab_size=cfg["vocab_size"], seed=int(data["pool_seed"]),
        doc_median=docs["median"], doc_sigma=docs["sigma"],
        doc_min=docs["min"], doc_max=docs["max"],
        **({"block": block} if block else {"kind": "causal"})).segment_ids


def _dense_live(mask: StaticMask, seg: np.ndarray) -> np.ndarray:
    """``[q tiles, kv tiles]`` bool by the dense mask: the tile holds a pair
    that the mask shows and that is of one document."""
    n = mask.n
    tq, tk = op._tile(n, op.TILE_Q), op._tile(n, op.TILE_KV)
    shown = mask.dense() & (seg[:, None] == seg[None, :])
    return shown.reshape(n // tq, tq, n // tk, tk).any(axis=(1, 3))


@pytest.mark.parametrize("name,kind,window,static,live_pct", [
    ("sdar-30b-a3b-ep8", "bd", 0, 80, 24.53),
    ("trinity-mini-ep16", "causal", 0, 136, 45.02),
    ("trinity-mini-ep16", "causal", 2048, 70, 25.90),
    ("lfm2-24b-a2b-ep8", "causal", 0, 136, 44.38),
])
def test_the_cells_pools_lose_the_tiles_their_documents_hide(
        name, kind, window, static, live_pct):
    """ISSUE 51's table: on each cell's own pool the range test gives the
    dense mask's tiles, sequence by sequence, and the pool's live share of
    the grid is the one the cell's counter is expected to read."""
    seg = _documents(name)
    if kind == "bd":
        seg = np.concatenate([seg, seg], axis=1)
        mask = StaticMask("bd", seg.shape[1], block=4)
    else:
        mask = StaticMask("causal", seg.shape[1], window=window)
    assert mask_tiles(mask) == (static, 256)
    shown = _dense_live(mask, np.zeros(mask.n, np.int32))
    counts = []
    for row in seg:
        want = _dense_live(mask, row)
        got = shown & ~np.asarray(dead_tiles(jnp.asarray(row)))
        np.testing.assert_array_equal(got, want)
        counts.append(int(want.sum()))
    np.testing.assert_array_equal(live_tiles(mask, jnp.asarray(seg)), counts)
    assert min(counts) < static and max(counts) == static
    assert round(100.0 * sum(counts) / (256 * len(counts)), 2) == live_pct


def _small(seed: int, n: int, block: int = 1) -> np.ndarray:
    return tokens.make_pool(
        6, n, vocab_size=64, block=block, seed=seed, doc_median=n / 4,
        doc_min=8, doc_max=n, kind="causal").segment_ids


@pytest.mark.parametrize("kind,kw", [
    ("causal", {}), ("causal", {"window": 96}), ("bd", {"block": 4})])
@pytest.mark.parametrize("seed", [0, 1])
def test_small_pools_lose_the_tiles_their_documents_hide(
        monkeypatch, kind, kw, seed):
    """Tiles of 64 over 512 positions, many documents a sequence and
    boundaries off the tiles' edges: exact, as at the cells' sizes."""
    monkeypatch.setattr(op, "TILE_Q", 64)
    monkeypatch.setattr(op, "TILE_KV", 64)
    seg = _small(seed, 256, 4) if kind == "bd" else _small(seed, 512)
    if kind == "bd":
        seg = np.concatenate([seg, seg], axis=1)
    mask = StaticMask(kind, 512, **kw)
    shown = _dense_live(mask, np.zeros(512, np.int32))
    fewer = 0
    for row in seg:
        want = _dense_live(mask, row)
        np.testing.assert_array_equal(
            shown & ~np.asarray(dead_tiles(jnp.asarray(row))), want)
        fewer += int(want.sum()) < int(shown.sum())
    assert fewer >= 3
    # one document: the static mask's own tiles
    assert not np.asarray(dead_tiles(jnp.zeros(512, jnp.int32))).any()


@pytest.mark.parametrize("seed", range(4))
def test_no_tile_with_a_visible_pair_is_called_dead(monkeypatch, seed):
    """Whatever the ids (here unsorted, which no batch of this repo is), the
    test errs one way only: a tile it calls dead holds no pair of one
    document. With ids in disorder it does call dead tiles live."""
    monkeypatch.setattr(op, "TILE_Q", 32)
    monkeypatch.setattr(op, "TILE_KV", 64)
    rng = np.random.default_rng(seed)
    seg = np.repeat(rng.integers(0, 8, 8), 32).astype(np.int32)
    dead = np.asarray(dead_tiles(jnp.asarray(seg)))
    assert dead.shape == (8, 4) and dead.any()
    same = (seg[:, None] == seg[None, :]).reshape(8, 32, 4, 64).any(
        axis=(1, 3))
    assert not (dead & same).any()
    # keys of documents 0 and 4, queries of document 2: the ranges meet
    seg = np.repeat(np.asarray([0, 4, 2, 2, 5, 5, 5, 5], np.int32), 32)
    dead = np.asarray(dead_tiles(jnp.asarray(seg)))
    assert not dead[2, 0] and dead[4, 0] and not dead[4, 2]
