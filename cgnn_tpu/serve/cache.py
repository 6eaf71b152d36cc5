"""LRU result cache keyed by a structure fingerprint.

Production graph-property traffic is heavily repeated (the same
trending structures queried by many users), and the forward pass is
deterministic given (params, structure) — so identical queries within
one param version can be answered from memory. The fingerprint hashes
the FEATURIZED arrays (atom features, edge features, connectivity), not
object identity, so equal structures hit regardless of which client
sent them.

Precision tiers (serve/quantize.py) are part of the key, not the value:
the server prefixes non-f32 fingerprints with the tier
(``"int8:<sha>"``), because a cached row is determined by (params,
structure, PROGRAM) — an f32 answer served to an int8 request would
silently undo the precision the client asked for (and vice versa), and
the tier-isolation test pins exactly that (tests/test_serve.py
TestPrecisionServing).

Staleness across hot param swaps is handled in TWO layers, both load-
bearing (server.py): entries are stored version-tagged, ``(row,
param_version)``, and REVALIDATED against the live version at hit time
— this is the correctness guarantee, because a micro-batch in flight
across a swap writes its old-version rows AFTER the swap fires; the
swap's ``cache.clear()`` (reload.py on_swap) is only bulk eviction so
dead entries stop occupying LRU slots. Do not remove the hit-time
version check in favor of the clear — that reintroduces the in-flight-
writer race (pinned by tests/test_serve.py hot-reload atomicity).
"""

from __future__ import annotations

import collections
import hashlib
import threading

import numpy as np

from cgnn_tpu.data.graph import CrystalGraph


def structure_fingerprint(graph: CrystalGraph) -> str:
    """Content hash of a featurized structure (layout-qualified).

    blake2b, not sha1: faster in software (no SHA-NI dependency — on
    accelerator hosts whose CPUs lack it, sha1 falls off a cliff) and
    this is an in-memory cache key with no persisted state, so the hash
    can change between releases without a migration. digest_size=20
    keeps the hex length sha1-compatible for logs and tier prefixes.
    """
    h = hashlib.blake2b(digest_size=20)
    for arr in (graph.atom_fea, graph.edge_fea, graph.centers,
                graph.neighbors):
        a = np.ascontiguousarray(arr)
        h.update(str(a.shape).encode())
        h.update(str(a.dtype).encode())
        h.update(a.tobytes())
    return h.hexdigest()


class ResultCache:
    """Thread-safe bounded LRU: fingerprint -> prediction row."""

    def __init__(self, capacity: int = 1024):
        if capacity < 1:
            raise ValueError(f"cache capacity must be >= 1, got {capacity}")
        self.capacity = capacity
        self._data: collections.OrderedDict = collections.OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def get(self, key: str):
        with self._lock:
            if key in self._data:
                self._data.move_to_end(key)
                self.hits += 1
                return self._data[key]
            self.misses += 1
            return None

    def put(self, key: str, value) -> None:
        with self._lock:
            self._data[key] = value
            self._data.move_to_end(key)
            while len(self._data) > self.capacity:
                self._data.popitem(last=False)

    def clear(self) -> None:
        with self._lock:
            self._data.clear()

    def __len__(self) -> int:
        with self._lock:
            return len(self._data)

    def snapshot(self) -> tuple:
        """Consistent ``(hits, misses, size, capacity)`` under the lock.

        ``hits``/``misses`` are mutated under ``_lock``; scraping the
        bare attributes from another thread could pair a pre-increment
        ``hits`` with a post-increment ``misses`` (a hit ratio that
        never existed). All metrics/stats readers go through here.
        """
        with self._lock:
            return (self.hits, self.misses, len(self._data), self.capacity)

    def stats(self) -> dict:
        hits, misses, size, capacity = self.snapshot()
        return {
            "size": size,
            "capacity": capacity,
            "hits": hits,
            "misses": misses,
        }
