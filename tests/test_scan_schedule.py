"""Scan-driver schedule statistics (VERDICT r4 weak #2 -> r5 item 4).

The r4 forensic (PERF.md 6e) found that chunk GRANULARITY — long
same-shape step runs from coarse chunks — cost ~35% multi-bucket val MAE
at MP-146k; chunk_steps=2 with randomized lengths and weighted-random
group picks recovers the per-step loop's convergence. Nothing cheaper
than a 146k re-run guarded that property. These tests pin it host-side
in milliseconds: they extract the driver's realized step sequence (the
scan bodies are stubbed; only scheduling runs) at the group sizes of the
at-scale regime (~85 batches/shape, where the original regression was
visible) and assert the same-shape run-length distribution stays in the
chunk-2 family. A scheduler change reintroducing chunk-8-style runs
(measured here: mean 5.7, p95 20 vs chunk-2's mean 2.8, p95 8) fails
immediately.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
from cgnn_tpu.data.graph import pack_graphs
from cgnn_tpu.train.loop import ScanEpochDriver

CFG = FeaturizeConfig(radius=6.0, max_num_nbr=12)
EPOCHS = 10


def _pack(sub, nc):
    return pack_graphs(sub, nc, nc * 12, len(sub), dense_m=12)


@pytest.fixture(scope="module")
def batches():
    """Three shape groups at at-scale group sizes (85/80/90 batches):
    replicated tiny packed batches — the scheduler sees only shapes."""
    graphs = load_synthetic_mp(48, CFG, seed=0)
    b0 = _pack(graphs[:16], 600)
    b1 = _pack(graphs[16:32], 800)
    b2 = _pack(graphs[32:], 1000)
    return [b0] * 85 + [b1] * 80 + [b2] * 90


def realized_schedule(batches, chunk_steps, epochs=EPOCHS, seed=0):
    """[(group_key, chunk_len)] over ``epochs`` driven epochs, with the
    jitted scan bodies stubbed out (host-side scheduling only)."""
    drv = ScanEpochDriver(
        lambda s, b: (s, {}), lambda s, b: {}, batches, [],
        np.random.default_rng(seed), chunk_steps=chunk_steps,
    )
    seq: list = []

    def fake_window_fn(cache, key, body, train):
        # the driver's cache key is (shape_key, chunk_len) — record the
        # SHAPE key and the realized length separately, else runs of one
        # shape split wherever the drawn length changes
        shape_key, length = key

        def fn(state, stacked, perm_all, cursor):
            # the chunk's rows lie inside the group's staged perm
            assert int(cursor) + length <= int(np.shape(perm_all)[0])
            seq.append((shape_key, length))
            return state, {}, cursor + length

        return fn

    drv._window_fn = fake_window_fn
    epoch_bounds = []
    for _ in range(epochs):
        drv._drive(None, drv._train_groups, {}, None, train=True,
                   first=False)
        epoch_bounds.append(len(seq))
    return seq, epoch_bounds


def run_lengths(seq):
    steps = [k for k, ln in seq for _ in range(ln)]
    runs, cur, n = [], None, 0
    for s in steps:
        if s == cur:
            n += 1
        else:
            if n:
                runs.append(n)
            cur, n = s, 1
    runs.append(n)
    return np.array(runs)


def test_chunk2_run_length_distribution(batches):
    """The property whose violation cost 35% val MAE: with the default
    chunk_steps=2, same-shape runs must track the per-step weighted
    interleave (measured family: mean ~2.8, p95 8), far from the chunk-8
    family (mean ~5.7, p95 20)."""
    seq, _ = realized_schedule(batches, chunk_steps=2)
    runs = run_lengths(seq)
    assert runs.mean() <= 3.5, f"mean same-shape run {runs.mean():.2f}"
    assert np.percentile(runs, 95) <= 10, f"p95 run {np.percentile(runs, 95)}"
    assert runs.max() <= 24, f"max run {runs.max()}"


def test_chunk_lengths_bounded_for_compile_keys(batches):
    """Dispatch lengths must stay in the bounded set {1..c/2, c, 2c} so
    distinct compiled scan programs stay O(1) per group."""
    for c in (2, 4):
        seq, _ = realized_schedule(batches, chunk_steps=c)
        lengths = {ln for _, ln in seq}
        assert max(lengths) <= 2 * c
        allowed = set(range(1, max(2, c // 2 + 1))) | {c, 2 * c}
        assert lengths <= allowed, f"c={c}: unexpected lengths {lengths - allowed}"


def test_every_batch_scheduled_once_per_epoch(batches):
    """Coverage invariant: each epoch dispatches each group's every batch
    exactly once (chunks partition the permutation)."""
    seq, bounds = realized_schedule(batches, chunk_steps=2, epochs=4)
    sizes = {85, 80, 90}
    start = 0
    for end in bounds:
        per_group: dict = {}
        for key, ln in seq[start:end]:
            per_group[key] = per_group.get(key, 0) + ln
        assert sorted(per_group.values()) == sorted(sizes)
        start = end


def test_coarse_chunks_would_fail_the_guard(batches):
    """Self-check that the thresholds bite: chunk-8 scheduling violates
    the distribution test (this is the regression the guard exists for)."""
    seq, _ = realized_schedule(batches, chunk_steps=8)
    runs = run_lengths(seq)
    assert runs.mean() > 3.5 and np.percentile(runs, 95) > 10


def test_chunk_steps_flag_reaches_driver(batches):
    drv = ScanEpochDriver(lambda s, b: (s, {}), lambda s, b: {},
                          batches[:3], [], np.random.default_rng(0),
                          chunk_steps=4)
    assert drv.chunk_steps == 4
    with pytest.raises(ValueError):
        ScanEpochDriver(lambda s, b: (s, {}), lambda s, b: {}, batches[:3],
                        [], np.random.default_rng(0), chunk_steps=0)


# ---- the realised sequence against the host's draw (PR 40) ---------------
#
# An epoch's perms reach the device as one array a bucket shape and every
# chunk's program finds its rows there through a cursor the chunks of the
# group hand on. What an epoch executes must still be exactly what
# ``_build_sched`` drew: these run the real programs over batches that carry
# their own number, with a step body that logs the number it was handed.

def _numbered(batches_by_shape):
    """[(batch, copies)] -> batches whose every target is their number."""
    out, number = [], 0
    for b, copies in batches_by_shape:
        for _ in range(copies):
            out.append(b.replace(
                targets=np.full_like(np.asarray(b.targets), number)))
            number += 1
    return out


def _expected(sched):
    """The (shape key, length, places in the group's stack) sequence that
    ``run_queues`` makes of a host schedule: the queues by the predrawn
    picks (round-robin where none were drawn), then the tail singles
    round-robin."""
    queues, tails, _steps, pick_order = sched

    def drain(entries, picks):
        qs = [(key, [np.asarray(ch).tolist() for ch in chunks])
              for key, _, chunks in entries]
        by_index, rr = list(qs), 0
        while qs:
            if picks:
                entry = by_index[picks.pop(0)]
            else:
                entry = qs[rr % len(qs)]
                rr += 1
            key, chunks = entry
            chunk = chunks.pop(0)
            yield key, len(chunk), chunk
            if not chunks:
                qs.remove(entry)

    return [*drain(queues, list(pick_order)), *drain(tails, [])]


def _numbers(groups, seq):
    """The numbers of the batches a sequence of ``_expected`` visits."""
    number = {key: np.asarray(stacked.targets)[:, 0, 0].astype(int)
              for key, stacked in groups.items()}
    return [int(number[key][i]) for key, _, places in seq for i in places]


def _logging_driver(batches, val, chunk_steps, seed=3):
    """A driver over real programs whose step body appends the number of
    the batch it was handed to a log in the carried state."""
    def train_body(state, batch):
        log, n = state
        return (log.at[n].set(batch.targets[0, 0].astype(jnp.int32)),
                n + 1), {"count": jnp.float32(1)}

    def eval_body(state, batch):
        return {"number": batch.targets[0, 0], "count": jnp.float32(1)}

    drv = ScanEpochDriver(train_body, eval_body, batches, val,
                          np.random.default_rng(seed),
                          chunk_steps=chunk_steps)
    drawn, dispatched = [], []
    build, window_fn = drv._build_sched, drv._window_fn

    def recording_build(groups, train, first):
        drawn.append(build(groups, train, first))
        return drawn[-1]

    def recording_window_fn(cache, key, body, train):
        fn = window_fn(cache, key, body, train)

        def dispatch(state, stacked, perm_all, cursor):
            at = int(cursor)
            dispatched.append(
                (key[0], key[1],
                 np.asarray(perm_all)[at:at + key[1]].tolist()))
            return fn(state, stacked, perm_all, cursor)

        dispatch.__name__ = fn.__name__
        return dispatch

    drv._build_sched, drv._window_fn = recording_build, recording_window_fn
    return drv, drawn, dispatched


@pytest.mark.parametrize("case", ["multi_bucket", "one_bucket", "first",
                                  "chunk_steps_4"])
def test_an_epoch_executes_the_schedule_the_host_drew(case):
    """Three train epochs of one rng: the programs dispatched, their
    lengths and the batches every step reads (logged on the device by the
    step body itself) are the host's draw, chunk for chunk, the mixed tail
    singles included; ``first=True`` visits every bucket in pack order."""
    graphs = load_synthetic_mp(24, CFG, seed=0)
    shapes = [(_pack(graphs[:8], 400), 9), (_pack(graphs[8:16], 500), 5),
              (_pack(graphs[16:], 600), 13)]
    if case == "one_bucket":
        shapes = shapes[2:]
    batches = _numbered(shapes)
    drv, drawn, dispatched = _logging_driver(
        batches, [], 4 if case == "chunk_steps_4" else 2)
    for epoch in range(3):
        first = case == "first" and epoch == 0
        state = (jnp.full(len(batches), -1, jnp.int32), jnp.int32(0))
        dispatched.clear()
        state, sums, steps = drv._drive(
            state, drv._train_groups, drv._train_scans, drv._train_body,
            train=True, first=first)
        # the schedule this epoch used: built on the miss at the first
        # epoch's head, prebuilt at the end of the epoch before otherwise
        sched = drawn[0] if epoch == 0 else drawn[epoch]
        want = _expected(sched)
        assert dispatched == want
        log, n = jax.device_get(state)
        assert int(n) == steps == len(batches)
        assert float(sums["count"]) == steps
        assert log.tolist() == _numbers(drv._train_groups, want)
        assert sorted(log.tolist()) == list(range(len(batches)))
        if len(shapes) > 1:
            tail = [ln for _, ln, _ in want][-len(sched[1]):]
            assert sched[1] and set(tail) == {1}  # the mixed tail singles
        if first:
            # arange perms, the buckets in turn
            for key in drv._train_groups:
                mine = [i for k, _, places in want if k == key
                        for i in places]
                assert mine == sorted(mine)
    assert len(drawn) == 4  # one on the miss, then one prebuild an epoch


def test_eval_epochs_reuse_one_staged_schedule():
    """The eval schedule (arange perms, drawn once, staged once) serves
    every epoch: its staged zero cursor is never consumed, so the third
    epoch reads the same batches in the same order as the first."""
    graphs = load_synthetic_mp(16, CFG, seed=0)
    val = _numbered([(_pack(graphs[:8], 400), 5),
                     (_pack(graphs[8:], 500), 3)])
    drv, drawn, dispatched = _logging_driver(val[:2], val, 2)
    seen = []
    for _ in range(3):
        dispatched.clear()
        _, sums, steps = drv._drive(
            None, drv._val_groups, drv._eval_scans, drv._eval_body,
            train=False, first=True)
        assert steps == len(val) and float(sums["count"]) == steps
        assert float(sums["number"]) == sum(range(len(val)))
        seen.append(list(dispatched))
    assert len(drawn) == 1 and seen[0] == seen[1] == seen[2]
    assert seen[0] == _expected(drawn[0])
    for key in drv._val_groups:
        mine = [i for k, _, places in seen[0] if k == key for i in places]
        assert mine == list(range(len(mine))) and len(mine) in (5, 3)
