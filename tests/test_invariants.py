"""GraphBatch invariant layer (VERDICT r2 #8, SURVEY.md §5 sanitizers).

Every deliberate corruption below must fail LOUDLY under check_batch;
conftest enables the global flag so every iterator-produced batch in the
whole suite is validated as a side effect.
"""

import numpy as np
import pytest

from cgnn_tpu.data import invariants
from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic
from cgnn_tpu.data.graph import batch_iterator, capacities_for


@pytest.fixture(scope="module")
def dense_batch():
    graphs = load_synthetic(24, FeaturizeConfig(radius=5.0, max_num_nbr=8),
                            seed=9, max_atoms=6)
    nc, ec = capacities_for(graphs, 8, dense_m=8, snug=True)
    return next(batch_iterator(graphs, 8, nc, ec, dense_m=8, snug=True))


def test_clean_batches_validate(dense_batch):
    assert invariants.check_batch(dense_batch, dense_m=8) is dense_batch


@pytest.mark.parametrize(
    "corrupt,match",
    [
        (lambda b: b.replace(
            centers=np.flip(np.asarray(b.centers).copy())),
         "non-decreasing|ownership"),
        (lambda b: b.replace(
            neighbors=np.full_like(np.asarray(b.neighbors),
                                   b.node_capacity + 3)),
         "out of node-slot range"),
        (lambda b: b.replace(
            edge_mask=1.0 - np.asarray(b.edge_mask)),
         "padding|prefix|features"),
        (lambda b: b.replace(
            node_mask=np.concatenate(
                [[0.0], np.asarray(b.node_mask)[1:]])),
         "prefix|padding node"),
        (lambda b: b.replace(
            graph_mask=np.asarray(b.graph_mask) * 0.5),
         "outside"),
        (lambda b: b.replace(
            in_slots=np.zeros_like(np.asarray(b.in_slots))),
         "transpose|twice"),
        # the same complete mapping with every row's entries reversed:
        # real entries are no longer a prefix of their row
        (lambda b: b.replace(
            in_slots=np.asarray(b.in_slots).reshape(
                np.shape(b.in_mask))[:, ::-1].reshape(-1).copy(),
            in_mask=np.asarray(b.in_mask)[:, ::-1].copy()),
         "in-degree prefixes"),
    ],
)
def test_corruptions_fail_loudly(dense_batch, corrupt, match):
    with pytest.raises(invariants.BatchInvariantError, match=match):
        invariants.check_batch(corrupt(dense_batch), dense_m=8)


def test_dense_ownership_checked(dense_batch):
    c = np.asarray(dense_batch.centers).copy()
    c[10] = (10 // 8) + 1  # wrong owner, still sorted-ish
    with pytest.raises(invariants.BatchInvariantError):
        invariants.check_batch(dense_batch.replace(centers=c), dense_m=8)


def test_stacked_batch_rows_checked(dense_batch):
    """DP-stacked batches validate per device row, and a corrupted row is
    localized in the error (VERDICT r3 next-step #7)."""
    from cgnn_tpu.parallel.data_parallel import stack_batches

    stacked = stack_batches([dense_batch, dense_batch])
    assert invariants.check_any(stacked, train=True) is stacked
    bad_row = dense_batch.replace(
        centers=np.flip(np.asarray(dense_batch.centers).copy())
    )
    with pytest.raises(invariants.BatchInvariantError):
        invariants.check_any(stack_batches([dense_batch, bad_row]))


def test_empty_row_rejected_for_training(dense_batch):
    """empty_batch_like rows are eval-only; a training-stacked batch with
    one must fail loudly (the enforced never-train contract)."""
    from cgnn_tpu.parallel.data_parallel import (
        empty_batch_like,
        stack_batches,
    )

    stacked = stack_batches([dense_batch, empty_batch_like(dense_batch)])
    # eval accepts the padding row...
    assert invariants.check_any(stacked) is stacked
    # ...training does not
    with pytest.raises(invariants.BatchInvariantError, match="eval-only"):
        invariants.check_any(stacked, train=True)


def test_parallel_train_step_guards_empty_rows(dense_batch):
    """The jitted DP train step itself rejects a host-side stacked batch
    with an all-padding row under --check-invariants (last line of
    defense for direct callers that bypass the iterators)."""
    import jax

    from cgnn_tpu.parallel.data_parallel import (
        empty_batch_like,
        make_parallel_train_step,
        stack_batches,
    )
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    step = make_parallel_train_step(mesh)
    stacked = stack_batches([dense_batch, empty_batch_like(dense_batch)])
    with pytest.raises(invariants.BatchInvariantError, match="eval-only"):
        step(object(), stacked)  # rejected before state is even touched


def test_scan_driver_validates_input_batches(dense_batch):
    """ScanEpochDriver checks every input batch before staging stacks."""
    from cgnn_tpu.train.loop import ScanEpochDriver

    bad = dense_batch.replace(
        neighbors=np.full_like(np.asarray(dense_batch.neighbors),
                               dense_batch.node_capacity + 3)
    )
    with pytest.raises(invariants.BatchInvariantError):
        ScanEpochDriver(
            lambda s, b: (s, {}), lambda s, b: {},
            [dense_batch, bad], [], np.random.default_rng(0),
            stage=lambda t: t,
        )


def test_cache_spot_check_catches_corruption(tmp_path):
    """A cache whose arrays were corrupted on disk fails loudly on reload
    under --check-invariants (sample-based, so corrupt a sampled graph)."""
    from cgnn_tpu.data.cache import load_graph_cache, save_graph_cache

    graphs = load_synthetic(6, FeaturizeConfig(radius=5.0, max_num_nbr=8),
                            seed=3, max_atoms=6)
    path = str(tmp_path / "cache.npz")
    save_graph_cache(graphs, path)
    assert len(load_graph_cache(path)) == 6  # clean cache passes

    # corrupt: neighbors of the FIRST graph point out of range (the spot
    # check always samples index 0)
    with np.load(path) as z:
        payload = {k: np.asarray(z[k]).copy() for k in z.files}
    payload["neighbors"][: int(payload["edge_counts"][0])] = 10**6
    with open(path, "wb") as f:
        np.savez(f, **payload)
    with pytest.raises(invariants.BatchInvariantError, match="out of range"):
        load_graph_cache(path)


def test_flag_gates_iterator_validation():
    graphs = load_synthetic(8, FeaturizeConfig(radius=5.0, max_num_nbr=8),
                            seed=9, max_atoms=6)
    nc, ec = capacities_for(graphs, 4, snug=True)
    was = invariants.enabled()
    try:
        invariants.enable(False)
        assert len(list(batch_iterator(graphs, 4, nc, ec, snug=True))) >= 1
        invariants.enable(True)
        assert len(list(batch_iterator(graphs, 4, nc, ec, snug=True))) >= 1
    finally:
        invariants.enable(was)
