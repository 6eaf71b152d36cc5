"""Compact staging (data/compact.py): raw-form packing + on-device
expansion must reproduce pack_graphs exactly (indices/masks) or to f32
roundoff (features), and compose with the scan-epoch training path."""

import numpy as np
import jax
import pytest

from cgnn_tpu.data import invariants
from cgnn_tpu.data.compact import (
    AtomVocab,
    CompactSpec,
    CompactUnsupported,
    compact_pack_fn,
    make_expander,
    pack_compact,
)
from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
from cgnn_tpu.data.featurize import GaussianDistance
from cgnn_tpu.data.graph import (
    batch_shape_key,
    bucketed_batch_iterator,
    capacities_for,
    overflow_cap,
    overflow_rows,
    pack_graphs,
)

CFG = FeaturizeConfig(radius=6.0, max_num_nbr=12)


@pytest.fixture(scope="module")
def graphs():
    return load_synthetic_mp(96, CFG, seed=11)


@pytest.fixture(scope="module")
def spec(graphs):
    return CompactSpec.build(graphs, CFG.gdf(), dense_m=CFG.max_num_nbr)


def _pack_pair(graphs, spec, in_cap=None, over_cap=None, edge_dtype=np.float32):
    nc, ec = capacities_for(graphs, len(graphs), dense_m=12, snug=True)
    full = pack_graphs(graphs, nc, ec, len(graphs), dense_m=12,
                       in_cap=in_cap, over_cap=over_cap,
                       edge_dtype=edge_dtype)
    comp = pack_compact(graphs, nc, ec, len(graphs), spec,
                        in_cap=in_cap, over_cap=over_cap)
    return full, comp


def test_expand_reproduces_pack_graphs(graphs, spec):
    oc = overflow_cap(graphs, len(graphs), 12)
    full, comp = _pack_pair(graphs, spec, over_cap=oc)
    got = jax.jit(make_expander(spec))(comp)
    # exact: everything except the exp()-computed edge features
    np.testing.assert_array_equal(np.asarray(got.nodes), full.nodes)
    np.testing.assert_array_equal(np.asarray(got.centers), full.centers)
    np.testing.assert_array_equal(np.asarray(got.neighbors), full.neighbors)
    np.testing.assert_array_equal(np.asarray(got.node_graph), full.node_graph)
    np.testing.assert_array_equal(np.asarray(got.node_mask), full.node_mask)
    np.testing.assert_array_equal(np.asarray(got.edge_mask), full.edge_mask)
    np.testing.assert_array_equal(np.asarray(got.graph_mask), full.graph_mask)
    np.testing.assert_array_equal(np.asarray(got.targets), full.targets)
    np.testing.assert_array_equal(np.asarray(got.target_mask),
                                  full.target_mask)
    np.testing.assert_array_equal(np.asarray(got.in_slots), full.in_slots)
    np.testing.assert_array_equal(np.asarray(got.in_mask), full.in_mask)
    np.testing.assert_array_equal(np.asarray(got.over_slots), full.over_slots)
    np.testing.assert_array_equal(np.asarray(got.over_nodes), full.over_nodes)
    np.testing.assert_array_equal(np.asarray(got.over_last), full.over_last)
    np.testing.assert_array_equal(np.asarray(got.over_runs), full.over_runs)
    assert overflow_rows(full) > 0
    np.testing.assert_allclose(np.asarray(got.edges), full.edges, atol=2e-6)
    # geometry comes back None (energy models never read it)
    assert got.positions is None and got.lattices is None


def test_expand_eval_batches_no_transpose(graphs, spec):
    # (batch_iterator normalizes eval's in_cap=0 to None before packing)
    full, comp = _pack_pair(graphs, spec, in_cap=None)
    assert comp.in_slots is None
    got = jax.jit(make_expander(spec))(comp)
    assert got.in_slots is None
    np.testing.assert_allclose(np.asarray(got.edges), full.edges, atol=2e-6)


def test_compact_batch_is_small(graphs, spec):
    oc = overflow_cap(graphs, len(graphs), 12)
    full, comp = _pack_pair(graphs, spec, over_cap=oc)
    nbytes = lambda b: sum(  # noqa: E731
        x.nbytes for x in jax.tree_util.tree_leaves(b)
    )
    assert nbytes(comp) < nbytes(full) / 8


def test_vocab_unsupported_on_continuous_features(graphs):
    import dataclasses

    rng = np.random.default_rng(0)
    cont = [
        dataclasses.replace(
            g, atom_fea=rng.standard_normal(g.atom_fea.shape).astype(
                np.float32
            )
        )
        for g in graphs
    ]
    with pytest.raises(CompactUnsupported):
        AtomVocab.build(cont, max_size=64)


def test_spec_rejects_wrong_gaussian(graphs):
    with pytest.raises(CompactUnsupported):
        CompactSpec.build(graphs, GaussianDistance(0.0, 4.0, 0.5),
                          dense_m=12)


def test_invariants_cover_compact(graphs, spec):
    oc = overflow_cap(graphs, len(graphs), 12)
    _, comp = _pack_pair(graphs, spec, over_cap=oc)
    invariants.check_compact_batch(comp)
    bad = comp.replace(neighbors=comp.neighbors.copy())
    bad.neighbors[0] = comp.node_capacity + 5
    with pytest.raises(invariants.BatchInvariantError):
        invariants.check_compact_batch(bad)
    bad2 = comp.replace(distances=comp.distances.copy())
    bad2.distances[comp.edge_mask == 0] = 1.0
    if (comp.edge_mask == 0).any():
        with pytest.raises(invariants.BatchInvariantError):
            invariants.check_compact_batch(bad2)


def test_iterator_with_compact_pack_fn(graphs, spec):
    stats_batches = list(
        bucketed_batch_iterator(
            graphs, 32, 2, dense_m=12, snug=True,
            pack_fn=compact_pack_fn(spec),
        )
    )
    assert all(hasattr(b, "atom_idx") for b in stats_batches)
    keys = {batch_shape_key(b) for b in stats_batches}
    assert all(k[0] == "compact" for k in keys)


def test_pack_compact_buffer_reuse_bit_identical(graphs, spec):
    """pack_compact(out=) must be indistinguishable from a fresh pack —
    including stale state from a PREVIOUS batch in the recycled buffer
    (the padding-tail zeroing is what this pins)."""
    from cgnn_tpu.data.compact import alloc_compact_buffers

    nc, ec = capacities_for(graphs, len(graphs), dense_m=12, snug=True)
    tdim = 1
    buf = alloc_compact_buffers(nc, 12, len(graphs), tdim)
    # dirty the buffer with a big batch, then pack a SMALLER one into it
    pack_compact(graphs, nc, ec, len(graphs), spec, num_targets=tdim,
                 out=buf)
    small = graphs[:5]
    fresh = pack_compact(small, nc, ec, len(graphs), spec,
                         num_targets=tdim)
    reused = pack_compact(small, nc, ec, len(graphs), spec,
                          num_targets=tdim, out=buf)
    import jax

    for leaf_fresh, leaf_reused in zip(
        jax.tree_util.tree_leaves(fresh), jax.tree_util.tree_leaves(reused)
    ):
        np.testing.assert_array_equal(leaf_fresh, leaf_reused)
    assert reused.atom_idx is buf.atom_idx  # actually reused, not copied


def test_pack_compact_out_rejects_mismatch_and_transpose(graphs, spec):
    from cgnn_tpu.data.compact import alloc_compact_buffers

    nc, ec = capacities_for(graphs, len(graphs), dense_m=12, snug=True)
    wrong = alloc_compact_buffers(nc + 8, 12, len(graphs), 1)
    with pytest.raises(ValueError, match="geometry"):
        pack_compact(graphs, nc, ec, len(graphs), spec, num_targets=1,
                     out=wrong)
    ok = alloc_compact_buffers(nc, 12, len(graphs), 1)
    with pytest.raises(ValueError, match="forward-only"):
        pack_compact(graphs, nc, ec, len(graphs), spec, num_targets=1,
                     over_cap=overflow_cap(graphs, len(graphs), 12), out=ok)


def test_graph_compactable_probe(graphs, spec):
    import dataclasses

    g = graphs[0]
    assert spec.graph_compactable(g)
    # no raw distances (the wire-format request case) -> full fidelity
    bare = dataclasses.replace(g, distances=None)
    assert not spec.graph_compactable(bare)
    # edge features inconsistent with distances -> full fidelity (the
    # exactness contract: compact staging must never change the answer)
    lying = dataclasses.replace(g, edge_fea=g.edge_fea + 0.25)
    assert not spec.graph_compactable(lying)
    # atom rows outside the vocabulary -> full fidelity
    alien = dataclasses.replace(
        g, atom_fea=np.full_like(g.atom_fea, 0.123456)
    )
    assert not spec.graph_compactable(alien)
    # the verdict is cached on the graph, keyed to THIS spec's identity
    # (a different spec in the same process must re-probe, not reuse)
    assert g._compact_ok == (spec._probe_token, True)
    assert alien._compact_ok == (spec._probe_token, False)
    spec2 = CompactSpec.build(graphs, CFG.gdf(), dense_m=12)
    assert spec2.graph_compactable(g)  # re-probed under spec2, not stale
    assert g._compact_ok[0] is spec2._probe_token


def test_fit_compact_matches_full(graphs):
    """Single-bucket scan training: compact staging must produce the same
    trajectory as full staging up to edge-feature roundoff."""
    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.train import (
        Normalizer,
        create_train_state,
        make_optimizer,
    )
    from cgnn_tpu.train.loop import fit

    train_g, val_g = graphs[:64], graphs[64:]
    spec = CompactSpec.build(train_g + val_g, CFG.gdf(), dense_m=12)
    results = {}
    for mode in ("full", "compact"):
        model = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=32,
                                    dense_m=12)
        tx = make_optimizer(optim="adam", lr=0.01, lr_milestones=[10**9])
        norm = Normalizer.fit(np.stack([g.target for g in train_g]))
        nc, ec = capacities_for(train_g, 16, dense_m=12, snug=True)
        example = pack_graphs(train_g[:4], nc, ec, 16, dense_m=12)
        state = create_train_state(model, example, tx, norm,
                                   rng=jax.random.key(0))
        _, res = fit(
            state, train_g, val_g, epochs=3, batch_size=16,
            node_cap=nc, edge_cap=ec, seed=0, print_freq=0,
            scan_epochs=True, snug=True, dense_m=12,
            compact=spec if mode == "compact" else None,
        )
        results[mode] = [h["val"]["mae"] for h in res["history"]]
    # the ~1-ulp jnp.exp/np.exp edge-feature difference is amplified by
    # training dynamics across epochs; trajectories track within ~1%
    np.testing.assert_allclose(results["compact"], results["full"],
                               rtol=2e-2)
