"""Multi-bucket batching + OC20 large-graph regime (BASELINE config #4,
SURVEY.md §5 long-context analog)."""

import numpy as np
import pytest

from cgnn_tpu.data.dataset import (
    FeaturizeConfig,
    load_synthetic,
    load_synthetic_oc20,
)
from cgnn_tpu.data.graph import (
    PaddingStats,
    batch_iterator,
    bucketed_batch_iterator,
    capacities_for,
    count_batches,
)

CFG = FeaturizeConfig(radius=5.0, max_num_nbr=10)


def _mixed_graphs():
    """Bimodal size mix: small MP-like crystals + large OC20-like slabs."""
    small = load_synthetic(24, CFG, seed=0, max_atoms=8)
    big = load_synthetic_oc20(8, CFG, seed=1)
    return small + big


def test_dense_layout_preserves_edge_set_and_invariants():
    """Dense slot packing: node n owns slots [n*M, (n+1)*M); the flat-COO
    invariants (sorted centers, masked padding) still hold, and the
    (center, neighbor, feature) edge multiset is exactly the flat one's."""
    graphs = _mixed_graphs()
    m = CFG.max_num_nbr
    nc, ec = capacities_for(graphs, 8, dense_m=m)
    assert ec == nc * m
    # same node_cap and non-binding flat edge_cap -> identical batch splits
    flat = list(batch_iterator(graphs, 8, nc, nc * m))
    dense = list(batch_iterator(graphs, 8, nc, ec, dense_m=m))
    assert len(flat) == len(dense)
    for fb, db in zip(flat, dense):
        c = np.asarray(db.centers)
        assert (np.diff(c) >= 0).all()  # sortedness invariant
        assert (c == np.arange(ec) // m).all()  # dense slot ownership
        mask = np.asarray(db.edge_mask) > 0
        # real edges per node never exceed M, and the edge multiset matches
        def key(b, sel):
            flat_edges = np.asarray(b.flat_edges)
            return sorted(
                zip(
                    np.asarray(b.centers)[sel].tolist(),
                    np.asarray(b.neighbors)[sel].tolist(),
                    flat_edges[sel].sum(axis=1).round(5).tolist(),
                )
            )
        assert key(db, mask) == key(fb, np.asarray(fb.edge_mask) > 0)
        # masked padding slots are self-loops on their owning node
        assert (np.asarray(db.neighbors)[~mask] == c[~mask]).all()


def test_dense_model_matches_flat_model():
    """Same graphs, same params: the dense-layout model must reproduce the
    flat-COO model's outputs and gradients (layout is not semantics)."""
    import jax
    import jax.numpy as jnp

    from cgnn_tpu.models import CrystalGraphConvNet

    graphs = load_synthetic(12, CFG, seed=3)
    m = CFG.max_num_nbr
    fnc, fec = capacities_for(graphs, 12)
    dnc, dec = capacities_for(graphs, 12, dense_m=m)
    fb = next(batch_iterator(graphs, 12, fnc, fec))
    db = next(batch_iterator(graphs, 12, dnc, dec, dense_m=m))
    flat_model = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=24)
    dense_model = CrystalGraphConvNet(
        atom_fea_len=16, n_conv=2, h_fea_len=24, dense_m=m
    )
    variables = flat_model.init(jax.random.key(0), fb)

    out_f = flat_model.apply(variables, fb)
    out_d = dense_model.apply(variables, db)
    np.testing.assert_allclose(
        np.asarray(out_f), np.asarray(out_d), rtol=1e-5, atol=1e-5
    )

    def loss(params, model, batch):
        out, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            batch, train=True, mutable=["batch_stats"],
        )
        return jnp.sum(out ** 2)

    gf = jax.grad(loss)(variables["params"], flat_model, fb)
    gd = jax.grad(loss)(variables["params"], dense_model, db)
    for a, b in zip(jax.tree_util.tree_leaves(gf), jax.tree_util.tree_leaves(gd)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


def test_transpose_slots_invariants():
    """The (two-tier) transpose is exact: every real edge slot appears
    exactly once across tier-1 in_slots + the overflow list's real
    prefix, each in the row/entry of the node it references; every node's
    overflow entries are one run, ended where over_last points."""
    from cgnn_tpu.data.graph import overflow_rows

    graphs = _mixed_graphs()
    m = CFG.max_num_nbr
    nc, ec = capacities_for(graphs, 8, dense_m=m)
    for b in batch_iterator(graphs, 8, nc, ec, dense_m=m):
        assert b.in_slots is not None and b.in_mask is not None
        assert b.in_slots.shape == (nc * m,)  # stored flat (pack_graphs)
        assert b.in_mask.shape == (nc, m)
        real = np.nonzero(np.asarray(b.edge_mask) > 0)[0]
        listed = np.asarray(b.in_slots).reshape(nc, m)[
            np.asarray(b.in_mask) > 0]
        rows, _ = np.nonzero(np.asarray(b.in_mask) > 0)
        k = overflow_rows(b)
        listed = np.concatenate([listed, np.asarray(b.over_slots)[:k]])
        rows = np.concatenate([rows, np.asarray(b.over_nodes)[:k]])
        assert sorted(listed.tolist()) == sorted(real.tolist())
        np.testing.assert_array_equal(
            np.asarray(b.neighbors)[listed], rows
        )
        # overflow list is node-sorted: a node's entries are one run
        over_nodes = np.asarray(b.over_nodes)
        assert np.all(np.diff(over_nodes) >= 0)
        # over_last: the run's last entry for its owner, out of range
        # (the zero row) for every other node; runs within the capacity
        last = np.asarray(b.over_last)
        extra = np.maximum(
            np.bincount(np.asarray(b.neighbors)[real], minlength=nc) - m, 0)
        assert extra.sum() == k and extra.max() <= len(b.over_runs)
        np.testing.assert_array_equal(
            last, np.where(extra > 0, np.cumsum(extra) - 1,
                           len(b.over_slots)))
        owners = np.nonzero(extra)[0]
        np.testing.assert_array_equal(over_nodes[last[owners]], owners)
        np.testing.assert_array_equal(
            np.asarray(b.over_runs),
            np.bincount(extra[owners] - 1, minlength=len(b.over_runs)))


def test_transpose_backward_matches_plain_gather():
    """The scatter-free gather backward (gather_slot_major) must produce the
    same gradients as autodiff through the plain gather."""
    import jax
    import jax.numpy as jnp

    from cgnn_tpu.models import CrystalGraphConvNet

    graphs = load_synthetic(12, CFG, seed=5)
    m = CFG.max_num_nbr
    nc, ec = capacities_for(graphs, 12, dense_m=m)
    db = next(batch_iterator(graphs, 12, nc, ec, dense_m=m))
    stripped = db.replace(in_slots=None, in_mask=None)
    model = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=24,
                                dense_m=m)
    variables = model.init(jax.random.key(0), stripped)

    def loss(params, batch):
        out, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            batch, train=True, mutable=["batch_stats"],
        )
        return jnp.sum(out ** 2)

    g_plain = jax.grad(loss)(variables["params"], stripped)
    g_transpose = jax.grad(loss)(variables["params"], db)
    # f32 reassociation tolerance: the linear_call transpose (r4) builds a
    # slightly different accumulation graph than custom_vjp did; semantic
    # exactness is pinned separately in f64 (max |diff| 2.8e-14 on this
    # exact setup) so 5e-6 absolute here is pure roundoff headroom
    for a, b in zip(
        jax.tree_util.tree_leaves(g_plain),
        jax.tree_util.tree_leaves(g_transpose),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=5e-6
        )


def test_over_cap_overrun_splits_batch_instead_of_dying():
    """A 3-sigma shuffle-tail over_cap overrun must split the offending
    batch (same compiled shape) with a warning, not abort the run; a
    single unsplittable graph still raises. (advisor r3; the recovery is
    caught BY TYPE — TransposeOverflowError — not by message text.)"""
    import warnings

    import pytest

    from cgnn_tpu.data.graph import (
        CrystalGraph,
        TransposeOverflowError,
        overflow_rows,
    )

    def star_graph(n, cid):
        # every node sends 2 edges to node 0 -> in-degree(0) = 2n, far
        # above dense_m=2, forcing (2n - 2) overflow entries per graph
        centers = np.repeat(np.arange(n, dtype=np.int32), 2)
        neighbors = np.zeros(2 * n, np.int32)
        return CrystalGraph(
            atom_fea=np.ones((n, 4), np.float32),
            edge_fea=np.ones((2 * n, 3), np.float32),
            centers=centers,
            neighbors=neighbors,
            target=np.zeros(1, np.float32),
            cif_id=cid,
        )

    graphs = [star_graph(5, "s0"), star_graph(5, "s1")]
    # each graph overflows 8 entries; over_cap=8 fits one graph per batch
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        batches = list(batch_iterator(
            graphs, 2, node_cap=16, edge_cap=32, dense_m=2, over_cap=8
        ))
    assert len(batches) == 2  # split in half, same capacities
    assert any("splitting it in half" in str(w.message) for w in caught)
    for b in batches:
        assert np.shape(b.nodes) == (16, 4)
        assert overflow_rows(b) == 8
    # an unsplittable single graph re-raises the typed error
    with pytest.raises(TransposeOverflowError):
        list(batch_iterator(
            [star_graph(8, "big")], 1, node_cap=16, edge_cap=32,
            dense_m=2, over_cap=8,
        ))


def test_transpose_in_cap_overflow_raises():
    from cgnn_tpu.data.graph import pack_graphs

    graphs = load_synthetic(4, CFG, seed=0, max_atoms=8)
    m = CFG.max_num_nbr
    nc, ec = capacities_for(graphs, 4, dense_m=m)
    try:
        pack_graphs(graphs, nc, ec, 4, dense_m=m, in_cap=1)
    except ValueError as e:
        assert "in-degree" in str(e)
    else:
        raise AssertionError("expected in_cap overflow to raise")


def test_oc20_graphs_are_large():
    graphs = load_synthetic_oc20(8, CFG, seed=0)
    sizes = [g.num_nodes for g in graphs]
    assert min(sizes) >= 20
    assert max(sizes) >= 50  # the large-graph regime config #4 targets


def test_count_batches_matches_iterator():
    graphs = _mixed_graphs()
    nc, ec = capacities_for(graphs, 8)
    n = sum(1 for _ in batch_iterator(graphs, 8, nc, ec))
    assert count_batches(graphs, 8, nc, ec) == n
    # and the naive len//batch_size estimate is indeed wrong here
    assert n >= len(graphs) // 8


def test_bucketed_iterator_yields_every_graph_once():
    graphs = _mixed_graphs()
    for shuffle in (False, True):
        ids = []
        for batch in bucketed_batch_iterator(
            graphs, 8, 3, shuffle=shuffle, rng=np.random.default_rng(0)
        ):
            node_graph = np.asarray(batch.node_graph)
            node_mask = np.asarray(batch.node_mask) > 0
            for k in range(int(np.asarray(batch.graph_mask).sum())):
                ids.append(int(((node_graph == k) & node_mask).sum()))
        assert len(ids) == len(graphs)
        assert sorted(ids) == sorted(g.num_nodes for g in graphs)


def test_bucketed_iterator_bounds_compiled_shapes():
    graphs = _mixed_graphs()
    stats = PaddingStats()
    for _ in bucketed_batch_iterator(graphs, 8, 3, stats=stats):
        pass
    assert 1 <= len(stats.shapes) <= 3


def test_buckets_beat_single_capacity_on_bimodal_mix():
    graphs = _mixed_graphs()
    nc, ec = capacities_for(graphs, 8)
    single = PaddingStats()
    for b in single.wrap(batch_iterator(graphs, 8, nc, ec)):
        pass
    multi = PaddingStats()
    for _ in bucketed_batch_iterator(graphs, 8, 3, stats=multi):
        pass
    assert multi.node_efficiency > single.node_efficiency


def test_oc20_trains_end_to_end_with_buckets():
    """Slab graphs pack, batch with buckets, and loss decreases."""
    import jax

    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.loop import fit

    graphs = load_synthetic_oc20(32, CFG, seed=2)
    train_g, val_g = graphs[:28], graphs[28:]
    norm = Normalizer.fit(np.stack([g.target for g in train_g]))
    model = CrystalGraphConvNet(atom_fea_len=32, n_conv=2, h_fea_len=32)
    nc, ec = capacities_for(train_g, 8)
    example = next(batch_iterator(train_g, 8, nc, ec))
    state = create_train_state(
        model, example, make_optimizer(optim="adam", lr=3e-3), norm,
        rng=jax.random.key(0),
    )
    state, res = fit(
        state, train_g, val_g, epochs=8, batch_size=8, buckets=2,
        print_freq=0, log_fn=lambda *_: None,
    )
    losses = [h["train"]["loss"] for h in res["history"]]
    assert losses[-1] < 0.5 * losses[0]


def test_snug_packing_efficiency_and_coverage():
    """Fill-to-capacity packing (VERDICT r2 #2): >=0.95 slot efficiency on
    the MP-like distribution, every graph packed exactly once, compiled
    shape count unchanged, count_batches in sync."""
    from cgnn_tpu.data.dataset import load_synthetic_mp
    from cgnn_tpu.data.graph import (
        PaddingStats,
        batch_iterator,
        bucketed_batch_iterator,
        capacities_for,
        count_batches,
    )

    graphs = load_synthetic_mp(512, FeaturizeConfig(radius=5.0), seed=0)
    stats = PaddingStats()
    batches = list(bucketed_batch_iterator(
        graphs, 64, 3, shuffle=True, rng=np.random.default_rng(1),
        dense_m=12, snug=True, stats=stats,
    ))
    assert stats.node_efficiency >= 0.95
    assert len(stats.shapes) <= 3
    packed = sum(int(np.asarray(b.graph_mask).sum()) for b in batches)
    assert packed == len(graphs)
    for b in batches:
        # mask consistency: real edges only on real nodes
        em = np.asarray(b.edge_mask).reshape(b.node_capacity, 12)
        nm = np.asarray(b.node_mask)
        assert not np.any(em.max(axis=1) > nm)

    nc, ec = capacities_for(graphs, 64, dense_m=12, snug=True)
    n = count_batches(graphs, 64, nc, ec, snug=True)
    assert n == len(list(batch_iterator(graphs, 64, nc, ec, dense_m=12,
                                        snug=True)))


def test_per_bucket_in_cap_tracks_bucket_skew():
    """per_bucket_in_cap (forced single-tier): the bucket containing the
    skewed hub graph gets a LARGER transpose capacity than the other
    bucket, which must stay below the dataset-wide cap — the point of the
    flag (one adsorbate-style outlier must not inflate every bucket)."""
    from cgnn_tpu.data.graph import bucketed_batch_iterator, in_degree_cap

    cfg = FeaturizeConfig(radius=5.0, max_num_nbr=8)
    graphs = load_synthetic(64, cfg, seed=2, max_atoms=6)
    # skew the LARGEST graph (lands in the top size bucket): a hub node
    # listed as neighbor by every edge -> in-degree = num_edges
    hub = max(graphs, key=lambda g: g.num_nodes)
    hub.neighbors = np.zeros_like(hub.neighbors)
    hub._max_in_degree = None
    global_cap = in_degree_cap(graphs)
    batches = list(bucketed_batch_iterator(
        graphs, 8, 2, dense_m=8, snug=True, per_bucket_in_cap=True,
    ))
    caps = {b.in_mask.shape[1] for b in batches}
    assert len(caps) == 2, caps
    assert max(caps) == global_cap  # hub bucket pays its own skew
    assert min(caps) < global_cap  # ...and the other bucket does not


def _crafted_edges(in_degree: dict, n: int, m: int):
    """A dense batch's flat ``neighbors`` [n*m] and its real-slot mask
    with the given in-degrees: sources take turns, each filling its own
    slots front to back (node 0's slot 0 is real, so the overflow list's
    padding entries, which name slot 0, gather a row that is NOT zero)."""
    neighbors = np.arange(n * m, dtype=np.int32) // m  # padding: own node
    real = np.zeros(n * m, bool)
    used = np.zeros(n, int)
    src = 0
    for dst, deg in in_degree.items():
        for _ in range(deg):
            while used[src] == m:
                src = (src + 1) % n
            neighbors[src * m + used[src]] = dst
            real[src * m + used[src]] = True
            used[src] += 1
            src = (src + 1) % n
    assert real[0]
    return neighbors, real


# case -> (in-degrees, node_cap, over_cap, run_cap), at dense_m 2
_CRAFTED_TIERS = {
    # a node whose run is exactly the capacity, beside a run of one
    "run_of_exactly_k": ({3: 7, 10: 3, 0: 2}, 16, 8, 5),
    # no overflow at all: every entry is padding, every pointer the zero row
    "no_overflow": ({1: 2, 2: 2, 7: 1, 15: 2}, 16, 8, 3),
    # the LAST node slot owns a run and the padding entries that follow it
    # name the same node: they must not reach its total
    "last_node_then_padding": ({15: 5, 4: 4}, 16, 16, 8),
    # two full-length runs back to back: neither takes the other's rows
    "runs_back_to_back": ({5: 8, 6: 8, 0: 1}, 16, 16, 6),
    # the run sum works on blocks of 128 entries with a halo of run_cap - 1
    # (8-aligned) before each: node 20's run of 9 = run_cap is entries
    # 120..128, so its total, the first row of block 1, needs the whole
    # halo; node 21's run follows inside block 1
    "run_straddles_block": ({**{i: 8 for i in range(20)}, 20: 11, 21: 8},
                            96, 144, 9),
    # a list that ends exactly on a block boundary: the zero row that the
    # nodes without a run point at is a block of its own
    "list_fills_its_block": ({i: 10 for i in range(16)}, 96, 128, 8),
}
@pytest.mark.parametrize("case", ["mp", *_CRAFTED_TIERS])
def test_two_tier_transpose_backward_matches_plain_gather(case):
    """Two-tier (tier-1 [N, M] + the overflow list's run sums, gathered
    through over_last) gather_slot_major gradients == plain-gather gradients
    through a full CGConv-like masked consumer. ``mp``: packed crystals
    whose in-degree exceeds dense_m, in a padded batch; the crafted cases:
    the edges of the run sum (_CRAFTED_TIERS)."""
    import jax
    import jax.numpy as jnp

    from cgnn_tpu.data.dataset import load_synthetic_mp
    from cgnn_tpu.data.graph import (
        batch_iterator,
        capacities_for,
        overflow_rows,
        transpose_slots,
    )
    from cgnn_tpu.ops.segment import gather, gather_slot_major

    if case == "mp":
        m = 12
        cfg = FeaturizeConfig(radius=6.0, max_num_nbr=m)
        graphs = load_synthetic_mp(64, cfg, seed=3)
        nc, ec = capacities_for(graphs, 32, dense_m=m, snug=True)
        b = next(batch_iterator(graphs, 32, nc, ec, dense_m=m, snug=True))
        assert overflow_rows(b) > 0, "no overflow exercised"
        assert int(np.asarray(b.edge_mask).sum()) < b.edge_capacity, \
            "no padding"
        neighbors, real = np.asarray(b.neighbors), np.asarray(b.edge_mask) > 0
        mapping = (b.in_slots, b.in_mask, b.over_slots, b.over_nodes,
                   b.over_last, b.over_runs)
    else:
        m = 2
        in_degree, nc, over_cap, run_cap = _CRAFTED_TIERS[case]
        neighbors, real = _crafted_edges(in_degree, nc, m)
        mapping = transpose_slots(neighbors, real, nc, m, None, over_cap,
                                  run_cap)
        extra = sum(max(d - m, 0) for d in in_degree.values())
        assert int((mapping[5] * np.arange(1, run_cap + 1)).sum()) == extra
        assert (extra == over_cap) == (case == "list_fills_its_block")
    mapping = tuple(jnp.asarray(x) for x in mapping)

    nodes = jnp.asarray(
        np.random.default_rng(0).normal(size=(nc, 16))
    ).astype(jnp.float32)
    emask = jnp.asarray(real, jnp.float32).reshape(-1, m, 1)
    # a consumer that weighs every slot differently: a mix-up of the row
    # order cannot cancel
    weight = jnp.asarray(np.random.default_rng(1).normal(
        size=(nc, m, 16))).astype(jnp.float32)
    neighbors = jnp.asarray(neighbors)

    def loss_two_tier(n):
        v_j = gather_slot_major(n, neighbors, m, *mapping)
        return ((v_j * emask * weight) ** 2).sum()

    def loss_plain(n):
        v_j = gather(n, neighbors).reshape(-1, m, 16)
        return ((v_j * emask * weight) ** 2).sum()

    np.testing.assert_array_equal(  # the same rows: bit-identical forward
        np.asarray(loss_two_tier(nodes)), np.asarray(loss_plain(nodes)))
    g1 = jax.grad(loss_two_tier)(nodes)
    g2 = jax.grad(loss_plain)(nodes)
    assert float(jnp.abs(g2).max()) > 0.1
    np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=1e-5)


def test_run_longer_than_run_cap_raises():
    """A node's overflow run beyond ``run_cap`` would lose gradient (the
    backward's look-back is compiled for the capacity): the pack raises, by
    type, from transpose_slots, pack_graphs and the compact packer alike,
    and batch_iterator does not split its way around it."""
    from cgnn_tpu.data.dataset import load_synthetic_mp
    from cgnn_tpu.data.graph import (
        TransposeRunError,
        batch_iterator,
        capacities_for,
        overflow_run_cap,
        pack_graphs,
        transpose_slots,
    )

    in_degree, _, over_cap, run_cap = _CRAFTED_TIERS["run_of_exactly_k"]
    neighbors, real = _crafted_edges(in_degree, 16, 2)
    transpose_slots(neighbors, real, 16, 2, None, over_cap, run_cap)
    with pytest.raises(TransposeRunError, match="run_cap=4"):
        transpose_slots(neighbors, real, 16, 2, None, over_cap, run_cap - 1)

    cfg = FeaturizeConfig(radius=6.0, max_num_nbr=12)
    graphs = load_synthetic_mp(24, cfg, seed=3)
    k = overflow_run_cap(graphs, 12)
    nc, ec = capacities_for(graphs, 24, dense_m=12, snug=True)
    b = pack_graphs(graphs, nc, ec, 32, dense_m=12, over_cap=4096, run_cap=k)
    longest = int(np.nonzero(np.asarray(b.over_runs))[0].max()) + 1
    assert 1 < longest <= k == len(b.over_runs)
    with pytest.raises(TransposeRunError):
        pack_graphs(graphs, nc, ec, 32, dense_m=12, over_cap=4096,
                    run_cap=longest - 1)
    with pytest.raises(TransposeRunError):
        list(batch_iterator(graphs, 24, nc, ec, dense_m=12, snug=True,
                            run_cap=longest - 1))


def _packed_by(packer):
    """One packed batch of every packer, as the model receives it."""
    import jax

    from cgnn_tpu.data.dataset import load_synthetic_mp

    cfg = FeaturizeConfig(radius=6.0, max_num_nbr=12)
    if packer == "neighbor_search":  # built inside the program (raw wire)
        from cgnn_tpu.data.dataset import featurize_structure
        from cgnn_tpu.data.rawbatch import (
            RawStructure,
            pack_raw,
            plan_raw_spec,
        )
        from cgnn_tpu.data.synthetic import synthetic_dataset
        from cgnn_tpu.ops.neighbor_search import make_raw_expander

        items = synthetic_dataset(6, seed=3)
        graphs = [featurize_structure(s, t, cfg, sid, keep_geometry=True)
                  for sid, s, t in items]
        spec = plan_raw_spec(graphs, cfg.gdf(), cfg.radius, 12)
        raws = [RawStructure.from_structure(s, t, sid) for sid, s, t in items]
        # 8 slots for 6 structures: two padding structures
        return jax.jit(make_raw_expander(spec))(pack_raw(raws, 8, spec))[0]
    graphs = load_synthetic_mp(48, cfg, seed=3)
    if packer == "coo":
        nc, ec = capacities_for(graphs, 32)
        return next(batch_iterator(graphs, 32, nc, ec))
    nc, ec = capacities_for(graphs, 32, dense_m=12, snug=True)
    if packer == "dense":
        return next(batch_iterator(graphs, 32, nc, ec, dense_m=12, snug=True))
    from cgnn_tpu.data.compact import (
        CompactSpec,
        compact_pack_fn,
        make_expander,
    )

    spec = CompactSpec.build(graphs, cfg.gdf(), dense_m=12)
    comp = next(batch_iterator(graphs, 32, nc, ec, dense_m=12, snug=True,
                               pack_fn=compact_pack_fn(spec)))
    return jax.jit(make_expander(spec))(comp)


@pytest.mark.parametrize(
    "packer", ["dense", "coo", "compact", "neighbor_search"])
def test_packed_indices_are_in_range(packer):
    """ops/segment.gather promises XLA in-range indices (``mode="clip"``
    pays for no out-of-range select): every index array a packer hands
    the model addresses a row that exists, padding entries included."""
    b = _packed_by(packer)
    n, e = b.node_capacity, b.edge_capacity
    bounds = {"centers": n, "neighbors": n, "node_graph": b.graph_capacity,
              "in_slots": e, "over_slots": e, "over_nodes": n}
    checked = 0
    for name, bound in bounds.items():
        idx = getattr(b, name)
        if idx is None:
            continue
        idx = np.asarray(idx)
        assert idx.size and idx.min() >= 0 and idx.max() < bound, (
            packer, name, int(idx.min()), int(idx.max()), bound)
        checked += 1
    assert checked >= 3
    if packer in ("dense", "compact"):  # the train packers carry the mapping
        assert b.in_slots is not None and b.over_slots is not None


def _select_under_take(jaxpr, under=False) -> int:
    """``select_n`` equations inside a ``jit(_take)`` of a jaxpr, at any
    depth: what ``jnp.take``'s default ``mode="fill"`` leaves behind."""
    n = 0
    for eqn in jaxpr.eqns:
        inside = under or eqn.params.get("name") == "_take"
        if under and eqn.primitive.name == "select_n":
            n += 1
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += _select_under_take(sub, inside)
    return n


@pytest.mark.parametrize("layout", ["dense", "coo"])
def test_train_step_gathers_pay_no_out_of_range_select(layout):
    """No ``_take`` of the train step carries a ``select_n``: on the chip
    each one re-reads and re-writes a whole gathered [E, F] (PERF.md §5)."""
    import jax
    import jax.numpy as jnp

    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.step import make_train_step

    # the counter sees what it is meant to see
    fill = jax.make_jaxpr(lambda x, i: jnp.take(x, i, axis=0))(
        jnp.ones((4, 3)), jnp.arange(2))
    assert _select_under_take(fill.jaxpr) == 1

    b = _packed_by(layout)
    model = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                dense_m=12 if layout == "dense" else None)
    state = create_train_state(
        model, b, make_optimizer(optim="sgd", lr=0.01),
        Normalizer(mean=np.zeros(1, np.float32), std=np.ones(1, np.float32)),
    )
    step = jax.make_jaxpr(make_train_step())(state, b)
    assert _select_under_take(step.jaxpr) == 0


def test_bf16_edge_storage_packs_validates_and_trains():
    """edge_dtype=bfloat16 (train.py --bf16): packs, passes the invariant
    checker, and one train step runs with finite loss."""
    import jax

    from cgnn_tpu.data import invariants
    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.step import make_train_step

    graphs = load_synthetic(16, CFG, seed=9, max_atoms=6)
    m = CFG.max_num_nbr
    nc, ec = capacities_for(graphs, 8, dense_m=m, snug=True)
    b = next(batch_iterator(graphs, 8, nc, ec, dense_m=m, snug=True,
                            edge_dtype=jax.numpy.bfloat16))
    assert b.edges.dtype == jax.numpy.bfloat16
    invariants.check_batch(b, dense_m=m)

    model = CrystalGraphConvNet(atom_fea_len=16, n_conv=2, h_fea_len=16,
                                dtype=jax.numpy.bfloat16, dense_m=m)
    state = create_train_state(
        model, b, make_optimizer(optim="sgd", lr=0.01),
        Normalizer.fit(np.stack([g.target for g in graphs])),
    )
    state, metrics = jax.jit(make_train_step())(state, b)
    assert np.isfinite(float(metrics["loss_sum"]))
