"""Raw wire format + in-program neighbor search (ISSUE 11).

The acceptance pins:

- in-program graph CONSTRUCTION is bit-exact vs the host featurizer
  over identical structures: identical edge sets, neighbor indices,
  canonical edge order (center, distance, source atom, lexicographic
  image), masks, and atom feature rows — with distances/features at f32
  roundoff (the host search runs f64; XLA contracts FMAs);
- cap overflow never silently truncates: the in-program flag fires for
  a lattice needing more periodic images than the rung provides, and
  serving routes the flagged request to the host-featurized fallback;
- zero post-warmup recompiles under mixed raw/featurized (+ mixed
  tier) load — the form boundary is a batch cut, not a retrace;
- wire-form structures that cannot stage raw are featurized on the
  PACK POOL, never on the admission thread (the ISSUE-11 bugfix).
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from cgnn_tpu.data.dataset import FeaturizeConfig, featurize_structure
from cgnn_tpu.data.elements import atom_features
from cgnn_tpu.data.featurize import gaussian_expand
from cgnn_tpu.data.neighbors import knn_neighbor_list
from cgnn_tpu.data.rawbatch import (
    RawSpec,
    RawStructure,
    pack_raw,
    plan_raw_spec,
    raw_fingerprint,
    raw_from_graph,
    raw_neighbor_graph_host,
)
from cgnn_tpu.data.structure import Structure
from cgnn_tpu.data.synthetic import synthetic_dataset
from cgnn_tpu.ops.neighbor_search import make_raw_expander, neighbor_search
from cgnn_tpu.serve.shapes import plan_shape_set

CFG = FeaturizeConfig(radius=6.0, max_num_nbr=12)


def _spec(items, m=12, coverage=1.0):
    graphs = [featurize_structure(s, t, CFG, sid, keep_geometry=True)
              for sid, s, t in items]
    return graphs, plan_raw_spec(graphs, CFG.gdf(), CFG.radius, m,
                                 coverage=coverage)


def _search(rb, spec):
    out = jax.jit(
        lambda rb: neighbor_search(rb.frac, rb.lattices, rb.atom_mask,
                                   spec)
    )(rb)
    return tuple(np.asarray(x) for x in out)


class TestInProgramSearch:
    def test_bitexact_graph_construction_vs_host_featurizer(self):
        """THE parity pin: per structure, the device search selects the
        SAME edges in the SAME canonical order as knn_neighbor_list —
        neighbor indices and masks integer-exact, distances at f32
        roundoff."""
        items = synthetic_dataset(16, seed=3)
        graphs, spec = _spec(items)
        raws = [RawStructure.from_structure(s, t, sid)
                for sid, s, t in items]
        rb = pack_raw(raws, len(raws), spec)
        nbr, dist, em, ne, ovf = _search(rb, spec)
        assert not ovf.any()
        for gi, (sid, s, _t) in enumerate(items):
            nl = knn_neighbor_list(s, CFG.radius, spec.dense_m,
                                   warn_under_coordinated=False)
            n = s.num_atoms
            counts = np.bincount(nl.centers, minlength=n)
            assert int(ne[gi]) == int(np.minimum(counts,
                                                 spec.dense_m).sum())
            for i in range(n):
                sel = nl.centers == i  # knn output is center-sorted,
                #                        distance-ordered within center
                want_nbr = nl.neighbors[sel]
                cnt = len(want_nbr)
                np.testing.assert_array_equal(nbr[gi, i, :cnt], want_nbr)
                np.testing.assert_allclose(dist[gi, i, :cnt],
                                           nl.distances[sel], atol=2e-5)
                assert em[gi, i, :cnt].min() == 1
                assert cnt == spec.dense_m or em[gi, i, cnt:].max() == 0

    def test_exact_tie_canonical_order(self):
        """Simple cubic: all 6 first neighbors at EXACTLY equal
        distance — ties must order by (source atom, lexicographic
        image), the host featurizer's stable-sort order."""
        s = Structure(np.eye(3) * 3.0, [[0, 0, 0]], [29])
        spec = RawSpec(snode_cap=8, images=(2, 2, 2), radius=6.0,
                       dense_m=12,
                       gauss_filter=CFG.gdf().filter,
                       gauss_var=CFG.gdf().var)
        rb = pack_raw([RawStructure.from_structure(s)], 1, spec)
        nbr, dist, em, ne, ovf = _search(rb, spec)
        nl = knn_neighbor_list(s, 6.0, 12, warn_under_coordinated=False)
        cnt = len(nl.centers)
        np.testing.assert_array_equal(nbr[0, 0, :cnt], nl.neighbors)
        np.testing.assert_allclose(dist[0, 0, :cnt], nl.distances,
                                   atol=2e-5)
        # the tie-broken order itself: image offsets sort
        # lexicographically within each distance shell on the host; the
        # device tie-break (candidate index = atom-major, image-minor)
        # must reproduce it exactly
        host_d = np.round(nl.distances, 5)
        assert (np.diff(host_d) >= 0).all()

    def test_numpy_twin_structural_parity(self):
        items = synthetic_dataset(8, seed=11)
        _graphs, spec = _spec(items)
        raws = [RawStructure.from_structure(s, t, sid)
                for sid, s, t in items]
        rb = pack_raw(raws, 12, spec)
        nbr, dist, em, ne, ovf = _search(rb, spec)
        for gi in range(12):
            hn, hd, hm, hne, hovf = raw_neighbor_graph_host(
                rb.frac[gi], rb.lattices[gi], rb.atom_mask[gi], spec)
            np.testing.assert_array_equal(hn, nbr[gi])
            np.testing.assert_array_equal(hm, em[gi].astype(np.uint8))
            np.testing.assert_allclose(hd, dist[gi], atol=2e-5)
            assert hne == int(ne[gi])
            assert (gi < len(raws)) == bool(rb.graph_mask[gi])

    def test_overflow_flag_fires_in_program(self):
        """A tiny cell needing more images than the caps MUST flag —
        and a comfortably-fitting one must not (the flag is per
        structure, computed from the STAGED lattice)."""
        spec = RawSpec(snode_cap=8, images=(1, 1, 1), radius=6.0,
                       dense_m=12, gauss_filter=CFG.gdf().filter,
                       gauss_var=CFG.gdf().var)
        ok = RawStructure(np.zeros((1, 3)), np.eye(3) * 7.0,
                          np.array([6], np.int32))
        tiny = RawStructure(np.zeros((1, 3)), np.eye(3) * 2.0,
                            np.array([6], np.int32))
        rb = pack_raw([ok, tiny], 4, spec)
        _nbr, _d, _em, _ne, ovf = _search(rb, spec)
        assert not ovf[0]
        assert ovf[1]
        assert not ovf[2:].any()  # padding slots never flag

    def test_skewed_lattice_overflow_axis(self):
        """High-aspect skew: one SHORT axis needs many images while the
        others need one — the per-axis caps must catch exactly that."""
        lat = np.diag([20.0, 20.0, 2.2])
        spec = RawSpec(snode_cap=8, images=(1, 1, 1), radius=6.0,
                       dense_m=12, gauss_filter=CFG.gdf().filter,
                       gauss_var=CFG.gdf().var)
        rs = RawStructure(np.array([[0.5, 0.5, 0.5]]), lat,
                          np.array([14], np.int32))
        assert not spec.admits(rs)
        spec_ok = RawSpec(snode_cap=8, images=(1, 1, 3), radius=6.0,
                          dense_m=12, gauss_filter=CFG.gdf().filter,
                          gauss_var=CFG.gdf().var)
        assert spec_ok.admits(rs)
        rb = pack_raw([rs], 1, spec_ok)
        nbr, dist, em, ne, ovf = _search(rb, spec_ok)
        assert not ovf[0]
        # parity on the self-image neighbors along the short axis
        s = Structure(lat, rs.frac_coords, rs.numbers)
        nl = knn_neighbor_list(s, 6.0, 12, warn_under_coordinated=False)
        np.testing.assert_array_equal(nbr[0, 0, : len(nl.centers)],
                                      nl.neighbors)


class TestRawExpander:
    def test_graphbatch_contract_and_feature_parity(self):
        items = synthetic_dataset(6, seed=5)
        _graphs, spec = _spec(items)
        raws = [RawStructure.from_structure(s, t, sid)
                for sid, s, t in items]
        rb = pack_raw(raws, 8, spec)
        gb, ovf, ne = jax.jit(make_raw_expander(spec))(rb)
        s_cap, m = spec.snode_cap, spec.dense_m
        g_cap = 8
        nodes = np.asarray(gb.nodes)
        centers = np.asarray(gb.centers)
        # dense-layout invariants: centers = slot // M (non-decreasing),
        # padding edge slots self-loop, masks zero on padding
        np.testing.assert_array_equal(
            centers, np.arange(g_cap * s_cap * m) // m)
        emask = np.asarray(gb.edge_mask)
        nbr = np.asarray(gb.neighbors)
        own = np.arange(g_cap * s_cap * m) // m
        assert (nbr[emask == 0] == own[emask == 0]).all()
        for gi, (sid, s, _t) in enumerate(items):
            n = s.num_atoms
            # atom rows: BIT-exact vs the host featurizer's table
            np.testing.assert_array_equal(
                nodes[gi * s_cap: gi * s_cap + n],
                atom_features(s.numbers))
            # neighbors point inside the owning structure's block
            blk = nbr[gi * s_cap * m: (gi + 1) * s_cap * m]
            assert blk.min() >= gi * s_cap
            assert blk.max() < (gi + 1) * s_cap
        # padding structures: all masks zero
        assert np.asarray(gb.node_mask)[len(items) * s_cap:].max() == 0
        assert np.asarray(gb.graph_mask)[len(items):].max() == 0
        # edge features = gaussian_expand of the search distances
        # (<= 1-ulp jnp.exp contract, like the compact expander)
        _nbr2, dist, em, _ne2, _ovf2 = _search(rb, spec)
        want = gaussian_expand(dist, CFG.gdf().filter, CFG.gdf().var)
        want = want * em[..., None]
        got = np.asarray(gb.edges).reshape(g_cap, s_cap, m, -1)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_end_to_end_prediction_parity(self):
        from cgnn_tpu.models import CrystalGraphConvNet
        from cgnn_tpu.train import (
            Normalizer,
            create_train_state,
            make_optimizer,
        )
        from cgnn_tpu.train.infer import run_fast_inference, \
            run_raw_inference
        from cgnn_tpu.train.step import make_predict_step

        items = synthetic_dataset(24, seed=2)
        graphs, spec = _spec(items)
        ladder = plan_shape_set(graphs, 8, rungs=2, dense_m=12, raw=spec)
        model = CrystalGraphConvNet(atom_fea_len=16, n_conv=2,
                                    h_fea_len=32, dense_m=12)
        state = create_train_state(
            model, ladder.pack_full([graphs[0]]), make_optimizer(),
            Normalizer.fit(np.stack([g.target for g in graphs])),
            rng=jax.random.key(0),
        )
        pstep = jax.jit(make_predict_step(
            raw_expander=ladder.raw_expander()))
        raws = [raw_from_graph(g) for g in graphs]
        assert all(r is not None and ladder.admits_raw(r) for r in raws)
        fp, _ = run_fast_inference(state, graphs, 8, shape_set=ladder,
                                   predict_step=pstep)
        rp, _ = run_raw_inference(state, raws, ladder,
                                  predict_step=pstep)
        np.testing.assert_allclose(rp, fp, atol=1e-4, rtol=1e-4)


class TestRawSpecPlanning:
    def test_coverage_quantile_caps(self):
        items = synthetic_dataset(40, seed=13)
        graphs, spec_full = _spec(items, coverage=1.0)
        _g2, spec_95 = _spec(items, coverage=0.9)
        assert spec_95.snode_cap <= spec_full.snode_cap
        assert all(a <= b for a, b in zip(spec_95.images,
                                          spec_full.images))
        raws = [raw_from_graph(g) for g in graphs]
        # full coverage admits everything; quantile coverage admits at
        # least its quantile share
        assert all(spec_full.admits(r) for r in raws)
        share = sum(spec_95.admits(r) for r in raws) / len(raws)
        assert share >= 0.85

    def test_plan_refuses_without_lattices(self):
        from cgnn_tpu.data.rawbatch import RawUnsupported

        items = synthetic_dataset(4, seed=0)
        graphs = [featurize_structure(s, t, CFG, sid)
                  for sid, s, t in items]  # no keep_geometry
        with pytest.raises(RawUnsupported):
            plan_raw_spec(graphs, CFG.gdf(), CFG.radius, 12)

    def test_fingerprint_form_isolated(self):
        items = synthetic_dataset(2, seed=1)
        r0 = RawStructure.from_structure(items[0][1])
        r1 = RawStructure.from_structure(items[1][1])
        assert raw_fingerprint(r0).startswith("raw:")
        assert raw_fingerprint(r0) != raw_fingerprint(r1)
        assert raw_fingerprint(r0) == raw_fingerprint(
            RawStructure.from_structure(items[0][1]))


def _tiny_server(tmp_path, **kw):
    from scripts.serve_loadgen import make_synth_ckpt

    from cgnn_tpu.serve.server import load_server

    ckpt = str(tmp_path / "ckpt")
    make_synth_ckpt(ckpt)
    server, parts = load_server(
        ckpt, batch_size=8, rungs=2, wire="raw", watch=False,
        cache_size=kw.pop("cache_size", 0), max_wait_ms=2.0, **kw,
    )
    server.start()
    return server, parts


class TestRawServing:
    def test_mixed_wire_zero_recompiles(self, tmp_path):
        """Raw + featurized + deferred requests interleaved: every
        answer lands, forms cut flush boundaries, and the compile count
        is PINNED at warmup."""
        server, parts = _tiny_server(tmp_path)
        try:
            assert server.shape_set.raw is not None
            cfg = parts["data_cfg"].featurize_config()
            items = synthetic_dataset(16, seed=21)
            futs = []
            for i, (sid, s, t) in enumerate(items):
                if i % 2 == 0:
                    futs.append(("raw", server.submit(
                        RawStructure.from_structure(s, cif_id=sid),
                        timeout_ms=30000)))
                else:
                    g = featurize_structure(s, t, cfg, sid)
                    futs.append(("featurized", server.submit(
                        g, timeout_ms=30000)))
            wires = {}
            for want, f in futs:
                res = f.result(60)
                assert res.wire == want
                wires[res.wire] = wires.get(res.wire, 0) + 1
            assert wires["raw"] == 8 and wires["featurized"] == 8
            assert server.stats()["recompiles_after_warm"] == 0
            occ = server.stats()["ingest"]["rung_edge_occupancy"]
            assert occ and all(0 < v <= 1 for v in occ.values())
        finally:
            server.drain()

    def test_overflow_flag_routes_to_fallback(self, tmp_path):
        """Pre-check disabled: the tiny cell reaches the device, the
        IN-PROGRAM flag fires, the featurized fallback answers — never
        the truncated graph (prediction equals the precheck-on path's
        bit for bit: same fallback featurizer, same program)."""
        server, _ = _tiny_server(tmp_path, raw_precheck=False)
        try:
            tiny = RawStructure(
                np.array([[0.2, 0.2, 0.2], [0.7, 0.6, 0.5]]),
                np.eye(3) * 1.8, np.array([6, 8], np.int32))
            res = server.predict(tiny, timeout_ms=30000)
            assert res.wire == "featurized"
            st = server.stats()["ingest"]
            assert st["cap_overflows"] == 1
            assert server.stats()["recompiles_after_warm"] == 0
        finally:
            server.drain()
        server2, _ = _tiny_server(tmp_path)
        try:
            res2 = server2.predict(tiny, timeout_ms=30000)
            assert res2.wire == "featurized"
            assert server2.stats()["ingest"]["cap_overflows"] == 0
            np.testing.assert_array_equal(res.prediction,
                                          res2.prediction)
        finally:
            server2.drain()

    def test_deferred_featurize_on_pack_pool(self, tmp_path):
        """A structure too big for the raw caps is admitted instantly
        and featurized at pack time (the ISSUE-11 bugfix: admission
        never featurizes); a malformed one fails ALONE at admission."""
        server, _ = _tiny_server(tmp_path, pack_workers=1)
        try:
            big_n = server.shape_set.raw.snode_cap + 4
            rng = np.random.default_rng(0)
            big = RawStructure(rng.random((big_n, 3)), np.eye(3) * 14.0,
                               np.full(big_n, 14, np.int32))
            res = server.predict(big, timeout_ms=30000)
            assert res.wire == "featurized"
            from cgnn_tpu.serve.batcher import ServeRejection

            with pytest.raises(ServeRejection):
                server.predict(RawStructure(
                    np.zeros((1, 3)), np.eye(3) * 4.0,
                    np.array([150], np.int32)), timeout_ms=3000)
            assert server.stats()["recompiles_after_warm"] == 0
        finally:
            server.drain()

    def test_raw_cache_isolated_from_featurized(self, tmp_path):
        """A row cached by the raw program must never answer the same
        structure's featurized-fallback request (form-qualified keys:
        the two programs agree only to f32 roundoff)."""
        server, parts = _tiny_server(tmp_path, cache_size=64)
        try:
            sid, s, t = synthetic_dataset(1, seed=33)[0]
            rs = RawStructure.from_structure(s, cif_id=sid)
            r1 = server.predict(rs, timeout_ms=30000)
            r2 = server.predict(rs, timeout_ms=30000)
            assert r1.wire == "raw" and r2.cached and r2.wire == "raw"
            cfg = parts["data_cfg"].featurize_config()
            g = featurize_structure(s, t, cfg, sid)
            r3 = server.predict(g, timeout_ms=30000)
            assert not r3.cached  # different wire, different key
        finally:
            server.drain()
