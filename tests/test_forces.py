"""Force-path correctness: LJ ground truth + stored-geometry consistency.

Covers two bugs that corrupted results in silence: Lennard-Jones forces
with the sign flipped, and stored geometry that was not wrapped into the
cell.
"""

import numpy as np
import pytest

from cgnn_tpu.data.dataset import FeaturizeConfig, featurize_structure
from cgnn_tpu.data.structure import Structure, lattice_from_parameters
from cgnn_tpu.data.synthetic import (
    lj_energy_forces,
    random_structure,
    synthetic_trajectory,
)


def test_lj_forces_match_finite_differences():
    """F must equal -dE/dx of the same energy function (central diff)."""
    rng = np.random.default_rng(7)
    s = random_structure(rng, 6, 6, a_range=(5.5, 7.0))
    energy, forces = lj_energy_forces(s)
    assert np.isfinite(energy)
    inv_lat = np.linalg.inv(s.lattice)
    h = 1e-5
    cart = s.cart_coords
    for atom in range(s.num_atoms):
        for axis in range(3):
            for sign, store in ((+1, "p"), (-1, "m")):
                c = cart.copy()
                c[atom, axis] += sign * h
                e = lj_energy_forces(Structure(s.lattice, c @ inv_lat, s.numbers))[0]
                if store == "p":
                    ep = e
                else:
                    em = e
            fd_force = -(ep - em) / (2 * h)
            assert forces[atom, axis] == pytest.approx(fd_force, rel=1e-3, abs=1e-5)


def test_lj_forces_sum_to_zero():
    """Newton's third law: net force on a periodic cell is zero."""
    rng = np.random.default_rng(3)
    s = random_structure(rng, 8, 8, a_range=(5.5, 7.0))
    _, forces = lj_energy_forces(s)
    np.testing.assert_allclose(forces.sum(axis=0), 0.0, atol=1e-4)


def test_trajectory_labels_are_consistent():
    frames = synthetic_trajectory(3, seed=1, num_atoms=6)
    for _, s, e, f in frames:
        e2, f2 = lj_energy_forces(s)
        assert e == pytest.approx(e2)
        np.testing.assert_allclose(f, f2, atol=1e-6)


def test_force_training_fits_lj_ground_truth():
    """End-to-end config #5: composite energy+force loss on LJ trajectory
    frames; force MAE vs the analytic forces must drop far below the
    untrained model and below an absolute bound (measured ~0.15 at 60
    epochs; bound leaves 2x margin). BASELINE config #5, SURVEY.md §7 ph. 7."""
    import jax

    from cgnn_tpu.data.dataset import load_trajectory
    from cgnn_tpu.data.graph import pack_graphs
    from cgnn_tpu.models.forcefield import ForceFieldCGCNN
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.force_step import (
        make_force_eval_step,
        make_force_train_step,
    )
    from cgnn_tpu.train.loop import capacities_for, evaluate, fit

    cfg = FeaturizeConfig(radius=6.0, max_num_nbr=12)
    graphs = load_trajectory(320, cfg, seed=0, num_atoms=6)
    train_g, val_g = graphs[:280], graphs[280:]
    norm = Normalizer.fit(np.stack([g.target for g in train_g]))
    model = ForceFieldCGCNN(atom_fea_len=64, n_conv=3, h_fea_len=64, dmax=6.0)
    node_cap, edge_cap = capacities_for(graphs, 32)
    example = pack_graphs(train_g[:32], node_cap, edge_cap, 32)
    state = create_train_state(
        model, example, make_optimizer(optim="adam", lr=2e-3), norm,
        rng=jax.random.key(0),
    )
    ev = make_force_eval_step()
    m0 = evaluate(state, val_g, 32, node_cap, edge_cap, eval_step_fn=ev)
    state, _ = fit(
        state, train_g, val_g, epochs=60, batch_size=32,
        node_cap=node_cap, edge_cap=edge_cap, print_freq=0,
        train_step_fn=make_force_train_step(),
        eval_step_fn=ev, best_metric="force_mae", log_fn=lambda *_: None,
    )
    m1 = evaluate(state, val_g, 32, node_cap, edge_cap, eval_step_fn=ev)
    assert float(m1["force_mae"]) < 0.25 * float(m0["force_mae"])
    assert float(m1["force_mae"]) < 0.30
    assert float(m1["mae"]) < float(m0["mae"])  # energy improves too


def _crystal_frames(num: int, seed: int):
    """Periodic crystals the in-model geometry cannot take for molecules:
    tight cells (3.6-4.6 A against a 6 A radius, so most edges cross a cell
    face and carry a non-zero image offset, and the 12 nearest images leave
    in-degrees uneven) mixed with wide, sparse ones (a 9-10 A cell: atoms
    with fewer than 12 neighbours, so edge slots are padded)."""
    rng = np.random.default_rng(seed)
    frames = []
    for k in range(num):
        sparse = k % 3 == 2
        s = random_structure(
            rng, 2, 3 if sparse else 5,
            a_range=(9.0, 10.0) if sparse else (3.6, 4.6),
            min_separation=2.0,
        )
        e, f = lj_energy_forces(s)
        frames.append((f"crystal-{k:03d}", s, e, f))
    return frames


@pytest.mark.parametrize("case", ["cell", "crystal", "forward_only"])
def test_dense_force_layout_matches_coo(case):
    """--task force --layout dense (VERDICT r3 next-step #4): the dense
    edge-slot layout must reproduce the flat-COO force model exactly —
    energies, forces, AND one composite-loss training step's gradients
    (the second-order path through linear_call's gather transpose).

    The dense model reads its geometry off the layout (models/forcefield.py
    edge_distances: a broadcast centre, one lattice an atom, the slot-major
    transposable position gather), the COO model off the flat index
    vectors. ``cell``: 6 atoms in a 6-7.5 A periodic cell. ``crystal``:
    cells smaller than the radius, with padded atom and edge slots and
    rows in the overflow tier of the transpose mapping. ``forward_only``:
    the crystals packed with ``in_cap=0`` (no transpose mapping: plain
    autodiff of the gather), energies and forces."""
    import jax
    import jax.numpy as jnp

    from cgnn_tpu.data.dataset import _trajectory_graphs, load_trajectory
    from cgnn_tpu.data.graph import batch_iterator, overflow_rows
    from cgnn_tpu.models.forcefield import ForceFieldCGCNN, energy_and_forces
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.force_step import make_force_train_step
    from cgnn_tpu.train.loop import capacities_for

    cfg = FeaturizeConfig(radius=6.0, max_num_nbr=12)
    if case == "cell":
        graphs = load_trajectory(24, cfg, seed=5, num_atoms=6)
    else:
        graphs = _trajectory_graphs(_crystal_frames(24, seed=11), cfg)
    norm = Normalizer.fit(np.stack([g.target for g in graphs]))

    nc_c, ec_c = capacities_for(graphs, 8)
    coo = next(batch_iterator(graphs, 8, nc_c, ec_c))
    nc_d, ec_d = capacities_for(graphs, 8, dense_m=12)
    dense = next(batch_iterator(
        graphs, 8, nc_d, ec_d, dense_m=12,
        in_cap=0 if case == "forward_only" else None,
    ))
    if case == "forward_only":
        assert dense.in_slots is None and dense.over_slots is None
    else:
        assert dense.in_slots is not None  # two-tier transpose is packed
    if case != "cell":
        real = np.asarray(dense.edge_mask) > 0
        assert np.abs(np.asarray(dense.edge_offsets)[real]).sum(-1).mean() > 0.5
        padded_edges = ~real.reshape(-1, 12)[np.asarray(dense.node_mask) > 0]
        assert padded_edges.any() and not padded_edges.all()
        assert (np.asarray(dense.node_mask) == 0).any()
        in_degree = np.bincount(np.asarray(dense.neighbors)[real])
        assert in_degree.max() > 12
        if case == "crystal":  # those rows ride the overflow tier
            assert overflow_rows(dense) == int(
                np.maximum(in_degree - 12, 0).sum()) > 0

    m_coo = ForceFieldCGCNN(atom_fea_len=32, n_conv=2, h_fea_len=32, dmax=6.0)
    m_dense = ForceFieldCGCNN(
        atom_fea_len=32, n_conv=2, h_fea_len=32, dmax=6.0, dense_m=12
    )
    variables = m_coo.init(jax.random.key(0), coo)
    # same params apply to both layouts (layout is batching, not identity)
    e_c, f_c, _ = energy_and_forces(m_coo, variables, coo)
    e_d, f_d, _ = energy_and_forces(m_dense, variables, dense)

    gm_c, gm_d = np.asarray(coo.graph_mask) > 0, np.asarray(dense.graph_mask) > 0
    np.testing.assert_allclose(
        np.asarray(e_c)[gm_c], np.asarray(e_d)[gm_d], rtol=1e-5, atol=1e-5
    )
    nm_c, nm_d = np.asarray(coo.node_mask) > 0, np.asarray(dense.node_mask) > 0
    assert np.abs(np.asarray(f_c)[nm_c]).max() > 1e-3  # not trivially equal
    np.testing.assert_allclose(
        np.asarray(f_c)[nm_c], np.asarray(f_d)[nm_d], rtol=1e-4, atol=1e-5
    )
    if case == "forward_only":
        return

    # one training step: params gradients must agree through the nested
    # (positions-then-params) differentiation on both layouts
    step = make_force_train_step()
    tx = make_optimizer(optim="adam", lr=1e-3)
    s_c = create_train_state(m_coo, coo, tx, norm, rng=jax.random.key(1))
    s_d = create_train_state(m_dense, dense, tx, norm, rng=jax.random.key(1))
    s_c2, met_c = step(s_c, coo)
    s_d2, met_d = step(s_d, dense)
    assert float(met_c["loss_sum"]) == pytest.approx(
        float(met_d["loss_sum"]), rel=1e-4
    )
    flat_c = jax.tree_util.tree_leaves(s_c2.params)
    flat_d = jax.tree_util.tree_leaves(s_d2.params)
    for a, b in zip(flat_c, flat_d):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-4, atol=2e-5
        )


def test_keep_geometry_stores_wrapped_positions():
    """Stored positions + offsets must reproduce the neighbor-list distances
    even when input fractional coordinates fall outside [0, 1)."""
    lattice = lattice_from_parameters(5.5, 6.0, 6.5, 88.0, 92.0, 95.0)
    # deliberately out-of-cell fracs (synthetic_trajectory jitter regime)
    fracs = np.array(
        [
            [0.1, 0.2, 0.3],
            [-0.35, 0.6, 1.42],
            [0.7, 1.15, -0.2],
            [2.3, 0.4, 0.55],
        ]
    )
    s = Structure(lattice, fracs, np.array([8, 14, 26, 29], np.int32))
    g = featurize_structure(
        s, 0.0, FeaturizeConfig(radius=6.0, max_num_nbr=12), keep_geometry=True
    )
    shift = g.offsets.astype(np.float64) @ g.lattice.astype(np.float64)
    rel = g.positions[g.neighbors].astype(np.float64) + shift - g.positions[g.centers].astype(np.float64)
    recomputed = np.linalg.norm(rel, axis=1)
    np.testing.assert_allclose(recomputed, g.distances, rtol=1e-5, atol=1e-5)


# ---- the MD17-shaped pool (data/synthetic.py synthetic_md17) -----------


@pytest.fixture(scope="module")
def md17_pool():
    from cgnn_tpu.data.dataset import load_synthetic_md17

    return load_synthetic_md17(6, FeaturizeConfig(), seed=3)


def test_md17_pool_is_one_aspirin_sized_molecule(md17_pool):
    """21 atoms (9 C, 8 H, 4 O) in every frame, the same species in the same
    order, a dozen neighbours each, geometry and force labels kept."""
    for g in md17_pool:
        assert g.num_nodes == 21 and g.num_edges == 21 * 12
        assert sorted(g.numbers.tolist()) == [1] * 8 + [6] * 9 + [8] * 4
        assert np.array_equal(g.numbers, md17_pool[0].numbers)
        assert g.positions.shape == (21, 3) and g.forces.shape == (21, 3)
        assert g.lattice is not None and g.offsets.shape == (252, 3)
        assert np.all(np.bincount(g.centers, minlength=21) == 12)
    # frames differ by their jitter, not by their molecule
    assert not np.allclose(md17_pool[0].positions, md17_pool[1].positions)
    assert np.allclose(md17_pool[0].positions, md17_pool[1].positions,
                       atol=0.5)


def test_md17_pool_has_no_periodic_image_within_the_radius(md17_pool):
    """The vacuum keeps every image beyond the featurization radius (8 A)
    and the potential's cutoff: no edge crosses the cell, and the molecule's
    span leaves more than the radius to its nearest image."""
    for g in md17_pool:
        assert not np.any(g.offsets)
        span = np.max(np.linalg.norm(
            g.positions[:, None] - g.positions[None], axis=-1))
        assert np.allclose(g.lattice, np.diag(np.diag(g.lattice)))
        assert g.lattice[0, 0] - span > FeaturizeConfig().radius
        assert g.distances.max() < FeaturizeConfig().radius


def test_md17_forces_are_minus_the_gradient_of_its_own_energy():
    """Labels are consistent: central differences of the frame's energy in
    each coordinate give its force labels."""
    from cgnn_tpu.data.synthetic import synthetic_md17

    _, s, energy, forces = synthetic_md17(2, seed=5)[1]
    assert energy == pytest.approx(lj_energy_forces(s)[0])
    inv_lat = np.linalg.inv(s.lattice)
    cart, h = s.cart_coords, 1e-5
    for atom in (0, 7, 20):
        for axis in range(3):
            e = []
            for sign in (+1, -1):
                c = cart.copy()
                c[atom, axis] += sign * h
                e.append(lj_energy_forces(
                    Structure(s.lattice, c @ inv_lat, s.numbers))[0])
            assert forces[atom, axis] == pytest.approx(
                -(e[0] - e[1]) / (2 * h), rel=1e-3, abs=1e-5)
    np.testing.assert_allclose(forces.sum(axis=0), 0.0, atol=1e-4)


def test_md17_pool_is_a_function_of_its_seed(md17_pool):
    from cgnn_tpu.data.dataset import load_synthetic_md17

    again = load_synthetic_md17(4, FeaturizeConfig(), seed=3)
    for a, b in zip(again, md17_pool):
        assert np.array_equal(a.positions, b.positions)
        assert np.array_equal(a.forces, b.forces)
        assert a.target == pytest.approx(b.target) and a.cif_id == b.cif_id
    other = load_synthetic_md17(2, FeaturizeConfig(), seed=4)
    assert not np.allclose(other[0].positions, md17_pool[0].positions)


def _dots(jaxpr):
    """Every dot_general of a jaxpr and of the jaxprs inside it."""
    import jax

    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots(sub)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_float32_force_model_asks_for_float32_matmuls(dtype):
    """Left to its default the TPU rounds a float32 matmul's operands to
    bfloat16 (PERF.md section 2, PR 27: the forces then read like the
    bfloat16 trunk's). In float32 every dot of the force train step, the
    image shifts' and both reverse passes' included, carries precision
    ``highest``; the bfloat16 trunk asks for nothing. The two matmuls that
    are no layer's — the gather's transpose summing the overflow list's runs
    with a 0/1 matrix a block (ops/segment.py _run_totals; told by its
    [blocks, 128, halo + 128] left operand) and the frames' energies summed
    with the [N, G] 0/1 matrix of the atoms' frames (_segment_totals, PR 50;
    told by that operand) — ask for ``highest`` whatever the trunk: their
    sums stand in for a scatter-add's, and the position gather's cotangent
    and the atoms' energies are float32 in both trunks."""
    import jax
    import jax.numpy as jnp

    from cgnn_tpu.config import DataConfig, ModelConfig, build_model
    from cgnn_tpu.data.dataset import load_synthetic_md17
    from cgnn_tpu.data.graph import batch_iterator, capacities_for
    from cgnn_tpu.ops.segment import _RUN_BLOCK
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.force_step import make_force_train_step

    graphs = load_synthetic_md17(8)
    nc, ec = capacities_for(graphs, 4, dense_m=12, snug=True)
    batch = next(batch_iterator(graphs, 4, nc, ec, dense_m=12, snug=True))
    model = build_model(
        ModelConfig(atom_fea_len=8, n_conv=2, h_fea_len=8, dtype=dtype,
                    dense_m=12), DataConfig(), "force")
    state = create_train_state(
        model, batch, make_optimizer(optim="adam", lr=1e-3),
        Normalizer(mean=jnp.zeros(1), std=jnp.ones(1)))
    jaxpr = jax.make_jaxpr(make_force_train_step())(state, batch).jaxpr
    dots = list(_dots(jaxpr))
    # embedding, 2 x fc_full, 2 readout layers, the image shifts: forward,
    # and their transposes under one and two reverse passes
    assert len(dots) > 12
    highest = (jax.lax.Precision.HIGHEST,) * 2
    run_sums = frame_sums = 0
    frames = (batch.node_capacity, batch.graph_capacity)
    assert frames[1] not in (8, 16)  # no layer's width
    for eqn in dots:
        got = eqn.params["precision"]
        lhs = eqn.invars[0].aval.shape
        run_sum = len(lhs) == 3 and lhs[1] == _RUN_BLOCK < lhs[2]
        frame_sum = lhs == frames
        if run_sum or frame_sum:
            run_sums += run_sum
            frame_sums += frame_sum
            assert tuple(got) == highest, eqn
            assert eqn.params["preferred_element_type"] == jnp.float32
        elif dtype == "float32":
            assert tuple(got) == highest, eqn
        else:
            assert got is None, eqn
    assert run_sums >= 3  # the two convs' and the position gather's
    assert frame_sums == 1  # forward; its transposes are row gathers
