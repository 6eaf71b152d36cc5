"""The energy-and-force model against its plain reference
(``benchmark/reference/force_ref.py``), at the published widths on a pool a
CPU test can hold: energies, forces, the composite loss, its parameter
gradient (a reverse pass over a reverse pass) and three Adam steps, through
the normal path (the scan driver's own one-step programs, the dense layout
with real padding and a real overflow tier), in float32 to roundoff and in
the cell's precision to the cell's limits; the bfloat16 control fails them.
"""

from __future__ import annotations

import copy
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import counts_force, run, weights_force  # noqa: E402
from benchmark.kinds import force_train  # noqa: E402
from benchmark.reference import force_ref as ref  # noqa: E402

SEED = 2**31 + 11
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def _driver(compute: str):
    """The cell's own driver at its published widths over 60 frames in
    batches of 13, set up (weights seeded, first three steps driven)."""
    cell = run.Cell(MANIFEST, "force.train")
    cell.config = copy.deepcopy(cell.config)
    cell.config["precision"]["compute"] = compute
    cell.config["data"].update(n=60, resident_copies=1)
    cell.config["train"]["batch_size"] = 12
    d = force_train.Driver(run.Context(cell, SEED, False))
    d.setup()
    return d


@pytest.fixture(scope="module")
def f32():
    return _driver("float32")


@pytest.fixture(scope="module")
def cell_precision():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "md17-force.json")) as f:
        compute = json.load(f)["precision"]["compute"]
    return _driver(compute)


def test_the_batches_have_padding_and_an_overflow_tier(f32):
    """What the comparison runs on is the dense layout as deployed: node
    slots beyond the real atoms, graph slots beyond the real frames, and
    incoming edges beyond rank M in the overflow tier."""
    batch = f32._first_batch()
    assert np.asarray(batch.edges).ndim == 3
    assert 0 < np.asarray(batch.node_mask).sum() < batch.node_capacity
    assert 0 < np.asarray(batch.graph_mask).sum() < batch.graph_capacity
    from cgnn_tpu.data.graph import overflow_rows

    assert overflow_rows(batch) > 0


def test_energies_and_forces_agree_in_float32(f32):
    import jax.numpy as jnp

    state = f32._seeded_state(SEED)
    batch = f32._first_batch()
    energy, forces = (np.asarray(x) for x in f32._predict(state, batch))
    frames = f32.members[0][0]
    want_e, want_f = ref.energies_and_forces(
        ref.as_jnp(f32.params0),
        ref.coo_batch([force_train.frame_as_ref(g) for g in frames]),
        f32.config["featurize"])
    n_atoms = sum(g.num_nodes for g in frames)
    got_e = (energy[:len(frames)] - f32.t_mean) / f32.t_std
    np.testing.assert_allclose(got_e, np.asarray(want_e), atol=2e-4)
    np.testing.assert_allclose(forces[:n_atoms] / f32.t_std,
                               np.asarray(want_f), atol=2e-5, rtol=2e-4)
    # padding rows carry no force and padding frames no energy
    assert not np.any(forces[n_atoms:]) and not np.any(energy[len(frames):])
    # the seeded model's mean energy error is the offset the kind sets
    labels = (np.array([float(g.target[0]) for g in frames]) - f32.t_mean
              ) / f32.t_std
    assert float(np.mean(got_e - labels)) == pytest.approx(
        force_train.ENERGY_OFFSET, abs=1e-3)
    assert float(jnp.std(want_f)) > 0


def test_loss_gradient_and_three_steps_agree_in_float32(f32):
    rows = {r["name"]: r["value"] for r in f32.check()}
    assert set(rows) == {
        "loss_step1_rel", "loss_step2_rel", "loss_step3_rel",
        "grad_diff_median_leaf", "grad_norm_worst_leaf",
        "delta_norm_median_leaf", "grad_diff_off_energy_median_leaf",
        "force_diff_rel"}
    assert all(v < 5e-5 for v in rows.values()), rows
    # leaf by leaf, not only in the middle
    for got, want in zip(*(map(np.asarray, _leaves(t))
                           for t in (f32.got["grad"], f32.want["grad"]))):
        np.testing.assert_allclose(
            got, want, atol=2e-4 * float(np.abs(want).max()))
    assert all(n > 0 for n in f32.want["delta_norm"].values())
    assert len(set(f32.check_batches)) == 3  # three steps, three batches


def _leaves(tree):
    import jax

    return jax.tree_util.tree_leaves(tree)


def test_the_cell_s_precision_reads_inside_the_cell_s_limits(cell_precision):
    rows = cell_precision.check()
    assert all(r["value"] <= r["limit"] for r in rows), rows


@pytest.mark.parametrize("which", ["f32", "cell_precision"])
def test_the_bfloat16_control_fails_the_cell_s_limits(which, request):
    """The reference with every matmul operand rounded to bfloat16, the
    precision below the float32 the configuration states, put in the
    program's place, reads over a limit of what the TIMED train step
    produced (its gradient, off the mean energy's direction) and over the
    forces' limit; the reference itself reads 0."""
    d = request.getfixturevalue(which)
    d.check()
    limits = d.config["limits"]["force_train"]
    same = force_train.compare(d.want, d.want, limits)
    assert all(r["value"] == 0 for r in same)
    ctrl = {r["name"]: r for r in d.check(control_mm=ref.mm_bf16)}
    for name in ("grad_diff_off_energy_median_leaf", "force_diff_rel"):
        assert ctrl[name]["value"] > ctrl[name]["limit"], ctrl[name]


def test_the_trunk_in_bfloat16_is_not_correct():
    """The program's own lower precision (train.py --task force --bf16) comes
    out as not correct through the cell's comparison, by the timed step's
    gradient and by the forces."""
    rows = {r["name"]: r for r in _driver("bfloat16").check()}
    for name in ("grad_diff_off_energy_median_leaf", "force_diff_rel"):
        assert rows[name]["value"] > rows[name]["limit"], rows[name]


def test_a_dropped_second_derivative_is_not_correct(f32, monkeypatch):
    """A gradient that leaves out the path through the forces (the outer
    reverse pass over the inner one) reads over the limits: what the cell
    exists for is what the comparison guards."""
    import jax
    import jax.numpy as jnp

    f32.check()
    tr, featurize = f32.config["train"], f32.config["featurize"]
    batch = ref.coo_batch([force_train.frame_as_ref(g)
                           for g in f32.members[f32.check_batches[0]][0]])

    def energy_loss_only(p):
        e, f = ref.energies_and_forces(p, batch, featurize)
        f = jax.lax.stop_gradient(f)  # forces read, not differentiated
        e_loss = jnp.mean((e - (batch["energies"] - f32.t_mean)
                           / f32.t_std) ** 2)
        return tr["energy_weight"] * e_loss + tr["force_weight"] * jnp.mean(
            (f - batch["forces"] / f32.t_std) ** 2)

    with jax.default_matmul_precision("highest"):
        broken = jax.grad(energy_loss_only)(ref.as_jnp(f32.params0))
    got = dict(f32.want, grad=jax.tree_util.tree_map(np.asarray, broken))
    limits = f32.config["limits"]["force_train"]
    rows = {r["name"]: r for r in force_train.compare(got, f32.want, limits)}
    row = rows["grad_diff_off_energy_median_leaf"]
    assert row["value"] > 3 * row["limit"], row


def test_off_energy_diff_by_hand():
    """An error along the energy direction reads 0; one across it reads its
    size against what is left of the reference."""
    want = {"a": np.array([3.0, 4.0])}
    u = {"a": np.array([1.0, 0.0])}
    assert ref.off_energy_diff({"a": np.array([7.0, 4.0])}, want, u) == 0
    assert ref.off_energy_diff({"a": np.array([3.0, 5.0])}, want, u) == \
        pytest.approx(0.25)
    assert ref.rel_diff(np.array([3.0, 5.0]), want["a"]) == pytest.approx(0.2)


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(ROOT, "benchmark", "reference",
                           "force_ref.py")) as f:
        text = f.read()
    code = [ln for ln in text.splitlines()
            if ln.startswith(("import ", "from "))]
    assert code and not any("cgnn_tpu" in ln for ln in code)
    assert 'default_matmul_precision("highest")' in text
    assert "Precision.HIGHEST" in text


def test_seeded_force_weights(f32):
    """The benchmark's weights have the model's own tree, depend on the seed
    alone, and have no trivial leaf."""
    import jax

    model = f32.config["model"]
    a = weights_force.make_weights(5, model, 92, 41)
    b = weights_force.make_weights(5, model, 92, 41)
    c = weights_force.make_weights(2**31 + 5, model, 92, 41)
    own = f32.model.init(jax.random.key(0), f32._first_batch())["params"]
    shape = lambda t: jax.tree_util.tree_map(np.shape, t)  # noqa: E731
    assert shape(a) == shape(jax.tree_util.tree_map(np.asarray, own))
    for x, y, z in zip(*map(_leaves, (a, b, c))):
        assert np.array_equal(x, y) and not np.allclose(x, z)
        assert np.asarray(x).dtype == np.float32
        assert float(np.abs(np.asarray(x)).min()) > 0
    assert counts_force.n_params(model, 92, 41) == sum(
        np.asarray(x).size for x in _leaves(a))


def test_force_counts_against_hand_worked_numbers():
    model = {"atom_fea_len": 2, "h_fea_len": 3, "n_conv": 2}
    # N=10 atoms, E=40 edges, K=5 filters, A=7 atom features
    got = counts_force.step_counts(10, 40, model, 5, 7)
    node, nbr, edge = 2 * 10 * 2 * 4, 2 * 40 * 2 * 4, 2 * 40 * 5 * 4
    head = 2 * 10 * 2 * 3 + 2 * 10 * 3
    # first conv: no inner pass over v (3 units), later convs 6; the edge
    # term 5 in every conv; the readout 6
    assert got["flops"] == (3 + 6) * (node + nbr) + 2 * 5 * edge + 6 * head
    p = (7 + 1) * 2 + 2 * ((4 + 5) * 4 + 4) + 3 * 3 + 4
    assert counts_force.n_params(model, 7, 5) == p
    nf = 10 * 2 * 2
    assert got["bytes"] == (2 * (13 * nf + 28 * 40) + 48 * 10
                            + (40 + 2 * nf) + 4 * nf + 24 * p)
    # the published widths, a step of the cell: FLOPs bind on a v5e
    real = counts_force.step_counts(
        5397, 64764, {"atom_fea_len": 64, "h_fea_len": 128, "n_conv": 3},
        41, 92)
    assert real["flops"] / 197e12 > real["bytes"] / 819e9
