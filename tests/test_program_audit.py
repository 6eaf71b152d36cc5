"""graftaudit tests (ISSUE 8): IR-level invariants + the roofline ledger.

Three layers, mirroring test_analysis.py:

- broken-program fixtures: toy programs with donation deliberately
  broken, an f64 sneaked in, or a ``pure_callback`` added — each must
  trip EXACTLY its check and stay quiet on the others;
- the live-repo pin: the real entry-program registry lowers and audits
  CLEAN (the IR-level twin of graftcheck's live-repo test), and the
  committed AUDIT_LEDGER.json carries a roofline row for every
  (rung, staging form) predict program plus the train step;
- the budget gate: ``diff_ledgers`` fails on a dropped program/key or a
  >threshold regression of a lower-is-better key, shrugs at
  improvements, and downgrades numeric drift to a warning under jax
  version skew — demonstrated end-to-end through the
  ``bench_regress.py --ledger`` CLI on a seeded regression.
"""

import copy
import json
import os
import subprocess
import sys
import warnings

import jax
import numpy as np
import pytest

from cgnn_tpu.analysis.program_audit import (
    CHECKS,
    LEDGER_GATE_KEYS,
    Program,
    check_donation,
    check_f64,
    check_hostcalls,
    check_identity,
    diff_ledgers,
    fingerprint,
    near_duplicates,
    run_audit,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LEDGER_PATH = os.path.join(REPO, "AUDIT_LEDGER.json")

F32 = jax.ShapeDtypeStruct((8,), np.float32)


def _lowered_text(jitted, *avals) -> str:
    with warnings.catch_warnings():
        # the broken-donation fixture provokes jax's own donation
        # warning on purpose
        warnings.simplefilter("ignore")
        return jitted.lower(*avals).as_text()


def _program(name, text, donated=0, callbacks=0) -> Program:
    p = Program(name=name, donated_leaves=donated, callbacks=callbacks)
    p.text = text
    p.lowered = object()  # marks it as successfully lowered
    return p


def _other_checks_quiet(p: Program, tripped: str):
    """The fixture trips EXACTLY its check: every other per-program
    check stays quiet."""
    by_check = {
        "GA-DONATION": check_donation,
        "GA-F64": check_f64,
        "GA-HOSTCALL": check_hostcalls,
    }
    for check_id, fn in by_check.items():
        if check_id == tripped:
            continue
        assert fn(p) == [], f"{check_id} fired on the {tripped} fixture"


class TestBrokenProgramFixtures:
    def test_broken_donation_is_flagged(self):
        # the donated input's shape matches no output, so XLA cannot
        # alias it: jax drops the donation with a warning and the
        # program silently pays a copy — the exact failure mode
        step = jax.jit(lambda x: x[:1].sum(), donate_argnums=0)
        p = _program("toy/broken-donation", _lowered_text(step, F32),
                     donated=1)
        findings = check_donation(p)
        assert [f.check for f in findings] == ["GA-DONATION"]
        assert "donation silently not applied" in findings[0].message
        _other_checks_quiet(p, "GA-DONATION")

    def test_applied_donation_is_clean(self):
        step = jax.jit(lambda x: x + 1, donate_argnums=0)
        p = _program("toy/good-donation", _lowered_text(step, F32),
                     donated=1)
        assert p.text.count("tf.aliasing_output") == 1
        assert check_donation(p) == []

    def test_f64_sneak_is_flagged(self):
        with jax.enable_x64(True):
            f64_aval = jax.ShapeDtypeStruct((4,), np.float64)
            step = jax.jit(lambda x: x * 2.0)
            p = _program("toy/f64", _lowered_text(step, f64_aval))
        findings = check_f64(p)
        assert [f.check for f in findings] == ["GA-F64"]
        _other_checks_quiet(p, "GA-F64")

    def test_f32_program_passes_f64_check(self):
        step = jax.jit(lambda x: x * 2.0)
        p = _program("toy/f32", _lowered_text(step, F32))
        assert check_f64(p) == []

    def test_pure_callback_is_flagged(self):
        step = jax.jit(lambda x: jax.pure_callback(
            np.asarray, jax.ShapeDtypeStruct((8,), np.float32), x))
        p = _program("toy/callback", _lowered_text(step, F32))
        findings = check_hostcalls(p)
        assert [f.check for f in findings] == ["GA-HOSTCALL"]
        assert "callback" in findings[0].message
        _other_checks_quiet(p, "GA-HOSTCALL")

    def test_sanctioned_callback_count_passes(self):
        step = jax.jit(lambda x: jax.pure_callback(
            np.asarray, jax.ShapeDtypeStruct((8,), np.float32), x))
        p = _program("toy/tap", _lowered_text(step, F32), callbacks=1)
        assert check_hostcalls(p) == []

    def test_unknown_custom_call_is_flagged(self):
        p = _program("toy/weird", 'stablehlo.custom_call @weird_target(%0)')
        findings = check_hostcalls(p)
        assert [f.check for f in findings] == ["GA-HOSTCALL"]
        assert "weird_target" in findings[0].message

    def test_constant_only_twins_are_near_duplicates(self):
        # the Python-scalar-leakage shape: two programs identical except
        # for a burned-in constant
        a = _lowered_text(jax.jit(lambda x: x + np.float32(1.0)), F32)
        b = _lowered_text(jax.jit(lambda x: x + np.float32(2.0)), F32)
        assert fingerprint(a) != fingerprint(b)
        pairs = near_duplicates([("prog/a", a), ("prog/b", b)])
        assert pairs == [("prog/a", "prog/b")]
        findings = check_identity(
            [_program("prog/a", a), _program("prog/b", b)],
            predict_expected=0)
        assert "GA-IDENT" in [f.check for f in findings]

    def test_near_duplicate_pair_names_the_constant_variant(self):
        # byte-identical twins in the group are the duplicate finding's
        # job; the near-duplicate pair must name programs with DISTINCT
        # exact fingerprints so the report points at the real variant
        a = _lowered_text(jax.jit(lambda x: x + np.float32(1.0)), F32)
        b = _lowered_text(jax.jit(lambda x: x + np.float32(2.0)), F32)
        pairs = near_duplicates([("p/a1", a), ("p/a2", a), ("p/b", b)])
        assert len(pairs) == 1
        assert "p/b" in pairs[0], pairs

    def test_structurally_distinct_programs_are_not_duplicates(self):
        a = _lowered_text(jax.jit(lambda x: x + np.float32(1.0)), F32)
        b = _lowered_text(jax.jit(lambda x: x * x), F32)
        assert near_duplicates([("prog/a", a), ("prog/b", b)]) == []
        assert check_identity(
            [_program("prog/a", a), _program("prog/b", b)],
            predict_expected=0) == []

    def test_identical_programs_are_flagged(self):
        a = _lowered_text(jax.jit(lambda x: x + 1), F32)
        findings = check_identity(
            [_program("predict/a", a), _program("predict/b", a)],
            predict_expected=2)
        assert [f.check for f in findings] == ["GA-IDENT"]
        assert "IDENTICAL" in findings[0].message

    def test_predict_count_mismatch_is_flagged(self):
        findings = check_identity(
            [_program("predict/rung0/full",
                      _lowered_text(jax.jit(lambda x: x + 1), F32))],
            predict_expected=6)
        assert [f.check for f in findings] == ["GA-IDENT"]
        assert "expected" in findings[0].message


class TestShardBudgetFixtures:
    """GA-SHARD (ISSUE 10): the replicated-batch mistake must trip the
    gate; the correctly batch-sharded twin must pass it."""

    def _mesh_fixtures(self):
        from jax.sharding import PartitionSpec as P

        from cgnn_tpu.parallel.executor import MeshExecutor

        ex = MeshExecutor(jax.devices())
        n = len(ex)

        def body(w, b):
            return (b @ w).sum(axis=-1)

        good = jax.jit(jax.shard_map(
            body, mesh=ex.mesh, in_specs=(P(), P("data")),
            out_specs=P("data"), check_vma=False))
        # the classic mistake: the batch staged WITHOUT its sharding —
        # every device holds (and reads) the full stack
        bad = jax.jit(jax.shard_map(
            lambda w, b: body(w, b)[:1], mesh=ex.mesh,
            in_specs=(P(), P()), out_specs=P("data"), check_vma=False))
        w_av = jax.ShapeDtypeStruct((64, 64), np.float32)
        b_av = jax.ShapeDtypeStruct((n, 128, 64), np.float32)
        budget = 64 * 64 * 4 + (n * 128 * 64 * 4) // n
        return good, bad, (w_av, b_av), budget

    def test_replicated_batch_is_flagged(self):
        from cgnn_tpu.analysis.program_audit import check_shard_budget

        good, bad, avals, budget = self._mesh_fixtures()
        mem = bad.lower(*avals).compile().memory_analysis()
        p = Program(name="fixture/replicated", arg_byte_budget=budget)
        findings = check_shard_budget(p, mem)
        assert len(findings) == 1
        assert findings[0].check == "GA-SHARD"
        assert "REPLICATED" in findings[0].message

    def test_sharded_batch_passes(self):
        from cgnn_tpu.analysis.program_audit import check_shard_budget

        good, bad, avals, budget = self._mesh_fixtures()
        mem = good.lower(*avals).compile().memory_analysis()
        p = Program(name="fixture/sharded", arg_byte_budget=budget)
        assert check_shard_budget(p, mem) == []

    def test_unbudgeted_program_is_ungated(self):
        from cgnn_tpu.analysis.program_audit import check_shard_budget

        _, bad, avals, _ = self._mesh_fixtures()
        mem = bad.lower(*avals).compile().memory_analysis()
        assert check_shard_budget(Program(name="x"), mem) == []

    def test_unmeasurable_args_is_itself_a_finding(self):
        from cgnn_tpu.analysis.program_audit import check_shard_budget

        class _NoArgs:
            argument_size_in_bytes = 0

        findings = check_shard_budget(
            Program(name="x", arg_byte_budget=100), _NoArgs())
        assert len(findings) == 1 and findings[0].check == "GA-SHARD"


class TestLowerTrainProgram:
    def test_one_lowering_path_for_train_programs(self):
        """`lower_train_program` is the ONE jit/lower plumbing for
        train steps (used by the audit registry via jit_train_step and
        by scripts/hlo_dump.py): it lowers on abstract avals, with the
        donation applied."""
        from cgnn_tpu.analysis.program_audit import lower_train_program
        from cgnn_tpu.data.dataset import (
            FeaturizeConfig,
            load_synthetic_mp,
        )
        from cgnn_tpu.data.graph import batch_iterator, capacities_for
        from cgnn_tpu.models import CrystalGraphConvNet
        from cgnn_tpu.train import (
            Normalizer,
            create_train_state,
            make_optimizer,
        )

        graphs = load_synthetic_mp(8, FeaturizeConfig(radius=6.0,
                                                      max_num_nbr=8),
                                   seed=0)
        nc, ec = capacities_for(graphs, 4, snug=True)
        batch = next(batch_iterator(graphs, 4, nc, ec, snug=True))
        model = CrystalGraphConvNet(atom_fea_len=8, n_conv=1,
                                    h_fea_len=16)
        state = create_train_state(
            model, batch, make_optimizer(),
            Normalizer.fit(np.stack([g.target for g in graphs])),
        )
        text = lower_train_program(state, batch).as_text()
        n_leaves = len(jax.tree_util.tree_leaves(state))
        assert text.count("tf.aliasing_output") >= n_leaves
        # guard-wrapped variant lowers through the same path
        guarded = lower_train_program(state, batch, guard=True).as_text()
        assert guarded.count("tf.aliasing_output") >= n_leaves


@pytest.fixture(scope="module")
def live_audit():
    """One no-compile audit of the real entry-program registry, shared
    by every live-repo test (lowering ~10 programs is the slow part)."""
    return run_audit(compile=False)


class TestLiveRepo:
    def test_live_repo_audit_is_clean(self, live_audit):
        """THE pin: the real train/predict/expander programs obey the
        IR-level catalog. A finding here means fix the program — never
        weaken the check (INVARIANTS.md policy)."""
        findings, _, _ = live_audit
        assert not findings, (
            "graftaudit findings on the live repo:\n"
            + "\n".join(f.format() for f in findings)
        )

    def test_every_ladder_program_lowers(self, live_audit):
        _, ledger, programs = live_audit
        lowered = {p.name for p in programs if p.lowered is not None}
        expected = ledger["meta"]["predict_programs_expected"]
        rungs = len(ledger["meta"]["ladder"]["shapes"])
        # the engine dimension (ISSUE 10) x the staging-form dimension
        # (ISSUE 11): compact + full + raw per rung for the
        # single-device ladder AND the mesh-sharded twin (the conftest
        # mesh has 8 devices, so the mesh engine registers)
        assert ledger["meta"]["mesh_devices"] >= 2
        assert expected == 3 * rungs * 2
        predict = {n for n in lowered if n.startswith("predict/")}
        assert len(predict) == expected, sorted(predict)
        mesh = {n for n in predict if n.startswith("predict/mesh/")}
        assert len(mesh) == 3 * rungs, sorted(mesh)
        assert "train/coo" in lowered
        assert "train/coo+guard" in lowered
        assert "train/coo+tap@step" in lowered
        assert "expander/rung0" in lowered
        assert "ops/neighbor_search/rung0" in lowered

    def test_mesh_programs_carry_shard_budgets(self, live_audit):
        """Every mesh-sharded predict program is GA-SHARD-budgeted —
        an unbudgeted one would make the replication gate vacuous."""
        _, _, programs = live_audit
        mesh = [p for p in programs if p.name.startswith("predict/mesh/")]
        assert mesh
        for p in mesh:
            assert p.arg_byte_budget > 0, p.name

    def test_skips_are_known_backend_gaps_only(self, live_audit):
        _, ledger, _ = live_audit
        known = {"train/dense", "train/dp", "predict/mesh"}
        assert set(ledger["meta"]["skipped"]) <= known, (
            "unexpected skip — a program stopped lowering: "
            f"{ledger['meta']['skipped']}"
        )


    def test_committed_ledger_lists_exactly_the_built_programs(
            self, live_audit):
        """File and code go together: ``diff_ledgers`` fails on a program
        the code dropped, and a program the code gained has no budget row
        until the file has one. On the suite's 8 devices nothing is
        skipped, so the two sets are equal."""
        _, ledger, programs = live_audit
        with open(LEDGER_PATH) as f:
            committed = json.load(f)
        assert not ledger["meta"]["skipped"], ledger["meta"]["skipped"]
        assert set(committed["programs"]) == {p.name for p in programs}


class TestCommittedLedger:
    """The committed AUDIT_LEDGER.json is the CI budget baseline."""

    @pytest.fixture(scope="class")
    def ledger(self):
        with open(LEDGER_PATH) as f:
            return json.load(f)

    def test_every_program_has_roofline_keys(self, ledger):
        assert ledger["programs"], "empty ledger"
        for name, entry in ledger["programs"].items():
            for key in ("flops", "bytes", "intensity_flops_per_byte",
                        "bytes_per_flop", "peak_temp_bytes"):
                assert key in entry, f"{name} missing {key}"
            assert entry["flops"] > 0, name
            assert entry["bytes"] > 0, name

    def test_ladder_coverage(self, ledger):
        names = set(ledger["programs"])
        rungs = len(ledger["meta"]["ladder"]["shapes"])
        for rung in range(rungs):
            for form in ("compact", "full", "raw"):
                assert f"predict/rung{rung}/{form}" in names
        assert "train/coo" in names
        # the ISSUE-11 neighbor-search program rides its GA-ROOFLINE
        # budget in the baseline: dropping either diffs red
        entry = ledger["programs"].get("ops/neighbor_search/rung0")
        assert entry is not None and entry.get("byte_budget", 0) > 0
        assert entry["bytes"] <= entry["byte_budget"] * 2.0
        assert ledger["meta"]["gate_keys"] == list(LEDGER_GATE_KEYS)

    def test_mesh_engine_coverage(self, ledger):
        """The committed baseline carries the mesh-sharded predict rows
        with their GA-SHARD budgets: a future session dropping them (or
        their budgets) diffs red, not silent."""
        rungs = len(ledger["meta"]["ladder"]["shapes"])
        for rung in range(rungs):
            for form in ("compact", "full", "raw"):
                entry = ledger["programs"].get(
                    f"predict/mesh/rung{rung}/{form}")
                assert entry is not None, (rung, form)
                assert entry.get("arg_byte_budget", 0) > 0
                assert 0 < entry.get("arg_bytes", 0) <= (
                    entry["arg_byte_budget"] * 1.5)

    def test_train_step_donation_survived_compilation(self, ledger):
        # alias_bytes > 0 is the compiled-side proof donation applied
        for name, entry in ledger["programs"].items():
            if name.startswith("train/"):
                assert entry["alias_bytes"] > 0, (
                    f"{name}: no aliased bytes in the compiled "
                    "executable — donation not applied"
                )


def _ledger_payload(**programs) -> dict:
    return {"meta": {"jax": jax.__version__}, "programs": programs}


ROW = {"flops": 100.0, "bytes": 1000.0, "bytes_per_flop": 10.0,
       "peak_temp_bytes": 512}


class TestDiffLedgers:
    def test_clean_roundtrip(self):
        old = _ledger_payload(a=dict(ROW))
        assert diff_ledgers(old, copy.deepcopy(old))["regressions"] == []

    def test_improvement_passes(self):
        old = _ledger_payload(a=dict(ROW))
        new = _ledger_payload(a={**ROW, "bytes": 500.0})
        assert diff_ledgers(old, new)["regressions"] == []

    def test_small_drift_within_threshold_passes(self):
        old = _ledger_payload(a=dict(ROW))
        new = _ledger_payload(a={**ROW, "bytes": 1100.0})
        assert diff_ledgers(old, new)["regressions"] == []

    def test_regression_beyond_threshold_fails(self):
        old = _ledger_payload(a=dict(ROW))
        new = _ledger_payload(a={**ROW, "bytes": 1250.0})
        regs = diff_ledgers(old, new)["regressions"]
        assert len(regs) == 1 and regs[0]["key"] == "a.bytes"
        assert "REGRESSION" in regs[0]["note"]

    def test_zero_baseline_to_nonzero_is_a_regression(self):
        # a zero budget has no ratio — the expander's peak_temp_bytes=0
        # must not be a free pass to start materializing temps
        old = _ledger_payload(a={**ROW, "peak_temp_bytes": 0})
        new = _ledger_payload(a={**ROW, "peak_temp_bytes": 4096})
        regs = diff_ledgers(old, new)["regressions"]
        assert [r["key"] for r in regs] == ["a.peak_temp_bytes"]
        assert "budget was 0" in regs[0]["note"]

    def test_zero_to_zero_passes(self):
        old = _ledger_payload(a={**ROW, "peak_temp_bytes": 0})
        assert diff_ledgers(old, copy.deepcopy(old))["regressions"] == []

    def test_dropped_program_is_a_regression(self):
        old = _ledger_payload(a=dict(ROW), b=dict(ROW))
        new = _ledger_payload(a=dict(ROW))
        regs = diff_ledgers(old, new)["regressions"]
        assert [r["key"] for r in regs] == ["b"]
        assert "DROPPED" in regs[0]["note"]

    def test_dropped_gate_key_is_a_regression(self):
        old = _ledger_payload(a=dict(ROW))
        entry = dict(ROW)
        del entry["peak_temp_bytes"]
        regs = diff_ledgers(old, _ledger_payload(a=entry))["regressions"]
        assert [r["key"] for r in regs] == ["a.peak_temp_bytes"]

    def test_version_skew_downgrades_numeric_drift_to_warning(self):
        old = _ledger_payload(a=dict(ROW))
        old["meta"]["jax"] = "0.0.1-other"
        new = _ledger_payload(a={**ROW, "bytes": 2000.0})
        diff = diff_ledgers(old, new)
        assert diff["version_skew"]
        assert diff["regressions"] == []
        assert [w["key"] for w in diff["warnings"]] == ["a.bytes"]

    def test_version_skew_keeps_structural_drops_hard(self):
        old = _ledger_payload(a=dict(ROW), b=dict(ROW))
        old["meta"]["jax"] = "0.0.1-other"
        new = _ledger_payload(a=dict(ROW))
        assert [r["key"] for r in
                diff_ledgers(old, new)["regressions"]] == ["b"]


class TestCLI:
    def test_list_checks(self):
        res = subprocess.run(
            [sys.executable, os.path.join(REPO, "graftaudit.py"),
             "--list-checks"],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )
        assert res.returncode == 0, res.stdout + res.stderr
        for check in CHECKS:
            assert check in res.stdout

    def _bench_regress(self, tmp_path, baseline, fresh):
        base = tmp_path / "baseline.json"
        new = tmp_path / "new.json"
        base.write_text(json.dumps(baseline))
        new.write_text(json.dumps(fresh))
        return subprocess.run(
            [sys.executable, os.path.join(REPO, "scripts",
                                          "bench_regress.py"),
             "--dir", str(tmp_path), "--github",
             "--ledger", str(base), str(new)],
            cwd=REPO, capture_output=True, text=True, timeout=120,
        )

    def test_budget_gate_fails_on_seeded_regression(self, tmp_path):
        """The acceptance pin: seed a regression against the committed
        ledger (baseline bytes halved => today's real bytes are 2x the
        budget) and the gate must go red with an ::error annotation."""
        with open(LEDGER_PATH) as f:
            baseline = json.load(f)
        seeded = copy.deepcopy(baseline)
        victim = sorted(seeded["programs"])[0]
        seeded["programs"][victim]["bytes"] *= 0.5
        res = self._bench_regress(tmp_path, seeded, baseline)
        assert res.returncode == 1, res.stdout + res.stderr
        assert "::error title=audit budget::" in res.stdout
        assert f"{victim}.bytes" in res.stdout

    def test_budget_gate_passes_on_identity(self, tmp_path):
        with open(LEDGER_PATH) as f:
            baseline = json.load(f)
        res = self._bench_regress(tmp_path, baseline, baseline)
        assert res.returncode == 0, res.stdout + res.stderr
        assert "audit budgets ok" in res.stdout
