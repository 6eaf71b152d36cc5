"""Compiled-program auditor: IR-level invariants + the roofline ledger.

graftcheck (rules.py) proves SOURCE-level invariants; the repo's
costliest incidents live a layer lower, in what XLA actually compiles:
donation silently not applied (the buffer-copy-per-step failure mode),
f64 creep doubling HBM traffic, stray host callbacks serializing the
device stream, and near-duplicate programs compiled per rung from a
leaked Python scalar. This module lowers the repo's REAL entry
programs — the train step (plain, guard-wrapped, telemetry-tapped,
dense, data-parallel where the backend allows), the serving/predict
program for every (rung, staging form) in the warm shape ladder, and
the compact expander — via ``jax.jit(...).lower()`` on abstract args
(no device dispatch), then statically audits the StableHLO/compiled
artifacts:

- **GA-DONATION** — input-output aliasing actually present for every
  ``donate_argnums`` leaf (``tf.aliasing_output`` in the StableHLO,
  ``alias_size_in_bytes`` in the compiled memory stats);
- **GA-F64** — no f64 values anywhere in any module;
- **GA-HOSTCALL** — the only callback custom-call in any program is
  the sanctioned observe/stream tap, and only in the telemetry=step
  program; every other custom-call target must be allowlisted;
- **GA-IDENT** — the ladder produces exactly programs x rungs x forms
  distinct programs PER ENGINE (the mesh-sharded predict programs are
  registered alongside the single-device ladder), and no two differ
  only in burned-in constants (the Python-scalar-leakage recompile
  shape);
- **GA-SHARD** — a mesh-sharded program's per-device argument bytes
  stay within the replicated-params + batch/N model, so a batch
  silently replicated to every device (the classic NamedSharding
  mistake) blocks CI;
- the **roofline ledger** — per-program FLOPs, memory bytes, and peak
  temp memory from XLA ``cost_analysis``/``memory_analysis``, with
  arithmetic intensity, written to ``AUDIT_LEDGER.json`` and gated in
  CI as budgets (``diff_ledgers``: dropped key or >20% regression of a
  lower-is-better key fails, mirroring scripts/bench_regress.py).

``graftaudit.py`` is the CLI; tests/test_program_audit.py holds the
broken-program fixtures (donation deliberately broken, an f64 sneaked
in, a pure_callback added) that each check must catch.

jax imports are LAZY (function-local): ``diff_ledgers`` and the check
catalog stay importable on a bare interpreter, like the rest of
``cgnn_tpu.analysis``.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import re
from typing import Any, Callable

# check id -> one-line description (the --list-checks output;
# INVARIANTS.md "IR-level invariants" carries the full write-ups)
CHECKS = {
    "GA-DONATION": (
        "donation declared but not applied: a donate_argnums leaf "
        "without input-output aliasing in the lowered/compiled program "
        "means XLA silently keeps BOTH buffers — the train step then "
        "pays a full parameter copy per step (the failure mode the "
        "PR-2 checkpoint incident proved donation is live on, "
        "CHANGES.md PR 2)."
    ),
    "GA-F64": (
        "f64 value in a compiled program: accidental float64 promotion "
        "doubles HBM bytes on the exact gather/scatter paths that hold "
        "MFU at ~3% (ROADMAP item 2) and falls off the TPU fast path "
        "entirely; the dtype policy is f32/bf16 everywhere."
    ),
    "GA-HOSTCALL": (
        "unsanctioned custom-call/callback in a compiled program: the "
        "ONE audited host callback is the observe/stream telemetry tap "
        "(unordered, muted at warmup), present only in the "
        "telemetry=step program (CHANGES.md PR 1); anything else "
        "serializes the device stream against the host."
    ),
    "GA-IDENT": (
        "program-identity drift: the warm ladder must produce exactly "
        "programs x rungs x forms distinct programs (CHANGES.md PR 3); "
        "two programs differing ONLY in burned-in constants are the "
        "Python-scalar-leakage shape — every new scalar value "
        "recompiles at runtime."
    ),
    "GA-LOWER": (
        "a registered entry program failed to lower for an unexpected "
        "reason (known backend gaps — e.g. this container's jax "
        "missing shard_map — are recorded as skips, not findings)."
    ),
    "GA-SHARD": (
        "a mesh-sharded program's per-device argument bytes exceed the "
        "replicated-params + batch/N model: the classic NamedSharding "
        "mistake is staging the batch WITHOUT the batch-axis sharding "
        "(or with P()), which silently replicates every staged byte to "
        "every device — N x the H2D traffic and HBM of the sharded "
        "layout with identical outputs, exactly the cost the mesh "
        "engine (parallel/executor.py, ISSUE 10) exists to avoid. The "
        "compiled executable's per-device argument_size_in_bytes is "
        "budgeted against the analytic sharded model so that mistake "
        "blocks CI."
    ),
    "GA-ROOFLINE": (
        "a byte-budgeted program's cost-analysis bytes exceed its "
        "analytic HBM model: the in-program neighbor search "
        "(ops/neighbor_search.py) is budgeted at a bounded number of "
        "passes over its [S, S*K] candidate plane — a later change that "
        "featurizes before truncating to the [S, M] survivors "
        "materializes a per-candidate [S, S*K, G] tensor in HBM, G-fold "
        "the intended working set, and this check blocks CI on it."
    ),
}

# lower-is-better ledger keys gated by diff_ledgers (the budget)
LEDGER_GATE_KEYS = ("bytes", "peak_temp_bytes", "bytes_per_flop")

# custom-call targets that are XLA plumbing, not host calls
_ALLOWED_CUSTOM_CALLS = {
    "Sharding",
    "SPMDFullToShardShape",
    "SPMDShardToFullShape",
    "annotate_device_placement",
}

_CUSTOM_CALL_RE = re.compile(r"custom_call\s+@([\w.$]+)")
_CONST_RE = re.compile(r"dense<[^>]*>")
_BACKEND_CONFIG_RE = re.compile(r'backend_config\s*=\s*"[^"]*"')


@dataclasses.dataclass
class AuditFinding:
    """One IR-level violation in one entry program."""

    check: str
    program: str
    message: str

    def format(self) -> str:
        return f"{self.program}: {self.check}: {self.message}"


@dataclasses.dataclass
class AuditConfig:
    """Deterministic synthetic setup the entry programs lower against.

    Small on purpose (the audit runs per-PR on CPU): the invariants
    checked — aliasing, dtypes, custom-call targets, program identity —
    are shape-independent, and the roofline ledger only needs to be
    SELF-consistent between rounds, which fixed shapes + a fixed seed
    guarantee."""

    n_graphs: int = 64
    batch_size: int = 16
    rungs: int = 3
    dense_m: int = 8
    seed: int = 0
    atom_fea_len: int = 16
    n_conv: int = 2
    h_fea_len: int = 32

    def to_meta(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass
class Program:
    """One lowered (or loudly skipped) entry program."""

    name: str
    jitted: Any = None
    args: tuple = ()
    donated_leaves: int = 0  # expected aliased input leaves (0 = none)
    callbacks: int = 0  # expected sanctioned callback custom-calls
    skip: str | None = None  # reason this backend cannot lower it
    lowered: Any = None
    text: str = ""
    # analytic HBM byte budget (0 = ungated): compiled cost-analysis
    # bytes above budget * GA-ROOFLINE's slack is a finding
    byte_budget: int = 0
    # analytic PER-DEVICE argument-byte budget (0 = ungated): the
    # GA-SHARD gate for mesh-sharded programs — replicated params +
    # this device's 1/N batch slice; a silently replicated batch blows
    # straight through it
    arg_byte_budget: int = 0


def abstract_avals(tree):
    """Map every leaf to a ``jax.ShapeDtypeStruct`` (PRNG-key dtypes
    preserved): the no-device-dispatch argument form for ``lower``."""
    import jax
    import numpy as np

    def aval(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        arr = np.asarray(x)
        return jax.ShapeDtypeStruct(arr.shape, arr.dtype)

    return jax.tree_util.tree_map(aval, tree)


def lower_train_program(state, batch, *, body: Callable | None = None,
                        guard: bool = False, telemetry=None):
    """Lower a train-step body through the ONE canonical path.

    ``train/step.jit_train_step`` declares the donation; this helper
    adds the standard wrappers in the order train/loop.py applies them
    (guard inside, telemetry tap outside) and lowers on abstract avals.
    Used by the audit registry AND scripts/hlo_dump.py, so there is
    exactly one jit/lower plumbing for train programs."""
    from cgnn_tpu.train.step import jit_train_step, make_train_step

    body = body or make_train_step()
    if guard:
        from cgnn_tpu.resilience.guard import guard_step

        body = guard_step(body)
    if telemetry is not None:
        body = telemetry.wrap_train_body(body)
    return jit_train_step(body).lower(
        abstract_avals(state), abstract_avals(batch)
    )


# ---- the entry-program registry --------------------------------------


def build_entry_programs(config: AuditConfig | None = None,
                         telemetry_dir: str | None = None):
    """-> (programs, meta): the repo's real entry programs, lowered.

    Known backend gaps become ``skip`` records (listed in the ledger
    meta, never silently absent): the data-parallel step and the
    mesh predict programs need >= 2 devices. Everything else
    must lower — an unexpected failure is a GA-LOWER finding, not a
    skip."""
    import tempfile

    import jax
    import numpy as np

    from cgnn_tpu.data.compact import CompactSpec, make_expander
    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
    from cgnn_tpu.data.graph import batch_iterator, capacities_for
    from cgnn_tpu.models import CrystalGraphConvNet
    from cgnn_tpu.serve.shapes import plan_shape_set
    from cgnn_tpu.train import (
        Normalizer,
        create_train_state,
        make_optimizer,
    )
    from cgnn_tpu.train.step import make_predict_step, make_train_step

    cfg = config or AuditConfig()
    m = cfg.dense_m
    fcfg = FeaturizeConfig(radius=6.0, max_num_nbr=m)
    # keep_geometry: the ISSUE-11 raw-wire spec calibrates its image
    # caps from the calibration lattices
    graphs = load_synthetic_mp(cfg.n_graphs, fcfg, seed=cfg.seed,
                               keep_geometry=True)
    targets = np.stack([g.target for g in graphs])
    spec = CompactSpec.build(graphs, fcfg.gdf(), dense_m=m)
    from cgnn_tpu.data.rawbatch import plan_raw_spec

    raw_spec = plan_raw_spec(graphs, fcfg.gdf(), fcfg.radius, m)
    ladder = plan_shape_set(graphs, cfg.batch_size, rungs=cfg.rungs,
                            dense_m=m, compact=spec, raw=raw_spec)

    def make_state(model, example):
        return create_train_state(
            model, example, make_optimizer(),
            Normalizer.fit(targets), rng=jax.random.key(cfg.seed),
        )

    # COO layout: the train programs every backend can lower
    model_coo = CrystalGraphConvNet(
        atom_fea_len=cfg.atom_fea_len, n_conv=cfg.n_conv,
        h_fea_len=cfg.h_fea_len,
    )
    nc, ec = capacities_for(graphs, cfg.batch_size, snug=True)
    coo_batch = next(batch_iterator(graphs, cfg.batch_size, nc, ec,
                                    snug=True))
    state_coo = make_state(model_coo, coo_batch)
    n_leaves = len(jax.tree_util.tree_leaves(abstract_avals(state_coo)))
    coo_av = abstract_avals(coo_batch)
    state_coo_av = abstract_avals(state_coo)

    # dense layout: the flagship/serving layout (predict + dense train)
    model_dense = CrystalGraphConvNet(
        atom_fea_len=cfg.atom_fea_len, n_conv=cfg.n_conv,
        h_fea_len=cfg.h_fea_len, dense_m=m,
    )
    ncd, ecd = capacities_for(graphs, cfg.batch_size, dense_m=m, snug=True)
    dense_batch = next(batch_iterator(graphs, cfg.batch_size, ncd, ecd,
                                      dense_m=m, snug=True))
    state_dense = make_state(model_dense, dense_batch)
    state_dense_av = abstract_avals(state_dense)

    from cgnn_tpu.train.step import jit_train_step

    programs: list[Program] = []

    def add(name, jitted, args, donated=0, callbacks=0):
        programs.append(Program(name=name, jitted=jitted, args=args,
                                donated_leaves=donated,
                                callbacks=callbacks))

    def add_skip(name, reason):
        programs.append(Program(name=name, skip=reason))

    # -- train step: plain / guard-wrapped / telemetry-tapped (COO) --
    add("train/coo", jit_train_step(make_train_step()),
        (state_coo_av, coo_av), donated=n_leaves)
    from cgnn_tpu.resilience.guard import guard_step

    add("train/coo+guard", jit_train_step(guard_step(make_train_step())),
        (state_coo_av, coo_av), donated=n_leaves)
    # telemetry=step: the ONE program allowed a host callback (the
    # observe/stream tap), wrapped exactly as train/loop.py wraps it
    # (guard inside, tap outside, grad health on at step level)
    from cgnn_tpu.observe.telemetry import Telemetry

    tel = Telemetry(level="step",
                    log_dir=telemetry_dir or tempfile.mkdtemp(
                        prefix="graftaudit-tap-"))
    try:
        tap_body = tel.wrap_train_body(
            guard_step(make_train_step(grad_health=True))
        )
        add("train/coo+tap@step", jit_train_step(tap_body),
            (state_coo_av, coo_av), donated=n_leaves, callbacks=1)
    finally:
        tel.close()

    # -- train step: dense layout (the bench/serving layout) --
    add("train/dense", jit_train_step(make_train_step()),
        (state_dense_av, abstract_avals(dense_batch)), donated=n_leaves)

    # -- train step: data-parallel (where the backend allows) --
    shard_gap = None
    if len(jax.devices()) < 2:
        shard_gap = (f"needs >= 2 devices, have {len(jax.devices())} "
                     f"(CI sets --xla_force_host_platform_device_count)")
    if shard_gap is None:
        from cgnn_tpu.parallel.data_parallel import (
            make_parallel_train_step,
            stack_batches,
        )
        from cgnn_tpu.parallel.mesh import make_mesh

        n_dev = len(jax.devices())
        mesh = make_mesh(n_dev)
        stacked_av = abstract_avals(stack_batches([coo_batch] * n_dev))
        add("train/dp", make_parallel_train_step(mesh).jitted,
            (state_coo_av, stacked_av), donated=n_leaves)
    else:
        add_skip("train/dp", shard_gap)

    # -- predict: every (rung, staging form) in the warm ladder — the
    # forms dimension now includes 'raw' (ISSUE 11: the in-program
    # neighbor-search + featurize program per rung) --
    pstep = jax.jit(make_predict_step(ladder.expander(),
                                      ladder.raw_expander()))
    batch_avals = ladder.abstract_batches(graphs[0])
    for (rung, form), batch_av in sorted(batch_avals.items()):
        add(f"predict/rung{rung}/{form}", pstep,
            (state_dense_av, batch_av))

    # -- predict: the mesh-sharded engine dimension (ISSUE 10) — the
    # same rungs x forms through the MeshExecutor single-dispatch
    # program, GA-SHARD-budgeted so a silently replicated batch (the
    # classic NamedSharding mistake) blocks CI. GA-IDENT's expected
    # predict count accounts for this engine dimension below.
    mesh_devices = 0
    if len(jax.devices()) >= 2:
        from cgnn_tpu.parallel.executor import MeshExecutor

        executor = MeshExecutor(jax.devices())
        mesh_devices = len(executor)
        mesh_pred = executor.shard_predict(
            make_predict_step(ladder.expander(), ladder.raw_expander()))

        def _aval_bytes(tree) -> int:
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                try:
                    item = np.dtype(leaf.dtype).itemsize
                except TypeError:
                    item = 8  # PRNG key leaves (uint32[2] key data)
                total += int(np.prod(leaf.shape, dtype=np.int64)) * item
            return total

        state_bytes = _aval_bytes(state_dense_av)
        for (rung, form), batch_av in sorted(batch_avals.items()):
            stacked_av = executor.abstract_stacked(batch_av)
            # the sharded model: every device holds the full replicated
            # state plus exactly its 1/N slice of the stacked batch
            # (XLA drops unused state args, so this is an upper bound
            # on the CORRECT layout and far below a replicated batch)
            budget = state_bytes + _aval_bytes(stacked_av) // mesh_devices
            programs.append(Program(
                name=f"predict/mesh/rung{rung}/{form}",
                jitted=mesh_pred, args=(state_dense_av, stacked_av),
                arg_byte_budget=budget,
            ))
    else:
        add_skip("predict/mesh",
                 "the mesh-sharded predict program needs >= 2 devices "
                 "(CI sets --xla_force_host_platform_device_count)")
    # -- the compact expander as its own program (the fused on-device
    # featurize the serving fast path rides on) --
    add("expander/rung0", jax.jit(make_expander(spec)),
        (batch_avals[(0, "compact")],))

    # -- the in-program neighbor search as its own program, GA-ROOFLINE
    # budgeted against its analytic candidate-matrix byte model: the
    # [S, S*K] dense candidate pass is the intended working set, and a
    # rematerialized per-candidate FEATURE tensor (the G-fold blowup the
    # budget exists to catch) blows straight through the slack --
    from cgnn_tpu.ops.neighbor_search import (
        neighbor_search,
        neighbor_search_hbm_bytes,
    )

    raw_av0 = batch_avals[(0, "raw")]
    g_cap0 = raw_av0.targets.shape[0]

    def _search_fn(frac, lats, amask):
        return neighbor_search(frac, lats, amask, raw_spec)

    search_budget = neighbor_search_hbm_bytes(
        g_cap0, raw_spec.snode_cap, raw_spec.n_images, raw_spec.dense_m
    )["budget_bytes"]
    programs.append(Program(
        name="ops/neighbor_search/rung0", jitted=jax.jit(_search_fn),
        args=(raw_av0.frac, raw_av0.lattices, raw_av0.atom_mask),
        byte_budget=search_budget,
    ))

    meta = {
        "config": cfg.to_meta(),
        "ladder": ladder.to_meta(),
        # the engine dimension counts (GA-IDENT): the single-device
        # ladder programs plus, where the backend has the devices, the
        # mesh-sharded twin of every (rung, form)
        "predict_programs_expected": len(batch_avals) * (
            2 if mesh_devices else 1),
        "mesh_devices": mesh_devices,
        "state_leaves": n_leaves,
        # the ISSUE-11 neighbor-search byte model (GA-ROOFLINE target)
        "neighbor_search_byte_model": neighbor_search_hbm_bytes(
            g_cap0, raw_spec.snode_cap, raw_spec.n_images,
            raw_spec.dense_m,
        ),
        "raw_spec": raw_spec.to_meta(),
    }
    return programs, meta


def lower_programs(programs: list[Program]) -> list[AuditFinding]:
    """Fill ``lowered``/``text`` per program; a program that fails to
    lower is a GA-LOWER finding (known backend gaps were registered as
    skips by ``build_entry_programs`` and are not attempted)."""
    findings = []
    for p in programs:
        if p.skip is not None:
            continue
        try:
            p.lowered = p.jitted.lower(*p.args)
            p.text = p.lowered.as_text()
        except Exception as e:  # noqa: BLE001 - findings, not crashes
            findings.append(AuditFinding(
                "GA-LOWER", p.name,
                f"unexpected lowering failure: {type(e).__name__}: {e}",
            ))
            p.skip = f"lowering failed: {type(e).__name__}"
    return findings


# ---- per-program text checks -----------------------------------------


def _has_f64(text: str) -> bool:
    # element types read 'tensor<4xf64>' / 'tensor<f64>'; free the
    # 'xf64' form so a word boundary exists, then match the dtype token
    return re.search(r"\bf64\b", text.replace("xf64", " f64")) is not None


def _custom_calls(text: str) -> list[str]:
    return _CUSTOM_CALL_RE.findall(text)


def _is_callback(target: str) -> bool:
    return "callback" in target.lower()


def check_donation(p: Program) -> list[AuditFinding]:
    if p.donated_leaves <= 0:
        return []
    out = []
    aliased = p.text.count("tf.aliasing_output")
    donors = p.text.count("jax.buffer_donor")
    if aliased < p.donated_leaves:
        out.append(AuditFinding(
            "GA-DONATION", p.name,
            f"only {aliased} of {p.donated_leaves} donated input leaves "
            f"carry tf.aliasing_output in the lowered module — the "
            f"un-aliased leaves get a fresh output buffer plus a copy "
            f"every step (donation silently not applied).",
        ))
    if donors:
        out.append(AuditFinding(
            "GA-DONATION", p.name,
            f"{donors} donated leaves lowered as unmatched "
            f"jax.buffer_donor (no output shares their shape/dtype): "
            f"the donation is declared but can never be applied.",
        ))
    return out


def check_donation_compiled(p: Program, mem) -> list[AuditFinding]:
    if p.donated_leaves <= 0 or mem is None:
        return []
    alias = int(getattr(mem, "alias_size_in_bytes", 0))
    if alias <= 0:
        return [AuditFinding(
            "GA-DONATION", p.name,
            f"compiled executable reports alias_size_in_bytes={alias} "
            f"for a program with {p.donated_leaves} donated leaves — "
            f"XLA dropped the aliasing after optimization.",
        )]
    return []


def check_f64(p: Program) -> list[AuditFinding]:
    if _has_f64(p.text):
        line = next((ln.strip() for ln in p.text.splitlines()
                     if _has_f64(ln)), "")
        return [AuditFinding(
            "GA-F64", p.name,
            f"f64 value in the lowered module (dtype policy is "
            f"f32/bf16): e.g. {line[:100]!r}",
        )]
    return []


def check_hostcalls(p: Program) -> list[AuditFinding]:
    out = []
    callbacks = 0
    for target in _custom_calls(p.text):
        if _is_callback(target):
            callbacks += 1
        elif target not in _ALLOWED_CUSTOM_CALLS:
            out.append(AuditFinding(
                "GA-HOSTCALL", p.name,
                f"custom_call @{target} is neither XLA partitioning "
                f"plumbing ({sorted(_ALLOWED_CUSTOM_CALLS)}) nor the "
                f"sanctioned callback — unknown host-call surface.",
            ))
    if callbacks != p.callbacks:
        expect = (f"exactly {p.callbacks} (the observe/stream tap)"
                  if p.callbacks else "none")
        out.append(AuditFinding(
            "GA-HOSTCALL", p.name,
            f"{callbacks} callback custom-call(s) in the module, "
            f"expected {expect}: the telemetry tap is the ONE audited "
            f"host callback, present only in the telemetry=step "
            f"program.",
        ))
    return out


# ---- cross-program identity ------------------------------------------


def _normalize(text: str) -> str:
    # callback backend_configs embed process-local pointers; strip them
    # so fingerprints are stable within a run
    return _BACKEND_CONFIG_RE.sub('backend_config = "_"', text)


def fingerprint(text: str) -> str:
    return hashlib.sha256(_normalize(text).encode()).hexdigest()[:16]


def const_fingerprint(text: str) -> str:
    """Fingerprint with every dense<...> literal masked: two programs
    equal under THIS hash but not under ``fingerprint`` differ only in
    burned-in constants — the Python-scalar-leakage shape."""
    return hashlib.sha256(
        _CONST_RE.sub("dense<_>", _normalize(text)).encode()
    ).hexdigest()[:16]


def near_duplicates(named_texts: list[tuple[str, str]]):
    """[(name_a, name_b)] pairs that differ ONLY in constants."""
    by_const: dict[str, list[tuple[str, str]]] = {}
    for name, text in named_texts:
        by_const.setdefault(const_fingerprint(text), []).append(
            (name, fingerprint(text))
        )
    pairs = []
    for group in by_const.values():
        # one representative per DISTINCT exact fingerprint: byte-equal
        # twins are duplicates (check_identity flags those separately),
        # not the constant-only variant this reports
        rep: dict[str, str] = {}
        for name, fp in group:
            rep.setdefault(fp, name)
        if len(rep) > 1:
            names = list(rep.values())
            pairs.append((names[0], names[1]))
    return pairs


def check_identity(programs: list[Program],
                   predict_expected: int) -> list[AuditFinding]:
    out = []
    lowered = [p for p in programs if p.lowered is not None]
    n_predict = sum(1 for p in lowered if p.name.startswith("predict/"))
    if n_predict != predict_expected:
        out.append(AuditFinding(
            "GA-IDENT", "predict/*",
            f"the ladder lowered {n_predict} predict programs, expected "
            f"rungs x forms = {predict_expected}: a rung or staging "
            f"form fell out of (or leaked into) the warm set.",
        ))
    by_fp: dict[str, list[str]] = {}
    for p in lowered:
        by_fp.setdefault(fingerprint(p.text), []).append(p.name)
    for names in by_fp.values():
        if len(names) > 1:
            out.append(AuditFinding(
                "GA-IDENT", names[0],
                f"programs {names} lower to the IDENTICAL module — "
                f"duplicate registry entries or a collapsed ladder rung "
                f"(each warmed program should be distinct work).",
            ))
    for a, b in near_duplicates([(p.name, p.text) for p in lowered]):
        out.append(AuditFinding(
            "GA-IDENT", a,
            f"programs {a!r} and {b!r} differ ONLY in burned-in "
            f"constants: a Python scalar traced as a constant — at "
            f"runtime every new value of it compiles a fresh program "
            f"(the warm-ladder recompile hazard, CHANGES.md PR 3).",
        ))
    return out


# ---- roofline ledger -------------------------------------------------


def _cost_dict(compiled) -> dict:
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    return dict(ca or {})


def roofline_entry(compiled) -> dict:
    """One ledger row from XLA's own analyses."""
    cost = _cost_dict(compiled)
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    entry = {
        "flops": flops,
        "bytes": nbytes,
        "intensity_flops_per_byte": round(flops / nbytes, 4) if nbytes
        else 0.0,
        "bytes_per_flop": round(nbytes / flops, 6) if flops else 0.0,
    }
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - backend-optional surface
        mem = None
    if mem is not None:
        entry.update(
            peak_temp_bytes=int(getattr(mem, "temp_size_in_bytes", 0)),
            arg_bytes=int(getattr(mem, "argument_size_in_bytes", 0)),
            out_bytes=int(getattr(mem, "output_size_in_bytes", 0)),
            alias_bytes=int(getattr(mem, "alias_size_in_bytes", 0)),
        )
    return entry


# GA-ROOFLINE slack over the analytic model: cost analysis counts glue
# ops (index prep, scalar outputs) beside the modelled passes — 2x
# headroom stays far below the G-fold (~40x) blowup of a per-candidate
# feature tensor in the neighbor search, so the check cannot
# false-positive on glue yet cannot miss the failure mode it exists for.
_ROOFLINE_SLACK = 2.0


def check_roofline_budget(p: Program, entry: dict) -> list[AuditFinding]:
    if p.byte_budget <= 0:
        return []
    measured = float(entry.get("bytes", 0.0))
    if measured <= 0:
        # a missing/zero cost-analysis byte count would make this check
        # VACUOUSLY green — the one failure mode a guard must not have.
        # Report it so a backend that stops exposing 'bytes accessed'
        # re-arms the budget instead of silently disarming it.
        return [AuditFinding(
            "GA-ROOFLINE", p.name,
            f"cost analysis reported {measured} accessed bytes for a "
            f"byte-budgeted program — the budget cannot be checked on "
            f"this backend/jax; the roofline gate would be vacuous, "
            f"which is itself a finding (fix the measurement or drop "
            f"the budget explicitly).",
        )]
    if measured > p.byte_budget * _ROOFLINE_SLACK:
        return [AuditFinding(
            "GA-ROOFLINE", p.name,
            f"cost-analysis bytes {measured:.3e} exceed the analytic "
            f"byte model ({p.byte_budget:.3e} x {_ROOFLINE_SLACK} "
            f"slack) — a tensor beyond the modelled working set is "
            f"round-tripping HBM (for the neighbor search: a "
            f"per-candidate feature tensor; ops/neighbor_search.py).",
        )]
    return []


# GA-SHARD slack over the analytic per-device model: the budget already
# over-counts (it charges the FULL state incl. optimizer leaves XLA
# drops from a forward program), and a replicated batch lands N x the
# batch term above it (N >= 2) — 1.5x headroom cannot false-positive on
# layout padding yet cannot miss the replication it exists to catch.
_SHARD_SLACK = 1.5


def check_shard_budget(p: Program, mem) -> list[AuditFinding]:
    if p.arg_byte_budget <= 0:
        return []
    if mem is None:
        # memory analysis unavailable on this backend/jax: the gate
        # would be VACUOUSLY green — report it instead of passing (same
        # posture as GA-ROOFLINE's zero-bytes branch)
        return [AuditFinding(
            "GA-SHARD", p.name,
            "memory_analysis() unavailable for a shard-budgeted "
            "program — the replication gate cannot be checked on this "
            "backend/jax; fix the measurement or drop the budget "
            "explicitly.",
        )]
    args = int(getattr(mem, "argument_size_in_bytes", 0))
    if args <= 0:
        # a missing per-device argument size would make this gate
        # vacuously green — the one failure mode a guard must not have
        return [AuditFinding(
            "GA-SHARD", p.name,
            f"memory analysis reported {args} per-device argument "
            f"bytes for a shard-budgeted program — the sharding gate "
            f"cannot be checked on this backend/jax; fix the "
            f"measurement or drop the budget explicitly.",
        )]
    if args > p.arg_byte_budget * _SHARD_SLACK:
        return [AuditFinding(
            "GA-SHARD", p.name,
            f"per-device argument bytes {args:.3e} exceed the "
            f"replicated-params + batch/N model "
            f"({p.arg_byte_budget:.3e} x {_SHARD_SLACK} slack) — the "
            f"batch is being REPLICATED to every device instead of "
            f"batch-axis sharded (the NamedSharding mistake the mesh "
            f"engine exists to avoid; parallel/executor.py).",
        )]
    return []


def run_audit(config: AuditConfig | None = None, *, compile: bool = True,
              programs: list[Program] | None = None, meta: dict | None = None):
    """Lower + audit the entry-program registry.

    -> (findings, ledger, programs). ``compile=False`` runs the
    StableHLO-level checks only (fast: no XLA compile) — the live-repo
    test pin; ``compile=True`` additionally verifies donation survived
    compilation and fills the roofline ledger."""
    import jax

    if programs is None:
        programs, meta = build_entry_programs(config)
    findings = lower_programs(programs)
    predict_expected = (meta or {}).get("predict_programs_expected", 0)
    for p in programs:
        if p.lowered is None:
            continue
        findings += check_donation(p)
        findings += check_f64(p)
        findings += check_hostcalls(p)
    findings += check_identity(programs, predict_expected)

    ledger = {
        "meta": {
            "jax": jax.__version__,
            "backend": jax.default_backend(),
            "devices": len(jax.devices()),
            **(meta or {}),
            "skipped": {p.name: p.skip for p in programs
                        if p.skip is not None},
            "gate_keys": list(LEDGER_GATE_KEYS),
        },
        "programs": {},
    }
    if compile:
        for p in programs:
            if p.lowered is None:
                continue
            compiled = p.lowered.compile()
            try:
                mem = compiled.memory_analysis()
            except Exception:  # noqa: BLE001
                mem = None
            findings += check_donation_compiled(p, mem)
            findings += check_shard_budget(p, mem)
            entry = roofline_entry(compiled)
            if p.byte_budget > 0:
                entry["byte_budget"] = p.byte_budget
            if p.arg_byte_budget > 0:
                entry["arg_byte_budget"] = p.arg_byte_budget
            findings += check_roofline_budget(p, entry)
            ledger["programs"][p.name] = entry
    findings.sort(key=lambda f: (f.program, f.check))
    return findings, ledger, programs


# ---- ledger budgets (stdlib-only; bench_regress.py reuses this) ------


def diff_ledgers(old: dict, new: dict, threshold: float = 0.2) -> dict:
    """Budget diff of two AUDIT_LEDGER payloads, mirroring
    bench_regress semantics with the sign flipped: gate keys are
    LOWER-is-better, a program or key missing from the NEW ledger is a
    regression (a budget that stopped being measured is how a
    regression hides).

    Numeric drifts are downgraded to warnings when the two ledgers
    were generated by different jax versions (``version_skew``) — XLA's
    cost model moves between releases; structural drops stay hard
    regressions regardless."""
    rows, regressions, warnings = [], [], []
    old_meta = old.get("meta", {})
    skew = old_meta.get("jax") != new.get("meta", {}).get("jax")
    new_programs = new.get("programs", {})
    for pname, oentry in sorted(old.get("programs", {}).items()):
        nentry = new_programs.get(pname)
        if nentry is None:
            row = {"key": pname, "old": "present", "new": None,
                   "note": "program DROPPED from the new ledger"}
            rows.append(row)
            regressions.append(row)
            continue
        for key in LEDGER_GATE_KEYS:
            o, n = oentry.get(key), nentry.get(key)
            if o is None and n is None:
                continue
            row = {"key": f"{pname}.{key}", "old": o, "new": n}
            if n is None:
                row["note"] = "key DROPPED from the new ledger"
                regressions.append(row)
            elif o and o > 0:
                ratio = n / o
                row["ratio"] = round(ratio, 4)
                if ratio > 1.0 + threshold:
                    row["note"] = (f"REGRESSION: {100 * (ratio - 1):.1f}% "
                                   f"above budget")
                    (warnings if skew else regressions).append(row)
            elif o == 0 and n > 0:
                # a zero budget has no ratio; any nonzero value of a
                # lower-is-better key is how e.g. the expander starts
                # materializing temps without anyone noticing
                row["note"] = f"REGRESSION: budget was 0, now {n}"
                (warnings if skew else regressions).append(row)
            rows.append(row)
    return {"rows": rows, "regressions": regressions,
            "warnings": warnings, "version_skew": skew}


def write_ledger(ledger: dict, path: str) -> None:
    with open(path, "w") as f:
        json.dump(ledger, f, indent=1, sort_keys=True, allow_nan=False)
        f.write("\n")


def load_ledger(path: str) -> dict:
    with open(path) as f:
        return json.load(f)
