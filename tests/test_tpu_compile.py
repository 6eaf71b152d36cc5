"""Compiles for the DESCRIBED chip (v5e; no chip attached, nothing runs):
what only the TPU compiler shows and every later PR should keep.

The one file of the suite that loads the TPU's library: the topology is
described inside a fixture, never at import, and the compile happens in the
test's own process (``on-chip-measurement`` guide, section 2).
"""

from __future__ import annotations

import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    import importlib.util

    if importlib.util.find_spec("libtpu") is None:
        pytest.skip("no libtpu in this installation: nothing can compile "
                    "for the described chip")
    # with libtpu installed (this container, the driver's) a topology that
    # cannot be described is a failure, not a skip: these tests are the only
    # pin of what they guard
    return topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The described host's four chips as the 'data' mesh of
    ``train.py --data-parallel``."""
    from jax.sharding import Mesh

    assert len(topo.devices) == 4
    return Mesh(np.array(topo.devices), ("data",))


@pytest.fixture()
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache and
    can never be read back without the chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# opcodes that hand an array on and compute nothing
_MOVED = ("parameter", "get-tuple-element", "bitcast", "tuple", "while",
          "copy-start", "copy-done")


@functools.cache  # two tests read the float32 force program
def _full_staging_scan_program(one_chip, task: str, dtype: str):
    """The scan program ``fit`` builds under full staging (chunk of two
    steps over the whole resident stack) at a tiny size, compiled for the
    described chip -> (optimized HLO text, graphs, batches, node capacity)."""
    from cgnn_tpu.config import DataConfig, ModelConfig, build_model
    from cgnn_tpu.data.dataset import load_synthetic_md17
    from cgnn_tpu.data.graph import batch_iterator, capacities_for
    from cgnn_tpu.resilience.guard import guard_step
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.force_step import (
        make_force_eval_step,
        make_force_train_step,
    )
    from cgnn_tpu.train.loop import ScanEpochDriver
    from cgnn_tpu.train.step import make_eval_step, make_train_step

    graphs = load_synthetic_md17(32)
    node_cap, edge_cap = capacities_for(graphs, 8, dense_m=12, snug=True)
    edge_dtype = jnp.bfloat16 if dtype == "bfloat16" else np.float32
    batches = list(batch_iterator(graphs, 8, node_cap, edge_cap, dense_m=12,
                                  snug=True, edge_dtype=edge_dtype))
    model = build_model(
        ModelConfig(atom_fea_len=16, n_conv=2, h_fea_len=32, dtype=dtype,
                    dense_m=12), DataConfig(), task)
    state = create_train_state(
        model, batches[0],
        make_optimizer(optim="adam", lr=1e-3, lr_milestones=[10**9]),
        Normalizer(mean=jnp.zeros(1), std=jnp.ones(1)))
    bodies = ((make_force_train_step(), make_force_eval_step())
              if task == "force" else
              (make_train_step(False), make_eval_step(False)))
    driver = ScanEpochDriver(
        guard_step(bodies[0]), bodies[1], batches, [],
        np.random.default_rng(0), chunk_steps=2)
    (key, stacked), = driver._train_groups.items()
    # the form an epoch runs: the group's whole perm and a cursor into it
    fn = driver._window_fn(driver._train_scans, (key, 2),
                           driver._train_body, True)
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip),
        (state, stacked, np.zeros(len(batches), np.int32),
         np.zeros((), np.int32)))
    text = fn.lower(*shapes).compile().as_text()
    return text, graphs, batches, node_cap


@functools.cache  # three tests read it
def _ocp_scan_program(one_chip):
    """Cell ``ocp.train``'s largest scan program (a chunk of four steps over
    the larger bucket's resident stack) at the cell's real size, assembled by
    the cell's own kind (``benchmark/kinds/ocp_train.py``: compact staging,
    snug packing, 2 buckets, the guard, Adam on the L1 loss, the published
    widths in bfloat16) and compiled for the described chip -> (optimized HLO
    text, the bucket's batch, node capacity, F, M, convs, Gaussians,
    compiled).

    The pool is 64 slabs, not the cell's 2,048 (4 GB of edge features on the
    host): a bucket's snug node capacity is 32 times its slabs' mean size,
    which 32 slabs give to ~10% (5,504 here, 5,008 in the cell). The stack
    is lowered at the cell's length, 32 batches x its resident copies."""
    import copy
    import os

    from benchmark import run, system
    from benchmark.kinds import ocp_train
    from cgnn_tpu.data import dataset
    from cgnn_tpu.train import loop

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cell = run.Cell(os.path.join(root, "BENCHMARK.json"), "ocp.train")
    cell.config = cfg = copy.deepcopy(cell.config)
    copies = int(cfg["data"]["resident_copies"])
    cfg["data"].update(n=64, resident_copies=1)
    graphs = dataset.load_synthetic_oc20_ocp(
        64, system.featurize_config(cfg), seed=0)
    bench = ocp_train.Driver(run.Context(cell, 0, False))
    bench._first_steps = lambda state: state
    # set-up builds the scan driver itself: its warm-up (a whole epoch) and
    # the pool's cache file are switched off while it runs
    warm, load_pool = loop.ScanEpochDriver.warm, system.load_pool
    loop.ScanEpochDriver.warm = lambda self, state: state
    system.load_pool = lambda config: (graphs, {"built": True,
                                                "seconds": 0.0})
    try:
        bench.setup()
    finally:
        loop.ScanEpochDriver.warm, system.load_pool = warm, load_pool
    drv, steps = bench.driver, 4

    def node_capacity(key):
        return int(loop.program_name((key, steps), True)
                   .split("_n")[1].split("_")[0])

    key = max(drv._train_groups, key=node_capacity)
    stacked = drv._train_groups[key]
    fn = drv._window_fn(drv._train_scans, (key, steps), drv._train_body,
                        True)
    stack = 32 * copies

    def shape(x, lead=None):
        dims = np.shape(x) if lead is None else (lead,) + np.shape(x)[1:]
        return jax.ShapeDtypeStruct(dims, x.dtype, sharding=one_chip)

    compiled = fn.lower(
        jax.tree_util.tree_map(shape, bench.state),
        jax.tree_util.tree_map(lambda x: shape(x, stack), stacked),
        shape(np.zeros(stack, np.int32)),
        shape(np.zeros((), np.int32))).compile()
    batch = jax.tree_util.tree_map(lambda x: x[0], stacked)
    m = cfg["model"]
    return (compiled.as_text(), batch, node_capacity(key),
            int(m["atom_fea_len"]), int(cfg["layout"]["dense_m"]),
            int(m["n_conv"]), int(m["num_gaussians"]), compiled)


def _conv_program(one_chip, task: str, dtype: str):
    """-> (optimized HLO text, one batch of the program's stack, node
    capacity, F, M, convs, Gaussians) for the tiny full-staging programs
    and, as task 'ocp', for ``ocp.train``'s largest at real size."""
    if task == "ocp":
        return _ocp_scan_program(one_chip)[:7]
    text, _graphs, batches, node_cap = _full_staging_scan_program(
        one_chip, task, dtype)
    return (text, batches[0], node_cap, 16, 12, 2,
            batches[0].edges.shape[-1])


@pytest.mark.parametrize("task,dtype", [
    ("force", "bfloat16"), ("force", "float32"), ("regression", "bfloat16")])
def test_full_staging_scan_program_converts_no_resident_stack(
        one_chip, no_compile_cache, task, dtype):
    """The force task rides full staging: its scan program is handed the
    whole resident stack of batches and slices one a step. Nothing in the
    program may compute an array the size of the stacked atom features (of
    the other members only a u8 mask's relayout is known, PERF.md section 7,
    and this compile picks entry layouts freely): the first chip run of this path
    (PR 27) found the atom features' cast to bfloat16 (the model's, or the
    compiler's own for a float32 matmul's operands) moved before the slice
    and hoisted out of the loop, a pass over all of bf16[832, 5400, 92] once
    a launch of two steps, 23% of the step (models/cgcnn.py
    masked_atom_features masks before it casts, which keeps the cast on the
    slice). In float32 the model asks for precision highest and the
    compiler splits each matmul operand into bfloat16 parts: the same trap,
    three converts wide. The first-order model casts through the same
    helper: no cell stages it in full, train.py without --compact does."""
    text, graphs, batches, node_cap = _full_staging_scan_program(
        one_chip, task, dtype)

    stack, feat = len(batches), graphs[0].atom_fea.shape[1]
    assert stack > 2  # a stack-sized shape is no step-sized one
    computed = [
        ln.strip()[:160] for ln in text.splitlines()
        if re.match(rf"\s+(ROOT )?%?[\w.\-]+ = \w+\[{stack},{node_cap},{feat}\]",
                    ln)
        and not re.search(r" (" + "|".join(_MOVED) + r")\(", ln)]
    assert not computed, computed


def _elements(dims: str) -> int:
    """'5504,50,768' -> the number of elements."""
    return int(np.prod([int(d) for d in dims.split(",") if d]))


# an instruction after its ``name = ``: type, dimensions, opcode, operands
_INSTR = re.compile(r"(\w+)\[([\d,]*)\]\S* ([a-z][\w\-]*)\((.*)$")


def test_force_geometry_reads_the_dense_layout(one_chip, no_compile_cache):
    """The pin that models/forcefield.py edge_distances' dense form engaged
    in the force train step (PR 28). Written for the flat COO edge list the
    geometry gathered three times by [E] indices, and on the chip its five
    longest operations were none of them arithmetic: the two scatter-adds of
    E 12-byte rows that transpose positions[neighbors] and
    positions[centers], the scalar gather node_graph[centers] (s32[E]), the
    lattice gather into f32[E, 3, 3] and the product that read it back, a
    fifth of the step. Under ``dense_m`` the centre is a broadcast, the
    lattice is gathered once an atom, and the neighbours' positions go
    through ops/segment.gather_slot_major with the batch's transpose
    mapping, whose overflow tier has been a run sum and a pointer gather
    since PR 33: no scatter is left under the ``edge_geom`` scope."""
    from cgnn_tpu.observe import phases

    text, _graphs, batches, node_cap = _full_staging_scan_program(
        one_chip, "force", "float32")
    edge_cap = node_cap * 12

    scatters, index_gathers, lattices_an_edge, scoped = [], [], [], 0
    for comp in phases._parse(text).values():
        parsed = {name: m.groups() for name, rest in comp["instrs"].items()
                  if (m := _INSTR.match(rest))}
        for name, (dtype, dims, op, operands) in parsed.items():
            rest = comp["instrs"][name]
            if (dtype, dims) == ("f32", f"{edge_cap},3,3"):
                lattices_an_edge.append(rest[:200])
            op_name = phases._OP_NAME.search(rest)
            if not op_name or phases.classify(
                    op_name.group(1))[0] != phases.EDGE_GEOM:
                continue
            scoped += 1
            if op == "scatter":
                scatters.append(rest[:200])
            if op == "gather" and dtype == "s32":
                index_gathers.append(rest[:200])
    assert scoped > 50  # the scope is there to be read
    assert not scatters, scatters
    assert not index_gathers, index_gathers
    assert not lattices_an_edge, lattices_an_edge


def _fused_computations(comps: dict) -> set:
    """The names of the computations that are some instruction's body (a
    fusion's, a reduction's): their instructions are no operations of their
    own on the device. A ``call``'s target is."""
    from cgnn_tpu.observe import phases

    return {target for comp in comps.values()
            for rest in comp["instrs"].values()
            for kind, target in phases._CALLED.findall(rest)
            if kind == "calls" or " call(" not in rest}


def _scatters(text: str) -> dict:
    """The compiled text's ``scatter`` instructions counted by model phase,
    fused or not."""
    from cgnn_tpu.observe import phases

    found = {}
    for comp in phases._parse(text).values():
        for rest in comp["instrs"].values():
            m = _INSTR.match(rest)
            if m and m.group(3) == "scatter":
                op_name = phases._OP_NAME.search(rest)
                phase = phases.classify(op_name.group(1))[0] if op_name else ""
                found[phase] = found.get(phase, 0) + 1
    return found


def test_the_scatter_count_sees_a_scatter(one_chip, no_compile_cache):
    """The control of ``test_no_scatter_under_the_conv_gather``: ``jax.ops.
    segment_sum``, which the pooling called until PR 50, compiled for the
    described chip at a tiny size, is a ``scatter`` under its scope to the
    parser and the classifier that the test below reads with."""
    from cgnn_tpu.observe import phases

    def pool(x, ids):
        with jax.named_scope(phases.POOL_HEAD):
            return jax.ops.segment_sum(x, ids, num_segments=8)

    text = jax.jit(pool).lower(
        jax.ShapeDtypeStruct((96, 16), jnp.bfloat16, sharding=one_chip),
        jax.ShapeDtypeStruct((96,), jnp.int32, sharding=one_chip),
    ).compile().as_text()
    assert _scatters(text) == {phases.POOL_HEAD: 1}, _scatters(text)


@pytest.mark.parametrize("program", [
    "regression-bfloat16", "force-float32", "ocp-bfloat16", "four-chip"])
def test_no_scatter_under_the_conv_gather(request, no_compile_cache, program):
    """The pin that no phase of the step holds a scatter in the program the
    chip runs, for the ``mp-flagship`` trunk (bfloat16, BatchNorm), the
    ``md17-force`` one (float32, two reverse passes), ``ocp.train``'s
    largest program at its real size (rows of 768 lanes, 50 slots a node,
    six convs; PR 35) and ``mp.train-dp4``'s at real size on the described
    host (PR 50).

    The gather's declared transpose (ops/segment.py _transpose_cotangent,
    PR 33): tier 1 is a row gather and a masked sum, the overflow tier a
    row gather, one batched matmul that sums each node's run of the list,
    and a row gather through ``over_last``. The sorted scatter-add that XLA
    made of the tier's ``segment_sum`` cost ~10 ns a row where a gathered
    row costs ~1.3-1.8 (PERF.md section 5), three times a step (five in the
    force step).

    The pooling (ops/segment.py _segment_totals, PR 50; the control that
    the count sees a scatter is ``test_the_scatter_count_sees_a_scatter``):
    ``pool_head``'s forward holds one matmul, by the 0/1 matrix of the nodes' graphs, which writes
    the sums and the counts together, [G, F + 1]; the matrix is built in
    that fusion, so no instruction that the device runs as an operation of
    its own writes an [N, G] array; the reverse pass gathers rows of F, not
    of F + 1."""
    from cgnn_tpu.observe import phases

    if program == "four-chip":
        compiled, node_cap, _stack, _row, batch = _four_chip_program(
            request.getfixturevalue("four_chips"))
        text, f = compiled.as_text(), 64
    else:
        text, batch, node_cap, f, *_sizes = _conv_program(
            request.getfixturevalue("one_chip"), *program.split("-"))
    n_blocks = batch.over_slots.shape[-1] // 128 + 1
    n_graphs = batch.graph_mask.shape[-1]
    # the tiny programs' G = F = 16: there a [N, G] array is not told from
    # a block of rows, and the two programs at real size decide
    told_apart = n_graphs not in (f, 2 * f)
    assert told_apart == (program in ("ocp-bfloat16", "four-chip"))

    comps = phases._parse(text)
    fused = _fused_computations(comps)
    run_sums, pool_sums, pool_gathers, one_hot = [], [], [], []
    for cname, comp in comps.items():
        for name, rest in comp["instrs"].items():
            if told_apart and cname not in fused and not re.search(
                    r"\s(" + "|".join(_MOVED) + r")\(", rest):
                one_hot += [rest[:200] for _dt, dims in _ARRAY.findall(
                    _result_type(rest))
                    if sorted(dims.split(",")) == sorted(
                        [str(node_cap), str(n_graphs)])]
            m = _INSTR.match(rest)
            op_name = phases._OP_NAME.search(rest)
            if not m or not op_name:
                continue
            phase, direction = phases.classify(op_name.group(1))
            _dt, dims, op, _operands = m.groups()
            if (op in ("dot", "convolution") and dims.startswith(
                    f"{n_blocks},128,")
                    and phase in (phases.CONV_GATHER, phases.EDGE_GEOM)):
                run_sums.append((phase, direction))
            if phase == phases.POOL_HEAD and direction == phases.FWD and (
                    op in ("dot", "convolution")
                    and _elements(dims) == n_graphs * (f + 1)):
                pool_sums.append(rest[:200])
            if phase == phases.POOL_HEAD and op == "gather":
                pool_gathers.append(dims)
    assert not _scatters(text), _scatters(text)
    # the run sums are there to be seen: one a conv and reverse pass that
    # needs the nodes' gradient, and in the force step the position gather's
    assert run_sums.count((phases.CONV_GATHER, phases.BWD)) >= 2, run_sums
    assert ((phases.EDGE_GEOM, phases.BWD) in run_sums) == (
        program == "force-float32")
    assert not one_hot, one_hot
    if program != "force-float32":  # its readout sums one number an atom
        assert len(pool_sums) == 1, pool_sums
        assert pool_gathers == [f"{node_cap},{f}"], pool_gathers


@pytest.mark.parametrize("task,dtype", [
    ("regression", "bfloat16"), ("force", "float32"), ("ocp", "bfloat16")])
def test_no_matmul_over_gathered_rows(one_chip, no_compile_cache, task,
                                      dtype):
    """The pin that fc_full's neighbour term is projected before the gather
    (models/cgcnn.py _SplitFcFull, PR 30), in the train step and in the
    force step with its two reverse passes. Before, ``v_j @ K_j`` ran over
    the gathered [N, M, F] rows: one [E, F] x [F, 2F] matmul a conv
    forward, ``dz @ K_j^T`` back into [E, F] and the weight gradient
    ``v_j^T @ dz`` contracted over E in every reverse pass, a fifth to a
    third of the step at ~2% of the FLOP peak (PERF.md section 6). Now no
    matmul under ``conv.fc_full`` reads or writes anything with E rows of F
    (the edge term's rows are G = 41 wide, z's 2F), every row gather under
    ``conv.gather`` moves rows of 2F, and the forward ones write [E, 2F]:
    the projected block that is a term of z. The reverse pass gathers E
    rows of dz (tier 1), the overflow list's rows in blocks of 128 with
    their halo, and one row a node (its run's total; PR 33)."""
    from cgnn_tpu.observe import phases

    text, batch, node_cap, f, m, n_convs, gauss = _conv_program(
        one_chip, task, dtype)
    edge_cap, over_cap = node_cap * m, batch.over_slots.shape[0]
    halo = -(-(batch.over_runs.shape[0] - 1) // 8) * 8
    over_rows = (over_cap // 128 + 1) * (halo + 128)  # _run_totals' windows
    assert len({edge_cap, over_rows, node_cap}) == 3  # told apart by size
    assert len({f, 2 * f, gauss}) == 3  # E rows are told apart by width

    matmuls, over_edge_rows, gathers = 0, [], []
    for comp in phases._parse(text).values():
        parsed = {name: m_.groups() for name, rest in comp["instrs"].items()
                  if (m_ := _INSTR.match(rest))}
        for name, (_dt, dims, op, operands) in parsed.items():
            rest = comp["instrs"][name]
            op_name = phases._OP_NAME.search(rest)
            if not op_name:
                continue
            phase, direction = phases.classify(op_name.group(1))
            if op in ("dot", "convolution") and phase == phases.CONV_FC_FULL:
                matmuls += 1
                sizes = [_elements(dims)] + [
                    _elements(parsed[o][1])
                    for o in phases._OPERAND.findall(
                        operands.partition(")")[0]) if o in parsed]
                assert len(sizes) == 3, rest[:200]  # both operands found
                if edge_cap * f in sizes:
                    over_edge_rows.append(rest[:200])
            if op == "gather" and phase == phases.CONV_GATHER:
                gathers.append((direction, dims))
    # three kernel slices a conv forward; their transposes in each reverse
    # pass (the force step has two)
    assert matmuls >= 3 * n_convs * (3 if task == "force" else 2)
    assert not over_edge_rows, over_edge_rows
    rows = {f"{edge_cap},{2 * f}", f"{over_rows},{2 * f}",
            f"{node_cap},{2 * f}"}
    assert {dims for _, dims in gathers} == rows, gathers
    assert [d for d in gathers if d[0] == phases.FWD] == [
        (phases.FWD, f"{edge_cap},{2 * f}")] * n_convs


def _result_type(rest: str) -> str:
    """The type an instruction writes, a tuple's members and all: what
    stands before its opcode (a layout's ``T(8,128)`` follows no blank)."""
    return rest[:re.search(r"\s[a-z][\w\-]*\(", rest).start()]


_ARRAY = re.compile(r"\b([a-z]+\d+)\[([\d,]*)\]")


@pytest.mark.parametrize("program", ["regression-bfloat16", "ocp-bfloat16",
                                     "four-chip"])
def test_no_float32_copy_of_z_is_written_beside_z(request, no_compile_cache,
                                                  program):
    """The pin that ``MaskedBatchNorm`` takes the shift of its one-pass
    moments from a slice of x in x's own dtype (ops/norm.py, PR 37), for
    the ``mp-flagship`` trunk at a tiny size, ``ocp.train``'s largest
    program at its real size and ``mp.train-dp4``'s at real size on the
    described host. With the slice taken from the converted whole array
    (``x.astype(float32)[:1]``) XLA hoisted that convert into the producer
    of z: the fusion of fc_full's edge term wrote ``(f32[N, M, 2F],
    bf16[N, M, 2F])``, and the float32 output's one consumer read its first
    row-block, 150 KB of 769 MB a conv in ``ocp.train``, six times a step,
    9% of the device's time (PERF.md section 6). Now no instruction that the
    device runs as an operation of its own (outside a fused computation)
    writes a float32 array of N*M*2F elements or more, in either direction,
    and every fusion under ``bn1`` that sums over the whole of z (the
    moments; the reverse pass's sums) reads it in the compute dtype."""
    from cgnn_tpu.observe import phases

    if program == "four-chip":
        compiled, node_cap, _stack, _row, _batch = _four_chip_program(
            request.getfixturevalue("four_chips"))
        text, f, m, n_convs = compiled.as_text(), 64, 12, 3
    else:
        text, _batch, node_cap, f, m, n_convs, _gauss = _conv_program(
            request.getfixturevalue("one_chip"), *program.split("-"))
    z = node_cap * m * 2 * f

    comps = phases._parse(text)
    fused = _fused_computations(comps)
    float32_z, z_written, bn1_sums, bn1_wide = [], 0, 0, []
    for cname, comp in comps.items():
        if cname in fused:
            continue
        written = {name: _ARRAY.findall(_result_type(rest))
                   for name, rest in comp["instrs"].items()}
        for name, rest in comp["instrs"].items():
            if re.search(r"\s(" + "|".join(_MOVED) + r")\(", rest):
                continue
            sizes = [(dt, _elements(dims)) for dt, dims in written[name]]
            z_written += ("bf16", z) in sizes
            if any(dt == "f32" and n >= z for dt, n in sizes):
                float32_z.append(rest[:200])
            op_name = phases._OP_NAME.search(rest)
            if not (op_name and " fusion(" in rest and re.search(
                    r"/bn1/.*reduce_sum", op_name.group(1))):
                continue
            operands = phases._OPERANDS.search(rest).group(1)
            read = [(dt, _elements(dims))
                    for o in phases._OPERAND.findall(operands)
                    for dt, dims in written.get(o, [])]
            # (the mask's sum slices the stacked u8 mask: not an activation)
            bn1_sums += ("bf16", z) in read
            bn1_wide += [rest[:200] for dt, n in read
                         if n >= z and dt in ("f32", "f64")]
    # the count can see: z itself is written a conv, and bn1's moments (s1
    # and s2 in one fusion) read it a conv
    assert z_written >= n_convs, z_written
    assert bn1_sums >= n_convs, bn1_sums
    assert not float32_z, float32_z
    assert not bn1_wide, bn1_wide


def test_ocp_scan_program_at_real_size_fits_the_chip(one_chip,
                                                     no_compile_cache):
    """``ocp.train``'s largest program compiles for the described chip at
    batch 32 and, with the resident stack it is handed (the larger bucket's
    half of the pool's resident copies), takes between a third and three
    fifths of the chip's 17.18e9 B: the slabs and six convs' [N, 50, 768]
    activations kept for the reverse pass (ISSUE 35 reckoned 0.9 MB an atom,
    5-7 GB in the larger bucket; the compile says 1.2 MB an atom at a node
    capacity of 5,504). This is the cell's real footprint: the chip's own
    ``peak_bytes_in_use`` does not count those temporaries (PR 35's first
    run read 0.94 GB with 0.53 GB staged; PERF.md section 4). Batch 64 would
    not fit beside them."""
    (_text, _batch, node_cap, f, m, n_convs, _gauss,
     compiled) = _ocp_scan_program(one_chip)
    assert (f, m, n_convs) == (384, 50, 6)
    assert 4500 < node_cap < 6200  # the real size: ~160 atoms a slab x 32
    mem = compiled.memory_analysis()
    on_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"ocp.train's largest program: node capacity {node_cap}, "
          f"temporaries {mem.temp_size_in_bytes / 1e9:.2f} GB "
          f"({mem.temp_size_in_bytes / node_cap / 1e6:.2f} MB an atom), "
          f"arguments {mem.argument_size_in_bytes / 1e9:.2f} GB, "
          f"{on_chip / 1e9:.2f} GB on the chip "
          f"({100 * on_chip / 17.18e9:.1f}%)")
    assert 0.33 * 17.18e9 < on_chip < 0.60 * 17.18e9, on_chip
    assert 0.8e6 < mem.temp_size_in_bytes / node_cap < 1.4e6


@functools.cache  # two tests read it
def _four_chip_program(mesh):
    """Cell ``mp.train-dp4``'s largest scan program (chunk of four steps
    over the largest bucket's resident stack) at the cell's real size on the
    described host's four chips, assembled as ``fit_data_parallel`` and
    ``benchmark/kinds/dp_train.py`` assemble it: 128 structures a chip,
    compact staging with flat rows, the guard, the published widths in
    bfloat16 -> (compiled, node capacity, stack length, bytes of one chip's
    row of the stack, one chip's batch as staged).

    This compile picks the entry layouts it likes best, so a relayout of
    the staged arrays that the chip pays for is not in its text (PERF.md
    section 6, PRs 25 and 32: only a traced chip run shows one)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cgnn_tpu.config import DataConfig, ModelConfig, build_model
    from cgnn_tpu.data.compact import (
        CompactSpec,
        compact_pack_fn,
        flat_rows,
        make_expander,
    )
    from cgnn_tpu.data.dataset import load_synthetic_mp
    from cgnn_tpu.data.graph import bucketed_batch_iterator
    from cgnn_tpu.parallel.data_parallel import (
        make_parallel_eval_step,
        make_parallel_train_step,
        stack_batches,
    )
    from cgnn_tpu.train import Normalizer, create_train_state, make_optimizer
    from cgnn_tpu.train.loop import ScanEpochDriver

    n_dev, per_dev, m = 4, 128, 12
    # the cell's steps a bucket: ~32 device groups an epoch-copy over three
    # buckets, 216 copies
    stack = 2304
    data_cfg = DataConfig()
    graphs = load_synthetic_mp(768, data_cfg.featurize_config(), seed=0)
    spec = CompactSpec.build(graphs, data_cfg.featurize_config().gdf(),
                             dense_m=m, edge_dtype=jnp.bfloat16)
    batches = list(bucketed_batch_iterator(
        graphs, per_dev, 3, dense_m=m, snug=True, edge_dtype=jnp.bfloat16,
        pack_fn=compact_pack_fn(spec)))
    largest = max(batches, key=lambda b: b.node_capacity)
    node_cap = largest.node_capacity
    assert node_cap > 4000  # the real size: ~30 atoms a structure and more
    group = stack_batches([largest] * n_dev)  # [D, ...]: shapes only
    model = build_model(
        ModelConfig(atom_fea_len=64, n_conv=3, h_fea_len=128,
                    dtype="bfloat16", dense_m=m), data_cfg, "regression")
    expand = make_expander(spec)
    state = create_train_state(
        model, expand(largest),
        make_optimizer(optim="sgd", lr=0.01, momentum=0.9,
                       lr_milestones=[10**9]),
        Normalizer(mean=jnp.zeros(1), std=jnp.ones(1)))
    driver = ScanEpochDriver(
        make_parallel_train_step(mesh, guard=True, expand=expand),
        make_parallel_eval_step(mesh, expand=expand), [group], [],
        np.random.default_rng(0), stage=flat_rows,  # shard_scan_stack's form
        chunk_steps=2)
    (key, stacked), = driver._train_groups.items()
    fn = driver._window_fn(driver._train_scans, (key, 4),
                           driver._train_body, True)
    replicated = NamedSharding(mesh, P())

    def staged(x):  # [1, D, ...] -> the real stack, split over the chips
        where = NamedSharding(
            mesh, P(None, "data", *([None] * (np.ndim(x) - 2))))
        return jax.ShapeDtypeStruct((stack,) + np.shape(x)[1:], x.dtype,
                                    sharding=where)

    shapes = (
        jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                           sharding=replicated), state),
        jax.tree_util.tree_map(staged, stacked),
        jax.ShapeDtypeStruct((stack,), np.int32, sharding=replicated),
        jax.ShapeDtypeStruct((), np.int32, sharding=replicated),
    )
    compiled = fn.lower(*shapes).compile()
    row = sum(int(np.prod(np.shape(x)[2:])) * x.dtype.itemsize
              for x in jax.tree_util.tree_leaves(stacked))
    return compiled, node_cap, stack, row, largest


def test_four_chip_scan_program_at_real_size(four_chips, no_compile_cache):
    """It compiles; a chip holds the program and the stack it is handed in
    well under its 16 GB; the only collectives are all-reduces and every
    one of them carries the ``dp.allreduce`` scope (train/step.py), so a
    device trace can attribute them; nothing gathers a batch or the element
    table across chips."""
    from cgnn_tpu.observe import phases

    compiled, node_cap, stack, row, _batch = _four_chip_program(four_chips)
    m = 12
    mem = compiled.memory_analysis()
    on_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert mem.argument_size_in_bytes >= stack * row  # one chip's share
    assert on_chip < 16e9 / 2, on_chip

    text = compiled.as_text()
    collectives, unscoped, batch_sized = [], [], []
    wide = re.compile(rf"\[(\d+,)*{node_cap},{m}(,\d+)*\]")
    for comp in phases._parse(text).values():
        for name, rest in comp["instrs"].items():
            op = re.search(r"\s(all-reduce|all-gather|all-to-all|"
                           r"collective-permute|reduce-scatter|"
                           r"collective-broadcast)(-start)?\(", rest)
            if not op:
                continue
            collectives.append(op.group(1))
            op_name = phases._OP_NAME.search(rest)
            if not op_name or phases.classify(
                    op_name.group(1))[0] != phases.DP_ALLREDUCE:
                unscoped.append(rest[:200])
            if wide.search(rest.partition(op.group(0))[0]):
                batch_sized.append(rest[:200])
    print(f"four-chip program: node capacity {node_cap}, {on_chip / 1e9:.2f} "
          f"GB a chip, {len(collectives)} all-reduces")
    assert collectives and set(collectives) == {"all-reduce"}, collectives
    assert not unscoped, unscoped
    assert not batch_sized, batch_sized


def _kernel_calls(text: str, kernel: str) -> int:
    """The compiled text's calls of the Pallas kernels named ``kernel*``."""
    return len(re.findall(rf"%{kernel}[\w.]* = [^\n]*custom-call\(", text))


def _check_the_refined_tables(text: str, calls: int):
    """``ops/masked_attention.py`` ``_refined`` in a decoder's window
    program (PR 51): of every splash kernel's scalar-prefetch tables the
    first two, ``data_next`` and ``block_mask``, are what an operation of
    the program wrote under the attention's scope before it called the
    library (in the forward pass a sequence's slice, ``squeeze``, of the
    tables that the scan over the sequences made of the step's
    ``segment_ids`` beforehand), where the mask's partial blocks, the
    kernel's last operand, are as built: a constant that the loops carry,
    which the library's own call converts. That Mosaic
    lowers a kernel whose tables are computed values is what the compile
    itself shows."""
    from cgnn_tpu.observe import phases

    def made(comp, name):
        wrote = phases._resolve(comps, comp, name)
        return wrote.endswith("/squeeze") or (
            "/attn." in wrote and "jit(_splash_attention)" not in wrote)

    comps = phases._parse(text)
    seen = 0
    for comp, body in comps.items():
        for name, rest in body["instrs"].items():
            if not (name.startswith("splash_mqa_")
                    and "custom-call(" in rest):
                continue
            operands = re.findall(
                r"%([\w.\-]+)", re.search(r"custom-call\(([^)]*)\)",
                                          rest).group(1))
            data_next, block_mask, blocks = *operands[:2], operands[-1]
            assert "s8[1," in body["instrs"][block_mask][:8], name
            assert "s32[" in body["instrs"][blocks][:8], name
            assert made(comp, data_next) and made(comp, block_mask), name
            assert not made(comp, blocks), name
            seen += 1
    assert seen == calls


def _take_the_head_kernels(monkeypatch):
    """``lm_blocks.prepare_heads`` asks the default backend, which is the
    CPU here: the described chip's answer is the shape's alone."""
    from cgnn_tpu.models import lm_blocks
    from cgnn_tpu.ops import prepare_heads

    monkeypatch.setattr(lm_blocks, "heads_fused", prepare_heads.supported)


def _check_the_prepared_heads(text: str, sites: int, n: int, heads: tuple,
                              d: int):
    """``lm_blocks.prepare_heads`` in a decoder's window program of
    ``sites`` call sites (PR 47): the forward kernel on q and on k a site in
    the forward pass and again where the layer's checkpoint rebuilds them,
    the reverse kernel once each, all booked ``attn.proj``; and under
    ``attn.proj`` nothing the device runs as an operation of its own writes
    a float32 ``[1, n, heads, d]`` (the norm's and RoPE's passes of before
    wrote three a site and direction). What is still float32 there at that
    size is on the attention's other side: the reverse pass's cotangent of
    the attention's output, ``f32[1, heads, n, d]`` beside its bfloat16."""
    import collections

    from cgnn_tpu.observe import phases

    table = phases.phase_table(text)
    booked = {k: collections.Counter(
        table[name] for name in table
        if name.startswith(f"prepare_heads_{k}")) for k in ("fwd", "bwd")}
    print(f"prepare_heads kernels: {booked}")
    assert booked["fwd"] == {(phases.ATTN_PROJ, phases.FWD): 2 * sites,
                             (phases.ATTN_PROJ, phases.BWD): 2 * sites}
    assert booked["bwd"] == {(phases.ATTN_PROJ, phases.BWD): 2 * sites}
    comps = phases._parse(text)
    wide = re.compile("|".join(rf"f32\[1,{n},{h},{d}\]" for h in heads))
    written = [rest[:200] for comp in comps.values()
               for name, rest in comp["instrs"].items()
               if table.get(name, ("",))[0] == phases.ATTN_PROJ
               and wide.search(_result_type(rest))]
    assert not written, written


def test_sdar_scan_program_at_real_size_fits_the_chip(one_chip,
                                                      no_compile_cache,
                                                      monkeypatch):
    """``sdar.train``'s window program (a chunk of two steps of the
    block-diffusion decoder at the cell's real size: 4 layers, 456.3 M
    parameters, 2 sequences of 4,096 tokens a step) compiles for the
    described chip with both kernels in it, under 15 GB by the compile's
    memory analysis (the state's 5.48 GB aliased in place, ~8.3 GB of
    temporaries of which 1.83 GB are the gradients and 0.55 what the
    layers' checkpoints keep of the attention: its output and log-sum-exp a
    layer and sequence, ``lm_blocks.by_sequence``; 13.79 GB in all, 13.09
    when a layer's input was all that was kept, PR 46), and never holds a
    ``[*, 8192, 8192]`` score array. The attention's forward kernel is
    there once: the one the reverse pass would rebuild is dead code. At 6
    layers the same analysis read 16.97 GB (PERF.md section 4, PR 42). The
    expert layer's switch over its rungs is there, forward and reverse, and
    its compact rungs hold no array of all ``T x k`` rows (PR 44). q and k
    reach the attention through ``lm_blocks.prepare_heads``'s kernels (PR
    47: ``_check_the_prepared_heads``)."""
    import dataclasses
    import json
    import os

    from benchmark.kinds import bd_train
    from benchmark.weights import seed_key
    from benchmark.weights_sdar import StateMaker
    from cgnn_tpu.data import tokens
    from cgnn_tpu.models import sdar
    from cgnn_tpu.train import lm_step, make_optimizer
    from cgnn_tpu.train.loop import ScanEpochDriver

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "sdar-30b-a3b-ep8.json")) as f:
        cfg = json.load(f)
    # the described chip is not the default backend: name the kernels
    mc = dataclasses.replace(bd_train.model_config(cfg), attn_impl="splash",
                             moe_impl="megablox")
    _take_the_head_kernels(monkeypatch)
    tr, length = cfg["train"], int(cfg["data"]["sequence_length"])
    tx = make_optimizer(optim="adamw", lr=tr["lr"], b1=tr["b1"], b2=tr["b2"],
                        weight_decay=tr["weight_decay"], lr_milestones=[])
    maker = StateMaker(mc, cfg["init"], tx,
                       functools.partial(sdar.apply, mc))
    state = jax.eval_shape(maker._build, seed_key(1))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        state.params)) == mc.n_params() == 456_346_624
    batches = tokens.split_batches(
        tokens.make_pool(8, length, vocab_size=mc.vocab_size,
                         block=mc.block_length, seed=0),
        int(tr["batch_size"]))
    tiles = sdar.attention_tiles(mc, length)
    driver = ScanEpochDriver(
        lm_step.make_lm_train_step(mc, tiles),
        lm_step.make_lm_eval_step(mc, tiles), batches, [],
        np.random.default_rng(0), chunk_steps=2)
    (key, stacked), = driver._train_groups.items()
    fn = driver._window_fn(driver._train_scans, (key, 2),
                           driver._train_body, True)
    assert fn.__name__ == "scan_train_n16384_l2"
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip),
        (state, stacked, np.zeros(len(batches), np.int32),
         np.zeros((), np.int32)))
    # the suite runs under jax_enable_x64 (conftest.py), which no entry
    # point sets and under which the kernels' lowering never ends
    with jax.enable_x64(False):
        compiled = fn.lower(*shapes).compile()
    mem = compiled.memory_analysis()
    on_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"sdar.train's window program: state "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB aliased, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, {on_chip / 1e9:.2f} GB "
          f"on the chip")
    assert mem.alias_size_in_bytes > 5.4e9  # the state is updated in place
    assert 8e9 < on_chip < 15e9, on_chip
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 8  # splash and megablox
    assert not re.search(r"\[(?:\d+,)*8192,8192\]", text)
    splash = {k: _kernel_calls(text, f"splash_mqa_{k}")
              for k in ("fwd", "dq", "dkv")}
    print(f"splash kernels: {splash}")
    # one call site (the layers are scanned): the parent of PR 46, whose
    # checkpoint kept the layer's input alone, read fwd 2, dq 1, dkv 1
    assert splash == {"fwd": 1, "dq": 1, "dkv": 1}, splash
    _check_the_refined_tables(text, 3)
    _check_the_prepared_heads(text, 1, 2 * length, (
        mc.num_attention_heads, mc.num_key_value_heads), mc.head_dim)
    # the expert layer's switch (ops/moe.py): one conditional forward and
    # one in the reverse pass (the rematerialised forward's is dead code),
    # a branch a rung; only the last rung's branch holds an array of all
    # T x k = 65,536 rows: a switch differentiated through would write the
    # full rung's residuals as zeros in the compact ones
    from cgnn_tpu.observe import phases
    from cgnn_tpu.ops import moe

    pairs = 2 * length * mc.num_experts_per_tok
    rungs = moe.ladder(pairs, mc.experts_held[1], mc.n_experts)
    assert rungs == (16384, 32768, 65536)
    comps = phases._parse(text)
    called = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")

    def lines_under(root):
        seen, todo, lines = set(), [root], []
        while todo:
            comp = todo.pop()
            if comp in seen or comp not in comps:
                continue
            seen.add(comp)
            for rest in comps[comp]["instrs"].values():
                lines.append(rest)
                todo += called.findall(rest)
        return lines

    all_pairs = re.compile(rf"\[(?:\d+,)*{pairs}(?:,\d+)+\]")
    switches = [re.search(r"branch_computations=\{([^}]*)\}", rest)
                for comp in comps.values()
                for rest in comp["instrs"].values()
                if re.search(r"\sconditional\(", rest)]
    assert len(switches) == 2, len(switches)
    for switch in switches:
        branches = [b.strip().lstrip("%") for b in
                    switch.group(1).split(",")]
        assert len(branches) == len(rungs)
        wide = [sum(bool(all_pairs.search(ln)) for ln in lines_under(b))
                for b in branches]
        print(f"arrays of {pairs} rows a branch: {wide}")
        assert wide[:-1] == [0] * (len(rungs) - 1) and wide[-1] > 0, wide


def test_trinity_scan_program_at_real_size_fits_the_chip(one_chip,
                                                         no_compile_cache,
                                                         monkeypatch):
    """``trinity.train``'s window program (a chunk of two steps of the
    window-and-full-attention decoder at the cell's real size: 1 dense + 4
    expert layers, 504.1 M parameters, 2 sequences of 8,192 tokens a step)
    compiles for the described chip with its kernels in it (splash under two
    masks, megablox) and leaves at least 0.5 GB of the chip's 15.75 by the
    compile's memory analysis, the state's 6.05 GB aliased in place (15.19
    GB in all; 14.03 before the layers' checkpoints kept the attention's
    output and log-sum-exp, 0.68 GB a step, PR 46). It never holds a ``[*,
    8192, 8192]`` score array. The attention's forward kernel and the
    expert layer's switch over its rungs are there once a call site (the
    dense stack and the period's two runs are scanned; the switch forward
    and reverse): the rematerialised forward's are dead code, because the
    layer's checkpoint keeps the attention's and the routed output. q and k
    reach the attention through ``lm_blocks.prepare_heads``'s kernels, with
    positions in the window layers and without in the full one (PR 47:
    ``_check_the_prepared_heads``)."""
    import dataclasses
    import json
    import os

    from benchmark.kinds import lm_train
    from benchmark.weights import seed_key
    from benchmark.weights_afmoe import StateMaker
    from cgnn_tpu.data import tokens
    from cgnn_tpu.models import afmoe
    from cgnn_tpu.ops import moe
    from cgnn_tpu.train import lm_step, make_optimizer
    from cgnn_tpu.train.loop import ScanEpochDriver

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "trinity-mini-ep16.json")) as f:
        cfg = json.load(f)
    # the described chip is not the default backend: name the kernels
    mc = dataclasses.replace(lm_train.model_config(cfg), attn_impl="splash",
                             moe_impl="megablox")
    _take_the_head_kernels(monkeypatch)
    tr, length = cfg["train"], int(cfg["data"]["sequence_length"])
    tx = make_optimizer(optim="adamw", lr=tr["lr"], b1=tr["b1"], b2=tr["b2"],
                        weight_decay=tr["weight_decay"], lr_milestones=[])
    maker = StateMaker(mc, cfg["init"], tx,
                       functools.partial(afmoe.apply, mc))
    state = jax.eval_shape(maker._build, seed_key(1), jnp.float32(0.0))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        state.params)) == mc.n_params() == 504_147_200
    assert state.batch_stats["router_bias"].shape == (1, 4, 128)
    batches = tokens.split_batches(
        tokens.make_pool(8, length, vocab_size=mc.vocab_size, seed=0,
                         kind="causal"), int(tr["batch_size"]))
    tiles = afmoe.attention_tiles(mc, length)
    assert tiles == {"window": (70, 256, 4), "full": (136, 256, 1)}
    driver = ScanEpochDriver(
        lm_step.make_lm_train_step(mc, tiles),
        lm_step.make_lm_eval_step(mc, tiles), batches, [],
        np.random.default_rng(0), chunk_steps=2)
    (key, stacked), = driver._train_groups.items()
    fn = driver._window_fn(driver._train_scans, (key, 2),
                           driver._train_body, True)
    assert fn.__name__ == "scan_train_n16384_l2"
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip),
        (state, stacked, np.zeros(len(batches), np.int32),
         np.zeros((), np.int32)))
    # the suite runs under jax_enable_x64 (conftest.py), which no entry
    # point sets and under which the kernels' lowering never ends
    with jax.enable_x64(False):
        compiled = fn.lower(*shapes).compile()
    mem = compiled.memory_analysis()
    on_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"trinity.train's window program: state "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB aliased, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, {on_chip / 1e9:.2f} GB "
          f"on the chip")
    assert mem.alias_size_in_bytes > 6.0e9  # the state is updated in place
    assert 8e9 < on_chip < 15.25e9, on_chip  # 0.5 GB of 15.75 to spare
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 8  # splash and megablox
    assert not re.search(r"\[(?:\d+,)*8192,8192\]", text)
    splash = {k: _kernel_calls(text, f"splash_mqa_{k}")
              for k in ("fwd", "dq", "dkv")}
    print(f"splash kernels: {splash}")
    # three call sites (the dense stack, the window run, the full run): the
    # parent of PR 46 read fwd 6, dq 3, dkv 3
    assert splash == {"fwd": 3, "dq": 3, "dkv": 3}, splash
    _check_the_refined_tables(text, 9)
    _check_the_prepared_heads(text, 3, length, (
        mc.num_attention_heads, mc.num_key_value_heads), mc.head_dim)
    pairs = length * mc.num_experts_per_tok
    assert moe.ladder(pairs, 8, 128) == (8192, 16384, 32768, 65536)
    from cgnn_tpu.observe import phases

    switches = [rest for comp in phases._parse(text).values()
                for rest in comp["instrs"].values()
                if re.search(r"\sconditional\(", rest)]
    print(f"{len(switches)} conditionals")
    # a switch forward and one in the reverse pass, each run of the period
    assert len(switches) == 2 * len(mc.runs) == 4, len(switches)



def test_lfm2_scan_program_at_real_size_fits_the_chip(one_chip,
                                                      no_compile_cache):
    """``lfm2.train``'s window program (a chunk of two steps of the hybrid
    short-convolution / attention decoder at the cell's real size: 1 dense
    + 4 expert layers, 469.3 M parameters, 2 sequences of 8,192 tokens a
    step) compiles for the described chip with its kernels in it (splash at
    64 lanes a head under the causal mask, megablox at the experts' width of
    1,536) and fits the chip's 15.75 GB by the compile's memory analysis,
    the state's 5.63 GB aliased in place. It never holds a ``[*, 8192,
    8192]`` score array. The one attention layer is one call site of the
    splash kernels (its checkpoint keeps the output, so the forward kernel
    runs once); the expert layers' switch over their three rungs is there
    forward and reverse, each run of the period. At 64 lanes q and k take
    ``lm_blocks.prepare_heads``'s composition: its kernel wants whole
    128-lane tiles a head."""
    import dataclasses
    import json
    import os

    from benchmark.kinds import lfm2_train
    from benchmark.weights import seed_key
    from benchmark.weights_lfm2 import StateMaker
    from cgnn_tpu.data import tokens
    from cgnn_tpu.models import lfm2, lm_blocks
    from cgnn_tpu.observe import phases
    from cgnn_tpu.ops import moe
    from cgnn_tpu.train import lm_step, make_optimizer
    from cgnn_tpu.train.loop import ScanEpochDriver

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "lfm2-24b-a2b-ep8.json")) as f:
        cfg = json.load(f)
    # the described chip is not the default backend: name the kernels
    mc = dataclasses.replace(lfm2_train.model_config(cfg),
                             attn_impl="splash", moe_impl="megablox")
    tr, length = cfg["train"], int(cfg["data"]["sequence_length"])
    assert not lm_blocks.fused.supported(length, mc.head_dim)
    tx = make_optimizer(optim="adamw", lr=tr["lr"], b1=tr["b1"], b2=tr["b2"],
                        weight_decay=tr["weight_decay"], lr_milestones=[])
    maker = StateMaker(mc, cfg["init"], tx,
                       functools.partial(lfm2.apply, mc))
    state = jax.eval_shape(maker._build, seed_key(1), jnp.float32(0.01))
    assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        state.params)) == mc.n_params() == 469_284_992
    assert state.batch_stats["router_bias"].shape == (1, 4, 64)
    batches = tokens.split_batches(
        tokens.make_pool(8, length, vocab_size=mc.vocab_size, seed=0,
                         kind="causal"), int(tr["batch_size"]))
    tiles = lfm2.attention_tiles(mc, length)
    assert tiles == {"full": (136, 256, 1)}
    driver = ScanEpochDriver(
        lm_step.make_lm_train_step(mc, tiles),
        lm_step.make_lm_eval_step(mc, tiles), batches, [],
        np.random.default_rng(0), chunk_steps=2)
    (key, stacked), = driver._train_groups.items()
    fn = driver._window_fn(driver._train_scans, (key, 2),
                           driver._train_body, True)
    assert fn.__name__ == "scan_train_n16384_l2"
    shapes = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=one_chip),
        (state, stacked, np.zeros(len(batches), np.int32),
         np.zeros((), np.int32)))
    # the suite runs under jax_enable_x64 (conftest.py), which no entry
    # point sets and under which the kernels' lowering never ends
    with jax.enable_x64(False):
        compiled = fn.lower(*shapes).compile()
    mem = compiled.memory_analysis()
    on_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"lfm2.train's window program: state "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB aliased, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, {on_chip / 1e9:.2f} GB "
          f"on the chip")
    assert mem.alias_size_in_bytes > 5.6e9  # the state is updated in place
    assert 8e9 < on_chip < 15.75e9, on_chip
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5  # splash and megablox
    assert not re.search(r"\[(?:\d+,)*8192,8192\]", text)
    splash = {k: _kernel_calls(text, f"splash_mqa_{k}")
              for k in ("fwd", "dq", "dkv")}
    print(f"splash kernels: {splash}")
    assert splash == {"fwd": 1, "dq": 1, "dkv": 1}, splash
    _check_the_refined_tables(text, 3)
    # the composition, at 64 lanes: no kernel of ops/prepare_heads.py
    assert _kernel_calls(text, "prepare_heads_") == 0
    pairs = length * mc.num_experts_per_tok
    assert moe.ladder(pairs, 8, 64) == (8192, 16384, 32768)
    table = phases.phase_table(text)
    seen = {phase for phase, _ in table.values()}
    assert {"sconv.proj", "sconv.mix", "attn.proj", "attn.full",
            "mlp.dense", "moe.route", "moe.expert", "lm.embed", "lm.head",
            "optimizer", "scan"} <= seen, seen
    assert {("sconv.mix", "fwd"), ("sconv.mix", "bwd")} <= set(
        table.values())
    switches = [rest for comp in phases._parse(text).values()
                for rest in comp["instrs"].values()
                if re.search(r"\sconditional\(", rest)]
    print(f"{len(switches)} conditionals")
    # a switch forward and one in the reverse pass, each run of the period
    assert len(switches) == 2 * len(mc.runs) == 4, len(switches)


# (sequences a step, their length, the analysis's bounds in bytes): the
# cell's traffic, and ISSUE 52's first choice, which does not fit the chip
@pytest.mark.parametrize("sequences, length, on_chip_bounds", [
    (4, 4096, (8e9, 15.75e9)), (2, 8192, (15.75e9, 17e9))])
def test_nemotron_scan_program_at_real_size_fits_the_chip(
        one_chip, no_compile_cache, sequences, length, on_chip_bounds):
    """``nemotron.train``'s window program (a chunk of two steps of the
    hybrid Mamba-2 / attention decoder at the cell's real size: 3 expert, 3
    Mamba-2 layers and 1 attention layer, 528.1 M parameters, 4 sequences of
    4,096 tokens a step) compiles for the described chip with its kernels
    in it (splash at 16 queries a key-value head under the causal mask,
    megablox at the hidden width 2,688 in 128-lane tiles and at the experts'
    1,856 padded to 1,920) and fits the chip's 15.75 GB by the compile's
    memory analysis, the state's 6.34 GB aliased in place. **At 2 sequences
    of 8,192, the same 16,384 positions a step, the analysis reads over
    15.75 GB (16.36): the reason the cell's traffic gave way, as ISSUE 52
    ruled, held here so that a change that makes it fit shows.** It never
    holds a head's ``[4096, 4096]`` score array. The stack holds one scan
    body a kind: the period ``E M E M E M *`` is the group ``(E, M)`` three
    times and ``*``, so the expert layers' switch over their four rungs is
    there once forward and once in reverse, and the one attention layer is
    one call site of the splash kernels."""
    import dataclasses
    import json
    import os

    from benchmark.kinds import nemotron_train
    from benchmark.weights import seed_key
    from benchmark.weights_nemotron import StateMaker
    from cgnn_tpu.data import tokens
    from cgnn_tpu.models import nemotron_h
    from cgnn_tpu.observe import phases
    from cgnn_tpu.ops import moe
    from cgnn_tpu.train import lm_step, make_optimizer
    from cgnn_tpu.train.loop import ScanEpochDriver

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "nemotron-3-nano-30b-a3b-ep16.json")) as f:
        cfg = json.load(f)
    # the described chip is not the default backend: name the kernels
    mc = dataclasses.replace(nemotron_train.model_config(cfg),
                             attn_impl="splash", moe_impl="megablox")
    tr, data = cfg["train"], cfg["data"]
    assert (4, 4096) == (tr["batch_size"], data["sequence_length"])
    assert 16 * 8192 == data["n"] * data["sequence_length"]
    # the suite runs under jax_enable_x64 (conftest.py), which no entry
    # point sets and under which the kernels' lowering never ends: the
    # state, the driver and the program are made as an entry point makes them
    with jax.enable_x64(False):
        tx = make_optimizer(optim="adamw", lr=tr["lr"], b1=tr["b1"],
                            b2=tr["b2"], weight_decay=tr["weight_decay"],
                            lr_milestones=[])
        maker = StateMaker(mc, cfg["init"], tx,
                           functools.partial(nemotron_h.apply, mc))
        state = jax.eval_shape(maker._build, seed_key(1), jnp.float32(0.01))
        assert sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
            state.params)) == mc.n_params() == 528_092_736
        assert state.batch_stats["router_bias"].shape == (1, 3, 128)
        assert mc.groups == ((("moe", "mamba"), 3), (("attention",), 1))
        # the cell's own 8 steps an epoch: at 2 (a pool of 8 sequences)
        # the analysis reads 0.6 GB more, a program no run of the cell has
        batches = tokens.split_batches(
            tokens.make_pool(8 * sequences, length, vocab_size=mc.vocab_size,
                             seed=0, kind="causal"), sequences)
        assert len(batches) == 8
        tiles = nemotron_h.attention_tiles(mc, length)
        assert tiles == {"full": {4096: (36, 64, 1),
                                  8192: (136, 256, 1)}[length]}
        driver = ScanEpochDriver(
            lm_step.make_lm_train_step(mc, tiles),
            lm_step.make_lm_eval_step(mc, tiles), batches, [],
            np.random.default_rng(0), chunk_steps=2)
        (key, stacked), = driver._train_groups.items()
        fn = driver._window_fn(driver._train_scans, (key, 2),
                               driver._train_body, True)
        assert fn.__name__ == "scan_train_n16384_l2"
        shapes = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                           sharding=one_chip),
            (state, stacked, np.zeros(len(batches), np.int32),
             np.zeros((), np.int32)))
        compiled = fn.lower(*shapes).compile()
    mem = compiled.memory_analysis()
    on_chip = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"nemotron.train's window program at {sequences} x {length}: state "
          f"{mem.alias_size_in_bytes / 1e9:.2f} GB aliased, temporaries "
          f"{mem.temp_size_in_bytes / 1e9:.2f} GB, {on_chip / 1e9:.2f} GB "
          f"on the chip")
    assert mem.alias_size_in_bytes > 6.3e9  # the state is updated in place
    low, high = on_chip_bounds
    assert low < on_chip < high, on_chip
    if length != data["sequence_length"]:
        return  # what does not fit is no program of the cell
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 5  # splash and megablox
    # (a sequence's [1, 4096, 4096] is its Mamba layers' inner width)
    assert not re.search(r"\[(?:\d+,)*(?:[2-9]|\d\d+),4096,4096\]", text)
    splash = {k: _kernel_calls(text, f"splash_mqa_{k}")
              for k in ("fwd", "dq", "dkv")}
    print(f"splash kernels: {splash}")
    assert splash == {"fwd": 1, "dq": 1, "dkv": 1}, splash
    _check_the_refined_tables(text, 3)
    # no norm over a head, nothing rotated: no kernel of ops/prepare_heads.py
    assert _kernel_calls(text, "prepare_heads_") == 0
    pairs = length * mc.num_experts_per_tok
    assert moe.ladder(pairs, 8, 128) == (3072, 6144, 12288, 24576)
    # megablox at both widths: the experts' 1,856 lanes padded to 1,920
    assert re.search(r"bf16\[8,2688,1920\]", text)
    assert re.search(r"bf16\[8,1920,2688\]", text)
    assert not re.search(r"bf16\[\d+,1856\]", text)
    table = phases.phase_table(text)
    seen = {phase for phase, _ in table.values()}
    assert {"ssm.proj", "ssm.conv", "ssm.scan", "ssm.gate", "attn.proj",
            "attn.full", "moe.route", "moe.expert", "moe.shared", "lm.embed",
            "lm.head", "optimizer", "scan"} <= seen, seen
    assert {("ssm.scan", "fwd"), ("ssm.scan", "bwd"), ("ssm.conv", "bwd"),
            ("ssm.gate", "bwd")} <= set(table.values())
    switches = [rest for comp in phases._parse(text).values()
                for rest in comp["instrs"].values()
                if re.search(r"\sconditional\(", rest)]
    print(f"{len(switches)} conditionals")
    # one expert body in the program: a switch forward and one in reverse
    assert len(switches) == 2, len(switches)
