#!/usr/bin/env python
"""Compile and run every Pallas kernel once at flagship shapes and compare
it with its XLA twin — on the TPU, where Mosaic (not the interpreter)
decides whether a kernel exists at all.

None of these kernels is on the default path; each is one flag away
(``--cgconv-impl pallas``, ``--fused-epilogue pallas``, ``--aggregation
pallas``, ``neighbor_search(impl="pallas")``, ``ops/pallas_gather.py``).
The verdict for each is one of: ``ok`` (compiled, agrees with the twin),
``mismatch`` (compiled, disagrees), ``refused`` (the compiler's message is
recorded). Nothing here is a timing.

Shapes: one dense batch of 512 MP-like structures (F=64, 3 convs, h=128,
M=12, radius 8 / 41 Gaussians), float32 and bfloat16.

    python scripts/kernel_check.py [NAME ...]   # needs a TPU; writes
                                                # chiprun_out/kernel_check.json
NAME filters the kernels by substring (default: all).
"""

from __future__ import annotations

import json
import os
import sys
import traceback

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cgnn_tpu.observe.metrics_io import jsonfinite  # noqa: E402
from cgnn_tpu.runtime import REPO_ROOT, configure_compile_cache  # noqa: E402

BATCH = 512
F, N_CONV, H, M = 64, 3, 128, 12


def _flat(tree):
    import jax
    import numpy as np

    return [np.asarray(v, np.float32)
            for _, v in sorted(
                jax.tree_util.tree_leaves_with_path(tree),
                key=lambda kv: jax.tree_util.keystr(kv[0]))]


def _max_rel(got, ref) -> float:
    """max over groups of max|a-b| / max|b|, one scale per GROUP of
    arrays (a group is e.g. all gradient leaves: a bias ahead of a
    BatchNorm has an exactly-zero gradient, and its roundoff noise must
    be judged against the gradient's scale, not its own). NaN if any
    kernel output is not finite."""
    import numpy as np

    worst = 0.0
    for a_list, b_list in zip(got, ref):
        if not all(np.isfinite(a).all() for a in a_list):
            return float("nan")
        scale = max(max(float(np.abs(b).max()) for b in b_list), 1e-12)
        worst = max(worst, max(float(np.abs(a - b).max())
                               for a, b in zip(a_list, b_list)) / scale)
    return worst


def _model_case(batch, dtype, **kernel_kw):
    """loss, grads and eval outputs of the flagship model with one kernel
    selection — through the same module fields the CLI flags set."""
    import jax
    import jax.numpy as jnp

    from cgnn_tpu.models import CrystalGraphConvNet

    dense = batch.edges.ndim == 3
    model = CrystalGraphConvNet(
        atom_fea_len=F, n_conv=N_CONV, h_fea_len=H, dtype=dtype,
        dense_m=M if dense else None, **kernel_kw)
    variables = model.init(jax.random.key(0), batch)

    real = jnp.asarray(batch.graph_mask, jnp.float32)[:, None]

    def loss(params):
        out, _ = model.apply(
            {"params": params, "batch_stats": variables["batch_stats"]},
            batch, train=True, mutable=["batch_stats"])
        return ((out.astype(jnp.float32) * real) ** 2).sum()

    val, grads = jax.jit(jax.value_and_grad(loss))(variables["params"])
    ev = jax.jit(lambda v: model.apply(v, batch, train=False).astype(
        jnp.float32) * real)(variables)
    # three groups: the loss, every gradient leaf, the eval outputs
    return [_flat(val), _flat(grads), _flat(ev)]


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    configure_compile_cache(None)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"kernel_check.py needs a TPU (Mosaic); jax found "
              f"{dev.platform}", file=sys.stderr)
        return 2

    from cgnn_tpu.data.dataset import FeaturizeConfig, load_synthetic_mp
    from cgnn_tpu.data.graph import batch_iterator, capacities_for
    from cgnn_tpu.data.rawbatch import plan_raw_spec, raw_from_graph
    from cgnn_tpu.ops import pallas_gather
    from cgnn_tpu.ops.neighbor_search import neighbor_search
    from cgnn_tpu.ops.pallas_cgconv import _win_starts, window_width
    from cgnn_tpu.ops.segment import aggregate_edge_messages
    from cgnn_tpu.serve.shapes import plan_shape_set

    cfg = FeaturizeConfig()
    graphs = load_synthetic_mp(BATCH, cfg, seed=0, keep_geometry=True)
    max_nodes = max(g.num_nodes for g in graphs)

    def one_batch(dense_m, edge_dtype):
        nc, ec = capacities_for(graphs, BATCH, dense_m=dense_m, snug=True)
        return next(batch_iterator(graphs, BATCH, nc, ec, dense_m=dense_m,
                                   snug=True, edge_dtype=edge_dtype))

    results = {}
    only = sys.argv[1:]

    def record(name, dtype_name, fn, tol):
        """fn() -> (kernel outputs, twin outputs), each a list of
        groups of arrays (see ``_max_rel``)."""
        key = f"{name}[{dtype_name}]"
        if only and not any(s in key for s in only):
            return
        try:
            got, ref = fn()
            err = _max_rel(got, ref)
            results[key] = {
                "verdict": "ok" if err <= tol else "mismatch",
                "max_rel_diff_vs_xla_twin": err, "tolerance": tol,
            }
        except Exception as e:  # noqa: BLE001 — the message IS the result
            msg = f"{type(e).__name__}: {e}"
            results[key] = {"verdict": "refused", "message": msg[:4000]}
            traceback.print_exc(limit=3)
        print(key, json.dumps(jsonfinite(results[key]))[:600], flush=True)

    for dtype, dname, tol in ((jnp.float32, "f32", 2e-3),
                              (jnp.bfloat16, "bf16", 5e-2)):
        edge_dtype = jnp.bfloat16 if dtype == jnp.bfloat16 else np.float32
        dense = one_batch(M, edge_dtype)
        coo = one_batch(None, edge_dtype)
        window = window_width(max_nodes)
        record("cgconv", dname, lambda: (
            _model_case(dense, dtype, cgconv_impl="pallas",
                        cgconv_window=window),
            _model_case(dense, dtype, cgconv_impl="xla",
                        cgconv_window=window)), tol)
        record("fused_epilogue", dname, lambda: (
            _model_case(dense, dtype, fused_epilogue="pallas"),
            _model_case(dense, dtype, fused_epilogue="xla")), tol)
        record("pallas_scatter(--aggregation pallas)", dname, lambda: (
            _model_case(coo, dtype, aggregation_impl="pallas"),
            _model_case(coo, dtype, aggregation_impl="xla")), tol)

        def scatter_op():
            e = int(coo.centers.shape[0])
            msg = jnp.asarray(np.random.default_rng(0).standard_normal(
                (e, 2 * F)).astype(np.float32), dtype)
            msg = msg * jnp.asarray(coo.edge_mask, dtype)[:, None]
            n = int(coo.nodes.shape[0])
            out = {impl: np.array(jax.jit(
                lambda m, impl=impl: aggregate_edge_messages(
                    m, jnp.asarray(coo.centers), n, impl=impl))(msg),
                np.float32) for impl in ("pallas", "xla")}
            return [[out["pallas"]]], [[out["xla"]]]

        record("pallas_scatter(op)", dname, scatter_op, tol)

        def gather_op():
            n = int(dense.nodes.shape[0])
            n_pad = -(-n // 128) * 128
            nodes = jnp.asarray(np.random.default_rng(1).standard_normal(
                (n_pad, F)).astype(np.float32), dtype)
            nbr = np.arange(n_pad * M, dtype=np.int32) // M  # self-loops
            nbr[: n * M] = np.asarray(dense.neighbors)
            ws = jnp.asarray(_win_starts(n_pad // 128, n_pad, window))
            got = jax.jit(lambda a, b, c: pallas_gather.windowed_gather(
                a, b, c, window))(nodes, jnp.asarray(nbr), ws)
            ref = jnp.take(nodes, jnp.asarray(nbr), axis=0).reshape(
                n_pad, M, F)
            return ([[np.asarray(got, np.float32)]],
                    [[np.asarray(ref, np.float32)]])

        record("pallas_gather(op)", dname, gather_op, tol)

    # the in-program neighbor search has no dtype axis (f32 geometry)
    spec = plan_raw_spec(graphs, cfg.gdf(), cfg.radius, M)
    ladder = plan_shape_set(graphs, 64, rungs=3, dense_m=M,
                            num_targets=1, raw=spec)
    raws = [r for r in (raw_from_graph(g) for g in graphs)
            if ladder.admits_raw(r)][: ladder.largest.graph_cap]
    rb = ladder.pack_raw(raws, shape=ladder.largest)

    def search():
        outs = {}
        for impl in ("pallas", "xla"):
            # one group per output: neighbors, distances, mask, counts
            outs[impl] = [[np.asarray(x, np.float32)] for x in jax.jit(
                lambda rb, impl=impl: neighbor_search(
                    rb.frac, rb.lattices, rb.atom_mask, spec, impl=impl)
            )(rb)]
        return outs["pallas"], outs["xla"]

    record(f"neighbor_search(S={spec.snode_cap},K={spec.n_images},"
           f"G={ladder.largest.graph_cap})", "f32", search, 1e-6)

    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
        "jax": jax.__version__,
        "shapes": {"batch": BATCH, "F": F, "n_conv": N_CONV, "h": H,
                   "M": M, "node_cap": int(dense.nodes.shape[0]),
                   "gaussians": int(graphs[0].edge_fea.shape[1])},
        "kernels": results,
    }
    out_dir = os.path.join(REPO_ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "kernel_check.json"), "w") as f:
        json.dump(jsonfinite(out), f, indent=1)
    print(json.dumps({k: v["verdict"] for k, v in results.items()},
                     allow_nan=False))
    return 0


if __name__ == "__main__":
    sys.exit(main())
