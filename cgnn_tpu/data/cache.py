"""On-disk graph cache: featurize once, stream tensors (SURVEY.md §7 phase 4).

At the 10k structures/sec/chip target, per-step CIF parsing + neighbor
search is orders of magnitude too slow (§3.4) — the reference's
DataLoader-worker model cannot feed a TPU. The pipeline is therefore:

    CIFs --(featurize, parallel, once)--> cache file --(mmap)--> batcher

Format: a single ``.npz`` holding the concatenation of all per-graph arrays
plus offset tables — O(1) metadata, zero-copy row slicing on load.
"""

from __future__ import annotations

import multiprocessing
import os
from concurrent.futures import ProcessPoolExecutor
from typing import Sequence

import numpy as np

from cgnn_tpu.data.graph import CrystalGraph

_VERSION = 1


def save_graph_cache(graphs: Sequence[CrystalGraph], path: str) -> None:
    """Serialize featurized graphs into one compact npz."""
    node_counts = np.array([g.num_nodes for g in graphs], np.int64)
    edge_counts = np.array([g.num_edges for g in graphs], np.int64)
    tgt = [np.atleast_1d(np.asarray(g.target, np.float32)) for g in graphs]
    tdim = max(len(t) for t in tgt)
    targets = np.zeros((len(graphs), tdim), np.float32)
    target_mask = np.zeros((len(graphs), tdim), np.float32)
    for i, (g, t) in enumerate(zip(graphs, tgt)):
        targets[i, : len(t)] = t
        if g.target_mask is not None:
            target_mask[i, : len(t)] = np.atleast_1d(g.target_mask)
        else:
            target_mask[i, : len(t)] = 1.0

    have_geom = all(
        g.positions is not None and g.lattice is not None and g.offsets is not None
        for g in graphs
    )
    payload = {
        "version": np.int64(_VERSION),
        "node_counts": node_counts,
        "edge_counts": edge_counts,
        "atom_fea": np.concatenate([g.atom_fea for g in graphs]),
        "edge_fea": np.concatenate([g.edge_fea for g in graphs]),
        "centers": np.concatenate([g.centers for g in graphs]),
        "neighbors": np.concatenate([g.neighbors for g in graphs]),
        "targets": targets,
        "target_mask": target_mask,
        "cif_ids": np.array([g.cif_id for g in graphs]),
        "has_geometry": np.int64(1 if have_geom else 0),
    }
    if all(g.distances is not None for g in graphs):
        payload["distances"] = np.concatenate([g.distances for g in graphs])
    if have_geom:
        payload["positions"] = np.concatenate([g.positions for g in graphs])
        payload["lattices"] = np.stack([g.lattice for g in graphs])
        payload["offsets"] = np.concatenate([g.offsets for g in graphs])
        if all(g.numbers is not None for g in graphs):
            # species ride with the geometry: together they are the raw
            # wire form (data/rawbatch.py raw_from_graph)
            payload["numbers"] = np.concatenate(
                [np.asarray(g.numbers, np.int32) for g in graphs])
    if all(g.forces is not None for g in graphs):
        payload["forces"] = np.concatenate([g.forces for g in graphs])
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        np.savez(f, **payload)
    os.replace(tmp, path)


def load_graph_cache(path: str) -> list[CrystalGraph]:
    """Load a cache back into CrystalGraphs (views into the mmap'd arrays)."""
    z = np.load(path, mmap_mode="r", allow_pickle=False)
    if int(z["version"]) != _VERSION:
        raise ValueError(
            f"cache {path} has version {int(z['version'])}, expected {_VERSION}"
        )
    node_counts = np.asarray(z["node_counts"])
    edge_counts = np.asarray(z["edge_counts"])
    node_off = np.concatenate([[0], np.cumsum(node_counts)])
    edge_off = np.concatenate([[0], np.cumsum(edge_counts)])
    atom_fea = z["atom_fea"]
    edge_fea = z["edge_fea"]
    centers = z["centers"]
    neighbors = z["neighbors"]
    targets = np.asarray(z["targets"])
    target_mask = np.asarray(z["target_mask"])
    cif_ids = np.asarray(z["cif_ids"])
    has_geom = bool(int(z["has_geometry"]))

    # every member is read ONCE, here: NpzFile re-reads the whole array on
    # each z[...] access and a per-graph slice keeps it alive, so a lookup
    # inside the loop below costs graphs x array bytes of host memory
    def member(key):
        return z[key] if key in z else None

    distances = member("distances")
    numbers = member("numbers")
    forces = member("forces")
    positions = member("positions") if has_geom else None
    lattices = member("lattices") if has_geom else None
    offsets = member("offsets") if has_geom else None
    from cgnn_tpu.data import invariants

    graphs = []
    for i in range(len(node_counts)):
        ns, ne = slice(node_off[i], node_off[i + 1]), slice(edge_off[i], edge_off[i + 1])
        graphs.append(
            CrystalGraph(
                atom_fea=atom_fea[ns],
                edge_fea=edge_fea[ne],
                centers=np.asarray(centers[ne]),
                neighbors=np.asarray(neighbors[ne]),
                target=targets[i],
                cif_id=str(cif_ids[i]),
                target_mask=target_mask[i],
                distances=None if distances is None else distances[ne],
                positions=None if positions is None else positions[ns],
                lattice=None if lattices is None else lattices[i],
                offsets=None if offsets is None else offsets[ne],
                forces=None if forces is None else forces[ns],
                numbers=None if numbers is None else numbers[ns],
            )
        )
    # sample-validate under --check-invariants: a truncated or bit-rotted
    # cache would otherwise surface as silent training corruption
    return invariants.maybe_spot_check_graphs(graphs)


def _featurize_one(args):
    import warnings

    from cgnn_tpu.data.cif import parse_cif_file
    from cgnn_tpu.data.dataset import FeaturizeConfig, featurize_structure

    cif_path, cif_id, target, mask, cfg_dict, keep_geometry = args
    cfg = FeaturizeConfig(**cfg_dict)
    try:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            structure = parse_cif_file(cif_path)
            return featurize_structure(
                structure, target, cfg, cif_id,
                target_mask=mask, keep_geometry=keep_geometry,
            )
    except Exception as e:  # noqa: BLE001 — mirror the reference: warn+skip
        return (cif_id, str(e))


def featurize_directory_parallel(
    root_dir: str,
    cfg,
    workers: int | None = None,
    id_prop_file: str = "id_prop.csv",
    keep_geometry: bool = False,
) -> tuple[list[CrystalGraph], list[tuple[str, str]]]:
    """Parallel CIF -> graph featurization (the offline preprocessor core).

    Returns (graphs, failures). Worker processes sidestep the GIL for the
    numpy-heavy neighbor search; the reference used DataLoader workers for
    the same reason, but per-epoch instead of once.
    """
    import csv
    import dataclasses

    workers = workers or os.cpu_count() or 1
    prop_path = os.path.join(root_dir, id_prop_file)
    if not os.path.exists(prop_path):
        raise FileNotFoundError(f"missing {prop_path}")
    jobs = []
    cfg_dict = dataclasses.asdict(cfg)
    with open(prop_path, newline="") as f:
        for row in csv.reader(f):
            if not row:
                continue
            cif_id = row[0].strip()
            raw = [c.strip() for c in row[1:]]
            target = np.array([float(c) if c else 0.0 for c in raw], np.float32)
            mask = np.array([1.0 if c else 0.0 for c in raw], np.float32)
            jobs.append(
                (os.path.join(root_dir, cif_id + ".cif"), cif_id, target, mask,
                 cfg_dict, keep_geometry)
            )
    graphs: list[CrystalGraph] = []
    failures: list[tuple[str, str]] = []

    def consume(results) -> None:
        # stream results as workers finish instead of materializing the
        # full list first: failures surface incrementally (a broken CIF
        # at position 3 of a 146k-file directory is visible in seconds,
        # not after the whole sweep) and peak host memory holds one
        # in-flight chunk per worker, not a second copy of every graph
        for r in results:
            if isinstance(r, CrystalGraph):
                graphs.append(r)
            else:
                failures.append(r)

    if workers <= 1:
        consume(map(_featurize_one, jobs))
    else:
        # spawn, not fork: the caller may already hold an accelerator
        # (train.py initialises its backend first) and is multithreaded
        # by then — forked children would inherit both
        with ProcessPoolExecutor(
            max_workers=workers,
            mp_context=multiprocessing.get_context("spawn"),
        ) as pool:
            consume(pool.map(_featurize_one, jobs, chunksize=32))
    return graphs, failures
