"""Dataset assembly: structures -> featurized CrystalGraphs -> splits.

TPU-native counterpart of the reference's ``CIFData`` + loader factory
(SURVEY.md §2 components 3, 12; §3.1). Differences by design:

- Featurization is an *offline, cached* step producing flat-COO graphs
  (SURVEY.md §7 phase 4: at 10k structures/s/chip, per-step CIF parsing is
  impossible; preprocess once, stream tensors).
- Neighbor layout is flat COO, truncated to ``max_num_nbr`` nearest like the
  reference, but without fake padding edges — static shapes come from the
  batcher (graph.py), not per-atom padding.
"""

from __future__ import annotations

import csv
import dataclasses
import os
import warnings
from typing import Sequence

import numpy as np

from cgnn_tpu.data.cif import parse_cif_file
from cgnn_tpu.data.elements import atom_features
from cgnn_tpu.data.featurize import GaussianDistance
from cgnn_tpu.data.graph import CrystalGraph
from cgnn_tpu.data.neighbors import knn_neighbor_list
from cgnn_tpu.data.structure import Structure
from cgnn_tpu.data.synthetic import synthetic_dataset


@dataclasses.dataclass
class FeaturizeConfig:
    """Featurization hyperparameters (mirror the reference CLI flags)."""

    radius: float = 8.0
    max_num_nbr: int = 12
    dmin: float = 0.0
    step: float = 0.2
    var: float | None = None  # the Gaussians' width; None = ``step``

    def gdf(self) -> GaussianDistance:
        return GaussianDistance(self.dmin, self.radius, self.step, self.var)


def featurize_structure(
    structure: Structure,
    target,
    cfg: FeaturizeConfig,
    cif_id: str = "",
    gdf: GaussianDistance | None = None,
    target_mask=None,
    keep_geometry: bool = False,
) -> CrystalGraph:
    """Structure + label -> flat-COO CrystalGraph (host-side)."""
    gdf = gdf or cfg.gdf()
    nl = knn_neighbor_list(
        structure, cfg.radius, cfg.max_num_nbr, warn_under_coordinated=False
    )
    if len(nl) == 0:
        raise ValueError(
            f"structure {cif_id!r} has no neighbors within radius {cfg.radius}"
        )
    graph = CrystalGraph(
        atom_fea=atom_features(structure.numbers),
        edge_fea=gdf.expand(nl.distances),
        centers=nl.centers,
        neighbors=nl.neighbors,
        target=np.atleast_1d(np.asarray(target, np.float32)),
        cif_id=cif_id,
        target_mask=(
            None if target_mask is None
            else np.atleast_1d(np.asarray(target_mask, np.float32))
        ),
        distances=nl.distances,
    )
    if keep_geometry:
        # neighbor offsets are computed against WRAPPED coordinates (both
        # neighbor backends wrap fracs into [0,1)); stored geometry must
        # match or in-model edge_distances() recomputes wrong distances
        graph.positions = structure.wrapped().cart_coords.astype(np.float32)
        graph.lattice = structure.lattice.astype(np.float32)
        graph.offsets = nl.offsets.astype(np.int32)
        graph.numbers = structure.numbers.copy()
    return graph


def load_cif_directory(
    root_dir: str,
    cfg: FeaturizeConfig | None = None,
    id_prop_file: str = "id_prop.csv",
    keep_geometry: bool = False,
) -> list[CrystalGraph]:
    """Reference-compatible directory layout: ``{root}/{id}.cif`` + id_prop.csv.

    Each id_prop.csv row is ``cif_id, target[, target2, ...]`` — multi-column
    rows feed the multi-task head; empty cells become masked-out labels.
    """
    cfg = cfg or FeaturizeConfig()
    gdf = cfg.gdf()
    prop_path = os.path.join(root_dir, id_prop_file)
    if not os.path.exists(prop_path):
        raise FileNotFoundError(f"missing {prop_path}")
    graphs: list[CrystalGraph] = []
    with open(prop_path, newline="") as f:
        for row in csv.reader(f):
            if not row:
                continue
            cif_id = row[0].strip()
            raw = [c.strip() for c in row[1:]]
            target = np.array([float(c) if c else 0.0 for c in raw], np.float32)
            mask = np.array([1.0 if c else 0.0 for c in raw], np.float32)
            cif_path = os.path.join(root_dir, cif_id + ".cif")
            try:
                structure = parse_cif_file(cif_path)
                graphs.append(
                    featurize_structure(
                        structure, target, cfg, cif_id, gdf,
                        target_mask=mask, keep_geometry=keep_geometry,
                    )
                )
            except Exception as e:  # noqa: BLE001 — reference warns and skips
                warnings.warn(f"skipping {cif_id}: {e}", stacklevel=2)
    if not graphs:
        raise ValueError(f"no usable structures under {root_dir}")
    return graphs


def load_synthetic(
    num_structures: int,
    cfg: FeaturizeConfig | None = None,
    seed: int = 0,
    keep_geometry: bool = False,
    **synth_kwargs,
) -> list[CrystalGraph]:
    cfg = cfg or FeaturizeConfig()
    gdf = cfg.gdf()
    return [
        featurize_structure(s, t, cfg, sid, gdf, keep_geometry=keep_geometry)
        for sid, s, t in synthetic_dataset(num_structures, seed, **synth_kwargs)
    ]


def load_synthetic_mp(
    num_structures: int,
    cfg: FeaturizeConfig | None = None,
    seed: int = 0,
    keep_geometry: bool = False,
) -> list[CrystalGraph]:
    """MP-like size distribution (lognormal ~30 atoms) for honest benching."""
    from cgnn_tpu.data.synthetic import synthetic_mp_dataset

    cfg = cfg or FeaturizeConfig()
    gdf = cfg.gdf()
    return [
        featurize_structure(s, t, cfg, sid, gdf,
                            keep_geometry=keep_geometry)
        for sid, s, t in synthetic_mp_dataset(num_structures, seed)
    ]


def load_synthetic_oc20(
    num_structures: int,
    cfg: FeaturizeConfig | None = None,
    seed: int = 0,
) -> list[CrystalGraph]:
    """OC20 IS2RE stand-in: large catalyst-slab graphs (50-200+ atoms).

    Exercises the large-graph regime of BASELINE config #4 — surface
    under-coordination, vacuum gaps, and a wide node/edge size spread that
    stresses the bucketed batcher (SURVEY.md §2 [B:10])."""
    from cgnn_tpu.data.synthetic import synthetic_oc20_dataset

    cfg = cfg or FeaturizeConfig()
    gdf = cfg.gdf()
    return [
        featurize_structure(s, t, cfg, sid, gdf)
        for sid, s, t in synthetic_oc20_dataset(num_structures, seed)
    ]


def load_synthetic_oc20_ocp(
    num_structures: int,
    cfg: FeaturizeConfig | None = None,
    seed: int = 0,
    a0: float = 3.0,
) -> list[CrystalGraph]:
    """OC20 IS2RE stand-in as the Open Catalyst baselines read it: the slabs
    of ``load_synthetic_oc20`` at a real metal's density, featurized by
    default at 6 A, the 50 nearest neighbours and 100 Gaussians of width
    sqrt(2) * step, which is the source's ``GaussianSmearing(0, 6, 100)``,
    exp(-0.5 (d - mu)^2 / step^2).

    ``a0`` 3.0 A is a bcc metal's lattice constant (Fe 2.87, V 3.03, Mo
    3.15, W 3.16): 0.074 atoms / A^3, inside the range of OC20's metals
    (fcc Au 0.059 .. Ni 0.091), where ``load_synthetic_oc20``'s 3.9 in the
    same bcc-like cell is 0.034, half of any of them. A bulk atom then has
    58 neighbours within 6 A (fcc Pt 54, Cu 78), over the baselines' cap of
    50; in these thin slabs the mean is ~44 and a third of the atoms sit at
    the cap.

    A loader of its own name because a featurized pool is cached by loader,
    size and seed, not by featurization (benchmark/system.py load_pool)."""
    from cgnn_tpu.data.synthetic import synthetic_oc20_dataset

    if cfg is None:
        step = 6.0 / 99
        cfg = FeaturizeConfig(radius=6.0, max_num_nbr=50, dmin=0.0,
                              step=step, var=2.0 ** 0.5 * step)
    gdf = cfg.gdf()
    return [
        featurize_structure(s, t, cfg, sid, gdf)
        for sid, s, t in synthetic_oc20_dataset(num_structures, seed, a0=a0)
    ]


def load_trajectory(
    num_frames: int,
    cfg: FeaturizeConfig | None = None,
    seed: int = 0,
    num_atoms: int = 8,
    jitter: float = 0.08,
) -> list[CrystalGraph]:
    """MD17 stand-in: LJ trajectory frames with energy + force labels.

    Graphs carry geometry (positions/lattice/offsets) so the differentiable
    force model can recompute distances in-model, plus per-atom ``forces``
    labels for the composite loss (BASELINE config #5).
    """
    from cgnn_tpu.data.synthetic import synthetic_trajectory

    return _trajectory_graphs(
        synthetic_trajectory(num_frames, seed=seed, num_atoms=num_atoms,
                             jitter=jitter), cfg)


def _trajectory_graphs(
    frames, cfg: FeaturizeConfig | None
) -> list[CrystalGraph]:
    """[(id, Structure, energy, forces)] -> graphs with geometry and force
    labels kept (what the force model reads)."""
    cfg = cfg or FeaturizeConfig()
    gdf = cfg.gdf()
    graphs = []
    for sid, s, energy, forces in frames:
        g = featurize_structure(
            s, energy, cfg, sid, gdf, keep_geometry=True
        )
        g.forces = forces.astype(np.float32)
        graphs.append(g)
    return graphs


def load_synthetic_md17(
    num_frames: int,
    cfg: FeaturizeConfig | None = None,
    seed: int = 0,
) -> list[CrystalGraph]:
    """MD17's real shape (BASELINE config #5): frames of one aspirin-sized
    molecule, 21 atoms, each with a total energy and 21 x 3 forces
    (``synthetic.synthetic_md17``). Geometry and force labels are always
    kept: the force model reads nothing else."""
    from cgnn_tpu.data.synthetic import synthetic_md17

    return _trajectory_graphs(synthetic_md17(num_frames, seed=seed), cfg)


def train_val_test_split(
    graphs: Sequence[CrystalGraph],
    train_ratio: float = 0.8,
    val_ratio: float = 0.1,
    seed: int = 0,
) -> tuple[list[CrystalGraph], list[CrystalGraph], list[CrystalGraph]]:
    """Deterministic shuffled split (reference: ratio-based sampler split)."""
    if train_ratio + val_ratio >= 1.0 + 1e-9:
        raise ValueError("train_ratio + val_ratio must leave room for test")
    idx = np.random.default_rng(seed).permutation(len(graphs))
    n_train = int(len(graphs) * train_ratio)
    n_val = int(len(graphs) * val_ratio)
    pick = lambda ids: [graphs[int(i)] for i in ids]  # noqa: E731
    return (
        pick(idx[:n_train]),
        pick(idx[n_train : n_train + n_val]),
        pick(idx[n_train + n_val :]),
    )
