"""In-program periodic neighbor search + featurization (ISSUE 11).

The front of the pipeline, compiled: given a staged :class:`RawBatch`
(positions, lattice, species — data/rawbatch.py), build the exact
dense-layout ``GraphBatch`` the models consume INSIDE the jitted
program. This is the host ``knn_neighbor_list`` + ``atom_features`` +
``GaussianDistance`` chain (data/neighbors.py, data/dataset.py), moved
on device under the padded-capacity discipline:

- per structure, every (atom j, periodic image k) pair is a CANDIDATE:
  a dense ``[S, S*K]`` f32 distance matrix over the rung's fixed image
  grid (``RawSpec.images``, lexicographic (ia, ib, ic) order). At
  serving-scale structures (S <= ~128 atoms, K <= ~100 images) this
  dense matrix IS the TPU-shaped form of a cell list — plain VPU
  elementwise work and one sort, no gather/scatter binning — and the
  fixed caps play the role the cell capacity plays in a binned search;
- selection is SORT-BASED: candidates sort by the canonical key
  (distance, then candidate index = source atom major, image minor) and
  the first ``dense_m`` in-radius survivors per center are the edges —
  exactly the host featurizer's ``max_num_nbr`` nearest truncation in
  exactly the host's canonical order (lexsort by (center, distance),
  ties by (source atom, image grid order));
- out-of-range slots are WHERE-masked, never multiplied: invalid
  candidates carry an ``inf`` sort key, masked edge slots emit the
  dense layout's self-loop neighbor and zero features (the same padding
  contract ``pack_graphs`` writes).

Overflow contract (INVARIANTS.md "raw-wire overflow flag"): the program
re-derives each structure's needed image counts from its STAGED lattice
(plane-spacing formula, ``data.rawbatch.needed_images_f32``) and flags
any structure whose lattice needs more images than the rung provides —
the only way this fixed-cap search can miss a true edge, given exact
top-M selection over the full candidate set. Flagged structures must
never be answered from the truncated graph (serving routes them to the
host-featurized fallback); the flag is computed IN-PROGRAM, not at
admission, because relaxation/MD (ROADMAP item 2) updates positions
on device where no host pre-check exists.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from cgnn_tpu.data.elements import full_embedding_table
from cgnn_tpu.data.graph import GraphBatch
from cgnn_tpu.data.rawbatch import RawBatch, RawSpec


def _needed_images_jnp(lat, radius: float):
    """[3] f32 needed-image counts — the jnp twin of
    ``data.rawbatch.needed_images_f32`` (same formula, same 1e-4 slack)."""
    cross = jnp.stack([
        jnp.cross(lat[1], lat[2]),
        jnp.cross(lat[2], lat[0]),
        jnp.cross(lat[0], lat[1]),
    ])
    det = jnp.abs(jnp.dot(lat[0], cross[0]))
    norms = jnp.sqrt((cross * cross).sum(axis=1))
    return jnp.ceil(jnp.float32(radius) * norms / det - jnp.float32(1e-4))


def _candidate_distances(frac, lat, offsets_f32):
    """[S, S*K] candidate distances, candidate index c = j*K + k (source
    atom major, lexicographic image minor — the canonical tie order)."""
    s_cap = frac.shape[0]
    k = offsets_f32.shape[0]
    cart = frac @ lat  # [S, 3]
    shifts = offsets_f32 @ lat  # [K, 3]
    pos_j = cart[:, None, :] + shifts[None, :, :]  # [S, K, 3]
    diff = pos_j[None, :, :, :] - cart[:, None, None, :]  # [S, S, K, 3]
    d2 = (diff[..., 0] * diff[..., 0] + diff[..., 1] * diff[..., 1]
          + diff[..., 2] * diff[..., 2])
    return jnp.sqrt(d2).reshape(s_cap, s_cap * k)


def _candidate_valid(amask, spec: RawSpec):
    """[S, S*K] bool: both atoms real, home-image self pair excluded.
    (The radius test is applied by the caller — it depends on d.)"""
    s_cap = amask.shape[0]
    k = spec.n_images
    m_b = amask.astype(bool)
    valid = m_b[:, None, None] & m_b[None, :, None]
    valid = valid & jnp.ones((s_cap, s_cap, k), bool)
    self_home = (jnp.eye(s_cap, dtype=bool)[:, :, None]
                 & (jnp.arange(k) == spec.home_image)[None, None, :])
    return (valid & ~self_home).reshape(s_cap, s_cap * k)


def _search_one(frac, lat, amask, spec: RawSpec, offsets_f32):
    """One structure's search (vmapped over the batch): ->
    (neighbors [S, M] i32 local, distances [S, M] f32,
    edge_mask [S, M] f32, n_edges i32, overflow bool)."""
    s_cap, m = spec.snode_cap, spec.dense_m
    k = spec.n_images
    d = _candidate_distances(frac, lat, offsets_f32)
    valid = _candidate_valid(amask, spec) & (d <= jnp.float32(spec.radius))
    key = jnp.where(valid, d, jnp.float32(jnp.inf))
    cand = jnp.broadcast_to(
        jnp.arange(s_cap * k, dtype=jnp.int32), (s_cap, s_cap * k)
    )
    # two-key lexicographic sort: distance, then candidate index — the
    # canonical order is exact even where the backend sort is unstable
    sk, sc = lax.sort((key, cand), dimension=1, num_keys=2)
    sk, sc = sk[:, :m], sc[:, :m]
    n_valid = valid.sum(axis=1)
    emask = jnp.arange(m)[None, :] < n_valid[:, None]
    nbr = jnp.where(emask, sc // k,
                    jnp.arange(s_cap, dtype=jnp.int32)[:, None])
    dist = jnp.where(emask, sk, jnp.float32(0.0))
    n_edges = jnp.minimum(n_valid, m).sum().astype(jnp.int32)
    need = _needed_images_jnp(lat, spec.radius)
    # padding structure slots (no real atoms; host-written identity
    # lattice) must never flag — there is no graph to truncate
    overflow = (jnp.any(need > jnp.asarray(spec.images, jnp.float32))
                & jnp.any(amask > 0))
    return nbr, dist, emask.astype(jnp.float32), n_edges, overflow


def neighbor_search(frac, lats, amask, spec: RawSpec):
    """Batched in-program search -> (neighbors [G, S, M] i32 local,
    distances [G, S, M] f32, edge_mask [G, S, M] f32, n_edges [G] i32,
    overflow [G] bool)."""
    offsets_f32 = jnp.asarray(
        spec.offsets_grid().astype(np.float32)
    )
    one = functools.partial(_search_one, spec=spec,
                            offsets_f32=offsets_f32)
    return jax.vmap(one)(frac, lats, amask)


def neighbor_search_hbm_bytes(g_cap: int, s_cap: int, k: int,
                              m: int) -> dict:
    """Analytic HBM byte model of one batched search — the GA-ROOFLINE
    budget (analysis/program_audit.py).

    The intended working set is the ``[S, S*K]`` candidate plane per
    structure, touched a bounded number of times: three per-axis
    position diffs, the squared-sum + sqrt, the validity/key masks, and
    the two-operand sort's read/write — ~16 f32 passes is a generous
    constant-factor bound. What the budget EXCLUDES (and therefore
    catches at ~G-fold = ~40x): a per-candidate FEATURE tensor
    ``[S, S*K, G]`` — featurization must happen after truncation to the
    ``[S, M]`` survivors, never on the full candidate set."""
    cand = g_cap * s_cap * s_cap * k
    passes = 16
    io = (g_cap * s_cap * 3 * 4 + g_cap * 9 * 4 + g_cap * s_cap * 5
          + g_cap * s_cap * m * 12 + g_cap * 8)
    return {
        "candidates": int(cand),
        "candidate_passes": passes,
        "io_bytes": int(io),
        "budget_bytes": int(cand * 4 * passes + io),
    }


def make_raw_expander(spec: RawSpec, edge_dtype=jnp.float32) -> Callable:
    """Jit-composable RawBatch -> (GraphBatch, overflow [G] bool,
    n_edges [G] i32) reconstruction — the raw-wire sibling of
    ``data.compact.make_expander``.

    The emitted GraphBatch uses the per-structure BLOCK layout:
    structure g owns node slots ``[g*S, (g+1)*S)``; every dense-layout
    invariant holds (centers = arange // M non-decreasing, masks zero
    on padding, padding edge slots self-loop their owning node).
    Geometry fields come back None like the compact expander — the
    energy-family models never read them.
    """
    table = full_embedding_table()
    mu = np.asarray(spec.gauss_filter, np.float32)
    var2 = np.float32(spec.gauss_var) ** 2
    m = spec.dense_m

    def expand(rb: RawBatch):
        g_cap, s_cap = rb.species.shape
        nbr, dist, emask, n_edges, overflow = neighbor_search(
            rb.frac, rb.lattices, rb.atom_mask, spec
        )
        node_mask = rb.atom_mask.reshape(-1).astype(jnp.float32)
        nodes = jnp.asarray(table)[rb.species.reshape(-1)] \
            * node_mask[:, None]
        # the one radial-basis formula, division form — matches
        # data.featurize.gaussian_expand exactly modulo jnp.exp's
        # <= 1 ulp (the compact-expander contract)
        efea = jnp.exp(-((dist[..., None] - jnp.asarray(mu)) ** 2) / var2)
        efea = (efea * emask[..., None]).astype(edge_dtype)
        edges = efea.reshape(g_cap * s_cap, m, efea.shape[-1])
        base = (jnp.arange(g_cap, dtype=jnp.int32) * s_cap)[:, None, None]
        neighbors = (nbr + base).reshape(-1)
        centers = jnp.arange(g_cap * s_cap * m, dtype=jnp.int32) // m
        node_graph = jnp.arange(g_cap * s_cap, dtype=jnp.int32) // s_cap
        gb = GraphBatch(
            nodes=nodes,
            edges=edges,
            centers=centers,
            neighbors=neighbors,
            node_graph=node_graph,
            node_mask=node_mask,
            edge_mask=emask.reshape(-1),
            graph_mask=rb.graph_mask,
            targets=rb.targets,
            target_mask=rb.target_mask,
            positions=None,
            lattices=None,
            edge_offsets=None,
            node_targets=None,
        )
        overflow = overflow & (rb.graph_mask > 0)
        return gb, overflow, n_edges

    return expand
