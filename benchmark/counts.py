"""Operations and bytes the ALGORITHM needs, from shapes: the yardstick for
roofline shares. Padding slots are excluded (real atoms N, real edges E, real
crystals G), and so is everything a fused implementation could keep on chip.

Both counts are lower bounds on purpose. A share of the roofline is the least
time over the measured time, and a count that included what today's
implementation happens to move (the per-edge ``z``, ``z2``, ``msg`` tensors,
the Gaussian-expanded edge features, layout copies) would let a later fused
conv read above 100% through no fault of its own. For the same reason a bound
follows the CHEAPEST algorithm known, not the one first written down: the
conv's ``fc_full`` over ``[v_i, v_j, e_ij]`` is linear, so its neighbour term
``v_j @ K_j`` can be projected once an ATOM and the projected rows gathered
(what the program does since PR 30), where it was once counted, and computed,
once an EDGE. A count of the dearer algorithm is no bound on the cheaper one.

FLOPs (matrix multiplications only; the MXU's peak is the denominator):
  per conv, forward:  v_i term 2*N*F*2F (contracted per atom, then broadcast
                      over its M slots), v_j term 2*N*F*2F (projected per
                      atom, then gathered by the neighbour index: the gather
                      is no matmul, and its bytes are the [N, F] reads the
                      byte count already has), edge term 2*E*K*2F
  per conv, backward: weight gradients for all three terms, input gradients
                      for the v_i and v_j terms only (edge features are data)
  head:               conv_to_fc 2*G*F*H and fc_out 2*G*H*T, x3 when training
  embedding:          0 - atom features are one of ~100 rows, so a table of
                      (row @ W_emb) gives it without a per-atom matmul

Bytes (HBM traffic a fused step cannot avoid), activations ``a`` bytes wide:
  per conv, forward:  read v [N,F], read neighbour index [E] i32 and distance
                      [E] f32 (the Gaussian expansion is recomputable on
                      chip), write v' [N,F]            -> 2*N*F*a + 8*E
  per conv, backward: re-read v, index and distance (recompute), read dv',
                      write dv                          -> 3*N*F*a + 8*E
  embedding:          read atom index [N] i32, write v; backward reads dv
  pooling + head:     read v [N,F]; backward writes dv [N,F]
  parameters:         forward reads P*4; training reads and writes P and the
                      momentum trace                    -> 16*P
BatchNorm in training mode needs its statistics before it can normalise, so
a real fused conv makes two passes over its inputs; one is counted.
"""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def n_params(model: dict, atom_dim: int, gauss_dim: int) -> int:
    f, h = model["atom_fea_len"], model["h_fea_len"]
    t = model.get("num_targets", 1)
    conv = (2 * f + gauss_dim) * 2 * f + 2 * f + 2 * (2 * f) + 2 * f
    return ((atom_dim + 1) * f + model["n_conv"] * conv + (f + 1) * h
            + (h + 1) * t)


def step_counts(n: float, e: float, g: float, model: dict, gauss_dim: int,
                atom_dim: int, *, train: bool, act_bytes: int = 2) -> dict:
    """{"flops", "bytes"} for one step over ``n`` real atoms, ``e`` real
    edges and ``g`` real crystals: forward only, or forward + backward +
    the SGD update when ``train``."""
    f, h, k = model["atom_fea_len"], model["h_fea_len"], gauss_dim
    t = model.get("num_targets", 1)
    c = model["n_conv"]
    node_term = 2.0 * n * f * 2 * f
    nbr_term = 2.0 * n * f * 2 * f  # project, then gather: once an atom
    edge_term = 2.0 * e * k * 2 * f
    head = 2.0 * g * f * h + 2.0 * g * h * t
    p = n_params(model, atom_dim, gauss_dim)
    nf = n * f * act_bytes
    if train:
        flops = c * (3 * (node_term + nbr_term) + 2 * edge_term) + 3 * head
        bytes_ = c * (5 * nf + 16 * e) + (8 * n + 2 * nf) + 2 * nf + 16 * p
    else:
        flops = c * (node_term + nbr_term + edge_term) + head
        bytes_ = c * (2 * nf + 8 * e) + (4 * n + nf) + nf + 4 * p
    return {"flops": flops, "bytes": bytes_}


def peaks_for(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table or device_kind == "source":
        raise SystemExit(f"benchmark: no peaks known for device kind "
                         f"{device_kind!r} (peaks.json)")
    return table[device_kind]


def least_seconds(counts: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which bound it is."""
    by_flops = counts["flops"] / peaks["bf16_flops"]
    by_bytes = counts["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "bytes")
