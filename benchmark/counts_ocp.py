"""Operations and bytes the Open Catalyst CGCNN's training step needs, from
shapes: the yardstick of ``step_roofline.train`` in ``ocp.train``. Real atoms
N, real edges E, real systems G; padding and everything a fused
implementation could keep on chip are excluded, as in ``counts.py``, whose
rules these are (both counts are lower bounds on purpose, of the cheapest
algorithm known: the neighbour term ``v_j @ K_j`` once an ATOM, projected
and then gathered, as the program computes it since PR 30).

What differs from ``counts.py``'s model:
- LayerNorm after the neighbour sum, in BatchNorm's place: no matmul, and
  its [N, F] passes stay on chip in a fused conv (its 2F parameters a conv
  are counted); no FLOPs and no unavoidable bytes.
- the head has ``n_h - 1`` hidden layers of 2*G*H*H between ``conv_to_fc``
  and ``fc_out``.
- Adam: parameters and both moments are read and written: 24*P.

FLOPs (matrix multiplications only), a training step:
  per conv:  3 * (v_i term 2*N*F*2F + v_j term 2*N*F*2F) + 2 * edge term
             2*E*K*2F   (weight gradients for all three, input gradients
             for the two node terms: edge features are data)
  head:      3 * (2*G*F*H + (n_h - 1) * 2*G*H*H + 2*G*H*T)
  embedding: 0 (one of ~100 rows, a table)
Bytes, activations ``a`` bytes wide: per conv 5*N*F*a + 16*E (forward: read
v, index and distance, write v'; backward: re-read them, read dv', write
dv); embedding 8*N + 2*N*F*a; pooling and head 2*N*F*a; parameters 24*P.
"""

from __future__ import annotations


def n_params(model: dict, atom_dim: int, gauss_dim: int) -> int:
    f, h = model["atom_fea_len"], model["h_fea_len"]
    t = model.get("num_targets", 1)
    # fc_full, bn1's scale and bias, LayerNorm's scale and bias
    conv = (2 * f + gauss_dim) * 2 * f + 2 * f + 2 * (2 * f) + 2 * f
    return ((atom_dim + 1) * f + model["n_conv"] * conv + (f + 1) * h
            + (model["n_h"] - 1) * (h + 1) * h + (h + 1) * t)


def step_counts(n: float, e: float, g: float, model: dict, gauss_dim: int,
                atom_dim: int, *, act_bytes: int = 2) -> dict:
    """{"flops", "bytes"} for one training step (forward, backward, Adam)
    over ``n`` real atoms, ``e`` real edges and ``g`` real systems."""
    f, h, k = model["atom_fea_len"], model["h_fea_len"], gauss_dim
    t = model.get("num_targets", 1)
    c = model["n_conv"]
    node_term = 2.0 * n * f * 2 * f
    nbr_term = 2.0 * n * f * 2 * f  # project, then gather: once an atom
    edge_term = 2.0 * e * k * 2 * f
    head = (2.0 * g * f * h + (model["n_h"] - 1) * 2.0 * g * h * h
            + 2.0 * g * h * t)
    nf = n * f * act_bytes
    return {
        "flops": c * (3 * (node_term + nbr_term) + 2 * edge_term) + 3 * head,
        "bytes": (c * (5 * nf + 16 * e) + (8 * n + 2 * nf) + 2 * nf
                  + 24 * n_params(model, atom_dim, gauss_dim)),
    }
