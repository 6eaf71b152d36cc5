"""Operations and bytes the second-order (energy-and-force) training step
needs, from shapes: the yardstick of ``step_roofline.train`` in a force cell.
Real atoms N, real edges E; padding and everything a fused implementation
could keep on chip are excluded, as in ``counts.py``, and both counts are
lower bounds on purpose.

The step is a reverse pass over a reverse pass: energies (forward), forces
(the inner reverse pass, input gradients only: weights are not differentiated
there), then the parameter gradient of a loss on both (the outer reverse pass
over the forward AND over the inner pass). A matmul ``y = x W`` of the
forward so runs, counted in units of its own FLOPs:

  forward                 y = x W                                    1
  inner reverse           dx = dy W^T      (only if x depends on
                                            the positions)           1
  outer, over forward     dW = x^T dy', dx' = dy' W^T (dx' only if
                                            x depends on a weight)   1 + 1
  outer, over inner       the transpose of dx = dy W^T in both its
                          inputs: d(dy) = ct W, dW = dy^T ct         2

FLOPs (matrix multiplications only; the MXU's peak is the denominator):
  edge term 2*E*K*2F, every conv: its input e_ij depends on the positions and
      on no weight                                  1 + 1 + 1 + 2 = 5 units
  v_i term 2*N*F*2F and v_j term 2*N*F*2F (projected once an atom, the
      projected rows then gathered: the cheapest algorithm known, the
      program's since PR 30, and the one ``counts.py`` bounds; the gather and
      its transposes are no matmuls), convs after the first: v depends on
      both                                          1 + 1 + 2 + 2 = 6 units
  the same terms in the first conv: v there is the embedding, which no
      position moves, so no inner pass and nothing over it
                                                    1 + 0 + 2 + 0 = 3 units
  readout fc 2*N*F*H and out 2*N*H*1                               6 units
  embedding: 0 (one of ~100 rows, a table); distances and Gaussians: 0 (no
      matmul; the VPU's work has no peak in ``peaks.json``)
This is the cheapest order known here: forward-over-reverse (the directional
derivative of the weight gradient along dL/dF) needs 7 units a matmul.

Bytes (HBM traffic a fused step cannot avoid), activations ``a`` bytes wide;
the four passes over a conv each read the neighbour index [E] i32 and the
image offset [E] 3 x i8 (distances and Gaussians are recomputed on chip from
the positions) and move node-sized tensors only:
  forward        read v, write v'                              2*N*F*a
  inner reverse  re-read v (recompute), read dv', write dv     3*N*F*a
  outer over inner    read v, dv' and the cotangent of dv, write the
                      cotangent of dv'                          4*N*F*a
  outer over forward  read v, dv' (second-order term) and the cotangent of
                      v', write the cotangent of v              4*N*F*a
                                           -> 13*N*F*a + 4*7*E a conv
  positions, force labels, forces and their cotangent, [N, 3] f32: 4 * 12*N
  embedding: read atom index [N] i32, write v; backward reads dv
  readout: read v, write dv in the inner pass; the same again in the outer
  parameters: read and write P and Adam's two moments: 24*P
"""

from __future__ import annotations


def n_params(model: dict, atom_dim: int, gauss_dim: int) -> int:
    f, h = model["atom_fea_len"], model["h_fea_len"]
    conv = (2 * f + gauss_dim) * 2 * f + 2 * f
    return (atom_dim + 1) * f + model["n_conv"] * conv + (f + 1) * h + h + 1


def step_counts(n: float, e: float, model: dict, gauss_dim: int,
                atom_dim: int, *, act_bytes: int = 2,
                nbr_per_edge: bool = True) -> dict:
    """{"flops", "bytes"} for one training step over ``n`` real atoms and
    ``e`` real edges.

    ``nbr_per_edge=False`` is the bound (the neighbour term once an atom:
    project, then gather) and what ``kinds/force_train.py`` asks for. The
    default counts it once an edge, the algorithm of before PR 30, for one
    reason: ``tests/test_force_ref.py``, outside the benchmark's paths, pins
    that number, and the ``benchmark`` PR that corrected the count (PR 34)
    may edit no file out there. Once that test is gone the keyword goes
    (PERF.md section 7)."""
    f, h, k = model["atom_fea_len"], model["h_fea_len"], gauss_dim
    c = model["n_conv"]
    node_term = 2.0 * n * f * 2 * f
    nbr_term = 2.0 * (e if nbr_per_edge else n) * f * 2 * f
    edge_term = 2.0 * e * k * 2 * f
    head = 2.0 * n * f * h + 2.0 * n * h
    flops = (c * 5 * edge_term + (3 + 6 * (c - 1)) * (node_term + nbr_term)
             + 6 * head)
    nf = n * f * act_bytes
    p = n_params(model, atom_dim, gauss_dim)
    bytes_ = (c * (13 * nf + 4 * 7 * e) + 4 * 12 * n + (4 * n + 2 * nf)
              + 4 * nf + 24 * p)
    return {"flops": flops, "bytes": bytes_}
