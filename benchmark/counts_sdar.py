"""Operations and bytes a training step of the block-diffusion decoder needs,
from shapes and from what the data and the routers did: the yardstick of
``step_roofline.train``, ``attn_bd_roofline.train`` and
``moe_expert_roofline.train`` in ``sdar.train``. ``counts.py``'s rules: both
counts are lower bounds on purpose, of the cheapest algorithm known, so that
a better kernel cannot read over 100%.

FLOPs (matrix multiplications only), a training step = 3 x the forward pass
(the reverse pass is two matmuls for each one; what is rematerialised is not
counted):
  projections, a position and layer: 2 H (Hq d + 2 Hkv d) + 2 Hq d H
  attention, a layer:    4 d Hq x the VISIBLE (query, key) pairs, exactly:
                         the mask's and the documents' (no tile rounding)
  router, a position and layer: 2 H E over all E experts
  experts, a layer:      2 x 3 H I x the rows that LANDED on the experts
                         held (no padding, no capacity)
  head:                  2 H V a NOISED position (the clean half is context)
  embedding:             0 (a row of a table)
Bytes (HBM traffic a fused step cannot avoid), activations 2 bytes wide:
  parameters: AdamW reads and writes the parameter and both moments: 24 P
  a layer:    forward reads and writes the residual stream, the reverse pass
              re-reads it, reads its cotangent and writes one: 5 T H a
  experts:    the held experts' weights are read in the compute dtype by
              the forward and by the reverse pass, and their gradient is
              written once in it; a routed row goes in and comes out
  attention:  q, k, v in and o out; the reverse pass reads those four and
              do, and writes dq, dk, dv
"""

from __future__ import annotations

ACT = 2  # bytes of an activation (bfloat16)


def visible_pairs(segment_ids, block: int) -> int:
    """The (query, key) pairs the block-diffusion mask shows over the packed
    sequences ``segment_ids [n, L]``, exactly. A document of ``b`` whole
    blocks of ``B`` tokens has, block by block: ``B^2`` noised-to-noised
    pairs; ``j B^2`` noised-to-clean pairs for its ``j``-th block (the
    earlier blocks of its own document); ``(j + 1) B^2`` clean-to-clean
    pairs: ``B^2 b (b + 1)`` in all."""
    import numpy as np

    total = 0
    for row in np.asarray(segment_ids):
        lengths = np.bincount(row)
        if (lengths % block).any():
            raise ValueError("a document boundary inside a block")
        b = lengths[lengths > 0] // block
        total += int((block * block * b * (b + 1)).sum())
    return total


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def layer_params(model: dict) -> int:
    """One layer as this chip holds it: attention whole, the router's
    published width, ``num_experts`` experts."""
    h, d = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    attn = h * hq * d + 2 * h * hkv * d + hq * d * h + 2 * d + h
    return (attn + h + h * model["num_experts_published"]
            + model["num_experts"] * expert_params(model))


def n_params(model: dict) -> int:
    h, v = model["hidden_size"], model["vocab_size"]
    return (2 * v * h + h
            + model["num_hidden_layers"] * layer_params(model))


def attention_counts(model: dict, positions: float, pairs: float) -> dict:
    """One layer's masked attention, a training step: ``pairs`` visible
    (query, key) pairs over ``positions`` positions."""
    d, hq = model["head_dim"], model["num_attention_heads"]
    hkv = model["num_key_value_heads"]
    q_bytes = positions * hq * d * ACT
    kv_bytes = positions * hkv * d * ACT
    return {"flops": 3 * 4.0 * d * hq * pairs,
            # q, o | q, o, do, dq and k, v | k, v, dk, dv
            "bytes": 6 * q_bytes + 2 * 4 * kv_bytes}


def expert_counts(model: dict, rows: float) -> dict:
    """One layer's held experts, a training step over ``rows`` routed rows."""
    h = model["hidden_size"]
    weights = model["num_experts"] * expert_params(model)
    return {"flops": 3 * 2.0 * expert_params(model) * rows,
            "bytes": 3 * weights * ACT + 3 * 2 * rows * h * ACT}


def step_counts(model: dict, *, positions: float, noised: float,
                pairs: float, rows: float) -> dict:
    """{"flops", "bytes"} of one training step: ``positions`` positions
    (both halves), ``noised`` of them in the noised half, ``pairs`` visible
    (query, key) pairs a layer, ``rows`` routed rows on the held experts a
    layer (the mean over the layers)."""
    h, d = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    layers = model["num_hidden_layers"]
    proj = 2.0 * h * (hq * d + 2 * hkv * d) + 2.0 * hq * d * h
    router = 2.0 * h * model["num_experts_published"]
    attn = attention_counts(model, positions, pairs)
    experts = expert_counts(model, rows)
    head = 2.0 * h * model["vocab_size"] * noised
    flops = (layers * (3 * positions * (proj + router) + attn["flops"]
                       + experts["flops"]) + 3 * head)
    bytes_ = (24.0 * n_params(model)
              + layers * (5 * positions * h * ACT + attn["bytes"]
                          + experts["bytes"]))
    return {"flops": flops, "bytes": bytes_}
