"""Operations and bytes a training step of the window-and-full-attention
mixture-of-experts decoder needs, from shapes and from what the data and the
routers did: the yardstick of ``step_roofline.train``,
``attn_causal_roofline.train`` and ``moe_expert_roofline.train`` in
``trinity.train``. ``counts.py``'s and ``counts_sdar.py``'s rules: both counts
are lower bounds on purpose, of the cheapest algorithm known, so that a
better kernel cannot read over 100%.

FLOPs (matrix multiplications only), a training step = 3 x the forward pass
(the reverse pass is two matmuls for each one; what is rematerialised is not
counted):
  projections, a position and layer: 2 H (2 Hq d + 2 Hkv d) + 2 Hq d H
                         (q, the gate, k, v; W_o)
  attention, a layer:    4 d Hq x the VISIBLE (query, key) pairs, exactly,
                         by the layer's kind: causal within the window and
                         the document, or causal within the document (no
                         tile rounding)
  dense MLP, a position and dense layer:    2 x 3 H I_dense
  router, a position and expert layer:      2 H E over all E experts
  shared expert, a position and expert layer: 2 x 3 H I x num_shared_experts
  experts, an expert layer: 2 x 3 H I x the rows that LANDED on the experts
                         held (no padding, no capacity)
  head:                  2 H V a WEIGHTED position (one whose next token is
                         of its own document)
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
SLIDING = "sliding_attention"


def visible_pairs(segment_ids, window: int = 0) -> int:
    """The (query, key) pairs the causal mask shows over the packed
    sequences ``segment_ids [n, L]``, exactly: query ``i`` of a document sees
    its ``min(i + 1, window)`` latest keys (``i`` counted within the
    document; all ``i + 1`` where ``window`` is 0). A document of ``n``
    tokens has ``n (n + 1) / 2`` pairs, or with ``n > w``: ``w (w + 1) / 2 +
    (n - w) w``."""
    import numpy as np

    total = 0
    for row in np.asarray(segment_ids):
        n = np.bincount(row).astype(np.int64)
        n = n[n > 0]
        if window:
            w = np.minimum(n, window)
            total += int((w * (w + 1) // 2 + (n - w) * w).sum())
        else:
            total += int((n * (n + 1) // 2).sum())
    return total


def expert_params(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def attention_params(model: dict) -> int:
    """q, the gate and W_o; k, v; the two head norms; the four norms."""
    h, d = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    return 3 * h * hq * d + 2 * h * hkv * d + 2 * d + 4 * h


def n_params(model: dict) -> int:
    """As this chip holds the stage: attention whole, the router's published
    width, ``num_experts`` experts and the shared ones a layer."""
    h, v = model["hidden_size"], model["vocab_size"]
    dense = attention_params(model) + 3 * h * model["intermediate_size"]
    expert = (attention_params(model) + h * model["num_experts_published"]
              + (model["num_experts"] + model["num_shared_experts"])
              * expert_params(model))
    n_dense = model["num_dense_layers"]
    return (2 * v * h + h + n_dense * dense
            + (model["num_hidden_layers"] - n_dense) * expert)


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


def _total(parts) -> dict:
    return {k: sum(p[k] for p in parts) for k in ("flops", "bytes")}


def causal_attention_counts(model: dict, positions: float,
                            pairs: dict) -> dict:
    """Every layer's masked attention, a training step: ``pairs`` = {layer
    kind: the visible pairs a layer of that kind}."""
    return _total([attention_counts(model, positions, pairs[kind])
                   for kind in model["layer_types"]])


def step_counts(model: dict, *, positions: float, weighted: float,
                pairs: dict, rows: float) -> dict:
    """{"flops", "bytes"} of one training step: ``positions`` positions,
    ``weighted`` of them in the loss, ``pairs`` the visible (query, key)
    pairs a layer by kind, ``rows`` routed rows on the held experts an
    expert layer (the mean over them)."""
    h, d = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    layers, n_dense = model["num_hidden_layers"], model["num_dense_layers"]
    n_expert = layers - n_dense
    proj = 2.0 * h * (2 * hq * d + 2 * hkv * d) + 2.0 * hq * d * h
    dense_mlp = 2.0 * 3 * h * model["intermediate_size"]
    shared = 2.0 * model["num_shared_experts"] * expert_params(model)
    router = 2.0 * h * model["num_experts_published"]
    attn = causal_attention_counts(model, positions, pairs)
    experts = expert_counts(model, rows)
    head = 2.0 * h * model["vocab_size"] * weighted
    flops = (3 * positions * (layers * proj + n_dense * dense_mlp
                              + n_expert * (router + shared))
             + attn["flops"] + n_expert * experts["flops"] + 3 * head)
    bytes_ = (24.0 * n_params(model) + layers * 5 * positions * h * ACT
              + attn["bytes"] + n_expert * experts["bytes"])
    return {"flops": flops, "bytes": bytes_}
