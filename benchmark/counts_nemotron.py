"""Operations and bytes a training step of the hybrid Mamba-2 / attention
mixture-of-experts decoder needs, from shapes and from what the data and the
routers did: the yardstick of ``step_roofline.train``,
``ssm_scan_roofline.train``, ``attn_causal_roofline.train`` and
``afmoe_moe_expert_roofline.train`` in ``nemotron.train``. ``counts.py``'s
rules: both counts are lower bounds on purpose, of the cheapest algorithm
known, so that a better kernel cannot read over 100%. The attention over the
visible pairs is ``counts_afmoe.py``'s, which reads the model's own sizes
(16 queries a key-value head here).

FLOPs (matrix multiplications only), a training step = 3 x the forward pass
(the reverse pass is two matmuls for each one; what is rematerialised is not
counted):
  Mamba layer, a position:     2 H (inner + conv + heads) + 2 inner H
                               (W_in; W_out)
  the scan, a position and Mamba layer, in chunks of Q positions (the
  state-space duality form), a position's (Q + 1) / 2 causal pairs inside
  its chunk:
                               2 G N (Q + 1) / 2       C_i . B_j, a group
                             + 2 heads P (Q + 1) / 2   the masked product on x
                             + 2 heads P N             the chunk's end state
                             + 2 heads P N             what the state gives
  attention layer, a position: 2 H (Hq d + 2 Hkv d) + 2 Hq d H
  attention, a layer:    4 d Hq x the VISIBLE (query, key) pairs, exactly
  router, a position and expert layer:      2 H E over all E experts
  shared expert, a position and expert layer: 2 x 2 H I_shared
  experts, an expert layer: 2 x 2 H I x the rows that LANDED on the experts
                         held (no padding, no capacity, at the published
                         width I = 1,856: the 64 lanes the program pads a
                         row to are not counted, so they show in the share)
  head:                  2 H V a WEIGHTED position (one whose next token is
                         of its own document); untied
  embedding, the taps, the gate and its norm: 0 (no matmul)
Bytes (HBM traffic a fused step cannot avoid), activations 2 bytes wide:
  parameters: AdamW reads and writes the parameter and both moments: 24 P
  a layer:    forward reads and writes the residual stream, the reverse pass
              re-reads it, reads its cotangent and writes one: 5 T H a
  the scan:   a position and Mamba layer, forward x, B, C, dt read and y
              written once (2 inner + 2 G N + heads); the reverse pass reads
              those operands and y's cotangent and writes theirs (as much
              again, twice): 3 x, in the compute dtype; the states pass
              between chunks on the chip
  experts:    the held experts' weights are read in the compute dtype by
              the forward and by the reverse pass, and their gradient is
              written once in it; a routed row goes in and comes out
  attention:  ``counts_afmoe.py``'s
"""

from __future__ import annotations

from benchmark.counts_afmoe import (  # noqa: F401
    ACT, attention_counts, visible_pairs,
)

LETTERS = {"M": "mamba", "E": "moe", "*": "attention"}


def inner(model: dict) -> int:
    return model["mamba_num_heads"] * model["mamba_head_dim"]


def conv_dim(model: dict) -> int:
    return inner(model) + 2 * model["n_groups"] * model["ssm_state_size"]


def mamba_params(model: dict) -> int:
    """W_in; the filter and its bias; dt_bias, A_log, D; the gated norm;
    W_out; the layer's norm."""
    h, heads = model["hidden_size"], model["mamba_num_heads"]
    return (h * (inner(model) + conv_dim(model) + heads)
            + conv_dim(model) * (model["conv_kernel"] + 1) + 3 * heads
            + inner(model) + inner(model) * h + h)


def attention_params(model: dict) -> int:
    """q and W_o; k, v; the layer's norm."""
    h, d = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    return 2 * h * hq * d + 2 * h * hkv * d + h


def expert_params(model: dict) -> int:
    return 2 * model["hidden_size"] * model["moe_intermediate_size"]


def moe_params(model: dict) -> int:
    """The held experts, the router's published width, the shared expert,
    the layer's norm."""
    h = model["hidden_size"]
    return (model["n_routed_experts"] * expert_params(model)
            + h * model["num_experts_published"]
            + 2 * h * model["moe_shared_expert_intermediate_size"] + h)


_PARAMS = {"M": mamba_params, "E": moe_params, "*": attention_params}


def n_params(model: dict) -> int:
    """As this chip holds the stage: the mixers and the shared expert
    whole, ``n_routed_experts`` experts a layer, embedding and head a slice
    each, the final norm."""
    h = model["hidden_size"]
    return (2 * model["vocab_size"] * h + h + sum(
        _PARAMS[c](model) for c in model["hybrid_override_pattern"]))


def ssm_scan_counts(model: dict, positions: float) -> dict:
    """One Mamba layer's scan, a training step."""
    heads, p = model["mamba_num_heads"], model["mamba_head_dim"]
    g, n, q = model["n_groups"], model["ssm_state_size"], model["chunk_size"]
    pairs = (q + 1) / 2.0
    flops = (2.0 * g * n * pairs + 2.0 * heads * p * pairs
             + 2 * 2.0 * heads * p * n)
    moved = 2 * inner(model) + 2 * g * n + heads
    return {"flops": 3 * flops * positions,
            "bytes": 3.0 * moved * ACT * positions}


def expert_counts(model: dict, rows: float) -> dict:
    """One layer's held experts, a training step over ``rows`` routed rows."""
    h = model["hidden_size"]
    weights = model["n_routed_experts"] * expert_params(model)
    return {"flops": 3 * 2.0 * expert_params(model) * rows,
            "bytes": 3 * weights * ACT + 3 * 2 * rows * h * ACT}


def step_counts(model: dict, *, positions: float, weighted: float,
                pairs: float, rows: float) -> dict:
    """{"flops", "bytes"} of one training step: ``positions`` positions,
    ``weighted`` of them in the loss, ``pairs`` the visible (query, key)
    pairs an attention layer, ``rows`` routed rows on the held experts an
    expert layer (the mean over them)."""
    h, d = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    pattern = model["hybrid_override_pattern"]
    n_mamba, n_moe, n_attn = (pattern.count(c) for c in "ME*")
    mamba_proj = (2.0 * h * (inner(model) + conv_dim(model)
                             + model["mamba_num_heads"])
                  + 2.0 * inner(model) * h)
    attn_proj = 2.0 * h * (hq * d + 2 * hkv * d) + 2.0 * hq * d * h
    router = 2.0 * h * model["num_experts_published"]
    shared = 2 * 2.0 * h * model["moe_shared_expert_intermediate_size"]
    scan = ssm_scan_counts(model, positions)
    attn = attention_counts(model, positions, pairs)
    experts = expert_counts(model, rows)
    head = 2.0 * h * model["vocab_size"] * weighted
    flops = (3 * positions * (n_mamba * mamba_proj + n_attn * attn_proj
                              + n_moe * (router + shared))
             + n_mamba * scan["flops"] + n_attn * attn["flops"]
             + n_moe * experts["flops"] + 3 * head)
    bytes_ = (24.0 * n_params(model) + len(pattern) * 5 * positions * h * ACT
              + n_mamba * scan["bytes"] + n_attn * attn["bytes"]
              + n_moe * experts["bytes"])
    return {"flops": flops, "bytes": bytes_}
