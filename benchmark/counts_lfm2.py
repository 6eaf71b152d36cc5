"""Operations and bytes a training step of the hybrid short-convolution /
attention mixture-of-experts decoder needs, from shapes and from what the
data and the routers did: the yardstick of ``step_roofline.train``,
``sconv_mix_roofline.train``, ``attn_causal_roofline.train`` and
``afmoe_moe_expert_roofline.train`` in ``lfm2.train``. ``counts.py``'s
rules: both counts are lower bounds on purpose, of the cheapest algorithm
known, so that a better kernel cannot read over 100%. The attention over the
visible pairs (at this model's 64 lanes a head: its own ``head_dim``, so a
kernel that pads the lanes to 128 shows in the share) and the experts over
the rows that landed are ``counts_afmoe.py``'s, which read the model's own
sizes.

FLOPs (matrix multiplications only), a training step = 3 x the forward pass
(the reverse pass is two matmuls for each one; what is rematerialised is not
counted):
  conv layer, a position:      2 H 3H + 2 H H            (W_in; W_out)
  attention layer, a position: 2 H (Hq d + 2 Hkv d) + 2 Hq d H
                               (q, k, v; W_o)
  attention, a layer:    4 d Hq x the VISIBLE (query, key) pairs, exactly
  dense MLP, a position and dense layer:    2 x 3 H I_dense
  router, a position and expert layer:      2 H E over all E experts
  experts, an expert layer: 2 x 3 H I x the rows that LANDED on the experts
                         held (no padding, no capacity)
  head:                  2 H V a WEIGHTED position (one whose next token is
                         of its own document); the embedding is the head
  embedding, the taps:   0 (a row of a table; multiply-adds, no matmul)
Bytes (HBM traffic a fused step cannot avoid), activations 2 bytes wide:
  parameters: AdamW reads and writes the parameter and both moments: 24 P
  a layer:    forward reads and writes the residual stream, the reverse pass
              re-reads it, reads its cotangent and writes one: 5 T H a
  the taps:   a position and conv layer, forward [B | C | u] read (3H) and
              the gated output written (H); the reverse pass reads [B | C |
              u] and the output's cotangent (4H) and writes [B | C | u]'s
              (3H): 11 H a, in the compute dtype
  experts, attention: ``counts_afmoe.py``'s
"""

from __future__ import annotations

from benchmark.counts_afmoe import (  # noqa: F401
    ACT, attention_counts, expert_counts, expert_params, visible_pairs,
)

CONV = "conv"


def conv_params(model: dict) -> int:
    """W_in, the taps, W_out."""
    h = model["hidden_size"]
    return 3 * h * h + model["conv_L_cache"] * h + h * h


def attention_params(model: dict) -> int:
    """q and W_o; k, v; the two head norms."""
    h, d = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    return 2 * h * hq * d + 2 * h * hkv * d + 2 * d


def mixer_params(model: dict, kind: str) -> int:
    return conv_params(model) if kind == CONV else attention_params(model)


def n_params(model: dict) -> int:
    """As this chip holds the stage: the mixers whole, two norms a layer,
    the router's published width, ``num_experts`` experts a layer, the
    embedding (which is the head) once and the final norm."""
    h, nd = model["hidden_size"], model["num_dense_layers"]
    total = model["vocab_size"] * h + h
    for i, kind in enumerate(model["layer_types"]):
        total += mixer_params(model, kind) + 2 * h
        if i < nd:
            total += 3 * h * model["intermediate_size"]
        else:
            total += (h * model["num_experts_published"]
                      + model["num_experts"] * expert_params(model))
    return total


def sconv_mix_counts(model: dict, positions: float) -> dict:
    """One conv layer's gates and taps, a training step: bandwidth alone."""
    return {"flops": 0.0,
            "bytes": 11.0 * model["hidden_size"] * ACT * positions}


def step_counts(model: dict, *, positions: float, weighted: float,
                pairs: float, rows: float) -> dict:
    """{"flops", "bytes"} of one training step: ``positions`` positions,
    ``weighted`` of them in the loss, ``pairs`` the visible (query, key)
    pairs an attention layer, ``rows`` routed rows on the held experts an
    expert layer (the mean over them)."""
    h, d = model["hidden_size"], model["head_dim"]
    hq, hkv = model["num_attention_heads"], model["num_key_value_heads"]
    types, n_dense = model["layer_types"], model["num_dense_layers"]
    n_conv = types.count(CONV)
    n_attn = len(types) - n_conv
    n_expert = len(types) - n_dense
    conv_proj = 2.0 * h * 3 * h + 2.0 * h * h
    attn_proj = 2.0 * h * (hq * d + 2 * hkv * d) + 2.0 * hq * d * h
    dense_mlp = 2.0 * 3 * h * model["intermediate_size"]
    router = 2.0 * h * model["num_experts_published"]
    attn = attention_counts(model, positions, pairs)
    experts = expert_counts(model, rows)
    taps = sconv_mix_counts(model, positions)
    head = 2.0 * h * model["vocab_size"] * weighted
    flops = (3 * positions * (n_conv * conv_proj + n_attn * attn_proj
                              + n_dense * dense_mlp + n_expert * router)
             + n_attn * attn["flops"] + n_expert * experts["flops"]
             + 3 * head)
    bytes_ = (24.0 * n_params(model) + len(types) * 5 * positions * h * ACT
              + n_attn * attn["bytes"] + n_expert * experts["bytes"]
              + n_conv * taps["bytes"])
    return {"flops": flops, "bytes": bytes_}
