"""A hybrid Mamba-2 / attention mixture-of-experts decoder (NVIDIA's
``nemotron_h`` block: Nemotron-3-Nano-30B-A3B), as one chip of an
expert-parallel layer holds it.

Every layer is ONE block under ONE norm, a mixer or a feed-forward part and
never both, by its letter in ``hybrid_override_pattern``; on the residual
stream ``x [S, L, H]`` (``x0 = embed[tokens]``):

    x += f(RMSNorm(x))
    M (``mamba``):  z, xBC, dt = h W_in       (no bias, split in this order)
                    xBC = silu(conv4(xBC) + b_conv)
                    (ops/short_conv.py: depthwise, causal, 4 taps; a tap
                    before the sequence or in another document adds 0)
                    x, B, C = xBC;  y = scan(x, dt, B, C) + D x
                    (ops/ssd.py: d_t = softplus(dt_t + dt_bias), a_t =
                    exp(-exp(A_log) d_t), S_t = a_t S_{t-1} + d_t x_t (x) B_t,
                    y_t = S_t C_t; head h reads group h // (heads / groups);
                    S is empty before a document's first position)
                    g = y * silu(z);  g = RMSNorm over each group's channels
                    (gate first, then the norm);  f = g W_out
    * (``attention``): q, k, v = h W_q, h W_k, h W_v; no norm on q or k and
                    no rotary embedding (the layer takes no positions: the
                    Mamba layers carry order); key j is visible to query i
                    iff doc(j) = doc(i) and j <= i
                    f = masked_attention(q / sqrt(d), k, v) W_o
                    (grouped queries)
    E (``moe``):    f = e_shared(h) + this share of sum_k p_k e_k(h),
                    e(h) = relu(h W_up)^2 W_down   (two matrices, no gate)
                    (ops/moe.py: sigmoid scores, the ``k`` largest of score +
                    bias, p the scores' own over their sum, scaled)
    logits = RMSNorm(x) W_head                     (the head is untied)

The stack is ``lm_blocks.Stack``'s, which models/afmoe.py and models/lfm2.py
share, with this model's kinds (``layer_kinds``: of the three one routes):
the period ``E M E M E M *`` is the repeated group ``(E, M) x 3`` and ``*``,
so the program holds one body a kind (``periods/run0/moe``,
``periods/run0/mamba``, ``periods/run1``). Within a layer the step's
sequences go one at a time, a ``jax.checkpoint`` a layer and sequence
(``lm_blocks.by_sequence``) that keeps the layer's input, an attention's
output and the routed experts' output. Weights are float32 and are cast to
the compute dtype inside the layer; norms, ``dt``, ``A``, the decays, the
state, the gated norm, the router, softmax and the loss are float32; ``x``,
``B``, ``C`` and the projections are in the compute dtype.

The selection biases (``e_score_correction_bias``) are no parameters and
nothing moves them (the published module holds them as a buffer, and the
config names no rate of update): they ride in the state's ``batch_stats``
(``router_bias [periods, expert layers a period, E]``) and a step returns
them as they were. The vocabulary is a slice (``vocab_size`` rows are held).
"""

from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from cgnn_tpu.models import lm_blocks
from cgnn_tpu.models.lm_blocks import (
    by_sequence, chunked_loss_sums, rms_norm,
)
from cgnn_tpu.observe import phases
from cgnn_tpu.ops import moe
from cgnn_tpu.ops.masked_attention import (
    StaticMask, live_tiles, mask_tiles, masked_attention,
)
from cgnn_tpu.ops.short_conv import silu_conv
from cgnn_tpu.ops.ssd import ssd_scan

MAMBA, MOE, ATTENTION = "mamba", "moe", lm_blocks.ATTENTION
# a letter of ``hybrid_override_pattern`` -> the layer's kind
LETTERS = {"M": MAMBA, "E": MOE, "*": ATTENTION}
# what an expert layer's checkpoint keeps beside its input
# (``lm_blocks.by_sequence``)
ROUTED = "moe.routed"
# leaves initialised at the output projections' scale (``init_params``)
OUTPUT_PROJECTIONS = ("w_out", "wo", "w_down", "shared_down")


@dataclasses.dataclass(frozen=True)
class NemotronHConfig(lm_blocks.Stack):
    # what train/lm_step.py makes of a batch (no field: the model's own)
    objective = "causal"
    # no leading dense layers; of the three kinds one routes
    num_dense_layers = 0
    layer_kinds = {MAMBA: (lm_blocks.SSM, False), MOE: (None, True),
                   ATTENTION: (lm_blocks.ATTENTION, False)}
    # the attention layers call no ``lm_blocks.prepare_heads``
    heads_prepared_a_layer = 0

    hidden_size: int = 2688
    num_hidden_layers: int = 7
    hybrid_override_pattern: str = "EMEMEM*"
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    # the router's outputs (all experts of the layer) and the experts a token
    # takes; ``experts_held`` = (first, count) is this chip's share
    n_experts: int = 128
    num_experts_per_tok: int = 6
    experts_held: tuple = (0, 8)
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    vocab_size: int = 16384
    layer_norm_epsilon: float = 1e-5
    dtype: str = "bfloat16"
    attn_impl: str = "auto"
    moe_impl: str = "auto"

    def __post_init__(self):
        unknown = set(self.hybrid_override_pattern) - set(LETTERS)
        if unknown:
            raise ValueError(f"hybrid_override_pattern names no layer by "
                             f"{sorted(unknown)}")
        object.__setattr__(self, "layer_types", tuple(
            LETTERS[c] for c in self.hybrid_override_pattern))
        self.check_stack()
        if self.mamba_num_heads % self.n_groups:
            raise ValueError("the Mamba heads are no whole number a group")

    @property
    def compute_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def mamba_inner(self) -> int:
        """The Mamba layers' inner width (heads x their size)."""
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        """The channels the filter runs over: ``[x | B | C]``."""
        return self.mamba_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def routing(self) -> moe.Router:
        return moe.Router(score_func="sigmoid", norm=self.norm_topk_prob,
                          norm_eps=1e-20, scale=self.routed_scaling_factor)

    def _layer_shapes(self, kind: str) -> dict:
        h, d = self.hidden_size, self.head_dim
        hq, hkv = self.num_attention_heads, self.num_key_value_heads
        inner, heads = self.mamba_inner, self.mamba_num_heads
        if kind == MAMBA:
            return {"norm": (h,),
                    "w_in": (h, inner + self.conv_dim + heads),
                    "conv_w": (self.conv_dim, self.conv_kernel),
                    "conv_b": (self.conv_dim,), "dt_bias": (heads,),
                    "a_log": (heads,), "d_skip": (heads,),
                    "gate_norm": (inner,), "w_out": (inner, h)}
        if kind == ATTENTION:
            return {"norm": (h,), "wq": (h, hq * d), "wk": (h, hkv * d),
                    "wv": (h, hkv * d), "wo": (hq * d, h)}
        e, i = self.experts_held[1], self.moe_intermediate_size
        shared = self.moe_shared_expert_intermediate_size
        return {"norm": (h,), "router": (h, self.n_experts),
                "w_up": (e, h, i), "w_down": (e, i, h),
                "shared_up": (h, shared), "shared_down": (shared, h)}

    def shapes(self) -> dict:
        """The parameter tree's shapes, float32 all."""
        h = self.hidden_size
        return {
            "embed": (self.vocab_size, h),
            **lm_blocks.stack_shapes(self, self._layer_shapes,
                                     self._layer_shapes),
            "final_norm": (h,),
            "head": (h, self.vocab_size),
        }

    def stats_shapes(self) -> dict:
        """``batch_stats``: the expert layers' selection biases, float32."""
        return {"router_bias": (
            self.n_periods, sum(map(self.routes, self.period)),
            self.n_experts)}

    def live_tiles(self, segment_ids) -> dict:
        """{``full``: (the tiles a head visits of each sequence ``[S]``, its
        documents given, the attention layers)} (ops/masked_attention.py)."""
        return {"full": (live_tiles(_mask(segment_ids.shape[-1]), segment_ids),
                         self.n_attention_layers)}


def _mask(n: int) -> StaticMask:
    return StaticMask("causal", n)


def _gated_norm(y, z, scale, groups: int, eps: float):
    """``RMSNorm`` over each of ``groups`` runs of channels of ``y *
    silu(z)`` (the gate first, then the norm), float32 -> float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    by_group = g.reshape(*g.shape[:-1], groups, -1)
    by_group = by_group * jax.lax.rsqrt(
        jnp.mean(by_group * by_group, axis=-1, keepdims=True) + eps)
    return by_group.reshape(g.shape) * scale


def _mamba_layer(cfg: NemotronHConfig, x, p, segment_ids):
    """``x += (the gated, normed scan) W_out`` on ``x [S, N, H]``."""
    dt = cfg.compute_dtype
    s, n, _ = x.shape
    inner, heads = cfg.mamba_inner, cfg.mamba_num_heads
    groups, state = cfg.n_groups, cfg.ssm_state_size
    with jax.named_scope(phases.SSM_PROJ):
        hn = rms_norm(x, p["norm"], cfg.layer_norm_epsilon).astype(dt)
        zxd = hn @ p["w_in"].astype(dt)
        z, xbc, steps = jnp.split(zxd, (inner, inner + cfg.conv_dim), axis=-1)
    with jax.named_scope(phases.SSM_CONV):
        xbc = silu_conv(xbc, p["conv_w"], p["conv_b"], segment_ids)
    with jax.named_scope(phases.SSM_SCAN):
        xs, b, c = jnp.split(xbc, (inner, inner + groups * state), axis=-1)
        y = ssd_scan(xs.reshape(s, n, heads, cfg.mamba_head_dim), steps,
                     b.reshape(s, n, groups, state),
                     c.reshape(s, n, groups, state), p["a_log"],
                     p["dt_bias"], p["d_skip"], segment_ids,
                     chunk=cfg.chunk_size)
    with jax.named_scope(phases.SSM_GATE):
        g = _gated_norm(y.reshape(s, n, inner), z, p["gate_norm"], groups,
                        cfg.layer_norm_epsilon).astype(dt)
    with jax.named_scope(phases.SSM_PROJ):
        return (x + g @ p["w_out"].astype(dt),)


def _attention_layer(cfg: NemotronHConfig, x, p, segment_ids):
    """``x += attention W_o`` on ``x [S, N, H]``: q and k as the projection
    left them, head-major (no norm over a head, nothing rotated)."""
    dt = cfg.compute_dtype
    s, n, _ = x.shape
    hq, hkv, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
                  cfg.head_dim)

    def heads(a, count):
        return jnp.swapaxes(a.reshape(s, n, count, d), 1, 2)

    with jax.named_scope(phases.ATTN_PROJ):
        hn = rms_norm(x, p["norm"], cfg.layer_norm_epsilon).astype(dt)
        # the scores' 1 / sqrt(d) rides in W_q's cast
        q = heads(hn @ (p["wq"] / math.sqrt(d)).astype(dt), hq)
        k = heads(hn @ p["wk"].astype(dt), hkv)
        v = heads(hn @ p["wv"].astype(dt), hkv)
    with jax.named_scope(phases.ATTN_FULL):
        a = masked_attention(q, k, v, segment_ids, _mask(n),
                             impl=cfg.attn_impl)
    with jax.named_scope(phases.ATTN_PROJ):
        a = jnp.swapaxes(a, 1, 2).reshape(s, n, hq * d)
        return (x + a @ p["wo"].astype(dt),)


def _expert_layer(cfg: NemotronHConfig, x, p, bias, segment_ids):
    """``x += shared(h) + the held experts' part`` -> (x, group_sizes, the
    rung that carried the held experts' rows: ops/moe.py)."""
    del segment_ids  # a token's experts see no other token
    dt = cfg.compute_dtype
    s, n, h = x.shape
    # the layer's norm and the residual sum are the rows' way out and back:
    # ``moe.route``
    with jax.named_scope(phases.MOE_ROUTE):
        hn = rms_norm(x, p["norm"], cfg.layer_norm_epsilon).astype(dt)
    with jax.named_scope(phases.MOE_EXPERT):
        w_up, w_down = moe.lane_aligned(p["w_up"].astype(dt),
                                        p["w_down"].astype(dt))
    routed, group_sizes, rung = moe.expert_share(
        hn.reshape(s * n, h), p["router"], w_up, w_down,
        experts_held=cfg.experts_held, k=cfg.num_experts_per_tok,
        impl=cfg.moe_impl, routing=cfg.routing, bias=bias, form="relu2")
    routed = checkpoint_name(routed, ROUTED)
    with jax.named_scope(phases.MOE_SHARED):
        # every chip of the layer computes the shared expert whole
        shared = moe.relu2(hn @ p["shared_up"].astype(dt)) \
            @ p["shared_down"].astype(dt)
    with jax.named_scope(phases.MOE_ROUTE):
        m = routed.reshape(s, n, h).astype(jnp.float32) + shared
        return x + m.astype(dt), group_sizes, rung


def hidden_states(cfg: NemotronHConfig, params, router_bias, tokens,
                  segment_ids):
    """``tokens, segment_ids [S, L]`` -> (the last layer's ``x [S, L, H]``,
    ``group_sizes [expert layers, E]``, ``rungs [expert layers, S]``: the
    rung of each expert layer's and sequence's call)."""
    with jax.named_scope(phases.LM_EMBED):
        x = params["embed"][tokens].astype(cfg.compute_dtype)

    def mixer_layer(kind, x, p):
        layer = _mamba_layer if kind == MAMBA else _attention_layer
        return by_sequence(lambda x_seq, seg: layer(cfg, x_seq, p, seg), x,
                           segment_ids)[0]

    def expert_layer(kind, x, p, bias):
        return by_sequence(
            lambda x_seq, seg: _expert_layer(cfg, x_seq, p, bias, seg), x,
            segment_ids, keep=(ROUTED,))

    return lm_blocks.scan_stack(cfg, x, params, router_bias, mixer_layer,
                                expert_layer)


def apply(cfg: NemotronHConfig, variables: dict, batch, train: bool = True):
    """The ``apply_fn`` of a ``TrainState``: -> (each sequence's loss ``[S]``
    float32, ``group_sizes [expert layers, E]``, ``rungs [expert layers,
    S]``: ``hidden_states``). Position ``i`` predicts token ``i + 1`` where
    ``batch.loss_weight`` says so (data/tokens.py, ``causal``); the logits
    are an intermediate (``lm_blocks.chunked_loss_sums``)."""
    del train  # no dropout, and no state that a step moves
    params = variables["params"]
    x, group_sizes, rungs = hidden_states(
        cfg, params, variables["batch_stats"]["router_bias"], batch.tokens,
        batch.segment_ids)
    with jax.named_scope(phases.LM_HEAD):
        # the last position's target is no token: its weight is 0
        targets = jnp.roll(batch.tokens, -1, axis=1)
        losses = chunked_loss_sums(
            x, targets, batch.loss_weight, params["final_norm"],
            params["head"], eps=cfg.layer_norm_epsilon,
            dtype=cfg.compute_dtype)
    return losses, group_sizes, rungs


# the initialiser's draws for the steps (``time_step_min`` / ``_max`` /
# ``_floor``) and for ``A`` (uniform in ``A_RANGE``)
TIME_STEP = (0.001, 0.1, 1e-4)
A_RANGE = (1.0, 16.0)


def mamba_leaf(name: str, key, shape, conv_kernel: int):
    """A Mamba layer's leaf that is no normal draw, float32, or None for
    one that is: ``dt_bias`` the inverse softplus of a log-uniform step
    between ``TIME_STEP``'s ends (no less than its floor), ``a_log`` the
    logarithm of uniform ``A_RANGE``, ``d_skip`` 1, the filter and its bias
    uniform in ``+-1 / sqrt(conv_kernel)`` as a depthwise ``Conv1d``'s
    default is (at normal(0.02) the scan would see inputs near 0 and add
    nothing beside the skip)."""
    lo, hi, floor = TIME_STEP
    if name == "dt_bias":
        step = jnp.maximum(jnp.exp(jax.random.uniform(
            key, shape, jnp.float32, math.log(lo), math.log(hi))), floor)
        return step + jnp.log(-jnp.expm1(-step))
    if name == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, *A_RANGE))
    if name == "d_skip":
        return jnp.ones(shape, jnp.float32)
    if name in ("conv_w", "conv_b"):
        bound = 1.0 / math.sqrt(conv_kernel)
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    return None


def init_params(cfg: NemotronHConfig, rng,
                n_layers_published: int | None = None, std: float = 0.02):
    """normal(``std``) weights, the output projections (``w_out``, ``wo``
    and every down projection) at ``std / sqrt(published depth)`` (one
    addition to the stream a layer); norms at 1; the Mamba layers' steps,
    decays, skips and filters by ``mamba_leaf``. float32."""
    depth = n_layers_published or cfg.num_hidden_layers
    params = lm_blocks.init_params(
        cfg.shapes(), rng, std=std, out_std=std / math.sqrt(depth),
        output_projections=OUTPUT_PROJECTIONS)
    flat, tree = jax.tree_util.tree_flatten_with_path(params)
    leaves = []
    for i, (path, leaf) in enumerate(flat):
        special = mamba_leaf(
            str(path[-1].key), jax.random.fold_in(rng, 1_000_000 + i),
            leaf.shape, cfg.conv_kernel)
        leaves.append(leaf if special is None else special)
    return jax.tree_util.tree_unflatten(tree, leaves)


def init_stats(cfg: NemotronHConfig) -> dict:
    """The selection biases at 0 (a trained model brings its own; nothing
    here moves them)."""
    return {k: jnp.zeros(s, jnp.float32)
            for k, s in cfg.stats_shapes().items()}


def attention_tiles(cfg: NemotronHConfig, seq_len: int) -> dict:
    """{``full``: (live tiles, grid tiles a head and a sequence, attention
    layers)}, documents aside (ops/masked_attention.py)."""
    return {"full": (*mask_tiles(_mask(seq_len)), cfg.n_attention_layers)}
