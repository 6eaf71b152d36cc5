"""What the decoders of this repo share (models/sdar.py, models/afmoe.py,
models/lfm2.py, models/nemotron_h.py): the float32 norm and RoPE, the one
op that makes a projection's output the attention's operand, the
initialiser, the plan that keeps a layer's input and its attention's output
for the reverse pass, the stack whose layers differ in kind (``Stack``,
``stack_shapes``, ``scan_stack``), and the head over the vocabulary slice a
chunk at a time.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from cgnn_tpu.observe import phases
from cgnn_tpu.ops import prepare_heads as fused
from cgnn_tpu.ops.masked_attention import KEPT


def rms_norm(x, scale, eps: float):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                            + eps)
    return y * scale


def rope(x, positions, theta: float):
    """``x [S, N, heads, D]`` float32, rotate-half as Qwen's."""
    d = x.shape[-1]
    cos, sin = fused.rope_tables(positions, d, theta)  # [N, D/2]
    cos = jnp.concatenate([cos, cos], -1)[None, :, None]
    sin = jnp.concatenate([sin, sin], -1)[None, :, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def heads_fused(n: int, head_dim: int) -> bool:
    """Whether ``prepare_heads`` takes the kernel on ``n`` positions of
    ``head_dim``: on the TPU, at the shapes the kernel takes."""
    return jax.default_backend() == "tpu" and fused.supported(n, head_dim)


def prepare_heads(x, norm_scale, positions, *, theta: float, eps: float,
                  scale: float = 1.0):
    """A projection's output -> the attention's operand: ``x [S, N, heads *
    D]`` in the compute dtype, ``norm_scale [D]``, ``positions [N]`` int32
    or None (a layer that takes no positions) -> ``rope(rms_norm(x)) scale``
    as ``[S, heads, N, D]`` in the compute dtype. float32 arithmetic; the
    array is rounded where the matmul left it and where the attention takes
    it. One pass on the chip (``ops/prepare_heads.py``) where
    ``heads_fused`` says so, the backend and the shape deciding as in
    ``masked_attention``'s ``auto``; else the composition below, which is
    the kernel's reference."""
    s, n, width = x.shape
    d = norm_scale.shape[-1]
    if heads_fused(n, d):
        return fused.prepare_heads(x, norm_scale, positions, theta, eps,
                                   scale)
    y = rms_norm(x.reshape(s, n, width // d, d), norm_scale, eps)
    if positions is not None:
        y = rope(y, positions, theta)
    return jnp.swapaxes((y * scale).astype(x.dtype), 1, 2)


def init_params(shapes: dict, rng, *, std: float, out_std: float,
                output_projections: tuple):
    """float32 leaves for a tree of shapes: normal(``std``), the leaves named
    in ``output_projections`` at ``out_std``; every ``*norm`` at 1."""
    flat, tree = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    leaves = []
    for i, (path, shape) in enumerate(flat):
        name = str(getattr(path[-1], "key", path[-1]))
        if name.endswith("norm"):
            leaves.append(jnp.ones(shape, jnp.float32))
            continue
        scale = out_std if name in output_projections else std
        leaves.append(scale * jax.random.normal(
            jax.random.fold_in(rng, i), shape, jnp.float32))
    return jax.tree_util.tree_unflatten(tree, leaves)


def by_sequence(layer, x, segment_ids, keep=()):
    """One layer over the step's sequences one at a time, a
    ``jax.checkpoint`` a layer and sequence: the reverse pass keeps a
    layer's input and what the attention's reverse kernels read of its
    forward one (``ops/masked_attention.py`` ``KEPT``: the output and the
    log-sum-exp, so the forward kernel runs once), and what it rebuilds (a
    sequence's projections, the rows routed to the experts held) is one
    sequence's at a time. ``layer(x [1, N, H], segment_ids [1, ..]) -> (x
    [1, N, H], *aux)``; -> ``(x [S, N, H], *aux stacked over the
    sequences)``. ``keep``: the names (``checkpoint_name``) of what else of
    its own a model has the checkpoint keep.

    A scan over the sequences, and it has to stay one: under ``vmap`` the
    expert layer's ``lax.switch`` becomes a select that runs every rung on
    every sequence."""
    policy = jax.checkpoint_policies.save_only_these_names(KEPT, *keep)

    @functools.partial(jax.checkpoint, prevent_cse=False, policy=policy)
    def one(row):
        x_seq, seg = row
        out, *aux = layer(x_seq[None], seg[None])
        return (out[0], *aux)

    return jax.lax.map(one, (x, segment_ids))


# what a layer mixes by (``Stack.mixer``); None: it is a feed-forward part
# alone
ATTENTION, CONV, SSM = "attention", "conv", "ssm"


class Stack:
    """A stack of leading dense layers and then layers in periods of
    ``layer_types``, for a frozen config dataclass with the fields
    ``layer_types``, ``num_hidden_layers``, ``num_dense_layers``,
    ``shapes()`` and ``layer_kinds``: {kind: (what a layer of the kind
    mixes by, whether it holds routed experts past the leading dense
    ones)}. Serves models/afmoe.py and models/lfm2.py, whose every layer is
    a mixer (an attention under some mask, or the short convolution) AND a
    feed-forward part that routes after the leading dense ones, and
    models/nemotron_h.py, whose every layer is ONE of the two (a mixer of
    None: a feed-forward part alone).

    The dense layers are one stack that is scanned; the periods are scanned,
    and inside a period each repeated group of kinds (``groups``) is a scan
    of its own over the repeats, its body one layer of each kind of the
    group (``periods/run<j>``: a group of one kind holds its leaves ``[periods,
    layers of the run, ...]``; a group of several a dict of them a kind,
    ``periods/run<j>/<kind>``): a layer of each kind is all the program text
    there is (a period ``E M E M E M *`` is ``(E, M) x 3`` and ``*``, three
    bodies), and a deeper stage is a longer leading axis."""

    # (q and k) the calls of ``prepare_heads`` an attention layer makes
    heads_prepared_a_layer = 2

    def mixer(self, kind: str):
        """What a layer of ``kind`` mixes by: ``ATTENTION``, ``CONV``,
        ``SSM`` or None."""
        return self.layer_kinds[kind][0]

    def routes(self, kind: str) -> bool:
        """Whether a layer of ``kind`` past the leading dense ones holds
        routed experts."""
        return self.layer_kinds[kind][1]

    def check_stack(self) -> None:
        """In ``__post_init__``: ``layer_types`` a tuple of the kinds that
        ``layer_kinds`` names."""
        types = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", types)
        if (len(types) != self.num_hidden_layers
                or set(types) - set(self.layer_kinds)):
            raise ValueError(f"layer_types {types} do not name "
                             f"{self.num_hidden_layers} layers")
        if len(set(types[:self.num_dense_layers])) > 1:
            raise ValueError("the leading dense layers are one scanned "
                             "stack: they have to be of one kind")
        if not (0 <= self.num_dense_layers < self.num_hidden_layers
                and self.n_expert_layers):
            raise ValueError("at least one expert layer")

    @property
    def period(self) -> tuple:
        """The kinds of the layers past the dense ones, one period of
        them."""
        types = self.layer_types[self.num_dense_layers:]
        for n in range(1, len(types) + 1):
            if len(types) % n == 0 and types == types[:n] * (len(types) // n):
                return types[:n]
        raise AssertionError

    @property
    def groups(self) -> tuple:
        """A period as repeated groups of kinds, ((kinds, repeats), ..):
        from each place on, the group (of distinct kinds) whose repeats
        cover the most layers, the shorter group of two that cover as many;
        a group that does not repeat is one kind."""
        period, out, at = self.period, [], 0
        while at < len(period):
            best = (period[at:at + 1], 1)
            for size in range(1, (len(period) - at) // 2 + 1):
                group = period[at:at + size]
                if len(set(group)) < size:
                    continue
                repeats = 1
                while period[at + repeats * size:
                             at + (repeats + 1) * size] == group:
                    repeats += 1
                if repeats > 1 and size * repeats > len(best[0]) * best[1]:
                    best = (group, repeats)
            out.append(best)
            at += len(best[0]) * best[1]
        return tuple(out)

    @property
    def runs(self) -> tuple:
        """``groups``, a group of one kind under the kind's own name: a
        period of runs of layers of one kind is ((kind, layers), ..)."""
        return tuple((group[0] if len(group) == 1 else group, n)
                     for group, n in self.groups)

    @property
    def n_periods(self) -> int:
        return ((self.num_hidden_layers - self.num_dense_layers)
                // len(self.period))

    def _count(self, mixer) -> int:
        return sum(self.mixer(kind) == mixer for kind in self.layer_types)

    @property
    def n_expert_layers(self) -> int:
        """Layers that hold routed experts."""
        return sum(self.routes(kind)
                   for kind in self.layer_types[self.num_dense_layers:])

    @property
    def n_conv_layers(self) -> int:
        """Layers whose mixer is the short convolution."""
        return self._count(CONV)

    @property
    def n_attention_layers(self) -> int:
        """Layers whose mixer is an attention of any mask."""
        return self._count(ATTENTION)

    @property
    def n_ssm_layers(self) -> int:
        """Layers whose mixer is the state-space scan (ops/ssd.py)."""
        return self._count(SSM)

    def n_params(self) -> int:
        return sum(math.prod(s) for s in jax.tree_util.tree_leaves(
            self.shapes(), is_leaf=lambda x: isinstance(x, tuple)))


def stack_shapes(cfg: Stack, dense_layer, expert_layer) -> dict:
    """The stack's part of a parameter tree's shapes: ``dense_layer(kind)``
    (a leading dense layer, or a layer of a kind that does not route) and
    ``expert_layer(kind)`` give one layer's ({leaf: shape}) ->
    {``periods``: {``run<j>``: ..}, ``dense``: .. where there is one}."""
    def stacked(layer: dict, *leading) -> dict:
        return {k: (*leading, *v) for k, v in layer.items()}

    def of(kind: str, n: int) -> dict:
        layer = expert_layer if cfg.routes(kind) else dense_layer
        return stacked(layer(kind), cfg.n_periods, n)

    tree = {"periods": {
        f"run{j}": (of(group[0], n) if len(group) == 1
                    else {kind: of(kind, n) for kind in group})
        for j, (group, n) in enumerate(cfg.groups)}}
    if cfg.num_dense_layers:
        tree["dense"] = stacked(dense_layer(cfg.layer_types[0]),
                                cfg.num_dense_layers)
    return tree


def scan_stack(cfg: Stack, x, params, router_bias, dense_layer,
               expert_layer):
    """``x [S, L, H]`` through the stack (``Stack``): ``dense_layer(kind,
    x, p) -> x`` (a leading dense layer, or a layer of a kind that does not
    route) and ``expert_layer(kind, x, p, bias [E]) -> (x, group_sizes [S,
    E], rungs [S])`` over all the step's sequences (each calls
    ``by_sequence`` with what its checkpoint keeps); ``params`` the tree of
    ``stack_shapes``, ``router_bias [periods, routing layers a period, E]``.
    -> (``x``, ``group_sizes [expert layers, E]``, ``rungs [expert layers,
    S]``: the rung of each expert layer's and sequence's call,
    ops/moe.py)."""
    def dense_step(x, p):
        # the casts to the compute dtype stay inside the layer: hoisted out
        # of the scan they are a second copy of every layer's weights
        p = jax.lax.optimization_barrier(p)
        return dense_layer(cfg.layer_types[0], x, p), None

    def group_step(group, x, layers):
        """One layer of each kind of ``group`` (a routing kind's with its
        bias); what the routing kinds counted, a kind each."""
        routed = []
        for kind, layer in zip(group, layers):
            if not cfg.routes(kind):
                x = dense_layer(kind, x, jax.lax.optimization_barrier(layer))
                continue
            p, bias = jax.lax.optimization_barrier(layer)
            x, sizes, rungs = expert_layer(kind, x, p, bias)
            routed.append((sizes.sum(axis=0), rungs))
        return x, tuple(routed)

    def in_stack_order(parts):
        """``[repeats, ..]`` a routing kind of a group -> ``[repeats x
        kinds, ..]``, the layers in the stack's order."""
        if len(parts) == 1:  # as it is: no op enters Trinity's or LFM2's text
            return parts[0]
        return jnp.stack(parts, axis=1).reshape(
            len(parts) * parts[0].shape[0], -1)

    def period_step(x, period):
        p, bias = period
        routed, at = [], 0
        for j, (group, n) in enumerate(cfg.groups):
            # a group of one kind holds its leaves without the kind's name
            leaves = p[f"run{j}"]
            if len(group) == 1:
                leaves = {group[0]: leaves}
            routing = [kind for kind in group if cfg.routes(kind)]
            r = len(routing)
            layers = tuple(
                (leaves[kind], bias[at + routing.index(kind):at + n * r:r])
                if kind in routing else leaves[kind] for kind in group)
            at += n * r
            x, of_kinds = jax.lax.scan(
                functools.partial(group_step, group), x, layers)
            if routing:
                routed.append(tuple(map(in_stack_order, zip(*of_kinds))))
        return x, tuple(jnp.concatenate(parts) for parts in zip(*routed))

    # the loops' own machinery (a layer's input and what its checkpoint
    # keeps stacked for the reverse pass, the gradients stacked and summed
    # over the sequences) is phase ``scan``; a layer's operations have
    # their own
    with jax.named_scope(phases.SCAN):
        if cfg.num_dense_layers:
            x, _ = jax.lax.scan(dense_step, x, params["dense"])
        x, (group_sizes, rungs) = jax.lax.scan(
            period_step, x, (params["periods"], router_bias))
    return (x, group_sizes.reshape(cfg.n_expert_layers, -1),
            rungs.reshape(cfg.n_expert_layers, -1))


# positions whose logits are held at once
HEAD_CHUNK = 1024


def sequence_loss(logits, targets, loss_weight):
    """One sequence's loss: ``logits [L, V]`` float32, ``targets [L]`` the
    ids to be predicted, ``loss_weight [L]``: ``-(1 / L) sum_i w_i log
    p(target_i)``."""
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logp, targets[:, None], axis=-1)[:, 0]
    return -(loss_weight * picked).sum() / targets.shape[0]


def chunked_loss_sums(x, targets, loss_weight, final_norm, head, *,
                      eps: float, dtype):
    """Each sequence's loss from the last layer's ``x [S, L, H]`` at the
    positions that predict (``targets``, ``loss_weight [S, L]``): the final
    norm, the head over the vocabulary slice and ``sequence_loss``,
    ``HEAD_CHUNK`` positions at a time and a ``jax.checkpoint`` each: a
    chunk's ``[HEAD_CHUNK, V]`` float32 logits are all that is ever held."""
    length = loss_weight.shape[-1]
    chunk = HEAD_CHUNK if length % HEAD_CHUNK == 0 else length

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def one(row):
        x_rows, tgt, weight = row
        hn = rms_norm(x_rows, final_norm, eps)
        logits = jnp.dot(hn.astype(dtype), head.astype(dtype),
                         preferred_element_type=jnp.float32)
        return sequence_loss(logits, tgt, weight) * (chunk / length)

    def rows(a):
        return a.reshape(-1, chunk, *a.shape[2:])

    losses = jax.lax.map(one, (rows(x), rows(targets), rows(loss_weight)))
    return losses.reshape(x.shape[0], -1).sum(axis=1)
