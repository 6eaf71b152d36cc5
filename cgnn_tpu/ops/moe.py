"""One chip's share of an expert-parallel mixture-of-experts layer.

The layer is told which experts it holds (``first``, ``count``). It routes
every token over ALL ``n_experts`` (``route``: softmax over the router's
logits, the ``k`` largest kept and renormalised to sum 1; or a ``Router``'s
other form: sigmoid scores, a bias that selects, a scale), computes the
experts it holds on the rows routed to them (``EXPERT_FORMS``: an expert is
``(silu(h W_g) * (h W_u)) W_d``, SwiGLU, which models/sdar.py, models/afmoe.py
and models/lfm2.py have, or ``relu(h W_up)^2 W_down``, two matrices and no
gate, which models/nemotron_h.py has), and adds nothing for the absent
ones: what comes out is this chip's part of the layer's result. On one chip
there is no exchange, and nothing here stands in for one.

Dropless, with static shapes, and the rows that travel are the rows that are
here. All ``T x k`` (token, expert) pairs are sorted by expert, which leaves
the held experts' rows one contiguous run of the sorted order; that run is
carried in a compact buffer of a static capacity (``_held_rows``): a gather
of ``capacity`` rows out (``spread``), the grouped matmul over them with the
held groups' sizes and one trailing group that is not held (``megablox`` on
the TPU visits the held rows only; ``lax.ragged_dot`` elsewhere), and the sum
of each token's rows back (``combine``). The capacities are a ladder from the
shapes (``ladder``: twice the balanced load, doubled up to ``T x k``), the
rung is picked on the device by the count of held rows (``lax.switch``), and
the last rung holds every pair: no row is dropped whatever the load, and no
count is read on the host.

Rows travel by gather, never by scatter: ``spread`` and ``combine`` are each
other's transpose and each is built of row gathers (``combine`` re-sorts the
rows by token, sums equal tokens a block of 128 with a 0/1 matrix on the MXU
as ``ops/segment.py`` sums the conv's overflow runs, and gathers one row a
token), so each one's reverse pass is the other.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from cgnn_tpu.observe import phases
from cgnn_tpu.ops.segment import _run_totals, _run_windows, gather


@dataclasses.dataclass(frozen=True)
class Router:
    """How scores become choices and weights (``route``): ``score_func``
    ``softmax`` over all experts or ``sigmoid`` of each logit; ``norm``: the
    ``k`` kept scores divided by their sum (``+ norm_eps``); ``scale``
    multiplies the weights. The default is Qwen3-MoE's."""
    score_func: str = "softmax"
    norm: bool = True
    norm_eps: float = 0.0
    scale: float = 1.0


def route(logits, k: int, router: Router = Router(), bias=None):
    """Router logits ``[T, E]`` float32 -> (``weights [T, k]``, ``experts
    [T, k]`` int32), over all ``E``. With ``bias [E]`` the ``k`` experts are
    the largest of ``score + bias`` and the weights are the scores' own: the
    bias selects and does not weigh, and no gradient reaches it."""
    logits = logits.astype(jnp.float32)
    if router.score_func == "softmax":
        scores = jax.nn.softmax(logits, axis=-1)
    elif router.score_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        raise ValueError(f"no score function {router.score_func!r}")
    if bias is None:
        top, experts = jax.lax.top_k(scores, k)
    else:
        _, experts = jax.lax.top_k(
            scores + jax.lax.stop_gradient(bias.astype(jnp.float32)), k)
        top = jnp.take_along_axis(scores, experts, axis=-1)
    if router.norm:
        total = top.sum(axis=-1, keepdims=True)
        top = top / (total + router.norm_eps if router.norm_eps else total)
    if router.scale != 1.0:
        top = top * router.scale
    return top, experts.astype(jnp.int32)


def ladder(rows: int, count: int, n_experts: int) -> tuple:
    """The capacities ``expert_share`` carries its held rows in, from the
    shapes alone (``rows`` = ``T x k`` pairs, ``count`` of ``n_experts``
    experts held): twice the balanced load rounded up to the grouped matmul's
    512-row tiles, doubled until it holds every pair, which the last rung
    does. A share that holds half the experts or more has the one rung."""
    balanced = -(-rows * count // n_experts)
    rung, rungs = 2 * (-(-balanced // 512) * 512), []
    while rung < rows:
        rungs.append(rung)
        rung *= 2
    return (*rungs, rows)


def row_plan(tok, valid, counts, k: int):
    """Where ``spread`` and ``combine`` move rows, from the carried rows'
    tokens alone: ``tok [C]`` the token of each carried row, ``valid [C]``
    whether the row is a held one, ``counts [T]`` each token's valid rows (at
    most ``k``). -> ``(tok, valid, win, same, last)``: ``win``, ``same`` the
    rows re-sorted by token and cut into blocks of 128 with a halo
    (``ops/segment.py`` ``_run_windows``); ``last [T]`` where a token's run
    ends in that order, and for a token with no row the entry past the end,
    whose total is zero."""
    c, t = tok.shape[0], counts.shape[0]
    ids, by_tok = jax.lax.sort_key_val(
        jnp.where(valid, tok, t), jnp.arange(c, dtype=jnp.int32))
    win, same = _run_windows(by_tok, jnp.where(ids < t, ids, -1), k)
    last = jnp.where(counts > 0, jnp.cumsum(counts) - 1, c)
    return tok, valid, win, same, last.astype(jnp.int32)


@jax.custom_vjp
def spread(x, plan):
    """A token's row to each of its carried places: ``x [T, H]`` -> ``[C,
    H]``, row ``r`` token ``tok[r]``'s where ``valid``, else zero (``plan``:
    ``row_plan``). The transpose is ``combine``."""
    tok, valid = plan[:2]
    return jnp.where(valid[:, None], gather(x, tok), 0)


@jax.custom_vjp
def combine(y, plan):
    """Every token's valid rows summed: ``y [C, H]`` -> ``[T, H]`` in ``y``'s
    dtype, each sum accumulated in float32 and rounded once. The rows are
    gathered in token order a block of 128 (with the halo a run may reach
    back over), a 0/1 matrix a block leaves each run's total at its last
    row, and one row a token is gathered from there: ``C + T`` gathered rows
    and no scatter. The transpose is ``spread``."""
    _, valid, win, same, last = plan
    # a row that is not valid holds whatever the kernel left: 0 x NaN
    return gather(_run_totals(jnp.where(valid[:, None], y, 0), win, same),
                  last)


spread.defvjp(lambda x, plan: (spread(x, plan), plan),
              lambda plan, g: (combine(g, plan), None))
combine.defvjp(lambda y, plan: (combine(y, plan), plan),
               lambda plan, g: (spread(g, plan), None))


@jax.custom_vjp
def swiglu(gate_up):
    """``silu(g) * u`` of ``[g | u]`` along the last axis, computed in
    float32 and returned in the input's dtype. The reverse pass keeps the
    input alone and works the activation out again: no float32 copy of a
    ``T x k``-row array outlives its fusion."""
    return _swiglu(gate_up)


def _swiglu(gate_up):
    inter = gate_up.shape[-1] // 2
    g = gate_up[..., :inter].astype(jnp.float32)
    u = gate_up[..., inter:].astype(jnp.float32)
    return (jax.nn.silu(g) * u).astype(gate_up.dtype)


def _swiglu_fwd(gate_up):
    return _swiglu(gate_up), gate_up


def _swiglu_bwd(gate_up, d):
    inter = gate_up.shape[-1] // 2
    g = gate_up[..., :inter].astype(jnp.float32)
    u = gate_up[..., inter:].astype(jnp.float32)
    d = d.astype(jnp.float32)
    s = jax.nn.sigmoid(g)
    dg = d * u * s * (1.0 + g * (1.0 - s))
    du = d * g * s
    return (jnp.concatenate([dg, du], axis=-1).astype(gate_up.dtype),)


swiglu.defvjp(_swiglu_fwd, _swiglu_bwd)


@jax.custom_vjp
def relu2(up):
    """``relu(u)^2``, computed in float32 and returned in the input's dtype;
    as ``swiglu``, the reverse pass keeps the input alone."""
    return _relu2(up)


def _relu2(up):
    r = jnp.maximum(up.astype(jnp.float32), 0.0)
    return (r * r).astype(up.dtype)


def _relu2_bwd(up, d):
    r = jnp.maximum(up.astype(jnp.float32), 0.0)
    return ((2.0 * r * d.astype(jnp.float32)).astype(up.dtype),)


relu2.defvjp(lambda up: (_relu2(up), up), _relu2_bwd)

# the forms of an expert's body: the activation between its two grouped
# matmuls, over ``[W_g | W_u]``'s output (``swiglu``) or ``W_up``'s
EXPERT_FORMS = {"swiglu": swiglu, "relu2": relu2}
# the lanes of a tile: what the grouped matmul's kernels cut a width into
LANES = 128


def lane_aligned(w_up, w_down):
    """``w_up [count, H, I]`` and ``w_down [count, I, H]`` of ``relu2``
    experts with ``I`` padded by zero columns and rows to whole tiles of
    ``LANES``: exact (``relu(0)^2 = 0``, and a zero row adds 0), and what
    megablox needs (its reverse kernel takes no block of 1,856 lanes: 14.5
    tiles). A width of whole tiles comes back as it is."""
    pad = -w_up.shape[-1] % LANES
    if not pad:
        return w_up, w_down
    return (jnp.pad(w_up, ((0, 0), (0, 0), (0, pad))),
            jnp.pad(w_down, ((0, 0), (0, pad), (0, 0))))


def _tile_of(n: int) -> int:
    for t in (1024, 768, 512, 256, 128):
        if n % t == 0:
            return t
    return n


def grouped_matmul(lhs, rhs, group_sizes, *, impl: str):
    """``lhs [R, K]`` rows sorted by group, ``rhs [count, K, N]`` the first
    ``count`` groups' matrices, ``group_sizes`` of ALL groups (they add up
    to ``R``): rows of group ``g`` times ``rhs[g]``. Rows of the groups
    past ``count`` come back as whatever the kernel left there: the caller
    masks them."""
    if impl == "megablox":
        from jax.experimental.pallas.ops.tpu.megablox import ops as megablox

        tiling = (512, _tile_of(lhs.shape[1]), _tile_of(rhs.shape[2]))
        return megablox.gmm(lhs, rhs, group_sizes, lhs.dtype, tiling,
                            jnp.zeros((), jnp.int32))
    if impl == "ragged":
        # every group a matrix, the absent ones zero: the plain form
        absent = group_sizes.shape[0] - rhs.shape[0]
        full = jnp.pad(rhs, ((0, absent), (0, 0), (0, 0)))
        return jax.lax.ragged_dot(
            lhs, full, group_sizes,
            preferred_element_type=jnp.float32).astype(lhs.dtype)
    raise ValueError(f"no grouped matmul {impl!r}")


def _held_rows(capacity: int, held: tuple, k: int, impl: str, form: str,
               x, w_flat, w_in, w_down, counts, order, group_sizes):
    """The held experts on their rows, carried in ``capacity`` rows: the
    one body of every rung. Right where the held rows are no more than
    ``capacity`` (``_held_experts`` picks the rung so; at ``T x k`` they
    always are). ``w_flat [T x k]`` the routing weights, ``counts [T]`` the
    held rows of each token, ``order`` the pairs sorted by expert."""
    first, count = held
    with jax.named_scope(phases.MOE_ROUTE):
        sizes = group_sizes[first:first + count]
        start, n_here = group_sizes[:first].sum(), sizes.sum()
        # the held rows are sorted entries [start, start + n_here)
        carried = jax.lax.dynamic_slice(
            jnp.pad(order, (0, capacity)), (start,), (capacity,))
        valid = jnp.arange(capacity, dtype=jnp.int32) < n_here
        plan = row_plan(carried // k, valid, counts, k)
        rows = spread(x, plan)
        # the sizes add up to the rows: one more group, which is not held
        sizes = jnp.concatenate([sizes, (capacity - n_here)[None]])
    with jax.named_scope(phases.MOE_EXPERT):
        # rows that are not held hold whatever the kernel's buffer held
        gu = jnp.where(valid[:, None], grouped_matmul(
            rows, w_in, sizes, impl=impl), 0)
        y = grouped_matmul(EXPERT_FORMS[form](gu), w_down, sizes, impl=impl)
    with jax.named_scope(phases.MOE_ROUTE):
        # the weights join the rows in the compute dtype; the sum over a
        # token's rows accumulates in float32 (combine)
        y = jnp.where(valid[:, None], y, 0) * gather(
            w_flat, carried).astype(y.dtype)[:, None]
        return combine(y, plan)


def _rung(rungs: tuple, held: tuple, group_sizes):
    """The first rung that holds the held rows: its index, int32."""
    first, count = held
    n_here = group_sizes[first:first + count].sum()
    return (n_here > jnp.asarray(rungs[:-1], jnp.int32)).sum(dtype=jnp.int32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4))
def _held_experts(rungs: tuple, held: tuple, k: int, impl: str, form: str,
                  floats: tuple, ints: tuple):
    """``_held_rows`` in the first of ``rungs`` that holds the held rows,
    picked on the device -> (its output, the rung's index). ``floats`` are
    ``(x, w_flat, w_in, w_down)``, ``ints`` ``(counts, order,
    group_sizes)``. The reverse pass is a switch of its own over ``jax.vjp``
    of the same bodies, from the operands alone: differentiated through, a
    ``lax.switch`` keeps the union of its branches' residuals and each branch
    writes zeros for the others' (the full rung's are ``T x k`` rows long)."""
    index = _rung(rungs, held, ints[-1])
    bodies = [functools.partial(_held_rows, r, held, k, impl, form)
              for r in rungs]
    return jax.lax.switch(index, bodies, *floats, *ints), index


def _held_experts_fwd(rungs, held, k, impl, form, floats, ints):
    return (_held_experts(rungs, held, k, impl, form, floats, ints),
            (floats, ints))


def _held_experts_bwd(rungs, held, k, impl, form, res, cts):
    floats, ints = res

    def pull(capacity, g, *floats):
        body = functools.partial(_held_rows, capacity, held, k, impl, form)
        return jax.vjp(lambda *f: body(*f, *ints), *floats)[1](g)

    grads = jax.lax.switch(
        _rung(rungs, held, ints[-1]),
        [functools.partial(pull, r) for r in rungs], cts[0], *floats)
    return grads, None


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def expert_share(x, router, w_in, w_down, *, experts_held: tuple,
                 k: int, impl: str = "auto", capacity: int | None = None,
                 routing: Router = Router(), bias=None,
                 form: str = "swiglu"):
    """``x [T, H]`` (normed hidden states) -> (this share's part of the
    layer's output ``[T, H]`` in ``x``'s dtype, each token's sum accumulated
    in float32; ``group_sizes [E]`` int32: the rows each of ALL experts was
    routed; the index of the rung that carried the held rows).

    ``router [H, E]`` float32; ``w_in`` and ``w_down [count, I, H]`` in the
    compute dtype, the experts ``first .. first + count - 1``, by ``form``
    (``EXPERT_FORMS``): ``swiglu``, ``w_in [count, H, 2I] = [W_g | W_u]``
    and ``e(h) = (silu(h W_g) * (h W_u)) W_d``; ``relu2``, ``w_in [count,
    H, I] = W_up`` and ``e(h) = relu(h W_up)^2 W_down``. ``capacity`` puts
    another compact rung under ``T x k`` in the ladder's place (tests, at
    sizes whose ladder is the one rung). ``routing`` and ``bias [E]``:
    ``route``'s.
    """
    first, count = experts_held
    t, n_experts = x.shape[0], router.shape[1]
    if impl == "auto":
        impl = "megablox" if jax.default_backend() == "tpu" else "ragged"
    rungs = ladder(t * k, count, n_experts)
    if capacity is not None:
        rungs = (capacity, t * k) if capacity < t * k else (t * k,)
    with jax.named_scope(phases.MOE_ROUTE):
        logits = jnp.dot(x.astype(jnp.float32), router,
                         precision=jax.lax.Precision.HIGHEST)
        weights, experts = route(logits, k, routing, bias)
        flat = experts.reshape(-1)
        order = jnp.argsort(flat, stable=True).astype(jnp.int32)
        group_sizes = (flat[:, None] == jnp.arange(
            n_experts, dtype=jnp.int32)).sum(axis=0, dtype=jnp.int32)
        counts = ((experts >= first) & (experts < first + count)).sum(
            axis=1, dtype=jnp.int32)
        out, rung = _held_experts(
            rungs, (first, count), k, impl, form,
            (x, weights.reshape(-1), w_in, w_down),
            (counts, order, group_sizes))
    return out, group_sizes, rung
