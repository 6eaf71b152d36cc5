"""The Mamba-2 state-space scan (models/nemotron_h.py): the mixer of the
layers that carry a state along the sequence.

A head ``h`` of ``P`` channels reads group ``g = h // (heads / groups)``'s
``B_t, C_t [N]``; with ``d_t = softplus(dt_t + dt_bias)`` and ``a_t =
exp(-exp(A_log) d_t)``, both float32 and a head's scalars:

    S_t = a_t S_{t-1} + d_t x_t (x) B_t          S [P, N], float32
    y_t = S_t C_t + D x_t

and a document's own: ``S`` is empty before a document's first position
(``segment_ids``, non-decreasing along a row as data/tokens.py packs them
and as ``ops/masked_attention.py`` takes them), as a server's state cache
starts every request empty.

Computed in chunks of ``CHUNK`` positions, the state-space duality form, the
same numbers: with ``cum_i`` the sum of ``log a`` over the positions of
``i``'s document inside ``i``'s chunk up to ``i``,

    inside a chunk   y_i += sum_{j <= i, doc(j) = doc(i)}
                            exp(cum_i - cum_j) d_j (C_i . B_j) x_j
                     (a masked ``[CHUNK, CHUNK]`` product a head)
    a chunk's end    E = sum_{j, doc(j) = doc(last)}
                         exp(cum_last - cum_j) d_j x_j (x) B_j
    between chunks   S_in' = [doc(last) = doc(the chunk before's last)]
                             exp(cum_last) S_in + E       (``lax.scan``)
    the state's part y_i += [doc(i) = doc(the chunk before's last)]
                            exp(cum_i) (S_in C_i)

A document's start cuts a decay to 0 by a mask on the products, never by a
logarithm of zero: every exponent is a sum of ``log a <= 0`` over shown
positions (a hidden pair's is replaced by 0 before the ``exp``), so nothing
is infinite in either direction. ``dt``, the decays and the states are
float32; ``x``, ``B``, ``C`` and the operands of every product are in the
compute dtype (the state is rounded where a product reads it, never where it
is carried), and every product accumulates in float32. The reverse pass is
``jax.grad`` of this form.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# positions a chunk (``chunk_size``)
CHUNK = 128
# chunks a block: what one step of the two passes over a sequence holds
BLOCK = 8
# the document of the positions a sequence is padded with to whole chunks
_PAD = jnp.iinfo(jnp.int32).min


def _shown_exp(shown, exponent):
    """``exp(exponent)`` where ``shown``, else 0, finite in both
    directions: a hidden entry's exponent (which may be positive) never
    reaches the ``exp``."""
    return jnp.where(shown, jnp.exp(jnp.where(shown, exponent, 0.0)), 0.0)


def _own_sums(log_a, seg):
    """``shown [.., q, q]``: the positions of a chunk that a position sees,
    its document's up to itself; ``cum [.., heads, q]``: the running sum of
    ``log a`` over them, as a masked product: another document's steps add
    exact zeros, so no rounding of theirs reaches this one's decays."""
    q = seg.shape[-1]
    i, j = jnp.arange(q)[:, None], jnp.arange(q)[None, :]
    shown = (seg[..., :, None] == seg[..., None, :]) & (j <= i)
    cum = jnp.einsum("zchj,zcij->zchi", log_a, shown.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return shown, cum


def _end_states(x, b, d, log_a, seg):
    """Each chunk's end state from its own positions, ``[S, chunks, groups,
    heads a group, P, N]`` float32, and the decay a state that enters the
    chunk leaves it with, ``[S, chunks, heads]`` (0 where a document starts
    inside the chunk: the caller cuts the one that starts ON it)."""
    s, nc, q, heads, p = x.shape
    groups = b.shape[3]
    _, cum = _own_sums(log_a, seg)
    to_end = _shown_exp((seg == seg[..., -1:])[:, :, None],
                        cum[..., -1:] - cum) * d  # [S, nc, heads, q]
    xw = (x.astype(jnp.float32) * jnp.swapaxes(to_end, 2, 3)[..., None]
          ).astype(x.dtype).reshape(s, nc, q, groups, heads // groups, p)
    ends = jnp.einsum("zcjgkp,zcjgn->zcgkpn", xw, b,
                      preferred_element_type=jnp.float32)
    whole = (seg[..., 0] == seg[..., -1])[..., None]
    return ends, jnp.where(whole, jnp.exp(cum[..., -1]), 0.0)


def _outputs(x, b, c, d, log_a, seg, before, s_in, d_skip):
    """``y [S, chunks, q, heads, P]`` in ``x``'s dtype from the chunks' own
    positions, the states ``s_in`` that enter them (of the document
    ``before [S, chunks]``; ``_PAD`` where none does) and the skip."""
    f32, dtype = jnp.float32, x.dtype
    per_group = x.shape[3] // b.shape[3]
    shown, cum = _own_sums(log_a, seg)
    # inside a chunk: (C_i . B_j) a group, then decay and step a head
    decay = _shown_exp(shown[:, :, None],
                       cum[..., :, None] - cum[..., None, :])
    cb = jnp.einsum("zcign,zcjgn->zcgij", c, b, preferred_element_type=f32)
    m = (jnp.repeat(cb, per_group, axis=2) * decay
         * d[..., None, :]).astype(dtype)  # [S, nc, heads, q(i), q(j)]
    y = jnp.einsum("zchij,zcjhp->zcihp", m, x, preferred_element_type=f32)
    # what the incoming state gives a position of its own document
    from_in = jnp.where((seg == before[..., None])[:, :, None],
                        jnp.exp(cum), 0.0)  # [S, nc, heads, q]
    y_in = jnp.einsum("zcign,zcgkpn->zcigkp", c, s_in.astype(dtype),
                      preferred_element_type=f32)
    y = y + y_in.reshape(y.shape) * jnp.swapaxes(from_in, 2, 3)[..., None]
    return (y + d_skip.astype(f32)[:, None] * x.astype(f32)).astype(dtype)


def _by_blocks(fn, per_block: int, *chunked):
    """``fn`` over ``per_block`` chunks at a time (arrays ``[S, chunks,
    ...]``), a ``jax.checkpoint`` each: what the reverse pass holds of the
    masked ``[q, q]`` products is one block's."""
    def blocks(a):
        a = a.reshape(a.shape[0], -1, per_block, *a.shape[2:])
        return jnp.moveaxis(a, 1, 0)

    def whole(a):
        a = jnp.moveaxis(a, 0, 1)
        return a.reshape(a.shape[0], -1, *a.shape[3:])

    out = jax.lax.map(lambda args: jax.checkpoint(fn)(*args),
                      tuple(blocks(a) for a in chunked))
    return jax.tree_util.tree_map(whole, out)


def ssd_scan(x, dt, b, c, a_log, dt_bias, d_skip, segment_ids, *,
             chunk: int = CHUNK):
    """``x [S, L, heads, P]``, ``b, c [S, L, groups, N]`` in the compute
    dtype, ``dt [S, L, heads]`` (before its bias and softplus), ``a_log,
    dt_bias, d_skip [heads]`` float32, ``segment_ids [S, L]`` int32 -> ``y
    [S, L, heads, P]`` in ``x``'s dtype. Two passes over blocks of
    ``BLOCK`` chunks with the recurrence between them: the chunks' end
    states; then, from the states that enter them, the outputs."""
    s, n, heads, p = x.shape
    groups, state = b.shape[2:]
    f32 = jnp.float32
    q = min(chunk, n)
    pad = -n % q
    if pad:
        x, dt, b, c = (jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),)
                               * (a.ndim - 2)) for a in (x, dt, b, c))
        segment_ids = jnp.pad(segment_ids, ((0, 0), (0, pad)),
                              constant_values=_PAD)
    nc = (n + pad) // q
    per_block = math.gcd(nc, BLOCK)

    def chunks(a):
        return a.reshape(s, nc, q, *a.shape[2:])

    d = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    log_a = -jnp.exp(a_log.astype(f32)) * d
    # [S, nc, heads, q]: a head's scalars of a chunk side by side
    d, log_a = (jnp.swapaxes(chunks(a), 2, 3) for a in (d, log_a))
    seg = chunks(segment_ids)  # [S, nc, q]
    xc, bc, cc = chunks(x), chunks(b), chunks(c)
    ends, kept = _by_blocks(_end_states, per_block, xc, bc, d, log_a, seg)

    # the document of the last position of the chunk before: the one the
    # incoming state belongs to (the first chunk's is empty)
    last = seg[:, :, -1]
    before = jnp.pad(last, ((0, 0), (1, 0)), constant_values=_PAD)[:, :-1]
    # a document that starts ON a chunk's first position cuts it too
    kept = jnp.where((last == before)[..., None], kept, 0.0)

    def pass_on(state_in, chunk_):
        keep, end = chunk_
        return keep[..., None, None] * state_in + end, state_in

    _, s_in = jax.lax.scan(
        pass_on, jnp.zeros((s, groups, heads // groups, p, state), f32),
        (jnp.moveaxis(kept.reshape(s, nc, groups, -1), 1, 0),
         jnp.moveaxis(ends, 1, 0)))
    y = _by_blocks(
        lambda *a: _outputs(*a, d_skip), per_block, xc, bc, cc, d, log_a,
        seg, before, jnp.moveaxis(s_in, 0, 1))
    return y.reshape(s, nc * q, heads, p)[:, :n]


def ssd_counts(segment_ids, chunk: int = CHUNK) -> dict:
    """What one layer's scan met in ``segment_ids [S, L]``, int32 each:
    ``resets`` the document starts (a state and a filter start empty there,
    the sequence's first position among them), ``chunks`` the chunks, and
    ``chunks_cut`` those a document's start falls inside (after their first
    position): the chunks whose mask is not the causal triangle alone."""
    s, n = segment_ids.shape
    q = min(chunk, n)
    pad = -n % q
    starts = 1 + (segment_ids[:, 1:] != segment_ids[:, :-1]).sum(
        axis=1, dtype=jnp.int32)
    seg = jnp.pad(segment_ids, ((0, 0), (0, pad)), mode="edge").reshape(
        s, -1, q)
    return {"resets": starts.sum(),
            "chunks": jnp.int32(s * seg.shape[1]),
            "chunks_cut": (seg[..., 0] != seg[..., -1]).sum(dtype=jnp.int32)}
