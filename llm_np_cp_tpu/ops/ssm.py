"""The recurrence of a Mamba-2 state-space mixer, chunked.

For head ``h`` of group ``g`` with ``x_t`` in R^P, ``B_t`` / ``C_t`` in R^N
(shared by the group's heads), a step ``dt_t > 0`` and one scalar ``A < 0``:

    H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T        H in R^{P x N}, float32
    y_t = H_t C_t + D x_t

``ssm_chunk`` advances ROWS of independent sequences by ``q`` tokens at
once: the state's share of every ``y`` is one product with the state the
chunk started from, the chunk's own tokens meet in a ``q x q`` form, and
the state moves once (a rank-``q`` update).  It is the ONE statement of the
recurrence: ``ssm_scan`` carries it over whole sequences (``models.forward``)
and ``ssm_packed`` runs it over the serving tick's packed token axis, where
it advances every row by its first token as ``q = 1`` (64 decode rows are 64
rank-one updates: nothing 128 wide) and the rest of a prefill chunk in
further passes over that chunk's own rows of the state.  On a TPU that
first pass is one Pallas kernel over the rows the tick touches
(``ops/pallas/ssm_state_update``: a row's state read once, both results
taken from that copy, written once); ``state_update_heads`` says where.

A token that is not there (padding, a row that is not in the tick) has
``dt = 0``: the decay is 1 and nothing enters, so the state passes through
and its ``y`` is dropped by the caller.  All arithmetic is float32 at the
highest matmul precision, whatever the model is served in.
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from llm_np_cp_tpu.ops.pallas import ssm_state_update as ssu
from llm_np_cp_tpu.ops.pallas import support

_HIGHEST = lax.Precision.HIGHEST


def ssm_chunk(
    h: jnp.ndarray,   # [R, nh, P, N] float32: the state the chunk starts from
    x: jnp.ndarray,   # [R, q, nh, P]
    dt: jnp.ndarray,  # [R, q, nh] float32 steps, 0 where there is no token
    a: jnp.ndarray,   # [nh] float32, negative
    b: jnp.ndarray,   # [R, q, ng, N]
    c: jnp.ndarray,   # [R, q, ng, N]
    d_skip: jnp.ndarray,  # [nh]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``q`` tokens of every row: ``(y [R, q, nh, P] float32, state after)``."""
    r, q, nh, p = x.shape
    ng, n = b.shape[2], b.shape[3]
    j = nh // ng  # heads a group: head h is (h // j, h % j)
    f32 = jnp.float32
    x5 = x.astype(f32).reshape(r, q, ng, j, p)
    h5 = h.reshape(r, ng, j, p, n)
    b, c = b.astype(f32), c.astype(f32)
    dt5 = dt.reshape(r, q, ng, j)
    da = dt5 * a.reshape(ng, j)  # log of each step's decay, <= 0
    skip = d_skip.astype(f32).reshape(ng, j, 1) * x5
    if q == 1:
        # one token a row: elementwise over the state, read once
        decay = jnp.exp(da[:, 0])[..., None, None]  # [R, ng, j, 1, 1]
        c0, b0 = c[:, 0, :, None, None, :], b[:, 0, :, None, None, :]
        from_state = decay[..., 0] * jnp.sum(h5 * c0, axis=-1)  # [R, ng, j, P]
        cb = jnp.sum(c[:, 0] * b[:, 0], axis=-1)[:, :, None, None]  # [R, ng, 1, 1]
        dtx = dt5[:, 0, :, :, None] * x5[:, 0]  # [R, ng, j, P]
        y = (from_state + cb * dtx)[:, None] + skip
        h_new = decay * h5 + dtx[..., None] * b0
        return y.reshape(r, q, nh, p), h_new.reshape(h.shape)
    cs = jnp.cumsum(da, axis=1)  # [R, q, ng, j] log decay from the chunk's start
    from_state = jnp.exp(cs)[..., None] * jnp.einsum(
        "rqgn,rgjpn->rqgjp", c, h5, precision=_HIGHEST)
    # token k's input as token t >= k sees it: decayed over (k, t]
    cb = jnp.einsum("rqgn,rkgn->rgqk", c, b, precision=_HIGHEST)
    cs_t = jnp.moveaxis(cs, 1, -1)  # [R, ng, j, q]
    span = cs_t[..., :, None] - cs_t[..., None, :]  # [R, ng, j, t, k]
    causal = jnp.tril(jnp.ones((q, q), jnp.bool_))
    weight = (jnp.exp(jnp.where(causal, span, -jnp.inf))
              * cb[:, :, None] * jnp.moveaxis(dt5, 1, -1)[..., None, :])
    within = jnp.einsum("rgjtk,rkgjp->rtgjp", weight, x5, precision=_HIGHEST)
    y = from_state + within + skip
    # the state after: decayed over the whole chunk, plus every token's
    # input decayed from its place to the chunk's end
    to_end = jnp.exp(cs[:, -1:] - cs) * dt5  # [R, q, ng, j]
    h_new = (jnp.exp(cs[:, -1])[..., None, None] * h5 + jnp.einsum(
        "rkgjp,rkgn->rgjpn", x5 * to_end[..., None], b, precision=_HIGHEST))
    return y.reshape(r, q, nh, p), h_new.reshape(h.shape)


def ssm_scan(
    h0: jnp.ndarray,  # [R, nh, P, N] float32
    x: jnp.ndarray,   # [R, S, nh, P]
    dt: jnp.ndarray,  # [R, S, nh]
    a: jnp.ndarray,
    b: jnp.ndarray,   # [R, S, ng, N]
    c: jnp.ndarray,
    d_skip: jnp.ndarray,
    *,
    chunk: int,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Whole sequences, ``chunk`` tokens at a time: ``(y [R, S, nh, P]
    float32, state after the last token)``.  A length that ``chunk`` does
    not divide is padded with tokens that are not there."""
    s = x.shape[1]
    if s <= chunk:
        return ssm_chunk(h0, x, dt, a, b, c, d_skip)
    pad = -s % chunk

    def chunks(t: jnp.ndarray) -> jnp.ndarray:  # [R, S, ..] -> [S/chunk, R, chunk, ..]
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(
            t.reshape((t.shape[0], -1, chunk) + t.shape[2:]), 1, 0)

    def step(h, xs):
        y, h = ssm_chunk(h, *xs[:2], a, *xs[2:], d_skip)
        return h, y

    h_end, ys = lax.scan(step, h0, tuple(chunks(t) for t in (x, dt, b, c)))
    y = jnp.moveaxis(ys, 0, 1).reshape((x.shape[0], -1) + x.shape[2:])
    return y[:, :s], h_end


def state_update_heads(state: Any, groups: int,
                       interpret: bool | None = None) -> int | None:
    """The heads a block of ``ops/pallas/ssm_state_update`` advances the
    rows of ``state [L, R, nh, P, N]`` (an array, or its shape and dtype)
    in, or None where ``ssm_packed``'s first pass is ``ssm_chunk``: told
    from the backend, the dtype and the shape, and on a TPU from the
    kernel's probe (a Mosaic refusal is one warning and the compiler's
    two passes, not a dead server).  ``interpret``: ``ssm_packed``'s."""
    if state.dtype != jnp.float32:
        return None  # (a test keeps the state lower; see ``ssm_packed``)
    heads = ssu.head_block(state.shape[2], groups, *state.shape[3:])
    if heads is None or (interpret is None and (
            jax.default_backend() != "tpu"
            or support.kernel_or_warn("ssm_state_update", "ssm_chunk"))):
        return None
    return heads


def _first_tokens(state, layer, x, dt, a, b, c, d_skip, count, fresh, heads,
                  interpret):
    """``ssm_chunk``'s ``q == 1`` branch over ``state[layer]``'s rows with
    ``count > 0``, its operations in its order, with everything that
    touches the state in the kernel: ``(y [R, nh, P], state)``."""
    r, nh, p = x.shape
    ng = b.shape[1]
    j = nh // ng
    f32 = jnp.float32
    x4 = x.astype(f32).reshape(r, ng, j, p)
    b, c = b.astype(f32), c.astype(f32)
    dt4 = dt.reshape(r, ng, j)
    decay = jnp.exp(dt4 * a.reshape(ng, j))
    dtx = dt4[..., None] * x4
    held, state = ssu.ssm_state_update(
        state, layer, decay.reshape(r, nh), dtx.reshape(r, nh, p), b, c,
        count=count, fresh=fresh, heads=heads, interpret=interpret)
    from_state = decay[..., None] * held.reshape(r, ng, j, p)
    cb = jnp.sum(c * b, axis=-1)[:, :, None, None]
    y = (from_state + cb * dtx) + d_skip.astype(f32).reshape(ng, j, 1) * x4
    return y.reshape(r, nh, p), state


def ssm_packed(
    state: jnp.ndarray,  # [L, R, nh, P, N] float32: every layer's rows
    layer: jnp.ndarray,  # int32 scalar: the layer whose rows advance
    x: jnp.ndarray,      # [T, nh, P]: tokens on ONE packed axis
    dt: jnp.ndarray,     # [T, nh]
    a: jnp.ndarray,
    b: jnp.ndarray,      # [T, ng, N]
    c: jnp.ndarray,
    d_skip: jnp.ndarray,
    *,
    tok_row: jnp.ndarray,   # [T] int32: the row each token belongs to
    start: jnp.ndarray,     # [R] int32: where a row's tokens start
    count: jnp.ndarray,     # [R] int32: how many it has in this tick (0: none)
    fresh: jnp.ndarray,     # [R] bool: the row's sequence starts in this tick
    chunk: int,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The serving tick: a row's tokens are consecutive on the packed axis
    (``start``, ``count``); ``state[layer, r]`` is where row ``r``'s
    sequence stands (nothing, for one that starts here: a slot's old state
    is never read by a new request).  Returns ``(y [T, nh, P] float32,
    state)`` with ``state[layer]`` advanced IN PLACE.

    Every row advances by its first token as ``q = 1``.  Where
    ``state_update_heads`` allows, the Pallas kernel does it: only the rows
    with a token are visited, each read once and written once (on a v5e at
    the published shapes 0.85 ms a layer when all 64 rows have one, 0.73
    for 55: PERF.md section 6, PR 45).  Elsewhere ``ssm_chunk`` does, over
    all of the layer's rows, as two passes of the compiler's (1.20 ms).
    ``interpret``: as the Pallas kernels take it — None lets the backend
    decide (the kernel compiled on a TPU, ``ssm_chunk`` elsewhere), True
    runs the kernel in the interpreter (tests), False compiles it.

    A row with more tokens (a prefill chunk)
    then advances by itself, ``chunk`` tokens a pass, on its own row of the
    state: a decode row never meets the chunk form, and a tick of decode
    rows alone never enters the loop.  One row a pass and not several:
    measured there, 2 to 16 such rows a tick cost the same within 0.1 ms a
    layer whether 1, 2, 4 or 8 advance together, and all 64 rows in one
    pass cost 5 ms and 771 MiB of temporaries more."""
    t = x.shape[0]
    first = jnp.clip(start, 0, t - 1)
    heads = state_update_heads(state, b.shape[1], interpret)
    if heads is not None:
        y, state = _first_tokens(
            state, layer, x[first], dt[first], a, b[first], c[first], d_skip,
            count, fresh, heads, interpret)
    else:
        h = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False)
        h = jnp.where(fresh[:, None, None, None], 0.0, h.astype(jnp.float32))
        y, h = ssm_chunk(
            h, x[first][:, None],
            jnp.where(count > 0, 1.0, 0.0)[:, None, None] * dt[first][:, None],
            a, b[first][:, None], c[first][:, None], d_skip)
        # (the casts are no-ops: the state is float32 wherever the program
        # allocates it; a test keeps it lower to show that the tolerance
        # sees it)
        state = lax.dynamic_update_index_in_dim(
            state, h.astype(state.dtype), layer, 0)
        y = y[:, 0]
    y = y[tok_row]  # right for a row's first token; the rest follow
    if t == 1:
        return y, state
    q = min(chunk, t - 1)
    lanes = jnp.arange(q, dtype=jnp.int32)
    # the rows with further tokens, first; ``n_more`` of them
    order = jnp.argsort(count <= 1, stable=True).astype(jnp.int32)
    n_more = jnp.sum(count > 1, dtype=jnp.int32)
    zero = jnp.int32(0)

    def more(carry):
        state, y, g, offset = carry
        row = order[g]
        at = offset + lanes  # the row's tokens ``offset .. offset + q``
        there = at < count[row]
        idx = jnp.clip(start[row] + at, 0, t - 1)
        # the row by a slice: a gather of rows out of the whole state is
        # compiled (for a v5e) as a pass over ALL of it
        where = (layer, row, zero, zero, zero)
        h = lax.dynamic_slice(state, where, (1, 1) + state.shape[2:])[0]
        y_p, h = ssm_chunk(
            h.astype(jnp.float32), x[idx][None],
            jnp.where(there[:, None], dt[idx], 0.0)[None], a, b[idx][None],
            c[idx][None], d_skip)
        state = lax.dynamic_update_slice(state, h[None].astype(state.dtype), where)
        y = y.at[jnp.where(there, idx, t)].set(y_p[0], mode="drop")
        done = offset + q >= count[row]
        return (state, y, jnp.where(done, g + 1, g),
                jnp.where(done, 1, offset + q))

    state, y, _, _ = lax.while_loop(
        lambda carry: carry[2] < n_more, more,
        (state, y, jnp.int32(0), jnp.int32(1)))
    return y, state
