"""The recurrence of a delta-rule linear-attention layer (KDA).

For one head with ``q_t, k_t`` in R^d (L2-normalised, ``q`` scaled by
``d^-0.5``), ``v_t`` in R^d, a per-CHANNEL log-decay ``g_t`` in ``[L, 0]^d``
(``L = kda_lower_bound``) and a scalar ``beta_t`` in (0, 1):

    S   <- Diag(exp g_t) S                S in R^{d x d}, float32
    u   =  v_t - S^T k_t                  what the state does not yet say of k_t
    S   <- S + beta_t k_t u^T             the rank-one correction
    o_t =  S^T q_t

``kda_recurrent`` is that, one token at a time: the statement the others are
tested against, and what a decode row is.  ``kda_chunk`` advances ROWS of
independent sequences by ``c`` tokens at once; ``kda_scan`` carries it over
whole sequences (``models.forward``), so the sequential depth is the number
of chunks, not of tokens; ``kda_packed`` runs the serving tick's packed token
axis under ``ops/ssm.ssm_packed``'s contract: every row advances by its first
token (on a TPU in one Pallas kernel over the rows the tick touches,
``ops/pallas/kda_state_update``: a row's state read once, used twice, written
once), and the rest of a prefill chunk goes on by chunks on its own row.

The chunk form.  With ``G_t`` the log-decay summed from the chunk's start
up to and including token ``t``, and ``w_j = beta_j u_j``:

    S_t = Diag(e^{G_t}) S_0 + sum_{j<=t} (k_j * e^{G_t - G_j}) w_j^T
    u_t = v_t - S_0^T (k_t e^{G_t}) - sum_{j<t} A[t, j] w_j
    A[t, j] = sum_c k_t[c] k_j[c] e^{G_t[c] - G_j[c]}

so ``U`` solves a unit lower-triangular system of ``c`` rows, and ``o`` and
the state after follow from it.  ``A`` is taken as ONE product of
``k e^{G - m}`` with ``k e^{m - G}``, ``m`` the log-decay at the chunk's
middle: each factor then spans ``e^{+-c |L| / 2}`` (about its START the
second would grow as ``e^{c |L|}``, ``e^{80}`` for 16 tokens at ``L = -5``,
and the first shrink to float32's denormals, which a TPU flushes), and the
masked upper triangle holds sums of ``d`` such terms before it is dropped.
float32 ends near ``e^{88.7}``, so ``c |L| / 2 + ln d`` must stay below it:
at ``L = -5`` and ``d = 128`` a chunk of 32 does (``e^{80}``, sums to
``e^{84.9}``) and one of 64 does not; ``kda_chunk`` refuses more than
``max_chunk``.  The program runs chunks of ``CHUNK`` = 16.  A gate
pinned at ``L`` for a whole chunk is the extreme the tests run.

A token that is not there (padding, a row that is not in the tick) has
``g = 0`` and ``beta = 0``: the state passes through and its ``o`` is dropped
by the caller.  All arithmetic is float32 at the highest matmul precision,
whatever the model is served in.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from llm_np_cp_tpu.ops.pallas import kda_state_update as ksu
from llm_np_cp_tpu.ops.pallas import support

_HIGHEST = lax.Precision.HIGHEST
# ln of the largest float32, with a little room
_F32_LOG_MAX = 88.0
# tokens a step of the program's chunk form advances (``models.forward``,
# a prefill row of the tick): not part of the mathematics, half of what
# ``max_chunk`` allows at the published bound
CHUNK = 16


def max_chunk(lower_bound: float, head_dim: int) -> int:
    """The most tokens ``kda_chunk`` may take at once (module docstring)."""
    room = _F32_LOG_MAX - math.log(head_dim)
    return max(1, int(2 * room // max(abs(lower_bound), 1e-6)))


def kda_step(s, q, k, v, g, beta):
    """One token of every row: ``s [.., d, d]``, ``q k v g [.., d]``,
    ``beta [..]`` -> ``(o [.., d], s)``.  The equations, as written."""
    return ksu.step(s, jnp.exp(g), k, q, v, beta)


def kda_recurrent(
    s0: jnp.ndarray,    # [R, H, d, d] float32
    q: jnp.ndarray,     # [R, S, H, d]
    k: jnp.ndarray,
    v: jnp.ndarray,
    g: jnp.ndarray,     # [R, S, H, d] log-decay, <= 0
    beta: jnp.ndarray,  # [R, S, H]
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Token by token: ``(o [R, S, H, d] float32, state after)``."""
    f32 = jnp.float32

    def step(s, xs):
        o, s = kda_step(s, *xs)
        return s, o

    s, o = lax.scan(step, s0.astype(f32), tuple(
        jnp.moveaxis(t.astype(f32), 1, 0) for t in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def kda_chunk(
    s0: jnp.ndarray,    # [R, H, d, d] float32: the state the chunk starts from
    q: jnp.ndarray,     # [R, c, H, d]
    k: jnp.ndarray,
    v: jnp.ndarray,
    g: jnp.ndarray,     # [R, c, H, d] log-decay in [lower_bound, 0]
    beta: jnp.ndarray,  # [R, c, H], 0 where there is no token
    *,
    lower_bound: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``c`` tokens of every row: ``(o [R, c, H, d] float32, state after)``."""
    c, d = q.shape[1], q.shape[-1]
    if c > max_chunk(lower_bound, d):
        raise ValueError(
            f"a chunk of {c} tokens at a log-decay of {lower_bound} a token "
            f"leaves float32 (at most {max_chunk(lower_bound, d)})")
    f32 = jnp.float32
    # heads in front of the tokens: [R, H, c, d]
    q, k, v, g = (jnp.swapaxes(t.astype(f32), 1, 2) for t in (q, k, v, g))
    beta = jnp.swapaxes(beta.astype(f32), 1, 2)  # [R, H, c]
    # (the bound is the configuration's; a gate below it would overflow)
    cs = jnp.cumsum(jnp.maximum(g, lower_bound), axis=2)  # G_t, inclusive
    k_dec, q_dec = k * jnp.exp(cs), q * jnp.exp(cs)
    mid = cs[:, :, (c - 1) // 2][:, :, None]  # exponents about the middle
    k_inv = k * jnp.exp(mid - cs)
    a = jnp.einsum("rhtc,rhjc->rhtj", k * jnp.exp(cs - mid), k_inv,
                   precision=_HIGHEST)
    b = jnp.einsum("rhtc,rhjc->rhtj", q * jnp.exp(cs - mid), k_inv,
                   precision=_HIGHEST)
    strict = jnp.tril(jnp.ones((c, c), jnp.bool_), -1)
    causal = jnp.tril(jnp.ones((c, c), jnp.bool_))
    a = jnp.where(strict, a, 0.0) * beta[..., None, :]
    b = jnp.where(causal, b, 0.0) * beta[..., None, :]
    rhs = v - jnp.einsum("rhtk,rhkv->rhtv", k_dec, s0, precision=_HIGHEST)
    u = jax.scipy.linalg.solve_triangular(
        a + jnp.eye(c, dtype=f32), rhs, lower=True, unit_diagonal=True)
    o = (jnp.einsum("rhtk,rhkv->rhtv", q_dec, s0, precision=_HIGHEST)
         + jnp.einsum("rhtj,rhjv->rhtv", b, u, precision=_HIGHEST))
    # the state after: decayed over the whole chunk, plus every token's
    # correction decayed from its place to the chunk's end
    to_end = k * jnp.exp(cs[:, :, -1:] - cs) * beta[..., None]
    s = (jnp.exp(cs[:, :, -1])[..., None] * s0 + jnp.einsum(
        "rhjk,rhjv->rhkv", to_end, u, precision=_HIGHEST))
    return jnp.swapaxes(o, 1, 2), s


def kda_scan(
    s0: jnp.ndarray,    # [R, H, d, d] float32
    q: jnp.ndarray,     # [R, S, H, d]
    k: jnp.ndarray,
    v: jnp.ndarray,
    g: jnp.ndarray,
    beta: jnp.ndarray,  # [R, S, H]
    *,
    chunk: int,
    lower_bound: float,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Whole sequences, ``chunk`` tokens at a time: ``(o [R, S, H, d]
    float32, state after the last token)``.  A length that ``chunk`` does
    not divide is padded with tokens that are not there."""
    s = q.shape[1]
    if s <= chunk:
        return kda_chunk(s0, q, k, v, g, beta, lower_bound=lower_bound)
    pad = -s % chunk

    def chunks(t: jnp.ndarray) -> jnp.ndarray:  # [R, S, ..] -> [S/chunk, R, chunk, ..]
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(
            t.reshape((t.shape[0], -1, chunk) + t.shape[2:]), 1, 0)

    def step(state, xs):
        o, state = kda_chunk(state, *xs, lower_bound=lower_bound)
        return state, o

    s_end, os_ = lax.scan(step, s0.astype(jnp.float32),
                          tuple(chunks(t) for t in (q, k, v, g, beta)))
    o = jnp.moveaxis(os_, 0, 1).reshape((q.shape[0], -1) + q.shape[2:])
    return o[:, :s], s_end


def state_update_impl(state: Any, interpret: bool | None = None) -> bool:
    """Whether ``kda_packed``'s first pass over ``state [L, R, H, d, d]``
    (an array, or its shape and dtype) is ``ops/pallas/kda_state_update``:
    told from the backend, the dtype and the shape, and on a TPU from the
    kernel's probe (a Mosaic refusal is one warning and the compiler's own
    passes, not a dead server).  ``interpret``: ``kda_packed``'s."""
    if state.dtype != jnp.float32 or not ksu.takes(*state.shape[2:]):
        return False  # (a test keeps the state lower; see ``kda_packed``)
    return interpret is not None or (
        jax.default_backend() == "tpu"
        and support.kernel_or_warn("kda_state_update", "kda_state_update_xla") is None)


def kda_packed(
    state: jnp.ndarray,  # [L, R, H, d, d] float32: every layer's rows
    layer: jnp.ndarray,  # int32 scalar: the layer whose rows advance
    q: jnp.ndarray,      # [T, H, d]: tokens on ONE packed axis
    k: jnp.ndarray,
    v: jnp.ndarray,
    g: jnp.ndarray,      # [T, H, d] log-decay
    beta: jnp.ndarray,   # [T, H]
    *,
    tok_row: jnp.ndarray,   # [T] int32: the row each token belongs to
    start: jnp.ndarray,     # [R] int32: where a row's tokens start
    count: jnp.ndarray,     # [R] int32: how many it has in this tick (0: none)
    fresh: jnp.ndarray,     # [R] bool: the row's sequence starts in this tick
    chunk: int,
    lower_bound: float,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The serving tick (``ops/ssm.ssm_packed``'s contract): a row's tokens
    are consecutive on the packed axis (``start``, ``count``);
    ``state[layer, r]`` is where row ``r``'s sequence stands (nothing, for
    one that starts here: a slot's old state is never read by a new
    request).  Returns ``(o [T, H, d] float32, state)`` with
    ``state[layer]`` advanced IN PLACE.

    Every row advances by its first token.  Where ``state_update_impl``
    allows, the Pallas kernel does it: only the rows with a token are
    visited, each read once and written once.  Elsewhere its twin in plain
    ``jnp`` does, over all of the layer's rows.  ``interpret``: as the Pallas kernels
    take it — None lets the backend decide (the kernel compiled on a TPU,
    the twin elsewhere), True runs the kernel in the interpreter
    (tests), False compiles it.

    A row with more tokens (a prefill chunk) then advances by itself,
    ``chunk`` tokens a pass, on its own row of the state: a decode row never
    meets the chunk form, and a tick of decode rows alone never enters the
    loop."""
    t = q.shape[0]
    f32 = jnp.float32
    q, k, v, g, beta = (a.astype(f32) for a in (q, k, v, g, beta))
    first = jnp.clip(start, 0, t - 1)
    update = (functools.partial(ksu.kda_state_update, interpret=interpret)
              if state_update_impl(state, interpret)
              else ksu.kda_state_update_xla)
    o, state = update(
        state, layer, jnp.exp(g[first]), k[first], q[first], v[first],
        beta[first], count=count, fresh=fresh)
    o = o[tok_row]  # right for a row's first token; the rest follow
    if t == 1:
        return o, state
    c = min(chunk, t - 1)
    lanes = jnp.arange(c, dtype=jnp.int32)
    # the rows with further tokens, first; ``n_more`` of them
    order = jnp.argsort(count <= 1, stable=True).astype(jnp.int32)
    n_more = jnp.sum(count > 1, dtype=jnp.int32)
    zero = jnp.int32(0)

    def more(carry):
        state, o, i, offset = carry
        row = order[i]
        at = offset + lanes  # the row's tokens ``offset .. offset + c``
        live = at < count[row]
        idx = jnp.clip(start[row] + at, 0, t - 1)
        # the row by a slice: a gather of rows out of the whole state is
        # compiled (for a v5e) as a pass over ALL of it
        where = (layer, row, zero, zero, zero)
        s = lax.dynamic_slice(state, where, (1, 1) + state.shape[2:])[0]
        o_p, s = kda_chunk(
            s.astype(f32), q[idx][None], k[idx][None], v[idx][None],
            jnp.where(live[:, None, None], g[idx], 0.0)[None],
            jnp.where(live[:, None], beta[idx], 0.0)[None],
            lower_bound=lower_bound)
        state = lax.dynamic_update_slice(state, s[None].astype(state.dtype), where)
        o = o.at[jnp.where(live, idx, t)].set(o_p[0], mode="drop")
        done = offset + c >= count[row]
        return (state, o, jnp.where(done, i + 1, i),
                jnp.where(done, 1, offset + c))

    state, o, _, _ = lax.while_loop(
        lambda carry: carry[2] < n_more, more,
        (state, o, jnp.int32(0), jnp.int32(1)))
    return o, state
