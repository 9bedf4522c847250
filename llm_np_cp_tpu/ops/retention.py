"""The recurrence of a power-retention layer (degree 2).

Linear attention whose feature map is the symmetric square of the key, with
a scalar forget gate a kv head and token, normalised by the gated sum of
the keys' features.  For one kv head with ``k_t`` in R^d, ``v_t`` in
R^{d_v}, a gate ``g_t`` in (0, 1] (``log g_t <= 0``) and the ``G`` query
heads ``q_i,t`` that share it (GQA: the state is the kv head's):

    phi(u) . phi(w) = (u . w)^2       phi: the monomials u_a u_b, a <= b
    S_t = g_t S_t-1 + phi(k_t) v_t^T          S_0 = 0
    z_t = g_t z_t-1 + phi(k_t)                z_0 = 0
    o_i,t = phi(q_i,t)^T S_t / phi(q_i,t) . z_t

Equivalently, with NO feature map (what ``benchmark/reference_brumby.py``
computes): ``A[t, j] = (q_t . k_j)^2 exp(sum_{s=j+1..t} log g_s)`` for ``j
<= t`` and ``o_t = sum_j A[t, j] v_j / sum_j A[t, j]``.  The power is even,
so every weight is non-negative, and a constant scale on ``q . k`` cancels
between numerator and denominator: none is applied.  A denominator of
exactly 0 (a token that is not there; a query orthogonal to every key so
far) gives ``o = 0``, here and in the reference alike.

What is HELD (``ops/pallas/retention_state_update`` has the layout): ``s
[.., H, rows, d_v]`` with ``rows = phi_rows(d)`` (8,704 at ``d`` = 128: the
8,256 distinct monomials in whole registers) and ``z [.., H, d, d]``, the
gated sum of ``k k^T`` — ``phi(q) . z_t`` is ``q^T Z_t q`` — both float32.

``retention_recurrent`` is the recurrence one token at a time: the
statement the others are tested against, and what a decode row is.
``retention_chunk`` advances ROWS of independent sequences by ``c`` tokens
at once, ``retention_scan`` carries it over whole sequences
(``models.forward``), and ``retention_packed`` runs the serving tick's
packed token axis under ``ops/ssm.ssm_packed``'s contract: every row
advances by its first token (on a TPU in one Pallas kernel over the rows
the tick touches), and the rest of a prefill segment goes on by chunks on
its own row.

The chunk form.  With ``G_t`` the log-gate summed from the chunk's start up
to and including token ``t`` (every exponent below is ``<= 0``: no
re-centring as ``kda_chunk`` needs):

    num_t = e^{G_t} phi(q_t)^T S_0 + sum_{j<=t} e^{G_t - G_j} (q_t . k_j)^2 v_j
    den_t = e^{G_t} q_t^T Z_0 q_t  + sum_{j<=t} e^{G_t - G_j} (q_t . k_j)^2
    S_c   = e^{G_c} S_0 + sum_j e^{G_c - G_j} phi(k_j) v_j^T       Z_c likewise

``phi`` of a chunk's queries is the one large temporary: ``c x G`` vectors
of ``rows`` a kv head (89 MB for 64 tokens of 40 heads of 128), which is
why the program's chunk is ``CHUNK`` and no wider.

A token that is not there (padding, a row that is not in the tick) has
``log g = 0`` and ``k = 0``: the state passes through and its ``o`` is
dropped by the caller.  All arithmetic is float32 at the highest matmul
precision, whatever the model is served in.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from llm_np_cp_tpu.ops.pallas import retention_state_update as rsu
from llm_np_cp_tpu.ops.pallas import support

_HIGHEST = lax.Precision.HIGHEST
# tokens a step of the program's chunk form advances (``models.forward``,
# a prefill row of the tick): not part of the mathematics
CHUNK = 64

phi = rsu.phi
phi_rows = rsu.phi_rows


def _grouped(q: jnp.ndarray, kv_heads: int) -> jnp.ndarray:
    """``[.., H, d] -> [.., Hk, G, d]``: query head ``i`` reads kv head
    ``i // G``."""
    return q.reshape(q.shape[:-2] + (kv_heads, -1, q.shape[-1]))


def retention_step(s, z, q, k, v, log_g):
    """One token of every row: ``s [.., Hk, rows, d_v]``, ``z [.., Hk, d,
    d]``, ``q [.., H, d]``, ``k [.., Hk, d]``, ``v [.., Hk, d_v]``, ``log_g
    [.., Hk]`` -> ``(o [.., H, d_v], s, z)``.  The equations, as written."""
    o, s, z = rsu.step(s, z, jnp.exp(log_g), k, _grouped(q, k.shape[-2]), v)
    return o.reshape(q.shape[:-1] + (v.shape[-1],)), s, z


def retention_recurrent(
    s0: jnp.ndarray,     # [R, Hk, rows, d_v] float32
    z0: jnp.ndarray,     # [R, Hk, d, d] float32
    q: jnp.ndarray,      # [R, S, H, d]
    k: jnp.ndarray,      # [R, S, Hk, d]
    v: jnp.ndarray,      # [R, S, Hk, d_v]
    log_g: jnp.ndarray,  # [R, S, Hk] log-gate, <= 0
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Token by token: ``(o [R, S, H, d_v] float32, s, z after)``."""
    f32 = jnp.float32

    def step(carry, xs):
        o, s, z = retention_step(*carry, *xs)
        return (s, z), o

    (s, z), o = lax.scan(step, (s0.astype(f32), z0.astype(f32)), tuple(
        jnp.moveaxis(t.astype(f32), 1, 0) for t in (q, k, v, log_g)))
    return jnp.moveaxis(o, 0, 1), s, z


def retention_chunk(
    s0: jnp.ndarray,     # [R, Hk, rows, d_v] float32: where the chunk starts
    z0: jnp.ndarray,     # [R, Hk, d, d] float32
    q: jnp.ndarray,      # [R, c, H, d]
    k: jnp.ndarray,      # [R, c, Hk, d], 0 where there is no token
    v: jnp.ndarray,      # [R, c, Hk, d_v]
    log_g: jnp.ndarray,  # [R, c, Hk] log-gate <= 0, 0 where there is no token
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``c`` tokens of every row: ``(o [R, c, H, d_v] float32, s, z after)``."""
    r, c, nh, _ = q.shape
    hk, d_v = k.shape[2], v.shape[-1]
    f32 = jnp.float32
    # heads in front of the tokens: q [R, Hk, G, c, d]; k, v [R, Hk, c, ..]
    q = jnp.moveaxis(_grouped(q.astype(f32), hk), 1, 3)
    k, v = (jnp.swapaxes(t.astype(f32), 1, 2) for t in (k, v))
    cs = jnp.cumsum(jnp.swapaxes(log_g.astype(f32), 1, 2), axis=-1)  # G_t
    causal = jnp.tril(jnp.ones((c, c), jnp.bool_))
    # e^{G_t - G_j}, j <= t (the masked triangle's exponents are positive)
    decay = jnp.where(causal, jnp.exp(jnp.minimum(
        cs[..., :, None] - cs[..., None, :], 0.0)), 0.0)  # [R, Hk, t, j]
    qk = jnp.einsum("rhgtd,rhjd->rhgtj", q, k, precision=_HIGHEST)
    a = jnp.square(qk) * decay[:, :, None]
    from_start = jnp.exp(cs)[:, :, None, :, None]  # e^{G_t}
    num = (from_start * jnp.einsum("rhgtp,rhpv->rhgtv", phi(q), s0,
                                   precision=_HIGHEST)
           + jnp.einsum("rhgtj,rhjv->rhgtv", a, v, precision=_HIGHEST))
    den = (from_start[..., 0] * jnp.einsum(
        "rhgta,rhab,rhgtb->rhgt", q, z0, q, precision=_HIGHEST)
        + jnp.sum(a, axis=-1))
    o = num / jnp.where(den > 0.0, den, 1.0)[..., None]
    # the state after: decayed over the whole chunk, plus every token's
    # term decayed from its place to the chunk's end
    to_end = jnp.exp(cs[..., -1:] - cs)[..., None]  # [R, Hk, c, 1]
    whole = jnp.exp(cs[..., -1])[..., None, None]
    s = whole * s0 + jnp.einsum("rhjp,rhjv->rhpv", phi(k) * to_end, v,
                                precision=_HIGHEST)
    z = whole * z0 + jnp.einsum("rhja,rhjb->rhab", k * to_end, k,
                                precision=_HIGHEST)
    return jnp.moveaxis(o, 3, 1).reshape(r, c, nh, d_v), s, z


def retention_scan(
    s0: jnp.ndarray,     # [R, Hk, rows, d_v] float32
    z0: jnp.ndarray,     # [R, Hk, d, d] float32
    q: jnp.ndarray,      # [R, S, H, d]
    k: jnp.ndarray,      # [R, S, Hk, d]
    v: jnp.ndarray,
    log_g: jnp.ndarray,  # [R, S, Hk]
    *,
    chunk: int,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Whole sequences, ``chunk`` tokens at a time: ``(o [R, S, H, d_v]
    float32, s, z after the last token)``.  A length that ``chunk`` does
    not divide is padded with tokens that are not there."""
    n = q.shape[1]
    if n <= chunk:
        return retention_chunk(s0, z0, q, k, v, log_g)
    pad = -n % chunk

    def chunks(t: jnp.ndarray) -> jnp.ndarray:  # [R, S, ..] -> [S/chunk, R, chunk, ..]
        t = jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        return jnp.moveaxis(
            t.reshape((t.shape[0], -1, chunk) + t.shape[2:]), 1, 0)

    def step(carry, xs):
        o, s, z = retention_chunk(*carry, *xs)
        return (s, z), o

    f32 = jnp.float32
    (s, z), os_ = lax.scan(step, (s0.astype(f32), z0.astype(f32)),
                           tuple(chunks(t) for t in (q, k, v, log_g)))
    o = jnp.moveaxis(os_, 0, 1).reshape((q.shape[0], -1) + os_.shape[3:])
    return o[:, :n], s, z


def state_update_impl(s: Any, interpret: bool | None = None) -> bool:
    """Whether ``retention_packed``'s first pass over ``s [L, R, Hk, rows,
    d_v]`` (an array, or its shape and dtype) is
    ``ops/pallas/retention_state_update``: told from the backend, the dtype
    and the shape, and on a TPU from the kernel's probe (a Mosaic refusal
    is one warning and the compiler's own passes, not a dead server).
    ``interpret``: ``retention_packed``'s."""
    if s.dtype != jnp.float32:
        return False  # (a test keeps the state lower; see ``retention_packed``)
    if interpret:
        return True  # (the interpreter has no lanes to fill)
    if not rsu.takes(*s.shape[2:]):
        return False
    return interpret is not None or (
        jax.default_backend() == "tpu"
        and support.kernel_or_warn(
            "retention_state_update", "retention_state_update_xla") is None)


def retention_packed(
    s: jnp.ndarray,      # [L, R, Hk, rows, d_v] float32: every layer's rows
    z: jnp.ndarray,      # [L, R, Hk, d, d] float32
    layer: jnp.ndarray,  # int32 scalar: the layer whose rows advance
    q: jnp.ndarray,      # [T, H, d]: tokens on ONE packed axis
    k: jnp.ndarray,      # [T, Hk, d], 0 where there is no token
    v: jnp.ndarray,      # [T, Hk, d_v]
    log_g: jnp.ndarray,  # [T, Hk] log-gate, 0 where there is no token
    *,
    tok_row: jnp.ndarray,   # [T] int32: the row each token belongs to
    start: jnp.ndarray,     # [R] int32: where a row's tokens start
    count: jnp.ndarray,     # [R] int32: how many it has in this tick (0: none)
    fresh: jnp.ndarray,     # [R] bool: the row's sequence starts in this tick
    chunk: int,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The serving tick (``ops/ssm.ssm_packed``'s contract): a row's tokens
    are consecutive on the packed axis (``start``, ``count``); ``s[layer,
    r]`` and ``z[layer, r]`` are where row ``r``'s sequence stands
    (nothing, for one that starts here: a slot's old state is never read
    by a new request).  Returns ``(o [T, H, d_v] float32, s, z)`` with the
    layer's rows advanced IN PLACE.

    Every row advances by its first token.  Where ``state_update_impl``
    allows, the Pallas kernel does it: only the rows with a token are
    visited, each kv head read once and written once.  Elsewhere its twin
    in plain ``jnp`` does, over all of the layer's rows.  ``interpret``:
    as the Pallas kernels take it — None lets the backend decide (the
    kernel compiled on a TPU, the twin elsewhere), True runs the kernel in
    the interpreter (tests), False compiles it.

    A row with more tokens (a prefill segment) then advances by itself,
    ``chunk`` tokens a pass, on its own row of the state: a decode row
    never meets the chunk form, and a tick of decode rows alone never
    enters the loop."""
    t, nh = q.shape[:2]
    hk, d_v = k.shape[1], v.shape[-1]
    f32 = jnp.float32
    q, k, v, log_g = (a.astype(f32) for a in (q, k, v, log_g))
    first = jnp.clip(start, 0, t - 1)
    update = (functools.partial(rsu.retention_state_update,
                                interpret=interpret)
              if state_update_impl(s, interpret)
              else rsu.retention_state_update_xla)
    o, s, z = update(
        s, z, layer, jnp.exp(log_g[first]), k[first],
        _grouped(q[first], hk), v[first], count=count, fresh=fresh)
    o = o.reshape(o.shape[0], nh, d_v)[tok_row]  # a row's first token's
    if t == 1:
        return o, s, z
    c = min(chunk, t - 1)
    lanes = jnp.arange(c, dtype=jnp.int32)
    # the rows with further tokens, first; ``n_more`` of them
    order = jnp.argsort(count <= 1, stable=True).astype(jnp.int32)
    n_more = jnp.sum(count > 1, dtype=jnp.int32)
    zero = jnp.int32(0)

    def more(carry):
        s, z, o, i, offset = carry
        row = order[i]
        at = offset + lanes  # the row's tokens ``offset .. offset + c``
        live = at < count[row]
        idx = jnp.clip(start[row] + at, 0, t - 1)
        # the row by a slice: a gather of rows out of the whole state is
        # compiled (for a v5e) as a pass over ALL of it
        where = (layer, row, zero, zero, zero)
        s_r = lax.dynamic_slice(s, where, (1, 1) + s.shape[2:])[0]
        z_r = lax.dynamic_slice(z, where, (1, 1) + z.shape[2:])[0]
        o_p, s_r, z_r = retention_chunk(
            s_r.astype(f32), z_r.astype(f32), q[idx][None],
            jnp.where(live[:, None, None], k[idx], 0.0)[None], v[idx][None],
            jnp.where(live[:, None], log_g[idx], 0.0)[None])
        s = lax.dynamic_update_slice(s, s_r[None].astype(s.dtype), where)
        z = lax.dynamic_update_slice(z, z_r[None].astype(z.dtype), where)
        o = o.at[jnp.where(live, idx, t)].set(o_p[0], mode="drop")
        done = offset + c >= count[row]
        return (s, z, o, jnp.where(done, i + 1, i),
                jnp.where(done, 1, offset + c))

    s, z, o, _, _ = lax.while_loop(
        lambda carry: carry[3] < n_more, more,
        (s, z, o, jnp.int32(0), jnp.int32(1)))
    return o, s, z
