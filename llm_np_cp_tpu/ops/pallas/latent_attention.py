"""Ragged attention over a paged LATENT cache (MLA in its absorbed form).

A token of a latent-attention layer leaves ONE row in the cache, ``[c' |
k_pe]`` (``rank + rope`` values; Kanana-2 / DeepSeek-V3: 512 + 64), and
every query head attends that same row: multi-query attention of ``H``
heads whose keys are the rows and whose values are the rows' first
``rank`` columns.  The caller folds ``W_UK`` into the query (``q_lat =
[q_nope W_UK | q_pe]``) and ``W_UV`` onto the result
(models/transformer.latent_attention_block), so the kernel multiplies by
no weight.

``ragged_latent_attention`` is the ragged paged kernel's sibling
(``decode_attention.ragged_paged_attention``: the same grid of query
tiles by groups of pages, the same tile scalars, its own double-buffered
page DMAs, float32 ``m`` / ``l`` / ``acc`` with the AMLA rescale) for a
page with no head axis: a page ``[BS, W]`` is fetched ONCE a step and
used as K (all ``W`` columns) and as V (its first ``rank``); a query tile
of 8 tokens is ``8 x H`` score rows.  A tile that holds one live token (a
decode row: seven dead lanes) multiplies that token's ``H`` rows alone -
at 32 heads the dead lanes would otherwise make a decode tick's
attention compute-bound on a v5e (69.6 kFLOP a (token, position) against
1,152 bytes).

The query side is sized by a tile's LIVE tokens (PR 48).  ``q`` and the
result are the step's DENSE token axis where it lies in HBM (one lane a
token, no alignment: serve/engine.mixed_operand_layout); the tiles are
metadata alone, and the kernel is told each tile's first dense token.
It copies a tile's ``tile_qlen`` tokens in itself (a token a copy, the
next live tile's started a tile ahead, as the pages are a step ahead),
clears and finalises ``tile_qlen == 1``'s 32 score rows or a chunk
tile's 256, and sends exactly the live tokens' rows out - a partial last
tile of a chunk must not write past its segment, whose next dense lanes
are another row's.  A dead tile and a dead step do nothing.  A dense
lane no tile owns comes back as zeros the kernel stores (the next layer
writes such a lane's row into scratch block 0, and ``0 x NaN`` is NaN).
Spread over 1,024 tile lanes and gathered back by the caller, the same
281 tokens cost 75 us a call of 636 on a v5e (PERF.md section 6, PR 48).

The pool stores a row padded with zeros to whole rows of 128 lanes
(serve/block_pool.latent_page_width: 576 -> 640): a ``[.., BS, 576]``
array is not kept in the order of its shape on a TPU.  ``q`` comes
padded the same way; zeros add nothing to a score.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_np_cp_tpu.ops.pallas.decode_attention import (
    _RAGGED_STEP_POSITIONS,
    _RM_COUNT,
    _RM_FIRST,
    _RM_NEXT,
    _RM_PAD,
    _RM_QLEN,
    _RM_QPOS0,
    _RM_ROW,
    _RM_START,
    _VMEM_BUDGET_BYTES,
    NEG_INF,
    RAGGED_Q_TILE,
    _amla_max,
    _amla_rescale,
    _amla_steps,
    _vmem_bytes,
)


def latent_pages_per_step(mb: int, block_s: int, width: int, dtype) -> int:
    """Pages of a tile's row one grid step streams and attends: as many
    as cover ``_RAGGED_STEP_POSITIONS`` kv positions, no more than the
    block table is wide, both buffer halves inside the VMEM budget."""
    slot = 2 * _vmem_bytes((block_s, width), dtype)
    return max(1, min(_RAGGED_STEP_POSITIONS // block_s, mb,
                      _VMEM_BUDGET_BYTES // slot))


def _result_width(rank: int) -> int:
    """The width the kernel writes a token's result rows in: whole rows
    of 128 lanes, because a copy cuts nothing narrower out of an array
    (a tiny preset's ``rank`` 32; the published 512 is its own width)."""
    return -(-rank // 128) * 128


def latent_vmem_scratch(heads: int, width: int, rank: int, pages: int,
                        block_s: int, dtype, page_dtype) -> list[tuple]:
    """The kernel's VMEM buffers, ``(shape, dtype)`` in the order it
    takes them: float32 ``m`` / ``l`` / ``acc`` of a whole tile's score
    rows, two halves of a group of pages, two halves of a tile's queries
    and of its results, one token's rows of zeros."""
    rows = RAGGED_Q_TILE * heads
    out = _result_width(rank)
    return [
        ((rows, 1), jnp.float32), ((rows, 1), jnp.float32),
        ((rows, rank), jnp.float32),
        ((2, pages, block_s, width), page_dtype),
        ((2, RAGGED_Q_TILE, heads, width), dtype),
        ((2, RAGGED_Q_TILE, heads, out), dtype),
        ((heads, out), dtype),
    ]


# this kernel's own meta row, after the ragged kernel's nine: the DENSE
# token a tile's first lane holds
_RM_TOK = 9

# the kernel's scalars between grid steps (one SMEM vector): which half
# of the page buffer / the query buffer / the result buffer is current,
# the result tokens each half still has on their way out, and the zero
# rows stored for the lanes no tile owns
# (``_ST_PEND`` is two scalars, one a half)
_ST_HALF, _ST_QHALF, _ST_OHALF, _ST_PEND, _ST_ZEROS, _ST_SIZE = 0, 1, 2, 3, 5, 6


def _latent_kernel(
    meta_ref, tables_ref, owned_ref, q_ref, pool_ref, o_ref,
    m_ref, l_ref, acc_ref, buf, q_buf, o_buf, zero_buf,
    sem, q_sem, o_sem, zero_sem, state, *,
    scale: float, heads: int, rank: int, block_s: int, q_tile: int,
    pages: int, mb: int, sel_ref=None,
):
    """One (query tile, group of pages) step; see the module docstring
    and ``decode_attention._ragged_kernel``, whose fetch discipline this
    is: a live step starts the NEXT live step's copies into the other
    buffer half before it waits for its own.  ``q_ref`` / ``o_ref`` are
    the dense arrays where they lie: a tile's first step waits for its
    own live tokens' queries (started a tile ahead), its last live step
    divides, casts and sends those tokens' rows out, and a tile or a
    step with nothing to attend does nothing at all.  ``sel_ref``: this
    step's block ``[q_tile, positions]`` of a per-token selection (1.0: the
    token attends the position), the pipeline's own copy."""
    ti = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    n_tiles = pl.num_programs(0)
    start, count = meta_ref[_RM_START, ti], meta_ref[_RM_COUNT, ti]
    pad, qpos0 = meta_ref[_RM_PAD, ti], meta_ref[_RM_QPOS0, ti]
    qlen = meta_ref[_RM_QLEN, ti]
    width = pages * block_s

    def copies(n, copy_of, wait: bool):
        """Start, or wait for, the ``n`` copies ``copy_of(0 .. n - 1)``."""
        def one(i, carry):
            copy = copy_of(i)
            copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, n, one, 0)

    def group_copies(tile, step, half, wait: bool):
        live = jnp.minimum(meta_ref[_RM_COUNT, tile] - step * pages, pages)
        first = (meta_ref[_RM_ROW, tile] * mb + meta_ref[_RM_START, tile]
                 + step * pages)
        copies(live, lambda p: pltpu.make_async_copy(
            pool_ref.at[tables_ref[first + p]], buf.at[half, p],
            sem.at[half]), wait)

    def query_copies(tile, half, wait: bool):
        """A tile's live tokens, a copy each: dense ``q`` -> ``q_buf``."""
        tok = meta_ref[_RM_TOK, tile]
        copies(meta_ref[_RM_QLEN, tile], lambda i: pltpu.make_async_copy(
            q_ref.at[tok + i], q_buf.at[half, i], q_sem.at[half]), wait)

    def result_copies(half, tok, n, wait: bool):
        """``n`` tokens of ``o_buf[half]`` -> dense lanes ``tok ..``: a
        partial tile sends its live tokens alone (the next lanes of the
        dense axis are another row's)."""
        copies(n, lambda i: pltpu.make_async_copy(
            o_buf.at[half, i], o_ref.at[tok + i], o_sem.at[half]), wait)

    def zero_copy(lane):
        return pltpu.make_async_copy(zero_buf, o_ref.at[lane], zero_sem.at[0])

    @pl.when((ti == 0) & (j == 0))
    def _prologue():
        for i in range(_ST_SIZE):
            state[i] = 0
        # a slot no copy fills must not hold NaN bits under the mask
        buf[...] = jnp.zeros_like(buf)
        # every lane of the result is defined: one no tile owns is zeros
        # (the next layer writes its row into scratch block 0)
        zero_buf[...] = jnp.zeros_like(zero_buf)

        def lane(d, n):
            dead = owned_ref[d] == 0

            @pl.when(dead)
            def _store():
                zero_copy(d).start()

            return n + dead.astype(jnp.int32)

        state[_ST_ZEROS] = jax.lax.fori_loop(0, o_ref.shape[0], lane, 0)

    def fetch_group():
        @pl.when((j == 0) & (meta_ref[_RM_FIRST, ti] == ti))
        def _first_live_step():
            group_copies(ti, j, 0, wait=False)
            query_copies(ti, 0, wait=False)

        half, qhalf = state[_ST_HALF], state[_ST_QHALF]
        more = (j + 1) * pages < count
        next_tile = jnp.where(more, ti, meta_ref[_RM_NEXT, ti])

        @pl.when(next_tile < n_tiles)
        def _prefetch():
            group_copies(next_tile, jnp.where(more, j + 1, 0), 1 - half,
                         wait=False)

        @pl.when(j == 0)
        def _queries():
            @pl.when(meta_ref[_RM_NEXT, ti] < n_tiles)
            def _next_tile():
                query_copies(meta_ref[_RM_NEXT, ti], 1 - qhalf, wait=False)

            query_copies(ti, qhalf, wait=True)

        group_copies(ti, j, half, wait=True)
        state[_ST_HALF] = 1 - half
        return half, qhalf, more

    def attend(kb, q, tokens: int):
        """The online-softmax update of the tile's first ``tokens``
        tokens (``q [tokens * heads, W]``, token-major) over the group
        ``kb [width, W]``."""
        rows = tokens * heads
        q_idx = jax.lax.broadcasted_iota(jnp.int32, (tokens, width), 0)
        kv_pos = (start + j * pages) * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (tokens, width), 1)
        q_slot = qpos0 + q_idx
        mask = (q_idx < qlen) & (kv_pos >= pad) & (kv_pos <= q_slot)
        if sel_ref is not None:
            mask = mask & (sel_ref[:tokens] > 0.5)
        mask = jnp.broadcast_to(
            mask[:, None, :], (tokens, heads, width)).reshape(rows, width)
        s = jax.lax.dot_general(
            q, kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_ref[:rows]
        m_new = _amla_max(m_prev, s)
        # a fully masked row (a dead lane) has m == NEG_INF: p would be 1
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        k_steps = _amla_steps(m_prev, m_new)
        l_ref[:rows] = (_amla_rescale(l_ref[:rows], k_steps)
                        + jnp.sum(p, axis=-1, keepdims=True))
        pv = jax.lax.dot_general(
            p.astype(kb.dtype), kb[:, :rank], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[:rows] = _amla_rescale(acc_ref[:rows], k_steps) + pv
        m_ref[:rows] = m_new

    @pl.when(j * pages < count)
    def _update():
        half, qhalf, more = fetch_group()
        kb = buf[half].reshape((width,) + buf.shape[3:])

        def tile_step(tokens: int):
            """The step of a tile that holds ``tokens`` lanes: it clears,
            attends and finalises ``tokens * heads`` score rows."""
            rows = tokens * heads

            @pl.when(j == 0)
            def _init():
                m_ref[:rows] = jnp.full((rows, 1), NEG_INF, m_ref.dtype)
                l_ref[:rows] = jnp.zeros((rows, 1), l_ref.dtype)
                acc_ref[:rows] = jnp.zeros((rows, rank), acc_ref.dtype)

            attend(kb, q_buf[qhalf, :tokens].reshape(rows, q_buf.shape[-1]),
                   tokens)

            @pl.when(jnp.logical_not(more))
            def _finalize():
                ohalf = state[_ST_OHALF]
                # the tile before last sent its rows from this half
                result_copies(ohalf, 0, state[_ST_PEND + ohalf], wait=True)
                l = jnp.where(l_ref[:rows] == 0.0, 1.0, l_ref[:rows])
                o_buf[ohalf, :tokens, :, :rank] = (acc_ref[:rows] / l).reshape(
                    tokens, heads, rank).astype(o_buf.dtype)
                result_copies(ohalf, meta_ref[_RM_TOK, ti], qlen, wait=False)
                state[_ST_PEND + ohalf] = qlen
                state[_ST_OHALF] = 1 - ohalf
                state[_ST_QHALF] = 1 - qhalf

        @pl.when(qlen == 1)
        def _decode_row():
            tile_step(1)

        @pl.when(qlen != 1)
        def _chunk():
            tile_step(q_tile)

    @pl.when((ti == n_tiles - 1) & (j == nj - 1))
    def _epilogue():
        for half in range(2):
            result_copies(half, 0, state[_ST_PEND + half], wait=True)

        copies(state[_ST_ZEROS], lambda i: zero_copy(0), wait=True)


def _latent_kernel_selected(meta_ref, tables_ref, owned_ref, q_ref, pool_ref,
                            sel_ref, o_ref, *scratch, **kw):
    """``_latent_kernel`` under a per-token selection (its ``sel_ref``)."""
    _latent_kernel(meta_ref, tables_ref, owned_ref, q_ref, pool_ref, o_ref,
                   *scratch, sel_ref=sel_ref, **kw)


def tile_meta(tables, tile_row, tile_qpos0, tile_qlen, tile_tok, pads,
              block_s: int) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The kernel's scalars a query tile, ``[10, NT]`` int32 (the ragged
    kernel's nine rows and ``_RM_TOK``): which pages a tile streams at all
    (from its row's pad up to its last live token's), and the next live
    tile after it; and, beside them, the tiles' page counts."""
    nt, = tile_row.shape
    mb = tables.shape[1]
    row_pad = pads[tile_row]
    hi = tile_qpos0 + jnp.maximum(tile_qlen, 1) - 1
    start = jnp.clip(row_pad // block_s, 0, jnp.maximum(mb - 1, 0))
    nb = jnp.clip(hi // block_s + 1, 1, mb)
    count = jnp.where(tile_qlen > 0, jnp.maximum(nb - start, 0), 0)
    tiles = jnp.arange(nt, dtype=jnp.int32)
    later = jax.lax.cummin(jnp.where(count > 0, tiles, nt), reverse=True)
    return jnp.stack([
        start, count, row_pad, tile_qpos0, tile_qlen,
        jnp.zeros_like(tile_row), tile_row,
        jnp.append(later[1:], nt), jnp.broadcast_to(later[0], tile_row.shape),
        tile_tok,
    ]).astype(jnp.int32), count


@functools.partial(
    jax.jit, static_argnames=("scale", "rank", "interpret"))
def ragged_latent_attention(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    tables: jnp.ndarray,
    tile_row: jnp.ndarray,
    tile_qpos0: jnp.ndarray,
    tile_qlen: jnp.ndarray,
    tile_tok: jnp.ndarray,
    pads: jnp.ndarray,
    *,
    scale: float,
    rank: int,
    interpret: bool | None = None,
    select: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Mixed prefill + decode latent attention straight off a paged pool.

    q ``[D, H, W]``: the step's DENSE token axis (one lane a token, a
    row's tokens consecutive, no alignment), every head's absorbed query,
    ``W`` the pool's row width (zeros past ``rank + rope``).  pool ``[NB,
    BS, W]``: one layer's pages, or the whole pool flat over (layer,
    block) with the layer's offset already in ``tables`` ``[R, MB]``.
    ``tile_row`` / ``tile_qpos0`` / ``tile_qlen`` per query tile and
    ``pads`` per row as ``ragged_paged_attention`` takes them, and
    ``tile_tok``, the dense lane of each tile's first token: a tile is
    the ``tile_qlen <= RAGGED_Q_TILE`` tokens from there on, and the
    kernel moves those alone.  ``select`` (a sparse-attention indexer's:
    ops/pallas/sparse_index.py) ``[NT, 8, steps * positions a step]``
    float32, 1.0 where a tile's token attends a position: the softmax
    then runs over those alone.  Returns ``[D, H, rank]``: softmax over
    the visible rows times their first ``rank`` columns for every token
    a tile holds, zeros for a lane no tile owns."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d, h, w = q.shape
    qt = RAGGED_Q_TILE
    nt, = tile_row.shape
    if not (tile_qpos0.shape == tile_qlen.shape == tile_tok.shape == (nt,)):
        raise ValueError(
            f"one metadata entry a query tile ({nt} of them), got qpos0 "
            f"{tile_qpos0.shape}, qlen {tile_qlen.shape}, tok "
            f"{tile_tok.shape}")
    if pool.ndim != 3 or pool.shape[-1] != w or rank > w:
        raise ValueError(
            f"a latent pool is [NB, BS, W] with W = q's width >= rank; got "
            f"pool {list(pool.shape)}, q width {w}, rank {rank}")
    _, block_s, _ = pool.shape
    mb = tables.shape[1]
    pages = latent_pages_per_step(mb, block_s, w, pool.dtype)
    steps = -(-mb // pages)

    meta, count = tile_meta(tables, tile_row, tile_qpos0, tile_qlen, tile_tok,
                            pads, block_s)
    # the dense lanes some tile attends (and so writes)
    lane = jnp.arange(d, dtype=jnp.int32)[:, None]
    last = tile_tok + jnp.where(count > 0, tile_qlen, 0)
    owned = jnp.any((lane >= tile_tok[None]) & (lane < last[None]), axis=1)

    selected = () if select is None else (pl.BlockSpec(
        (None, qt, pages * block_s), lambda ti, j, *_: (ti, 0, j)),)
    out = pl.pallas_call(
        functools.partial(
            _latent_kernel if select is None else _latent_kernel_selected,
            scale=scale, heads=h, rank=rank,
            block_s=block_s, q_tile=qt, pages=pages, mb=mb),
        out_shape=jax.ShapeDtypeStruct((d, h, _result_width(rank)), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(nt, steps),
            in_specs=[
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
                *selected,
            ],
            out_specs=pl.BlockSpec(memory_space=pl.ANY),
            scratch_shapes=[
                *(pltpu.VMEM(shape, dtype) for shape, dtype in
                  latent_vmem_scratch(h, w, rank, pages, block_s, q.dtype,
                                      pool.dtype)),
                pltpu.SemaphoreType.DMA((2,)),  # pages, one a half
                pltpu.SemaphoreType.DMA((2,)),  # queries in
                pltpu.SemaphoreType.DMA((2,)),  # results out
                pltpu.SemaphoreType.DMA((1,)),  # the zero rows
                pltpu.SMEM((_ST_SIZE,), jnp.int32),
            ],
        ),
        interpret=interpret,
    )(meta, tables.reshape(-1).astype(jnp.int32), owned.astype(jnp.int32),
      q, pool, *(() if select is None else (select,)))
    return out[..., :rank]


def ragged_latent_attention_xla(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    tables: jnp.ndarray,
    tok_row: jnp.ndarray,
    tok_slot: jnp.ndarray,
    tok_live: jnp.ndarray,
    pads: jnp.ndarray,
    *,
    scale: float,
    rank: int,
    select: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """XLA twin of ``ragged_latent_attention`` with per-TOKEN metadata
    (``ragged_paged_attention_xla``'s): gathers each row's pages into a
    contiguous view and attends every packed token over its own row's.
    q ``[T, H, W']`` with ``W' <= W`` (no padding needed).  Materializes
    ``[T, S_max, W]``: the fallback and the parity oracle."""
    wq = q.shape[-1]
    block_s = pool.shape[1]
    s_max = tables.shape[1] * block_s
    rows = pool[tables].reshape(tables.shape[0], s_max, pool.shape[-1])
    k_t = rows[tok_row]  # [T, S_max, W]
    kv_idx = jnp.arange(s_max, dtype=jnp.int32)[None, :]
    mask = ((kv_idx >= pads[tok_row][:, None])
            & (kv_idx <= tok_slot[:, None]) & tok_live[:, None])
    if select is not None:  # bool [T, S_max]: a token's selection
        mask = mask & select
    s = jnp.einsum("thw,tsw->ths", q, k_t[..., :wq],
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[:, None, :], jnp.exp(s), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("ths,tsr->thr", p.astype(k_t.dtype), k_t[..., :rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)
