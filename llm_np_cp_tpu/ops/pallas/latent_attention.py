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


def _latent_kernel(
    meta_ref, tables_ref, q_ref, pool_ref, o_ref, m_ref, l_ref, acc_ref,
    buf, sem, half_ref, *,
    scale: float, heads: int, rank: int, block_s: int, q_tile: int,
    pages: int, mb: int,
):
    """One (query tile, group of pages) step; see the module docstring
    and ``decode_attention._ragged_kernel``, whose fetch discipline this
    is: a live step starts the NEXT live step's copies into the other
    buffer half before it waits for its own."""
    ti = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    n_tiles = pl.num_programs(0)
    start, count = meta_ref[_RM_START, ti], meta_ref[_RM_COUNT, ti]
    pad, qpos0 = meta_ref[_RM_PAD, ti], meta_ref[_RM_QPOS0, ti]
    qlen = meta_ref[_RM_QLEN, ti]
    width = pages * block_s

    def group_copies(tile, step, half, wait: bool):
        live = jnp.minimum(meta_ref[_RM_COUNT, tile] - step * pages, pages)
        first = (meta_ref[_RM_ROW, tile] * mb + meta_ref[_RM_START, tile]
                 + step * pages)

        def slot(p, carry):
            copy = pltpu.make_async_copy(
                pool_ref.at[tables_ref[first + p]], buf.at[half, p],
                sem.at[half])
            copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, live, slot, 0)

    def fetch_group():
        @pl.when((j == 0) & (meta_ref[_RM_FIRST, ti] == ti))
        def _first_live_step():
            # a slot no copy fills must not hold NaN bits under the mask
            buf[...] = jnp.zeros_like(buf)
            half_ref[0] = 0
            group_copies(ti, j, 0, wait=False)

        half = half_ref[0]
        more = (j + 1) * pages < count
        next_tile = jnp.where(more, ti, meta_ref[_RM_NEXT, ti])

        @pl.when(next_tile < n_tiles)
        def _prefetch():
            group_copies(next_tile, jnp.where(more, j + 1, 0), 1 - half,
                         wait=False)

        group_copies(ti, j, half, wait=True)
        half_ref[0] = 1 - half
        return half

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    def attend(kb, tokens: int):
        """The online-softmax update of the tile's first ``tokens``
        tokens (``tokens * heads`` score rows, token-major) over the
        group ``kb [width, W]``."""
        rows = tokens * heads
        q_idx = jax.lax.broadcasted_iota(jnp.int32, (tokens, width), 0)
        kv_pos = (start + j * pages) * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (tokens, width), 1)
        q_slot = qpos0 + q_idx
        mask = (q_idx < qlen) & (kv_pos >= pad) & (kv_pos <= q_slot)
        mask = jnp.broadcast_to(
            mask[:, None, :], (tokens, heads, width)).reshape(rows, width)
        q = q_ref[:tokens].reshape(rows, q_ref.shape[-1])
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
        half = fetch_group()
        kb = buf[half].reshape((width,) + buf.shape[3:])

        @pl.when(qlen == 1)
        def _decode_row():
            attend(kb, 1)

        @pl.when(qlen != 1)
        def _chunk():
            attend(kb, q_tile)

    @pl.when(j == nj - 1)
    def _finalize():
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[...] = (acc_ref[:] / l).reshape(o_ref.shape).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("scale", "rank", "interpret"))
def ragged_latent_attention(
    q: jnp.ndarray,
    pool: jnp.ndarray,
    tables: jnp.ndarray,
    tile_row: jnp.ndarray,
    tile_qpos0: jnp.ndarray,
    tile_qlen: jnp.ndarray,
    pads: jnp.ndarray,
    *,
    scale: float,
    rank: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Mixed prefill + decode latent attention straight off a paged pool.

    q ``[T, H, W]``: the packed token axis in ``RAGGED_Q_TILE``-aligned
    row segments, every head's absorbed query, ``W`` the pool's row
    width (zeros past ``rank + rope``).  pool ``[NB, BS, W]``: one
    layer's pages, or the whole pool flat over (layer, block) with the
    layer's offset already in ``tables`` ``[R, MB]``.  ``tile_row`` /
    ``tile_qpos0`` / ``tile_qlen`` per tile and ``pads`` per row as
    ``ragged_paged_attention`` takes them.  Returns ``[T, H, rank]``:
    softmax over the visible rows times their first ``rank`` columns."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, h, w = q.shape
    qt = RAGGED_Q_TILE
    if t % qt or tile_row.shape != (t // qt,):
        raise ValueError(
            f"packed token axis ({t}) must be whole tiles of {qt} with one "
            f"metadata entry each, got {tile_row.shape}")
    if pool.ndim != 3 or pool.shape[-1] != w or rank > w:
        raise ValueError(
            f"a latent pool is [NB, BS, W] with W = q's width >= rank; got "
            f"pool {list(pool.shape)}, q width {w}, rank {rank}")
    nt = t // qt
    _, block_s, _ = pool.shape
    mb = tables.shape[1]
    pages = latent_pages_per_step(mb, block_s, w, pool.dtype)
    steps = -(-mb // pages)

    # which pages a tile streams at all: up to its last live token's
    row_pad = pads[tile_row]
    hi = tile_qpos0 + jnp.maximum(tile_qlen, 1) - 1
    start = jnp.clip(row_pad // block_s, 0, jnp.maximum(mb - 1, 0))
    nb = jnp.clip(hi // block_s + 1, 1, mb)
    count = jnp.where(tile_qlen > 0, jnp.maximum(nb - start, 0), 0)
    tiles = jnp.arange(nt, dtype=jnp.int32)
    later = jax.lax.cummin(jnp.where(count > 0, tiles, nt), reverse=True)
    meta = jnp.stack([
        start, count, row_pad, tile_qpos0, tile_qlen,
        jnp.zeros_like(tile_row), tile_row,
        jnp.append(later[1:], nt), jnp.broadcast_to(later[0], tile_row.shape),
    ]).astype(jnp.int32)  # [9, NT], the ragged kernel's rows

    def tile_map(ti, j, meta_ref, tables_ref):
        return (ti, 0, 0)

    rows = qt * h
    out = pl.pallas_call(
        functools.partial(
            _latent_kernel, scale=scale, heads=h, rank=rank,
            block_s=block_s, q_tile=qt, pages=pages, mb=mb),
        out_shape=jax.ShapeDtypeStruct((t, h, rank), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, steps),
            in_specs=[
                pl.BlockSpec((qt, h, w), tile_map, memory_space=pltpu.VMEM),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec(
                (qt, h, rank), tile_map, memory_space=pltpu.VMEM),
            scratch_shapes=[
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, rank), jnp.float32),
                pltpu.VMEM((2, pages, block_s, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        interpret=interpret,
    )(meta, tables.reshape(-1).astype(jnp.int32), q, pool)
    return out


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
    s = jnp.einsum("thw,tsw->ths", q, k_t[..., :wq],
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, None, :], s, NEG_INF)
    s = s - jnp.max(s, axis=-1, keepdims=True)
    p = jnp.where(mask[:, None, :], jnp.exp(s), 0.0)
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("ths,tsr->thr", p.astype(k_t.dtype), k_t[..., :rank],
                      preferred_element_type=jnp.float32).astype(q.dtype)
