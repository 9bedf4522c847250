"""A sparse-attention indexer over a paged pool: score, select, attend.

``ops/sparse_index.py`` has the equations and the tie rule.  On the served
path a token's index key lies in a pool of its own beside the latent rows
(``[NB, BS, dim_I]``, the rows' block ids and tables), and a layer runs three
kernels in the tick's one program:

1. ``ragged_index_scores``: ``I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])``
   of every query tile over its row's index-key pages — the ragged latent
   kernel's grid (query tiles by groups of pages, the group's copies started a
   step ahead), a tile's ``8 x heads_I`` query rows against a group's keys on
   the MXU, ReLU, the head weights and the sum over heads on the VPU in
   float32.  A decode row reads its context's keys once (256 B a position, 32
   FLOP a byte: the pages' bytes bound it); a prompt chunk's tile shares them.
2. ``select_topk_tiles``: the EXACT top ``k`` of a tile's rows of scores as a
   mask, a tile's ``[8, S]`` sheet resident in VMEM: the ``k``-th largest score
   bit by bit (32 counts over the row), then the positions AT it from the
   lowest on, by the same bisection over the position (ties to the lower
   position).  No sort, no approximation.
3. ``latent_attention.ragged_latent_attention(select=)``: the page walk of
   the latent kernel under that per-token mask.

The three share ONE layout: a tile's scores and its mask are ``[8, S]`` with
``S`` the grid's steps times a step's positions, column ``c`` of step ``j``
the position ``(start + j * pages) * BS + c`` the latent kernel attends
there (``start``: the block of the row's pad) — the scores are written and
the mask is read a ``[8, positions a step]`` block a grid step, and nothing is
gathered or transposed between the kernels.  A lane past a tile's live tokens,
a position past a token's own and a step past the tile's pages hold whatever
was there: the selection masks by what a token may SEE before it looks at a
score.

One form for both kinds of tile (the page walk under a mask), measured on the
chip (PERF.md section 6, PR 58; one layer, 24 decode rows at contexts of
6.6-8.6k): the masked walk 599 us (unmasked 577) against attending 2,048
gathered rows 1,258 us through XLA's gather, plus 211 to compact the mask into
positions — the gather reads a quarter of the bytes and takes twice the time;
a prompt chunk's gather would move 1.3 GB a chunk and layer.  A gathered form
waits for a kernel of its own and contexts where the walk reads 8 x or more
of what it attends.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from llm_np_cp_tpu.ops import sparse_index
from llm_np_cp_tpu.ops.pallas.decode_attention import (
    _RM_COUNT,
    _RM_FIRST,
    _RM_NEXT,
    _RM_PAD,
    _RM_QLEN,
    _RM_QPOS0,
    _RM_ROW,
    _RM_START,
    RAGGED_Q_TILE,
)
from llm_np_cp_tpu.ops.pallas.latent_attention import (
    latent_pages_per_step,
    ragged_latent_attention,
    ragged_latent_attention_xla,
    tile_meta,
)
from llm_np_cp_tpu.ops.sparse_index import (
    SCOPE_DSA_ATTN,
    SCOPE_DSA_SCORE,
    SCOPE_DSA_SELECT,
)

INT32_MIN = sparse_index.INT32_MIN


def _score_kernel(meta_ref, tables_ref, q_ref, w_ref, pool_ref, o_ref,
                  buf, sem, state, *, heads: int, pages: int, mb: int):
    """One (query tile, group of index-key pages) step: the group's copies
    were started a live step ahead (``latent_attention._latent_kernel``'s
    discipline), the tile's queries and head weights and its block of the
    result are the pipeline's."""
    ti, j = pl.program_id(0), pl.program_id(1)
    n_tiles = pl.num_programs(0)
    count = meta_ref[_RM_COUNT, ti]

    def group_copies(tile, step, half, wait: bool):
        live = jnp.minimum(meta_ref[_RM_COUNT, tile] - step * pages, pages)
        first = (meta_ref[_RM_ROW, tile] * mb + meta_ref[_RM_START, tile]
                 + step * pages)

        def one(p, carry):
            copy = pltpu.make_async_copy(
                pool_ref.at[tables_ref[first + p]], buf.at[half, p],
                sem.at[half])
            copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, live, one, 0)

    @pl.when((ti == 0) & (j == 0))
    def _prologue():
        state[0] = 0
        # a slot no copy fills must not hold NaN bits
        buf[...] = jnp.zeros_like(buf)

    @pl.when(j * pages < count)
    def _score():
        @pl.when((j == 0) & (meta_ref[_RM_FIRST, ti] == ti))
        def _first_live_step():
            group_copies(ti, j, 0, wait=False)

        half = state[0]
        more = (j + 1) * pages < count
        next_tile = jnp.where(more, ti, meta_ref[_RM_NEXT, ti])

        @pl.when(next_tile < n_tiles)
        def _prefetch():
            group_copies(next_tile, jnp.where(more, j + 1, 0), 1 - half,
                         wait=False)

        group_copies(ti, j, half, wait=True)
        state[0] = 1 - half
        kb = buf[half].reshape((-1,) + buf.shape[3:])  # [positions, dim]
        s = jax.lax.dot_general(
            q_ref[...], kb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)  # [tokens * heads, positions]
        s = jnp.maximum(s, 0.0) * w_ref[...]
        o_ref[...] = jnp.sum(
            s.reshape(s.shape[0] // heads, heads, s.shape[1]), axis=1)


@functools.partial(jax.jit, static_argnames=("pages", "interpret"))
def ragged_index_scores(
    q_idx: jnp.ndarray,
    w_idx: jnp.ndarray,
    pool: jnp.ndarray,
    tables: jnp.ndarray,
    tile_row: jnp.ndarray,
    tile_qpos0: jnp.ndarray,
    tile_qlen: jnp.ndarray,
    tile_tok: jnp.ndarray,
    pads: jnp.ndarray,
    *,
    pages: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Index scores of every query tile over its row's pages, ``[NT, 8, S]``
    float32 in the tile layout (module docstring).  q_idx ``[D, heads_I,
    dim_I]`` and w_idx ``[D, heads_I]`` (float32) on the step's DENSE token
    axis, pool ``[NB, BS, dim_I]`` the index keys, the tile metadata as
    ``ragged_latent_attention`` takes it; ``pages``: the pages a grid step of
    THAT kernel attends (``latent_pages_per_step``)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    d, heads, dim = q_idx.shape
    qt = RAGGED_Q_TILE
    nt, = tile_row.shape
    _, block_s, _ = pool.shape
    mb = tables.shape[1]
    steps = -(-mb // pages)
    width = pages * block_s
    meta, _ = tile_meta(tables, tile_row, tile_qpos0, tile_qlen, tile_tok,
                        pads, block_s)
    # a tile's tokens, gathered off the dense axis (a lane past the live
    # ones reads a neighbour's: its scores are never looked at)
    lanes = jnp.clip(tile_tok[:, None] + jnp.arange(qt, dtype=jnp.int32),
                     0, d - 1)
    q_t = q_idx[lanes].reshape(nt, qt * heads, dim)
    w_t = w_idx.astype(jnp.float32)[lanes].reshape(nt, qt * heads, 1)
    return pl.pallas_call(
        functools.partial(_score_kernel, heads=heads, pages=pages, mb=mb),
        out_shape=jax.ShapeDtypeStruct((nt, qt, steps * width), jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, steps),
            in_specs=[
                pl.BlockSpec((None, qt * heads, dim),
                             lambda ti, j, *_: (ti, 0, 0)),
                pl.BlockSpec((None, qt * heads, 1),
                             lambda ti, j, *_: (ti, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((None, qt, width),
                                   lambda ti, j, *_: (ti, 0, j)),
            scratch_shapes=[
                pltpu.VMEM((2, pages, block_s, dim), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
            ],
        ),
        interpret=interpret,
    )(meta, tables.reshape(-1).astype(jnp.int32), q_t, w_t, pool)


def _select_kernel(meta_ref, s_ref, o_ref, *, topk: int, block_s: int):
    """One tile's ``[8, S]`` scores -> its mask (1.0 / 0.0): the module
    docstring's two bisections, every count a float32 sum over the row (exact
    below 2^24 positions)."""
    ti = pl.program_id(0)
    start, pad = meta_ref[_RM_START, ti], meta_ref[_RM_PAD, ti]
    qpos0, qlen = meta_ref[_RM_QPOS0, ti], meta_ref[_RM_QLEN, ti]
    shape = s_ref.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    pos = start * block_s + col
    visible = (lane < qlen) & (pos >= pad) & (pos <= qpos0 + lane)
    s = s_ref[...]
    s = jnp.where(s == 0, 0.0, s)
    bits = jax.lax.bitcast_convert_type(s, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    keys = jnp.where(visible, keys, jnp.int32(INT32_MIN))

    def count(hit):
        return jnp.sum(jnp.where(hit, 1.0, 0.0), axis=-1, keepdims=True)

    def score_bit(i, thr):
        cand = thr + jax.lax.shift_left(jnp.int32(1), jnp.int32(31) - i)
        return jnp.where(count(keys >= cand) >= topk, cand, thr)

    thr = jax.lax.fori_loop(
        0, 32, score_bit, jnp.full((shape[0], 1), INT32_MIN, jnp.int32))
    above = keys > thr
    at = (keys == thr) & visible
    room = topk - count(above)
    bits_pos = int(shape[1]).bit_length()

    def position_bit(i, edge):
        cand = edge + jax.lax.shift_left(jnp.int32(1),
                                         jnp.int32(bits_pos - 1) - i)
        return jnp.where(count(at & (col < cand)) <= room, cand, edge)

    edge = jax.lax.fori_loop(
        0, bits_pos, position_bit, jnp.zeros((shape[0], 1), jnp.int32))
    o_ref[...] = jnp.where(
        visible & (above | (at & (col < edge))), 1.0, 0.0).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("topk", "block_s", "interpret"))
def select_topk_tiles(
    scores: jnp.ndarray,
    tables: jnp.ndarray,
    tile_row: jnp.ndarray,
    tile_qpos0: jnp.ndarray,
    tile_qlen: jnp.ndarray,
    tile_tok: jnp.ndarray,
    pads: jnp.ndarray,
    *,
    topk: int,
    block_s: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``[NT, 8, S]`` float32, 1.0 where a tile's token attends a position:
    the ``min(topk, visible)`` visible positions of largest ``scores [NT, 8,
    S]`` (``ragged_index_scores``'s), ties to the lower position.  Exact."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nt, qt, s = scores.shape
    meta, _ = tile_meta(tables, tile_row, tile_qpos0, tile_qlen, tile_tok,
                        pads, block_s)
    block = pl.BlockSpec((None, qt, s), lambda ti, *_: (ti, 0, 0))
    return pl.pallas_call(
        functools.partial(_select_kernel, topk=topk, block_s=block_s),
        out_shape=jax.ShapeDtypeStruct(scores.shape, jnp.float32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(nt,),
            in_specs=[block], out_specs=block),
        interpret=interpret,
    )(meta, scores)


def ragged_index_scores_xla(
    q_idx: jnp.ndarray,
    w_idx: jnp.ndarray,
    pool: jnp.ndarray,
    tables: jnp.ndarray,
    tok_row: jnp.ndarray,
) -> jnp.ndarray:
    """XLA twin of ``ragged_index_scores`` with per-TOKEN metadata: ``[T,
    S_max]`` float32, column ``s`` position ``s`` of the token's row.
    Materializes ``[T, heads_I, S_max]``: the fallback and the oracle."""
    s_max = tables.shape[1] * pool.shape[1]
    keys = pool[tables].reshape(tables.shape[0], s_max, pool.shape[-1])
    return sparse_index.index_scores(
        q_idx[:, None], w_idx[:, None], keys[tok_row].astype(q_idx.dtype))[:, 0]


def select_xla(scores: jnp.ndarray, tok_slot: jnp.ndarray,
               tok_live: jnp.ndarray, tok_pad: jnp.ndarray,
               topk: int) -> jnp.ndarray:
    """XLA twin of ``select_topk_tiles`` over ``ragged_index_scores_xla``'s
    ``[T, S_max]``: bool, each token's selection among the positions ``pad ..
    slot`` of its row."""
    pos = jnp.arange(scores.shape[1], dtype=jnp.int32)[None, :]
    visible = ((pos >= tok_pad[:, None]) & (pos <= tok_slot[:, None])
               & tok_live[:, None])
    return sparse_index.select_topk(scores, visible, topk)


def sparse_latent_attention(
    q, pool, q_idx, w_idx, idx_pool, tables, tile_row, tile_qpos0, tile_qlen,
    tile_tok, pads, *, scale: float, rank: int, topk: int,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Score, select, attend (module docstring): ``ragged_latent_attention``
    of ``q [D, H, W]`` over ``pool [NB, BS, W]`` where every token attends its
    ``topk`` best positions by the indexer ``(q_idx [D, heads_I, dim_I], w_idx
    [D, heads_I] float32, idx_pool [NB, BS, dim_I])``.  ``[D, H, rank]``."""
    block_s = pool.shape[1]
    tiles = (tables, tile_row, tile_qpos0, tile_qlen, tile_tok, pads)
    pages = latent_pages_per_step(
        tables.shape[1], block_s, pool.shape[-1], pool.dtype)
    with jax.named_scope(SCOPE_DSA_SCORE):
        scores = ragged_index_scores(
            q_idx, w_idx, idx_pool, *tiles, pages=pages, interpret=interpret)
    with jax.named_scope(SCOPE_DSA_SELECT):
        select = select_topk_tiles(
            scores, *tiles, topk=topk, block_s=block_s, interpret=interpret)
    with jax.named_scope(SCOPE_DSA_ATTN):
        return ragged_latent_attention(
            q, pool, *tiles, scale=scale, rank=rank, select=select,
            interpret=interpret)


def sparse_latent_attention_xla(
    q, pool, q_idx, w_idx, idx_pool, tables, tok_row, tok_slot, tok_live,
    pads, *, scale: float, rank: int, topk: int,
) -> jnp.ndarray:
    """XLA twin of ``sparse_latent_attention`` with per-TOKEN metadata
    (``ragged_latent_attention_xla``'s): the fallback and the oracle."""
    with jax.named_scope(SCOPE_DSA_SCORE):
        scores = ragged_index_scores_xla(q_idx, w_idx, idx_pool, tables,
                                         tok_row)
    with jax.named_scope(SCOPE_DSA_SELECT):
        select = select_xla(scores, tok_slot, tok_live, pads[tok_row], topk)
    with jax.named_scope(SCOPE_DSA_ATTN):
        return ragged_latent_attention_xla(
            q, pool, tables, tok_row, tok_slot, tok_live, pads, scale=scale,
            rank=rank, select=select)
