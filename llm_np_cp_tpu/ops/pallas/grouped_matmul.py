"""Grouped matmul for the dropless routed experts (ops/moe.moe_dropless).

The tick's (token, expert) pairs come sorted by expert, ``sizes[e]`` rows
each.  ``lax.ragged_dot`` multiplies such rows by ``w[e]`` in one call,
and a v5e runs its lowering at under half the rate the chip streams
weights into narrow operands (PERF.md section 6, PR 42).  Here the groups are
laid out ALIGNED to a row tile first (``align_groups``): every tile of
``tm`` rows belongs to one expert, at most ``(rows + E * (tm - 1)) // tm``
tiles whatever the counts, the live ones first.  The kernel's grid is
(column block, LIVE row tile) — the second bound is a value, not a shape:
the tiles the pairs could have filled and did not are no steps — and its
operands are plain pipelined blocks:

- the weights ``[K, tn]`` of the tile's expert, K whole (so the
  accumulator is the dot's own and no block is visited twice), read
  where they lie in ``[E, K, N]``.  Consecutive tiles of one expert name
  the same block, which the pipeline fetches once; an expert with no row
  owns no tile, so its weights are never named;
- the tile's rows ``[tm, K]`` and its result ``[tm, tn]``.

Given two weight arrays and ``act`` the body is the experts' first half
in one pass over the rows: ``act(x @ w1[e]) * (x @ w3[e])``, each product
accumulated in float32 and rounded to the rows' dtype BEFORE ``act`` and
the product (``moe_dropless``'s rounding points).  Given one it is ``x @
w[e]``, returned in ``out_dtype``.

A layer's rows ENTER AND LEAVE inside its two calls (PR 49; where
``token_rows_fit``), those of the pairs a chip holds alone: told each
laid row's token, the first call holds the tokens' ``x [T, K]`` float32
whole (a block every step shares, fetched once) and a tile cuts its real
rows out of it, rounding them to the weights' dtype; told its weight too,
the second keeps a float32 block ``[T, tn]`` of the tokens' result
through a column's tiles — zeros before the first — and adds each real
row of a tile, weighted, to its token's: ``out[token[r]] += weight[r] *
y[r]``.  A row that pads a group, the one tile the grid visits when
nothing is held, and a token none of whose pairs is held add nothing.
Outside them XLA gathers ``x[token]`` for every laid row and brings the
result back through an un-sort and a masked sum over ALL ``T * k`` pairs:
seven pairs of eight are another chip's where a chip holds an eighth of
the experts (PERF.md section 6, PR 49).

VMEM holds two buffers of each block and, the tokens' rows apart, nothing
that grows with the row count: the sorted rows are tiled, never held
whole.
"""

from __future__ import annotations

import functools
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# both pipeline buffers of a call's weight blocks stay inside this (a v5e
# has 128 MiB of VMEM; the compiler's default scope is 16)
_WEIGHT_VMEM_BYTES = 48 * 2**20
# ... and those beside both buffers of a result block the call keeps
# through a column's tiles (``column_block``), inside this
_BLOCK_VMEM_BYTES = 88 * 2**20
# laid rows whose token and weight a call takes as scalars (64 KiB each)
_SCALAR_ROWS = 16384
_ROW_TILES = (16, 32, 64, 128)
_COLUMN_CHUNKS = (256, 128)


def row_tile(rows: int, experts: int) -> int:
    """The row tile for ``rows`` pairs spread over ``experts``: the
    smallest power of two from 16 (a bf16 vreg's sublanes) to 128 (the
    MXU's rows) that holds one and a half times an expert's mean share.
    An expert should own ONE tile — a second multiplies the whole of its
    weights again — and no wider a one than that takes: the MXU's time is
    the weights' loads up to 128 rows, and past what the copies hide from
    64 (measured at 8 / 40 / 4.5 / 16.5 rows an expert: PERF.md section 6,
    PR 42)."""
    want = 1.5 * rows / max(experts, 1)
    return next((tm for tm in _ROW_TILES if tm >= want), _ROW_TILES[-1])


def tile_count(rows: int, held: int, tm: int) -> int:
    """Tiles of ``tm`` rows that hold any ``held`` groups of ``rows``
    rows in all, each group padded to whole tiles."""
    return max(1, (rows + held * (tm - 1)) // tm)


class GroupLayout(NamedTuple):
    """Sorted rows laid out in whole tiles of one expert each."""

    tile_expert: jnp.ndarray  # [tiles] int32 — whose weights a tile reads
    live: jnp.ndarray         # [1] int32 — tiles that hold a row
    rows: jnp.ndarray         # [tiles] int32 — a tile's real rows, its first
    src: jnp.ndarray          # [tiles * tm] int32 — sorted row of a laid row
    dest: jnp.ndarray         # [rows] int32 — laid row of a sorted row


@functools.partial(jax.jit, static_argnums=(1, 2))  # traced once, not a layer
def align_groups(sizes: jnp.ndarray, rows: int, tm: int) -> GroupLayout:
    """The layout of ``rows`` sorted rows, ``sizes[e]`` of them expert
    ``e``'s (rows past ``sum(sizes)`` are nobody's), in tiles of ``tm``.
    A laid row that pads a group reads sorted row 0 and a sorted row of
    no group lands on laid row 0: any row will do, nothing uses either.
    A tile past the live ones is given the last expert (the kernel does
    not visit it)."""
    held = sizes.shape[0]
    tiles = tile_count(rows, held, tm)
    sizes = sizes.astype(jnp.int32)
    per = (sizes + tm - 1) // tm  # an expert's tiles
    # running sums over at most a few dozen experts as compare-and-sum: a
    # handful of fused reductions a layer where cumsum, repeat and gathers
    # of 32-entry tables were three dozen operations of 2-3 us each

    def total(mask, of):  # sum of ``of [E]`` over the experts ``mask [n, E]``
        return jnp.sum(jnp.where(mask, of[None, :], 0), axis=1, dtype=jnp.int32)

    expert = jnp.arange(held, dtype=jnp.int32)
    upto = expert[None, :] <= expert[:, None]
    tile_end, row_end = total(upto, per), total(upto, sizes)
    tile = jnp.arange(tiles, dtype=jnp.int32)[:, None]
    before = tile_end[None, :] <= tile  # experts wholly before a tile
    owns = ~before & ((tile_end - per)[None, :] <= tile)  # none past the live
    tile_expert = jnp.minimum(
        jnp.sum(before, axis=1, dtype=jnp.int32), held - 1)
    # a laid row's place in its expert's group, and in the sorted rows
    first = (tile - total(before, per)[:, None]) * tm
    r = first + jnp.arange(tm, dtype=jnp.int32)[None, :]
    group = total(owns, sizes)[:, None]  # 0 past the live tiles
    src = jnp.where(r < group, total(before, sizes)[:, None] + r,
                    0).reshape(tiles * tm)
    # a sorted row moves down by the padding of the groups before its own
    row = jnp.arange(rows, dtype=jnp.int32)
    dest = jnp.where(
        row < row_end[-1],
        row + total(row_end[None, :] <= row[:, None], per * tm - sizes), 0)
    return GroupLayout(tile_expert, tile_end[-1:],
                       jnp.clip(group - first, 0, tm).reshape(tiles), src, dest)


def _kernel(*refs, act, n_weights, tm, gather, combine):
    # scalars (tile_expert is the index maps'), operands, result, scratch
    refs = iter(refs[1:])
    if gather or combine:
        rows_ref, token_ref = next(refs), next(refs)
    if combine:
        weight_ref = next(refs)
    x_ref = next(refs)
    w_refs = [next(refs) for _ in range(n_weights)]
    o_ref = next(refs)
    tile = pl.program_id(1)
    base = tile * tm

    if gather:
        # the tile's rows cut out of the tokens' ``[T, K]`` by ``token``
        # and rounded here; a row that pads keeps what an earlier tile
        # left there, which nothing reads
        xs_ref = next(refs)

        @pl.when((pl.program_id(0) == 0) & (tile == 0))
        def _():
            xs_ref[...] = jnp.zeros_like(xs_ref)

        def take(r, carry):
            xs_ref[pl.ds(r, 1), :] = x_ref[pl.ds(token_ref[base + r], 1), :]
            return carry

        jax.lax.fori_loop(0, rows_ref[tile], take, 0)
        x = xs_ref[...].astype(w_refs[0].dtype)
    else:
        x = x_ref[...]

    # where the products go: the caller's block, or (``combine``) a tile
    # of float32 that the rows below are added from
    y_ref = next(refs) if combine else o_ref
    # the block's columns in a loop of chunks: one dot over a whole
    # [2048, 1792] block unrolls into 224 MXU passes, and Mosaic compiles
    # every call of every layer of every program by itself (1.7 s a layer
    # against 0.24 for the loop: PERF.md section 6, PR 42)
    chunk = next(c for c in _COLUMN_CHUNKS if y_ref.shape[-1] % c == 0)

    def columns(c, carry):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        prods = [
            jnp.dot(x, w[:, at], preferred_element_type=jnp.float32)
            for w in w_refs
        ]
        if act is None:
            out, = prods
        else:
            # act and the product of two rounded factors, evaluated in
            # float32 and rounded where the rows' dtype would round them
            # (a v5e has no bf16 vector unit, and Mosaic no bf16 logistic)
            f32 = jnp.float32
            gate, up = (p.astype(x.dtype).astype(f32) for p in prods)
            out = act(gate).astype(x.dtype).astype(f32) * up
        y_ref[:, at] = out.astype(y_ref.dtype)
        return carry

    jax.lax.fori_loop(0, y_ref.shape[-1] // chunk, columns, 0)

    if combine:
        # the block is every tile's of this column: zeros before the
        # first, then each REAL row of a tile added to its token's row
        # under its weight (a tile past the pairs held has none)
        @pl.when(tile == 0)
        def _():
            t = o_ref.shape[0]
            zeros = jnp.zeros((8, o_ref.shape[1]), o_ref.dtype)

            def clear(r, carry):
                o_ref[pl.ds(pl.multiple_of(r * 8, 8), 8), :] = zeros
                return carry

            jax.lax.fori_loop(0, t // 8, clear, 0)
            if t % 8:
                o_ref[t - t % 8:, :] = zeros[:t % 8]

        def add(r, carry):
            at = pl.ds(token_ref[base + r], 1)
            o_ref[at, :] = o_ref[at, :] + weight_ref[base + r] * y_ref[
                pl.ds(r, 1), :]
            return carry

        jax.lax.fori_loop(0, rows_ref[tile], add, 0)


def column_block(k: int, n: int, n_weights: int, itemsize: int, *,
                 held_bytes: int = 0, kept_rows: int = 0) -> int:
    """Columns of a weight block ``[k, tn]``: the widest whole number of
    128 lanes that divides ``n`` with both pipeline buffers of the
    call's weight blocks inside ``_WEIGHT_VMEM_BYTES`` — the whole of ``n``
    where that fits: fewer, larger copies and one pass over the rows.
    A call that holds the tokens' rows (``held_bytes`` of them) or keeps a
    float32 block ``[kept_rows, tn]`` of its result through a column's
    tiles wants those, both buffers, beside the weights inside
    ``_BLOCK_VMEM_BYTES``: 0 where not even 128 columns leave the room."""
    lanes = n // 128
    for parts in range(1, lanes + 1):
        tn = n // parts
        weights = 2 * n_weights * k * tn * itemsize
        if lanes % parts == 0 and weights <= _WEIGHT_VMEM_BYTES and (
                weights + held_bytes + 2 * kept_rows * tn * 4
                <= _BLOCK_VMEM_BYTES):
            return tn
    return 0 if held_bytes or kept_rows else 128


def token_rows_fit(tokens: int, laid: int, h: int, inter: int,
                   itemsize: int) -> bool:
    """Whether a layer's two calls can move the rows themselves
    (``grouped_experts`` given ``token`` and ``weight``): the tokens'
    ``[tokens, h]`` float32 whole beside gate and up's blocks, a float32
    result block ``[tokens, tn]`` beside down's, and the ``laid`` rows'
    token and weight among the call's scalars.  A plain forward over a
    whole prompt (the benchmark's check) does not, and keeps XLA's gather
    and combine: told from the shapes alone."""
    return (laid <= _SCALAR_ROWS
            and column_block(h, inter, 2, itemsize,
                             held_bytes=2 * tokens * h * 4) > 0
            and column_block(inter, h, 1, itemsize, kept_rows=tokens) > 0)


def grouped_matmul(
    x: jnp.ndarray,
    weights: tuple[jnp.ndarray, ...],
    tile_expert: jnp.ndarray,
    live: jnp.ndarray,
    *,
    tm: int,
    act: Any = None,
    out_dtype: Any = None,
    tile_rows: jnp.ndarray | None = None,
    token: jnp.ndarray | None = None,
    gather: bool = False,
    weight: jnp.ndarray | None = None,
    tokens: int | None = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``x [tiles * tm, K]`` in ``align_groups``' layout times the
    experts' ``weights`` (each ``[E, K, N]``): ``x @ w[e]`` for one
    array, ``act(x @ w1[e]) * (x @ w3[e])`` for two with ``act``; ``[tiles
    * tm, N]`` in ``out_dtype`` (default: ``x``'s).  Rows of a tile past
    ``live`` are not written.

    Given ``tile_rows [tiles]`` (the layout's ``rows``) and ``token
    [tiles * tm]`` (a laid row's token) the rows enter or leave INSIDE the
    call, a tile's real rows alone:

    - ``gather``: ``x [T, K]`` float32 is the TOKENS' rows, held whole; a
      tile cuts its rows out by ``token`` and rounds them to the weights'
      dtype (the default ``out_dtype``).  A row that pads multiplies
      whatever an earlier tile left.
    - ``weight [tiles * tm]`` float32 and ``tokens`` = T: the result is
      ``[T, N]`` float32 BY TOKEN, ``sum_r weight[r] * (x[r] @ w[e])``
      over the real laid rows of a token, added in float32 in the tiles'
      order; a token with none reads zeros."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    held, k, n = weights[0].shape
    dtype = weights[0].dtype
    tiles, = tile_expert.shape
    if len(weights) != (1 if act is None else 2) or any(
            w.shape != (held, k, n) or w.dtype != dtype for w in weights):
        raise ValueError(
            f"one [E, K, N] array, or two alike with act; got "
            f"{[(w.shape, w.dtype.name) for w in weights]}, act={act}")
    laid = (tiles * tm, k)
    if k % 128 or n % 128 or x.shape[1:] != (k,) or (
            x.shape != laid or x.dtype != dtype if not gather
            else x.dtype != jnp.float32):
        raise ValueError(
            f"rows {laid} of {dtype} in whole tiles of {tm} with an expert "
            f"each ({tile_expert.shape}), or the tokens' [T, {k}] float32 to "
            f"gather from, K and N whole lanes; got {x.shape} of {x.dtype}, "
            f"N {n}")
    if (gather or weight is not None) and (
            tile_rows is None or token is None or tile_rows.shape != (tiles,)
            or token.shape != laid[:1]):
        raise ValueError("gather and weight take tile_rows [tiles] and "
                         "token [tiles * tm]")
    if weight is not None:
        if weight.shape != laid[:1] or not tokens:
            raise ValueError("weight [tiles * tm] float32 comes with tokens")
    return _grouped_call(
        x, tuple(weights), tile_expert, live, tile_rows, token, weight,
        tm=tm, act=act, gather=gather, tokens=tokens,
        out_dtype=jnp.dtype(jnp.float32 if weight is not None
                            else out_dtype or dtype),
        interpret=interpret)


@functools.partial(jax.jit, static_argnames=(
    "act", "tm", "out_dtype", "gather", "tokens", "interpret"))
def _grouped_call(x, weights, tile_expert, live, tile_rows, token, weight, *,
                  tm, act, out_dtype, gather, tokens, interpret):
    k = x.shape[1]
    n = weights[0].shape[-1]
    tiles, = tile_expert.shape
    item = weights[0].dtype.itemsize
    combine = weight is not None
    tn = column_block(
        k, n, len(weights), item,
        held_bytes=2 * x.size * x.dtype.itemsize if gather else 0,
        kept_rows=tokens if combine else 0)
    if not tn:
        raise ValueError(
            f"{x.shape[0] if gather else tokens} tokens' rows do not fit "
            f"beside the [{k}, N] weight blocks: ask token_rows_fit")

    if not interpret:
        # read where they lie: left to itself the compiler copies a layer's
        # experts into VMEM whole ahead of the call where they fit
        # (Kanana-2's 50 MB do), the untouched ones too
        weights = tuple(
            pltpu.with_memory_space_constraint(w, pltpu.HBM) for w in weights)
    prefetch = [tile_expert.astype(jnp.int32)]
    if gather or combine:
        prefetch += [tile_rows.astype(jnp.int32), token.astype(jnp.int32)]
    if combine:
        prefetch.append(weight.astype(jnp.float32))
    scratch = []
    if gather:  # the tokens' rows whole: one block every step shares
        x_spec = pl.BlockSpec(x.shape, lambda j, i, *_: (0, 0),
                              memory_space=pltpu.VMEM)
        scratch.append(pltpu.VMEM((tm, k), x.dtype))
    else:
        x_spec = pl.BlockSpec((tm, k), lambda j, i, *_: (i, 0),
                              memory_space=pltpu.VMEM)
    if combine:  # one block a column, every tile's
        out_rows = tokens
        out_spec = pl.BlockSpec((tokens, tn), lambda j, i, *_: (0, j),
                                memory_space=pltpu.VMEM)
        scratch.append(pltpu.VMEM((tm, tn), jnp.float32))
    else:
        out_rows = tiles * tm
        out_spec = pl.BlockSpec((tm, tn), lambda j, i, *_: (i, j),
                                memory_space=pltpu.VMEM)
    blocks = (2 * (len(weights) * k * tn * item
                   + x_spec.block_shape[0] * k * x.dtype.itemsize
                   + out_spec.block_shape[0] * tn * out_dtype.itemsize)
              + gather * tm * k * x.dtype.itemsize + combine * tm * tn * 4)
    return pl.pallas_call(
        functools.partial(_kernel, act=act, n_weights=len(weights), tm=tm,
                          gather=gather, combine=combine),
        out_shape=jax.ShapeDtypeStruct((out_rows, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            # the live tiles alone: a step that does nothing still costs
            # a third of a microsecond, and Kanana-2's tiles are mostly
            # those its pairs COULD fill (section 6, PR 42)
            grid=(n // tn, jnp.clip(live[0], 1, tiles)),
            in_specs=[
                x_spec,
                *(pl.BlockSpec((None, k, tn),
                               lambda j, i, te, *_: (te[i], 0, j),
                               memory_space=pltpu.VMEM) for _ in weights),
            ],
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(blocks + (8 << 20), 100 << 20)),
        interpret=interpret,
        name="grouped_matmul",  # as a profile names the calls
    )(*prefetch, x, *weights)


def grouped_experts(x: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
                    w2: jnp.ndarray, layout: GroupLayout,
                    token: jnp.ndarray | None = None,
                    weight: jnp.ndarray | None = None, *, act: Any,
                    tm: int, interpret: bool | None = None) -> jnp.ndarray:
    """The routed experts over ``layout``'s rows: ``act(x @ w1[e]) * (x @
    w3[e])`` in one call, in the experts' dtype, then ``@ w2[e]`` in
    another, in float32.  Given a laid row's ``token`` and ``weight``
    (those of a row that pads are never read) ``x [T, H]`` float32 is the
    TOKENS' rows and the result ``[T, H]`` their weighted sums: a tile
    gathers its real rows in the first call and adds its results to
    their tokens' in the second (``grouped_matmul``; where
    ``token_rows_fit``).  Without them ``x [tiles * tm, H]`` is the laid
    rows in the experts' dtype, and laid rows come back."""
    tiles = dict(tile_expert=layout.tile_expert, live=layout.live, tm=tm,
                 interpret=interpret)
    if weight is not None:
        tiles.update(tile_rows=layout.rows, token=token)
    hidden = grouped_matmul(x, (w1, w3), act=act, gather=weight is not None,
                            **tiles)
    return grouped_matmul(
        hidden, (w2,), out_dtype=jnp.float32, weight=weight,
        tokens=None if weight is None else x.shape[0], **tiles)


def ragged_dot(x: jnp.ndarray, w: jnp.ndarray, sizes: jnp.ndarray, *,
               tm: int | None = None, out_dtype: Any = jnp.float32,
               interpret: bool | None = None) -> jnp.ndarray:
    """``lax.ragged_dot(x, w, sizes, preferred_element_type=out_dtype)``
    through the kernel: sorted rows ``[M, K]`` laid out, multiplied and
    brought back.  Rows of no group come back as whatever laid row 0
    holds (``lax.ragged_dot`` gives zeros)."""
    rows = x.shape[0]
    tm = tm or row_tile(rows, w.shape[0])
    layout = align_groups(sizes, rows, tm)
    out = grouped_matmul(
        x[layout.src], (w,), layout.tile_expert, layout.live, tm=tm,
        out_dtype=out_dtype, interpret=interpret)
    return out[layout.dest]
