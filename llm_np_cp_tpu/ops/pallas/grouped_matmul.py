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

VMEM holds two buffers of each block and nothing that grows with the
row count: the sorted rows are tiled, never held whole.
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
    r = (tile - total(before, per)[:, None]) * tm + jnp.arange(
        tm, dtype=jnp.int32)[None, :]
    src = jnp.where(r < total(owns, sizes)[:, None],
                    total(before, sizes)[:, None] + r, 0).reshape(tiles * tm)
    # a sorted row moves down by the padding of the groups before its own
    row = jnp.arange(rows, dtype=jnp.int32)
    dest = jnp.where(
        row < row_end[-1],
        row + total(row_end[None, :] <= row[:, None], per * tm - sizes), 0)
    return GroupLayout(tile_expert, tile_end[-1:], src, dest)


def _kernel(tile_expert_ref, x_ref, *refs, act):
    *w_refs, o_ref = refs
    # the block's columns in a loop of chunks: one dot over a whole
    # [2048, 1792] block unrolls into 224 MXU passes, and Mosaic compiles
    # every call of every layer of every program by itself (1.7 s a layer
    # against 0.24 for the loop: PERF.md section 6, PR 42)
    chunk = next(c for c in _COLUMN_CHUNKS if o_ref.shape[-1] % c == 0)

    x = x_ref[...]

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
        o_ref[:, at] = out.astype(o_ref.dtype)
        return carry

    jax.lax.fori_loop(0, o_ref.shape[-1] // chunk, columns, 0)


def column_block(k: int, n: int, n_weights: int, itemsize: int) -> int:
    """Columns of a weight block ``[k, tn]``: the widest whole number of
    128 lanes that divides ``n`` with both pipeline buffers of the
    call's blocks inside ``_WEIGHT_VMEM_BYTES`` — the whole of ``n``
    where that fits: fewer, larger copies and one pass over the rows."""
    lanes = n // 128
    for parts in range(1, lanes + 1):
        if lanes % parts == 0 and (
                2 * n_weights * k * (n // parts) * itemsize
                <= _WEIGHT_VMEM_BYTES):
            return n // parts
    return 128


def grouped_matmul(
    x: jnp.ndarray,
    weights: tuple[jnp.ndarray, ...],
    tile_expert: jnp.ndarray,
    live: jnp.ndarray,
    *,
    tm: int,
    act: Any = None,
    out_dtype: Any = None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """``x [tiles * tm, K]`` in ``align_groups``' layout times the
    experts' ``weights`` (each ``[E, K, N]``): ``x @ w[e]`` for one
    array, ``act(x @ w1[e]) * (x @ w3[e])`` for two with ``act``; ``[tiles
    * tm, N]`` in ``out_dtype`` (default: ``x``'s).  Rows of a tile past
    ``live`` are not written."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, k = x.shape
    held, _, n = weights[0].shape
    if len(weights) != (1 if act is None else 2) or any(
            w.shape != (held, k, n) or w.dtype != x.dtype for w in weights):
        raise ValueError(
            f"one [E, {k}, N] array of {x.dtype}, or two with act; got "
            f"{[(w.shape, w.dtype.name) for w in weights]}, act={act}")
    if rows % tm or tile_expert.shape != (rows // tm,) or k % 128 or n % 128:
        raise ValueError(
            f"rows ({rows}) in whole tiles of {tm} with an expert each "
            f"({tile_expert.shape}), K and N whole lanes; got {k} x {n}")
    return _grouped_call(
        x, tuple(weights), tile_expert, live, tm=tm, act=act,
        out_dtype=jnp.dtype(out_dtype or x.dtype), interpret=interpret)


@functools.partial(
    jax.jit, static_argnames=("act", "tm", "out_dtype", "interpret"))
def _grouped_call(x, weights, tile_expert, live, *, tm, act, out_dtype,
                  interpret):
    rows, k = x.shape
    n = weights[0].shape[-1]
    item = x.dtype.itemsize
    tn = column_block(k, n, len(weights), item)

    if not interpret:
        # read where they lie: left to itself the compiler copies a layer's
        # experts into VMEM whole ahead of the call where they fit
        # (Kanana-2's 50 MB do), the untouched ones too
        weights = tuple(
            pltpu.with_memory_space_constraint(w, pltpu.HBM) for w in weights)
    blocks = 2 * (len(weights) * k * tn * item + tm * k * item
                  + tm * tn * out_dtype.itemsize)
    return pl.pallas_call(
        functools.partial(_kernel, act=act),
        out_shape=jax.ShapeDtypeStruct((rows, n), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            # the live tiles alone: a step that does nothing still costs
            # a third of a microsecond, and Kanana-2's tiles are mostly
            # those its pairs COULD fill (section 6, PR 42)
            grid=(n // tn, jnp.clip(live[0], 1, rows // tm)),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, i, te: (i, 0),
                             memory_space=pltpu.VMEM),
                *(pl.BlockSpec((None, k, tn), lambda j, i, te: (te[i], 0, j),
                               memory_space=pltpu.VMEM) for _ in weights),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, i, te: (i, j),
                                   memory_space=pltpu.VMEM),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(blocks + (8 << 20), 100 << 20)),
        interpret=interpret,
        name="grouped_matmul",  # as a profile names the calls
    )(tile_expert.astype(jnp.int32), x, *weights)


def grouped_experts(x: jnp.ndarray, w1: jnp.ndarray, w3: jnp.ndarray,
                    w2: jnp.ndarray, layout: GroupLayout, *, act: Any,
                    tm: int, interpret: bool | None = None) -> jnp.ndarray:
    """The routed experts over rows ``x [tiles * tm, H]`` laid out by
    ``layout``: ``act(x @ w1[e]) * (x @ w3[e])`` in one call, in ``x``'s
    dtype, then ``@ w2[e]`` in another, in float32 — laid rows out."""
    tiles = dict(tile_expert=layout.tile_expert, live=layout.live, tm=tm,
                 interpret=interpret)
    hidden = grouped_matmul(x, (w1, w3), act=act, **tiles)
    return grouped_matmul(hidden, (w2,), out_dtype=jnp.float32, **tiles)


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
