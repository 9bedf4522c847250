"""Fused decode-step attention over the static KV cache.

The decode step attends ONE query token per sequence against the whole
cache slab ([B, S_max, K, D]).  The XLA path computes scores → softmax →
weighted sum as separate HLOs over the FULL slab — static shapes mean it
always streams every slot, valid or not.  This kernel streams each KV
block through VMEM once with online-softmax state (the decode analogue
of the prefill flash kernel; the reference's custom CUDA kernel role,
SURVEY §2.3) and additionally SKIPS blocks outside each row's visible
range — the structural advantage a kernel has over XLA here.

Design (round-5 rewrite; the r4 kernel ran at 58% of the XLA path):
- mask-driven, not position-driven: the caller passes the SAME [B, S_max]
  boolean mask the XLA path uses (cache validity ∧ causality ∧ sliding
  window ∧ ragged-batch pads), so every decode feature — including
  per-row lengths from batched speculative decoding — works unchanged.
- per-row block bounds are DERIVED from the mask with two cheap XLA
  reductions and fed through scalar prefetch: the kv-block index map
  clamps into [start_b, nb_b), so blocks before the sliding window or
  past the row's valid length are never DMA'd (a repeated block index
  skips the fetch) and their grid steps do no compute.  Ragged batches
  stream only what each row can see.
- grid is (batch, kv_blocks) and ALL kv heads are processed per block.
  The r4 kernel ran the online-softmax update once per kv head on
  [G, block_s] tiles — G is 4-8, so every VPU op ran at half sublane
  occupancy and the per-op overhead repeated K times per block, which
  profiling pointed at as the 951-vs-1,629 tok/s gap.  Here the per-head
  MXU dots are concatenated into ONE [H, block_s] score tile and the
  entire mask/softcap/exp/max/rescale pipeline runs once per block at
  full width.
- dots take bf16 operands with f32 accumulation (MXU-native, same
  contract as the XLA path's einsums) instead of pre-casting to f32.
- Mosaic requires the last two block dims to be 8/128-aligned or equal
  to the full array dims; taking the full (K, D) trailing dims of the
  native [B, S, K, D] slab satisfies that with ZERO transposes or copies.
- int8 cache mode dequantizes the whole [block_s, K, D] block in VMEM
  with a single multiply (HBM streams 1-byte values + f32 scales).

The serving kernel below takes the cache as a POOL of pages and a block
table a row.  ``_ragged_kernel`` (the served tick, every benchmark
cell) has a grid of query tiles x GROUPS of pages: a kv grid
step attends ``P = ragged_pages_per_step(...)`` consecutive pages of its
tile's row — as many as cover 512 kv positions, no more than the table
is wide, inside ``_VMEM_BUDGET_BYTES``; read off the page's shape and
dtype, 8 pages of 64 tokens in every cell.  The pool stays in HBM and
the kernel copies pages itself, one DMA a page with the id from the
scalar-prefetched table, into one half of a two-half VMEM buffer: a live
step first starts the copies of the NEXT live step (this tile's next
group, or the first group of the next tile that has one) into the other
half, then waits for its own, so a group is in flight while one is
attended; a slot past the row's last page starts no copy, a step past it
does nothing, a dead tile has no live step.  A call's time then follows
the pages it reads and its live steps, not the table's width (PERF.md
§6, PR 33: 437 → 206 us a call at 64 decode rows of 410 tokens).  Pool
arrays whose pages a DMA cannot cut out in whole tiles (scale pages, two
int8 heads, ``head_dim`` 64) ride ``P`` blocked operands and the
automatic pipeline instead (``_dma_slices_pages``).  A live step's
update has two forms chosen from the tile's own ``tile_qlen``: the whole
tile's ``K * 8 * G`` score rows, or — a tile of ONE live token — that
token's rows alone.  Float ``[BS, K, D]`` pages the kernel copies itself
are attended AS THEY LIE, ``[BS * K, D]`` with a position's kv heads on
consecutive rows: one dot scores every kv head's query rows against all
of them and a row keeps the columns of its own head, so no head's rows
are ever taken out of a page (PERF.md §6, PR 44: 206 → 109 us a call).

Benchmark-gated like every kernel here (SURVEY §7 step 7): wired as
``attn_impl="flash_decode"``, default stays XLA, and Generator probes
Mosaic support once at construction, downgrading to XLA with a warning
instead of dying at first dispatch (ops/pallas/support.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

# VMEM working-set budget for the double-buffered K/V (+scale) blocks.
# v5e VMEM is ~16 MiB/core; leave generous headroom for q/mask/scratch
# and the compiler's own buffers.
_VMEM_BUDGET_BYTES = 8 * 2**20

# ----------------------------------------------------------------------
# AMLA rescaling (PAPERS.md: "AMLA: MUL by ADD in FlashAttention
# Rescaling").  The classic online-softmax block update rescales the
# accumulator and normalizer with alpha = exp(m_prev - m_new) — two
# full-width VPU multiplies (plus one transcendental) per block.  AMLA's
# observation: if the running max is kept on the ln2 grid, alpha is an
# EXACT power of two, and multiplying a float by 2^k is an integer ADD
# on its exponent field.  The serving kernel below (_ragged_kernel)
# uses this additive-max formulation; quantizing the max
# UP to the grid keeps every exp argument <= 0, so the only numerical
# change is that p = exp(s - m) sits up to one octave lower — the
# final acc/l ratio is mathematically unchanged (parity-pinned against
# the XLA oracle at fp32/bf16/int8 in tests).  Validating the win on
# real HBM traffic is recorded live-TPU debt (README/ROADMAP).
# ----------------------------------------------------------------------
_LN2 = 0.6931471805599453
_LOG2E = 1.4426950408889634
# exponent-step clamp: anything below this underflows every f32 anyway,
# and the clamp keeps k * 2^23 inside int32 (250 * 2^23 < 2^31)
_AMLA_KMIN = -250.0


def _amla_max(m_prev: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """New running max, snapped UP to the ln2 grid.  A fully-masked
    block's tile max is NEG_INF (its grid snap overflows to -inf) and
    the maximum keeps m_prev — the running max never leaves the grid
    (or its NEG_INF init) once a real score has been seen."""
    t = jnp.max(s, axis=-1, keepdims=True)
    return jnp.maximum(m_prev, jnp.ceil(t * _LOG2E) * _LN2)


def _amla_steps(m_prev: jnp.ndarray, m_new: jnp.ndarray) -> jnp.ndarray:
    """Rescale exponent delta k <= 0 with alpha = 2^k: both maxes sit on
    the ln2 grid, so the division is an exact integer.  The init case
    (m_prev = NEG_INF) clips to the underflow floor, where the rescale
    of the still-zero accumulator is a no-op by construction."""
    d = (m_prev - m_new) * _LOG2E
    d = jnp.where(jnp.isnan(d), 0.0, d)  # belt: -inf minus -inf
    return jnp.round(jnp.clip(d, _AMLA_KMIN, 0.0)).astype(jnp.int32)


def _amla_rescale(x: jnp.ndarray, k: jnp.ndarray) -> jnp.ndarray:
    """``x * 2^k`` (k int32 <= 0) as an integer add on the f32 exponent
    field — the MUL-by-ADD at the heart of AMLA.  Exponent underflow
    (including x == 0 and the NEG_INF-init case) flushes to zero, which
    is exactly what the multiplicative form's denormal underflow did."""
    k23 = k * (1 << 23)
    xi = jax.lax.bitcast_convert_type(x, jnp.int32)
    ok = (xi & jnp.int32(0x7F800000)) + k23 > 0
    return jnp.where(
        ok, jax.lax.bitcast_convert_type(xi + k23, jnp.float32), 0.0
    )


def _decode_kernel(
    bounds_ref, *refs, scale: float, softcap: float | None, quantized: bool,
    kv_heads: int, group: int,
):
    if quantized:
        (q_ref, k_ref, v_ref, mask_ref, ks_ref, vs_ref,
         o_ref, m_ref, l_ref, acc_ref) = refs
    else:
        q_ref, k_ref, v_ref, mask_ref, o_ref, m_ref, l_ref, acc_ref = refs
    bi = pl.program_id(0)
    j = pl.program_id(1)  # kv block (innermost: scratch accumulates per b)
    nj = pl.num_programs(1)
    start, nb = bounds_ref[0, bi], bounds_ref[1, bi]

    @pl.when(j == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Blocks outside [start, nb) hold nothing visible for this row: their
    # index map repeats a fetched block (no DMA) and the update is skipped.
    @pl.when(start + j < nb)
    def _update():
        mask = mask_ref[0, :, 0]  # [block_s]
        kb = k_ref[0]  # [block_s, K, D]
        vb = v_ref[0]
        dtype = q_ref.dtype
        if quantized:
            # int8 cache: HBM streams 1-byte values; dequant happens here
            # in VMEM, one multiply for the whole block (the XLA path fuses
            # the same multiply into its einsum operand read)
            kb = kb.astype(dtype) * ks_ref[0][..., None].astype(dtype)
            vb = vb.astype(dtype) * vs_ref[0][..., None].astype(dtype)

        # Per-head MXU dots (bf16 × bf16 → f32), concatenated to ONE
        # full-width score tile so the VPU pipeline below runs once per
        # block at [H, block_s] instead of K times at [G, block_s].
        s = jnp.concatenate(
            [
                jax.lax.dot_general(
                    q_ref[0, ki], kb[:, ki], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for ki in range(kv_heads)
            ],
            axis=0,
        ) * scale  # [H, block_s]
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(mask[None, :], s, NEG_INF)

        m_prev = m_ref[:]  # [H, 1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        # re-zero masked slots: exp(NEG_INF - m) underflows to 0 for any
        # real m, but a FULLY-masked row has m == NEG_INF and would get
        # p == 1 everywhere, silently averaging V over garbage slots
        p = jnp.where(mask[None, :], p, 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        pb = p.astype(vb.dtype)  # bf16 PV dots, same as the XLA path
        pv = jnp.concatenate(
            [
                jax.lax.dot_general(
                    pb[ki * group:(ki + 1) * group], vb[:, ki],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for ki in range(kv_heads)
            ],
            axis=0,
        )  # [H, D]
        acc_ref[:] = acc_ref[:] * alpha + pv
        m_ref[:] = m_new

    @pl.when(j == nj - 1)
    def _finalize():
        # A row with nothing visible (can't happen for real rows — the
        # current token is always valid) has l == 0 thanks to the p
        # re-zeroing above; emit zeros instead of dividing by zero.
        l = jnp.where(l_ref[:] == 0.0, 1.0, l_ref[:])
        o_ref[0] = (acc_ref[:] / l).reshape(o_ref.shape[1:]).astype(o_ref.dtype)


# Sublane alignment for a block_s that is NOT the full cache length.
# block_s is the SECOND-MINOR dim of the bool mask block (1, block_s, 1)
# — Mosaic's sublane tiling for 1-byte element types is (32, 128), so an
# 8-aligned-but-not-32-aligned partial block compiles for the f32/bf16
# K/V specs and then dies on the mask spec.  That is the BENCH_TPU_LIVE_r4
# "block shape divisibility" warm-log failure class (fdec): interpret
# mode hides it, only a hardware compile rejects it.  32 also covers the
# int8 K/V pages, whose own second-minor is block_s-free (full trailing
# dims) but whose scale pages ride the same block length.
_BLOCK_S_ALIGN = 32


def select_block_s(
    s: int, kv_heads: int, head_dim: int, kv_itemsize: int,
    requested: int, quantized: bool,
) -> int:
    """Largest kv-block length that divides ``s``, is 32-aligned (the
    strictest sublane tile among the streamed operands — see
    ``_BLOCK_S_ALIGN``), and keeps the double-buffered K/V(+scale)
    working set inside the VMEM budget.

    Falls back to a single whole-``s`` block for short caches with no
    aligned divisor (then every block dim equals the full array dim,
    which Mosaic always accepts).  Raises for caches that have no
    aligned divisor and are too large for one VMEM block —
    ``decode_attention`` catches that and PADS the cache instead of
    dying (the r4 fdec debt: validate/pad, never hand Mosaic an
    unaligned partial block).
    """
    a = _BLOCK_S_ALIGN
    # hints below the alignment (8/16/24 were valid pre-32) would make
    # the candidate range empty and mis-raise on perfectly divisible
    # caches; the alignment is the real floor, so clamp up to it
    requested = max(requested, a)
    row_bytes = kv_heads * head_dim * kv_itemsize * 2  # K and V
    if quantized:
        row_bytes += kv_heads * 4 * 2  # f32 k/v scales
    cap = max(a, (_VMEM_BUDGET_BYTES // (2 * row_bytes)) // a * a)
    best = 0
    # start aligned DOWN — an unaligned start would step through
    # exclusively unaligned candidates and miss every valid divisor
    for cand in range(min(requested, cap, s) // a * a, a - 1, -a):
        if s % cand == 0:
            best = cand
            break
    if best:
        return best
    # same double-buffering factor as the cap path above
    if 2 * s * row_bytes <= _VMEM_BUDGET_BYTES:
        return s  # single block; block dim == full dim satisfies Mosaic
    raise ValueError(
        f"decode_attention: cache length {s} has no {a}-aligned divisor "
        f"and is too large for a single VMEM block; pad the cache to a "
        f"multiple of {a} (decode_attention does this automatically)"
    )


def _block_bounds(mask: jnp.ndarray, block_s: int, n_blocks: int) -> jnp.ndarray:
    """Per-row [start_block, n_blocks_visible) from the boolean mask —
    two XLA reductions, traced into the surrounding jit.  Rows see
    nothing outside [first_visible, last_visible], so clamping the kv
    block index into these bounds never changes the result (the in-block
    mask still handles partial blocks)."""
    b, s = mask.shape
    pos = jax.lax.broadcasted_iota(jnp.int32, (b, s), 1)
    last = jnp.max(jnp.where(mask, pos, -1), axis=1)  # [B]
    first = jnp.min(jnp.where(mask, pos, s), axis=1)
    nb = jnp.clip(last // block_s + 1, 1, n_blocks)
    start = jnp.clip(first // block_s, 0, nb - 1)
    return jnp.stack([start, nb]).astype(jnp.int32)  # [2, B]


# ----------------------------------------------------------------------
# Ragged mixed prefill+decode attention (the unified-tick kernel)
# ----------------------------------------------------------------------

# Query-tile width for the ragged kernel's packed token axis.  Every
# row's token segment is padded up to a multiple of this so each q tile
# belongs to exactly ONE row (the scalar-prefetched tile metadata then
# names that row's pages).  8 = the f32 sublane tile; a decode row costs
# one tile.  What a tile costs is its row's pages in groups of
# ``ragged_pages_per_step``, not the pool's table width one page at a
# time (PERF.md §6, PR 33), and a tile of ONE live token (a decode row)
# pays for that token's ``K * G`` score rows, each kv head's in whole
# sublane tiles, not for a sheet of 8 tokens of which 7 are masked
# (PERF.md §6, PR 44: a tenth of a live step; what a step of ``[BS, K,
# D]`` pages paid most for was taking each kv head's rows out of them,
# and they are attended as they lie now).  Several decode rows to a tile
# is a mechanism of its own (PERF.md §7); many tokens of ONE row to a tile
# is the wide tile (``ragged_wide_tile``, PERF.md §6, PR 57).
RAGGED_Q_TILE = 8

# kv positions one grid step of the ragged kernel attends: the group of
# pages it streams is as many as cover this much context (PERF.md §6,
# PR 33, has the sweep on the chip that chose it)
_RAGGED_STEP_POSITIONS = 512
# ... in a call that may hold wide tiles (``ragged_wide_tile``): such a
# call's grid is a prompt tick's widest, most of its tiles dead, and what
# it pays for most besides the wide tiles' sheets is its grid steps, so
# fewer of twice the positions (PERF.md section 6, PR 57, the probe)
_RAGGED_WIDE_STEP_POSITIONS = 1024

# FLOPs a byte of pages above which a tile of ``RAGGED_Q_TILE`` tokens is
# no longer bound by its pages' bytes, so that reading them once for more
# tokens buys nothing: a third of a v5e's 240 (197 TFLOP/s over 819 GB/s) —
# between what gained on the chip (Trinity's pages, 48: a prompt tick's
# calls −35 %) and what lost (MiMo-V2's, 102 and 205: two heads of 192 to
# a 384-deep dot, a group of 8 / 16: +1.8 ms a prompt tick at a tile of
# 32; PERF.md section 6, PR 57)
_WIDE_MAX_INTENSITY = 80

# Score rows one dot of a WIDE tile's update holds: a wide tile's tokens
# are scored a kv head and a block of tokens at a time, each block a
# sheet of at most this many rows by the step's positions (float32: 1 MB)
_WIDE_SHEET_ROWS = 512

# meta rows for _ragged_kernel (computed in-graph per layer — the
# sliding-window bound is a traced per-layer value)
(_RM_START, _RM_COUNT, _RM_PAD, _RM_QPOS0, _RM_QLEN, _RM_WIN, _RM_ROW,
 _RM_NEXT, _RM_FIRST, _RM_BASE) = range(10)


def _sublane_tile(rows: int, dtype) -> int:
    """Rows of the tile Mosaic lays an array's second-minor dimension
    in: the smallest power-of-two multiple of the dtype's packing (1 for
    f32, 2 for bf16, 4 for int8) that covers ``rows``, at most 8
    sublanes of 32 bits."""
    tile = packing = max(4 // jnp.dtype(dtype).itemsize, 1)
    while tile < min(rows, 8 * packing):
        tile *= 2
    return tile


def _vmem_bytes(shape: tuple[int, ...], dtype) -> int:
    """Bytes an array takes in VMEM: its last two dims in whole tiles of
    ``_sublane_tile`` rows by 128 lanes (``[64, 2, 128]`` bf16 takes
    what it holds, ``[64, 8, 64]`` bf16 twice that, an ``[64, 2]`` f32
    scale page 64 times)."""
    *lead, rows, cols = shape
    tile = _sublane_tile(rows, dtype)
    n = (-(-rows // tile) * tile * -(-cols // 128) * 128
         * jnp.dtype(dtype).itemsize)
    for dim in lead:
        n *= dim
    return n


def _dma_slices_pages(pool: jnp.ndarray) -> bool:
    """Whether a DMA can cut one page out of ``pool`` ``[NB, ...]``:
    Mosaic slices a tiled array in whole tiles only, so a page's last
    two dims have to fill theirs (bf16 ``[.., 2, 128]`` does; an int8
    ``[.., 2, 128]``, a ``[.., 8, 64]`` or a scale page does not)."""
    rows, cols = pool.shape[-2:]
    return cols % 128 == 0 and rows % _sublane_tile(rows, pool.dtype) == 0


def _lane_pack(kv_heads: int, head_dim: int) -> int:
    """kv heads of a MERGED page (``[BS, K * D]``) that share one row of
    128 lanes, and are attended together: 2 at ``head_dim`` 64.  A head
    taken alone out of such a row sits at a lane offset that is no
    multiple of a tile — a relayout of the whole group every step; the
    row is sliced whole instead, ``q`` comes with each head's values on
    its own lanes and zeros on its neighbours' (``_lane_spread``), and
    the MXU, 128 deep whatever it is given, does the same work."""
    pack = 128 // head_dim if head_dim < 128 and 128 % head_dim == 0 else 1
    if head_dim == 192:  # a lane and a half: two heads are three whole rows
        pack = 2
    return pack if kv_heads % pack == 0 else 1


def _lane_spread(qf: jnp.ndarray, q_tile: int, pack: int) -> jnp.ndarray:
    """``q [T, K, G, D]`` → ``[T / q_tile, K / pack, pack * q_tile * G,
    pack * D]`` for the merged page form: the rows of a tile's ``pack``
    heads that share a row of lanes one under the other — order (head,
    token, group), the order of the kernel's score sheet — each with its
    values at its own head's lanes and zeros elsewhere, so that ONE dot
    against the shared ``[.., pack * D]`` slice of K scores them all."""
    t, kh, g, d = qf.shape
    q6 = qf.reshape(t // q_tile, q_tile, kh // pack, pack, g, d)
    q6 = q6.transpose(0, 2, 3, 1, 4, 5)  # [NT, C, pack, q_tile, G, D]
    wide = jnp.einsum("ncsqgd,sr->ncsqgrd", q6,
                      jnp.eye(pack, dtype=qf.dtype))
    return wide.reshape(t // q_tile, kh // pack, pack * q_tile * g, pack * d)


def ragged_pages_per_step(
    mb: int, block_s: int, kv_heads: int, head_dim: int, kv_dtype,
    quantized: bool, merged: bool = False, wide: bool = False,
) -> int:
    """``P``: the pages of a tile's row one grid step of the ragged
    kernel streams and attends — the largest count that covers at most
    ``_RAGGED_STEP_POSITIONS`` kv positions, is no wider than the block
    table (``mb``), and keeps what a page slot takes of VMEM inside
    ``_VMEM_BUDGET_BYTES``: K and V in both buffer halves and, for an
    int8 pool, the scale pages likewise plus the group dequantized whole
    (about twelve float32 copies of a page between K and V — the v5e
    compiler wanted 16.8 MB of scoped VMEM at six ``[64, 4, 256]`` pages
    and 24.2 MB at eight, of its 16).  From shapes alone: every caller
    gets the ``P`` of its page shape.  A ``merged`` page ``[BS, K * D]``
    takes what it holds (``[64, 8, 64]`` twice that).  ``wide``: of a
    call that may hold wide tiles, ``_RAGGED_WIDE_STEP_POSITIONS``."""
    page = ((block_s, kv_heads * head_dim) if merged
            else (block_s, kv_heads, head_dim))
    slot = 2 * 2 * _vmem_bytes(page, kv_dtype)
    if quantized:
        slot += 2 * 2 * _vmem_bytes((block_s, kv_heads), jnp.float32)
        slot += 12 * 4 * block_s * kv_heads * head_dim
    positions = (_RAGGED_WIDE_STEP_POSITIONS if wide
                 else _RAGGED_STEP_POSITIONS)
    return max(1, min(positions // block_s, mb, _VMEM_BUDGET_BYTES // slot))


def ragged_wide_tile(
    kv_heads: int, group: int, head_dim: int, value_dim: int, kv_dtype,
    merged: bool,
) -> int:
    """Lanes of the WIDE query tile a packer should lay a prompt chunk's
    tokens in over such pages (0: none): that many to a tile stream their
    row's pages once where tiles of ``RAGGED_Q_TILE`` would stream them
    once each.  From shapes alone, as ``P``: the widest tile the kernel
    has for the pages (``_wide_tile_limit``), where a tile of
    ``RAGGED_Q_TILE`` tokens is bound by its pages' bytes — the FLOPs of
    its two dots a byte of K and V under ``_WIDE_MAX_INTENSITY`` — and
    none where its arithmetic already is what it waits for."""
    pack = _lane_pack(kv_heads, head_dim)
    v_pack = _lane_pack(kv_heads, value_dim)
    flops = 2 * RAGGED_Q_TILE * group * (pack * head_dim + v_pack * value_dim)
    bytes_ = (head_dim + value_dim) * jnp.dtype(kv_dtype).itemsize
    if flops > _WIDE_MAX_INTENSITY * bytes_:
        return 0
    return _wide_tile_limit(kv_heads, group, head_dim, value_dim, kv_dtype,
                            merged)


def _wide_tile_limit(
    kv_heads: int, group: int, head_dim: int, value_dim: int, kv_dtype,
    merged: bool,
) -> int:
    """The widest tile ``ragged_paged_attention`` takes over such pages
    (0: none).  Merged float pages only (a head is a static slice of a
    page's lanes: a wide tile's sheets are a kv head's), and a query
    group whose ``RAGGED_Q_TILE`` tokens are whole sublane tiles of ``q``
    (a tile of a wide block reads its rows at a dynamic offset).  The
    width is the largest of 64 / 32 / 16 whose block — ``q`` and the
    result in both buffers, the running maximum, denominator and
    accumulator of every score row — leaves the pages' buffers and a
    sheet their room in a scoped VMEM of 16 MiB
    (``_wide_compiler_params`` asks for what it counts)."""
    packing = max(4 // jnp.dtype(kv_dtype).itemsize, 1)
    if (not merged or not jnp.issubdtype(kv_dtype, jnp.floating)
            or (RAGGED_Q_TILE * group) % (8 * packing)
            # (pages the kernel copies itself: ``_dma_slices_pages``)
            or kv_heads * head_dim % 128 or kv_heads * value_dim % 128):
        return 0
    for wide in (64, 32, 16):
        if _wide_block_bytes(wide, kv_heads, group, head_dim, value_dim,
                             kv_dtype) <= 9 * 2**20:
            return wide
    return 0


def _wide_block_bytes(wide: int, kv_heads: int, group: int, head_dim: int,
                      value_dim: int, dtype) -> int:
    """VMEM a wide tile's block takes beside the pages' buffers: ``q``
    (lane-spread) and the result in both pipeline buffers, and the
    float32 scratch — a running maximum and a denominator a score row
    (a column each: a sublane tile of 128 lanes holds 8) and the
    accumulator."""
    pack, v_pack = _lane_pack(kv_heads, head_dim), _lane_pack(kv_heads, value_dim)
    rows = kv_heads * wide * group
    return (2 * _vmem_bytes((rows, pack * head_dim), dtype)
            + 2 * wide * kv_heads * _vmem_bytes((group, value_dim), dtype)
            + 2 * _vmem_bytes((rows, 1), jnp.float32)
            + _vmem_bytes((rows, v_pack * value_dim), jnp.float32))


def _wide_compiler_params(wide: int, kv_heads: int, group: int,
                          head_dim: int, value_dim: int, block_s: int,
                          pages: int, dtype) -> dict:
    """``pallas_call``'s ``compiler_params`` for a call with wide tiles
    (none without: that call is the one it was): the scoped VMEM its
    block, its pages' two halves and the sheets of a wide update's
    temporaries (counted amply: a ceiling, not an allocation — the masked
    and the plain form of a block's update keep theirs apart) come to,
    where that is past the 16 MiB a kernel gets unasked."""
    if not wide:
        return {}
    kv = 2 * pages * (
        _vmem_bytes((block_s, kv_heads * head_dim), dtype)
        + _vmem_bytes((block_s, kv_heads * value_dim), dtype))
    sheets = 16 * _WIDE_SHEET_ROWS * pages * block_s * 4
    need = (_wide_block_bytes(wide, kv_heads, group, head_dim, value_dim, dtype)
            + kv + sheets + (4 << 20))
    if need <= 16 * 2**20:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        vmem_limit_bytes=min(need, 64 << 20))}


def _ragged_kernel(
    meta_ref, tables_ref, *refs,
    scale: float, softcap: float | None, quantized: bool, kv_heads: int,
    group: int, block_s: int, q_tile: int, head_dim: int, pages: int,
    mb: int, by_hand: tuple[bool, ...], pack: int = 0,
    value_dim: int | None = None, v_pack: int | None = None,
    has_sink: bool = False, has_base: bool = False,
    heads_in_rows: bool = False, wide: int = 0,
):
    """Mixed-batch block-table attention: each q tile holds up to
    ``q_tile`` consecutive tokens of ONE row (a prefill-chunk slice, or a
    decode row's single token with the tail masked), and a kv grid step
    attends a GROUP of ``pages`` consecutive pages of that row.  The pool
    stays in HBM; a live step waits for its own group's copies (one DMA a
    page, the page id read from the scalar-prefetched table, into the
    buffer half that is its turn) only after it has started the copies
    of the NEXT live step — the next group of this tile, or the first
    group of the next tile that has any — into the other half, so a
    group is in flight while one is attended.  A slot of the group past
    the row's last page starts no copy, a step past it does nothing, and
    a dead tile (``tile_qlen == 0``) has no live step at all.  A pool
    array whose pages a DMA cannot slice (``by_hand`` False,
    ``_dma_slices_pages``: scale pages, two int8 kv heads, an
    unmerged ``head_dim`` of 64) comes as ``pages`` blocked operands instead,
    fetched by the automatic pipeline.

    The online-softmax update runs once a group, on a
    ``[K * q_tile * G, pages * block_s]`` score sheet — or, in a tile
    whose ``tile_qlen`` is 1 (a decode row, the one-token tail of a
    prefill segment: token 0 at slot ``qpos0`` either way), on that
    token's rows alone: each kv head's ``G`` rows in whole sublane
    tiles, read from and written to the scratch where the tile's sheet
    has them (rows ordered (kv head, token, group head): a head's token
    0 starts at a multiple of 8); the other lanes keep what ``_init``
    gave them and ``_finalize`` turns into zeros.  Visibility is
    derived in-kernel from the tile's (pad, qpos0, qlen, window)
    scalars: token i at cache slot ``qpos0 + i`` sees kv slots in
    ``[max(pad, slot - win + 1), slot]`` — causal within the tile's own
    freshly-written K/V too, because the caller scatters the whole
    packed batch into the pool before attending (same discipline as the
    paged decode step).  A page slot no copy filled holds zeros or an
    earlier page; its positions lie beyond every query slot and the
    causal mask drops them.

    ``pack`` > 0: the pages are MERGED, ``[block_s, kv_heads *
    head_dim]`` with the heads side by side on the lanes, and ``q`` comes
    as ``_lane_spread`` lays it out: the ``pack`` heads of one row of
    lanes are scored by one dot against that whole slice of K, their
    ``p @ V`` comes out ``pack * head_dim`` wide (each head's result on
    its own lanes, its neighbours' beside it) and ``_finalize`` takes
    each head's lanes.  A value head may have another width than a key
    head (``value_dim``; merged V pages pack by their own width,
    ``v_pack``: at 128 lanes a head is sliced alone).

    ``has_sink``: a ``[rows, 1]`` float32 operand holds one learned logit
    a query head, laid out like the score sheet's rows; it seeds the
    running maximum (on AMLA's grid) and the denominator of a tile before
    its first page — a column of the softmax that has no value.
    ``has_base``: meta row ``_RM_BASE`` is the logical block the row's
    table starts at (a window chain's first block is not position 0).

    ``heads_in_rows``: the ``[BS, K, D]`` pages come ``[BS * K, D]``,
    the same bytes with a position's kv heads on consecutive rows, and
    are attended so (``attend``): on a v5e a head's rows of such a page
    are every ``K``-th HALF of a 32-bit row, and taking them out cost
    more than everything else a live step did (PERF.md §6, PR 44).

    ``wide`` > 0 (merged pages): the call may hold WIDE tiles — a
    prompt chunk's ``q_tile < tile_qlen <= wide`` consecutive tokens of
    one row, on ``wide`` lanes from a multiple of ``wide`` on, named by
    the FIRST of the ``wide / q_tile`` tiles those lanes make (the
    others are dead and leave the lanes alone).  Such a tile walks its
    row's pages ONCE: a group is copied once and scored against all of
    the tile's tokens, a kv head and a block of tokens
    (``_WIDE_SHEET_ROWS`` score rows) at a time, where ``wide / q_tile``
    tiles would each have streamed it again.  A block of ``q`` and of
    the result is then ``wide`` lanes (rows ordered (kv head, token of
    the block, group head), as the scratch's); a tile of ``q_tile``
    lanes reads and writes its own lanes of it, and its update is the
    one it has without."""
    value_dim = head_dim if value_dim is None else value_dim
    v_pack = pack if v_pack is None else v_pack
    it = iter(refs)
    q_ref = next(it)
    sink_ref = next(it) if has_sink else None
    # a pool array: the whole of it in HBM, or its ``pages`` page blocks
    sources = [next(it) if hand else [next(it) for _ in range(pages)]
               for hand in by_hand]
    o_ref, m_ref, l_ref, acc_ref = (next(it) for _ in range(4))
    # ...and, copied by hand, its two halves of a group in VMEM
    bufs = [next(it) if hand else None for hand in by_hand]
    copied = [(src, buf) for src, buf in zip(sources, bufs) if buf is not None]
    if copied:
        sem, half_ref = next(it), next(it)
    ti = pl.program_id(0)
    j = pl.program_id(1)
    nj = pl.num_programs(1)
    n_tiles = pl.num_programs(0)
    start, count = meta_ref[_RM_START, ti], meta_ref[_RM_COUNT, ti]
    pad, qpos0 = meta_ref[_RM_PAD, ti], meta_ref[_RM_QPOS0, ti]
    qlen, win = meta_ref[_RM_QLEN, ti], meta_ref[_RM_WIN, ti]
    width = pages * block_s
    # a tile's lanes of its block of ``q`` and of the result (``wide``:
    # the block is ``wide`` lanes and holds ``wide / q_tile`` tiles)
    block_q = wide or q_tile
    lane0 = ti % (wide // q_tile) * q_tile if wide else 0
    block_rows = block_q * group  # a kv head's rows of a block

    def group_copies(tile, step, half, wait: bool):
        """Start (or wait for) one copy a LIVE page slot of group
        ``step`` of ``tile``, into buffer half ``half``."""
        live = jnp.minimum(meta_ref[_RM_COUNT, tile] - step * pages, pages)
        first = (meta_ref[_RM_ROW, tile] * mb + meta_ref[_RM_START, tile]
                 + step * pages)

        def slot(p, carry):
            page = tables_ref[first + p]
            for pool_ref, buf in copied:
                copy = pltpu.make_async_copy(
                    pool_ref.at[page], buf.at[half, p], sem.at[half])
                copy.wait() if wait else copy.start()
            return carry

        jax.lax.fori_loop(0, live, slot, 0)

    def fetch_group():
        """→ the buffer half this step attends, its group waited for."""
        @pl.when((j == 0) & (meta_ref[_RM_FIRST, ti] == ti))
        def _first_live_step():
            # nothing is in flight yet: the buffers start as zeros (a
            # slot no copy fills must not hold NaN bits under the mask)
            # and this step fetches its own group
            for _, buf in copied:
                buf[...] = jnp.zeros_like(buf)
            half_ref[0] = 0
            group_copies(ti, j, 0, wait=False)

        half = half_ref[0]
        # the next live step: this tile's next group, else the first
        # group of the next tile that has one (``n_tiles``: none)
        more = (j + 1) * pages < count
        next_tile = jnp.where(more, ti, meta_ref[_RM_NEXT, ti])

        @pl.when(next_tile < n_tiles)
        def _prefetch():
            group_copies(next_tile, jnp.where(more, j + 1, 0), 1 - half,
                         wait=False)

        group_copies(ti, j, half, wait=True)
        half_ref[0] = 1 - half
        return half

    head_rows = q_tile * group  # a kv head's rows of the scratch

    def init(rows: int, tiles: int = 1):
        """The scratch's first ``rows`` rows before a tile's first page
        (``tiles``: the sink's rows, a tile's, that many times over)."""
        if has_sink:
            b = sink_ref[:]
            if tiles > 1:  # (kv head, token, group head), more tokens
                b = jnp.concatenate(
                    [b[ki * head_rows:(ki + 1) * head_rows]
                     for ki in range(kv_heads) for _ in range(tiles)], axis=0)
            m0 = jnp.ceil(b * _LOG2E) * _LN2  # the grid point above it
            m_ref[:rows] = m0
            l_ref[:rows] = jnp.exp(b - m0)
        else:
            m_ref[:rows] = jnp.full((rows, 1), NEG_INF, m_ref.dtype)
            l_ref[:rows] = jnp.zeros((rows, 1), l_ref.dtype)
        acc_ref[:rows] = jnp.zeros((rows,) + acc_ref.shape[1:], acc_ref.dtype)

    if wide:
        # a tile of ``q_tile`` lanes keeps the rows it has without; a
        # wide one's are all the scratch has
        @pl.when((j == 0) & (qlen <= q_tile))
        def _init():
            init(kv_heads * head_rows)

        @pl.when((j == 0) & (qlen > q_tile))
        def _init_wide():
            init(kv_heads * block_rows, wide // q_tile)
    else:
        @pl.when(j == 0)
        def _init():
            init(kv_heads * head_rows)

    # a ONE-token tile's rows of a kv head: its ``G`` group heads in whole
    # sublane tiles (the rows past ``G`` are token 1's first, masked like
    # every dead lane); a head's token 0 starts at a multiple of 8 in the
    # scratch, whose rows are ordered (kv head, token, group head)
    token_rows = -(-group // 8) * 8

    def scratch_rows(one: bool):
        """→ (load, store) of the scratch rows an update touches: all of
        them, or (``one``) each kv head's ``token_rows`` of token 0."""
        if not one:
            n = kv_heads * head_rows  # (all the scratch has without ``wide``)

            def store(ref, rows):
                ref[:n] = rows

            return (lambda ref: ref[:n]), store

        def load(ref):
            return jnp.concatenate(
                [ref[ki * head_rows:ki * head_rows + token_rows]
                 for ki in range(kv_heads)], axis=0)

        def store(ref, rows):
            for ki in range(kv_heads):
                ref[ki * head_rows:ki * head_rows + token_rows] = (
                    rows[ki * token_rows:(ki + 1) * token_rows])

        return load, store

    def head_mask(one: bool, cols: int, heads: int = 1):
        """A kv head's ``[rows, cols]`` of the score sheet's mask, rows
        ordered (token, group head): which kv positions of this step's
        group each of the tile's tokens (``one``: its ONE token) sees.
        ``heads`` > 1: the columns are (position, kv head) pairs, a
        position's heads side by side."""
        tokens, rows = (1, token_rows) if one else (q_tile, head_rows)
        base = meta_ref[_RM_BASE, ti] if has_base else 0
        # rank-2 iota (Mosaic rejects rank-1 iota on TPU)
        q_idx = jax.lax.broadcasted_iota(jnp.int32, (tokens, cols), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (tokens, cols), 1)
        if heads > 1:
            col = col >> (heads.bit_length() - 1)
        kv_pos = (base + start + j * pages) * block_s + col
        q_slot = qpos0 + q_idx
        seen = (
            (q_idx < qlen)
            & (kv_pos >= pad)
            & (kv_pos > q_slot - win)  # sliding window (win huge = global)
            & (kv_pos <= q_slot)       # causal
        )  # [tokens, cols]
        if not one:
            return jnp.broadcast_to(
                seen[:, None, :], (q_tile, group, cols)).reshape(rows, cols)
        mine = jnp.broadcast_to(seen, (rows, cols))
        if rows != group:  # (the rows past ``G``: token 1's)
            mine &= jax.lax.broadcasted_iota(
                jnp.int32, (rows, cols), 0) < group
        return mine

    def online_softmax(s, mask, rows, weighted):
        """The AMLA additive-max update (see ``_amla_rescale``: ln2-grid
        max, group rescale = exponent-field integer add, not a multiply)
        of the scratch rows (``rows``: their ``(load, store)``) under the
        score sheet ``s``; ``weighted(p)`` → ``p @ V``."""
        load, store = rows
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(mask, s, NEG_INF)
        m_prev = load(m_ref)
        m_new = _amla_max(m_prev, s)
        # re-zero masked slots: a FULLY-masked query row (dead packing
        # lane) has m == NEG_INF and would otherwise get p == 1
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        k_steps = _amla_steps(m_prev, m_new)
        store(l_ref, _amla_rescale(load(l_ref), k_steps)
              + jnp.sum(p, axis=-1, keepdims=True))
        store(acc_ref, _amla_rescale(load(acc_ref), k_steps) + weighted(p))
        store(m_ref, m_new)

    def attend(half, one: bool):
        """The update of this step's group of pages: the whole tile's
        ``K * q_tile * G`` score rows, or (``one``) those of the tile's
        ONE live token."""
        rows = token_rows if one else head_rows  # a kv head's

        def group_of(src, buf):  # the step's pages end to end
            if buf is not None:
                return buf[half].reshape((-1,) + buf.shape[3:])
            if pages == 1:
                return src[0][0]
            return jnp.concatenate([r[0] for r in src], axis=0)

        kb, vb, *scales = (group_of(*sb) for sb in zip(sources, bufs))

        def q_rows(ki):  # (the tokens whose rows fill ``rows``)
            n = -(-rows // group)
            return q_ref[:n, ki].reshape(n * group, head_dim)[:rows]

        if heads_in_rows:
            # the pages as they lie, ``[width * K, D]`` with a position's
            # kv heads on consecutive rows: ONE dot scores every kv
            # head's query rows against all of them — a ``[K * rows,
            # width * K]`` sheet of which a row keeps the columns of its
            # own head — and one dot weights V's rows in the same order
            # (what a row masked is zero, so the other heads' values add
            # nothing).  No head's rows are taken out of the group.
            cols = width * kv_heads
            s = jax.lax.dot_general(
                jnp.concatenate([q_rows(ki) for ki in range(kv_heads)], axis=0),
                kb, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            mine = head_mask(one, cols, kv_heads)
            col_head = jax.lax.broadcasted_iota(
                jnp.int32, (rows, cols), 1) & (kv_heads - 1)
            mask = jnp.concatenate(
                [mine & (col_head == ki) for ki in range(kv_heads)], axis=0)
            online_softmax(
                s, mask, scratch_rows(one), lambda p: jax.lax.dot_general(
                    p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            return

        dtype = q_ref.dtype
        if quantized:
            kb = kb.astype(dtype) * scales[0][..., None].astype(dtype)
            vb = vb.astype(dtype) * scales[1][..., None].astype(dtype)
        if pack:
            # merged pages [width, K * D]: the heads of one row of lanes
            # together, a static whole-row slice of the group each
            lanes = pack * head_dim
            dots = kv_heads // pack

            def q_of(c):
                if not one and not wide:
                    return q_ref[0, c]
                # (``wide``: the tile's lanes of the block's rows)
                at = lane0 * group
                if wide:
                    at = pl.multiple_of(at, head_rows)
                return jnp.concatenate(
                    [q_ref[0, c, pl.ds(s * block_rows + at, rows)]
                     for s in range(pack)], axis=0)

            def k_of(b, c):
                return b[:, c * lanes:(c + 1) * lanes]
        else:
            dots, q_of = kv_heads, q_rows

            def k_of(b, ki):
                return b[:, ki]
        if v_pack:
            v_lanes = v_pack * value_dim
            v_dots, v_rows = kv_heads // v_pack, v_pack * rows

            def v_of(b, c):
                return b[:, c * v_lanes:(c + 1) * v_lanes]
        else:
            v_dots, v_rows = kv_heads, rows

            def v_of(b, ki):
                return b[:, ki]

        # per-kv-head MXU dots over the tile's live rows, concatenated to
        # ONE [K * rows, width] score sheet (rows ordered (ki, qi, gi))
        # so the mask/softcap/exp/rescale VPU pipeline runs once per
        # group at full width — the _decode_kernel r5 lesson applied
        s = jnp.concatenate(
            [
                jax.lax.dot_general(
                    q_of(i), k_of(kb, i), (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )
                for i in range(dots)
            ],
            axis=0,
        ) * scale  # [K * rows, width]
        # mask rows order (qi, gi), identical for every kv head
        mask = jnp.concatenate([head_mask(one, width)] * kv_heads, axis=0)

        def weighted(p):
            pb = p.astype(vb.dtype)
            return jnp.concatenate(
                [
                    jax.lax.dot_general(
                        pb[i * v_rows:(i + 1) * v_rows],
                        v_of(vb, i), (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32,
                    )
                    for i in range(v_dots)
                ],
                axis=0,
            )  # [K * rows, Dv]  (merged: v_pack * Dv wide)

        online_softmax(s, mask, scratch_rows(one), weighted)

    def attend_wide(half):
        """The update of this step's group under a WIDE tile: the group
        as one tile's step has it, scored a kv head and a block of the
        tile's tokens at a time (the sheet of all of them at once would
        be ``wide / q_tile`` times a tile's, most of a scoped VMEM), the
        block's mask built once for every kv head."""
        kb, vb = (buf[half].reshape((-1,) + buf.shape[3:]) for buf in bufs)
        lanes, v_lanes = pack * head_dim, v_pack * value_dim
        tokens = wide
        while tokens * group > _WIDE_SHEET_ROWS and tokens > q_tile:
            tokens //= 2
        rows = tokens * group
        # a score row's token, without a division: (row * m) >> 16
        m = -(-(1 << 16) // group)
        assert all(r * m >> 16 == r // group for r in range(rows))
        base = meta_ref[_RM_BASE, ti] if has_base else 0
        def block_update(first, mask):
            for ki in range(kv_heads):
                c, cv = ki // pack, ki // v_pack
                q0 = ki % pack * block_rows + first * group
                r0 = ki * block_rows + first * group

                def load(ref, r0=r0):
                    return ref[r0:r0 + rows]

                def store(ref, val, r0=r0):
                    ref[r0:r0 + rows] = val

                s = jax.lax.dot_general(
                    q_ref[0, c, q0:q0 + rows],
                    kb[:, c * lanes:(c + 1) * lanes],
                    (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                online_softmax(
                    s, mask, (load, store),
                    lambda p, cv=cv: jax.lax.dot_general(
                        p.astype(vb.dtype),
                        vb[:, cv * v_lanes:(cv + 1) * v_lanes],
                        (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32))

        kv_pos = (base + start + j * pages) * block_s + (
            jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1))
        for first in range(0, wide, tokens):
            # what a row of the block sees of the group: the positions
            # from its window's (or its row's) first to its own, none
            # where its token is past the tile's last.  (A group INSIDE
            # what every token sees needs no mask; the update's two forms
            # under a branch cost more than the selects they saved:
            # PERF.md section 6, PR 57)
            tok = first + (jax.lax.broadcasted_iota(
                jnp.int32, (rows, 1), 0) * m >> 16)
            q_slot = qpos0 + tok
            last = jnp.where(tok < qlen, q_slot, -1)
            block_update(first, (
                (kv_pos >= jnp.maximum(pad, q_slot - win + 1))
                & (kv_pos <= last)))

    @pl.when(j * pages < count)
    def _update():
        half = fetch_group() if copied else None

        # a tile of ONE live token (a decode row, or a prefill segment's
        # one-token tail: token 0 at slot ``qpos0`` either way) pays for
        # that token's score rows, not for a sheet of ``q_tile`` tokens
        # of which it would mask all but one
        @pl.when(qlen == 1)
        def _one_token():
            attend(half, True)

        @pl.when((qlen != 1) & (qlen <= q_tile) if wide else qlen != 1)
        def _tile():
            attend(half, False)

        if wide:
            @pl.when(qlen > q_tile)
            def _wide():
                attend_wide(half)

    def finalize(tokens: int, lanes):
        """The scratch's ``tokens`` tokens a kv head → those ``lanes`` of
        the result's block."""
        rows, n = tokens * group, kv_heads * tokens * group
        l = jnp.where(l_ref[:n] == 0.0, 1.0, l_ref[:n])
        acc = acc_ref[:n] / l
        for ki in range(kv_heads):
            mine = acc[ki * rows:(ki + 1) * rows]
            if v_pack > 1:  # this head's lanes of the row it shares
                at = ki % v_pack * value_dim
                mine = mine[:, at:at + value_dim]
            o_ref[lanes, ki] = (
                mine.reshape(tokens, group, value_dim).astype(o_ref.dtype)
            )

    if wide:
        # the tile that names this block's lanes, where it is a wide one,
        # has written them all: the tiles after it leave them alone
        named = meta_ref[_RM_QLEN, ti - lane0 // q_tile] > q_tile

        @pl.when((j == nj - 1) & (lane0 == 0) & named)
        def _finalize_wide():
            finalize(wide, slice(None))

        @pl.when((j == nj - 1) & jnp.logical_not(named))
        def _finalize():
            finalize(q_tile, pl.ds(pl.multiple_of(lane0, q_tile), q_tile))
    else:
        @pl.when(j == nj - 1)
        def _finalize():
            finalize(q_tile, slice(None))


@functools.partial(
    jax.jit,
    static_argnames=("scale", "logit_softcap", "interpret", "wide_tile"),
)
def ragged_paged_attention(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    tables: jnp.ndarray,
    tile_row: jnp.ndarray,
    tile_qpos0: jnp.ndarray,
    tile_qlen: jnp.ndarray,
    pads: jnp.ndarray,
    window: jnp.ndarray,
    *,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    scale: float,
    logit_softcap: float | None = None,
    interpret: bool | None = None,
    sink: jnp.ndarray | None = None,
    block0: jnp.ndarray | None = None,
    wide_tile: int = 0,
) -> jnp.ndarray:
    """Mixed prefill+decode GQA attention straight off a paged KV pool.

    One invocation handles a PACKED batch of rows with heterogeneous
    query lengths — prefill-chunk slices and single-token decode rows —
    against the same pool slabs (Ragged Paged Attention, the
    unified-tick kernel).

    q [T, H, D] — the packed token axis: each row's segment occupies
    consecutive, ``RAGGED_Q_TILE``-aligned positions (the serve engine's
    packer guarantees this; dead lanes between segments are masked via
    ``tile_qlen``).  k_pages/v_pages [NB, BS, K, D] — a pool of pages:
    one layer's slab, or (the unified tick) the whole pool flat over
    layer and block with the layer's offset already in ``tables`` — or
    MERGED, [NB, BS, K * D] (float pools; ``K`` follows from ``q``'s
    ``D``): the form a ``head_dim``-64 pool is stored in, because a TPU
    keeps THAT in the order its shape lists and a DMA can cut a page out
    of it (serve/block_pool.py).
    tables [R, MB] int32 page ids per engine row.  Per TILE
    (T / RAGGED_Q_TILE entries): ``tile_row`` — the owning engine row,
    ``tile_qpos0`` — the cache slot of the tile's first token,
    ``tile_qlen`` — live tokens in the tile (0 = dead padding tile).
    pads [R] — left-pad slots per row.  window — traced int32 scalar:
    sliding-window width for this layer (pass a huge value for global
    layers; the per-layer flag stays traced, so one compile serves
    both).  → [T, H, D].

    Token i of a tile sees kv slots ``[max(pad, slot_i - window + 1),
    slot_i]`` where ``slot_i = tile_qpos0 + i`` — the visibility of a
    causal forward over the row's tokens, so outputs are parity-testable
    against ``models.forward``.

    The grid is ``(T / RAGGED_Q_TILE, ceil(MB / P))``: a kv step streams
    and attends ``P = ragged_pages_per_step(...)`` pages of the tile's
    row (a function of the shapes alone), copied from the pool in HBM
    into one half of a VMEM buffer while the other half is attended.
    Pages outside the tile's visible range are never copied, a dead tile
    streams nothing, and steps past the row's last page do nothing.
    A tile of ONE live token attends that token's score rows alone (the
    kernel branches on its ``tile_qlen``); float ``[BS, K, D]`` pages
    the kernel copies itself are handed over ``[BS * K, D]`` — the same
    bytes, a bitcast on the chip — and attended as they lie.

    int8 pool mode: k_scale/v_scale [NB, BS, K] f32 scale pages ride
    along and the kernel dequantizes per group in VMEM.

    ``v_pages`` may hold heads of another width than ``k_pages``
    (``[.., K, Dv]`` or merged ``[.., K * Dv]``): the result is ``[T, H,
    Dv]``.  ``sink`` [H] float32: a learned logit a query head in every
    row's softmax denominator, with no value (None, a static absence,
    compiles to the kernel without one).  ``block0`` [R] int32: the
    logical block of each row's table column 0 (None: 0) — a window
    layer's chain holds only the blocks its window still reaches, so its
    table starts at ``block0[row]`` and kv position ``p`` lies in column
    ``p // BS - block0[row]``.

    ``wide_tile`` (static; ``ragged_wide_tile`` says which pages should
    have one, ``_wide_tile_limit`` which can): the packed axis may hold WIDE tiles — ``RAGGED_Q_TILE <
    tile_qlen <= wide_tile`` consecutive tokens of one row on
    ``wide_tile`` lanes that start at a multiple of it, named by the
    first of those lanes' ``wide_tile / RAGGED_Q_TILE`` tile entries
    (the others read 0).  Such a tile streams its row's pages once for
    all its tokens; a call without one (0) is the call it was.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = k_scale is not None
    if (
        quantized != (k_pages.dtype == jnp.int8)
        or quantized != (v_pages.dtype == jnp.int8)
        or quantized != (v_scale is not None)
    ):
        raise ValueError(
            "int8 k_pages AND v_pages require both k_scale and v_scale "
            f"pages (and vice versa); got k={k_pages.dtype}, "
            f"v={v_pages.dtype}, "
            f"k_scale={'set' if k_scale is not None else None}, "
            f"v_scale={'set' if v_scale is not None else None}"
        )
    t, h, d = q.shape
    qt = RAGGED_Q_TILE
    if t % qt:
        raise ValueError(
            f"packed token axis ({t}) must be a multiple of "
            f"RAGGED_Q_TILE ({qt})"
        )
    nt = t // qt
    if tile_row.shape != (nt,):
        raise ValueError(
            f"tile metadata must have T/RAGGED_Q_TILE = {nt} entries, "
            f"got {tile_row.shape}"
        )
    merged = k_pages.ndim == 3
    if merged:
        if quantized or k_pages.shape[-1] % d:
            raise ValueError(
                f"merged pages [NB, BS, K * D] are float pages of whole "
                f"heads of q's head_dim {d}; got {k_pages.dtype}"
                f"{list(k_pages.shape)}")
        _, block_s, kd = k_pages.shape
        kh = kd // d
        dv = v_pages.shape[-1] // kh
    else:
        _, block_s, kh, _ = k_pages.shape
        dv = v_pages.shape[-1]
    g = h // kh
    mb = tables.shape[1]
    pages = ragged_pages_per_step(
        mb, block_s, kh, d, k_pages.dtype, quantized, merged=merged,
        wide=bool(wide_tile))
    steps = -(-mb // pages)
    pack = _lane_pack(kh, d) if merged else 0
    v_pack = _lane_pack(kh, dv) if merged else 0
    if wide_tile and (
            wide_tile > _wide_tile_limit(kh, g, d, dv, k_pages.dtype, merged)
            or wide_tile % (2 * qt) or t % wide_tile):
        raise ValueError(
            f"a wide tile of {wide_tile} lanes on a packed axis of {t} over "
            f"{k_pages.dtype}{list(k_pages.shape[1:])} pages: "
            "_wide_tile_limit says which pages have one, and how wide at most")
    # lanes of a block of ``q`` and of the result, and tiles to a block
    wq = wide_tile or qt
    sub = wq // qt

    qf = q.reshape(t, kh, g, d)
    # per-tile kv page bounds: the window lower bound is tightest at the
    # tile's FIRST token; the causal upper bound is set by its LAST live
    # token.  The in-kernel mask handles per-token exactness — these only
    # decide which pages are streamed at all (none for a dead tile).
    row_pad = pads[tile_row]
    lo = jnp.maximum(row_pad, tile_qpos0 - window + 1)
    hi = tile_qpos0 + jnp.maximum(tile_qlen, 1) - 1
    row_b0 = 0 if block0 is None else block0[tile_row]
    start = jnp.clip(lo // block_s - row_b0, 0, jnp.maximum(mb - 1, 0))
    nb = jnp.clip(hi // block_s + 1 - row_b0, 1, mb)
    count = jnp.where(tile_qlen > 0, jnp.maximum(nb - start, 0), 0)
    # the next tile with a page to stream, and the first of all: what a
    # tile's last live step prefetches, and which step has to fetch for
    # itself (``nt`` = none)
    tiles = jnp.arange(nt, dtype=jnp.int32)
    later = jax.lax.cummin(jnp.where(count > 0, tiles, nt), reverse=True)
    meta = jnp.stack([
        start, count, row_pad, tile_qpos0, tile_qlen,
        jnp.broadcast_to(window, tile_row.shape), tile_row,
        jnp.append(later[1:], nt), jnp.broadcast_to(later[0], tile_row.shape),
        *([] if block0 is None else [row_b0]),
    ]).astype(jnp.int32)  # [9, NT] (a table with a base: [10, NT])

    def tile_map(ti, j, meta_ref, tables_ref):
        return (ti // sub if wide_tile else ti, 0, 0, 0)

    def page_spec(p, block):
        """Page slot ``p`` of the step's group as a blocked operand; past
        the tile's last page it names that page, and keeps it."""
        zeros = (0,) * (len(block) - 1)

        def index_map(ti, j, meta_ref, tables_ref):
            last = jnp.maximum(meta_ref[_RM_COUNT, ti], 1) - 1
            block_i = meta_ref[_RM_START, ti] + jnp.minimum(
                j * pages + p, last)
            return (tables_ref[meta_ref[_RM_ROW, ti] * mb + block_i], *zeros)

        return pl.BlockSpec(block, index_map, memory_space=pltpu.VMEM)

    pools = [k_pages, v_pages] + ([k_scale, v_scale] if quantized else [])
    by_hand = tuple(_dma_slices_pages(a) for a in pools)
    # ``[BS, K, D]`` float pages the kernel copies itself go in as they
    # lie, ``[BS * K, D]`` — a position's kv heads on consecutive rows,
    # the same bytes in the same order (a bitcast on the chip: the
    # device keeps both shapes alike) — and are attended so: see
    # ``_ragged_kernel``
    heads_in_rows = (not merged and not quantized and all(by_hand)
                     and kh & (kh - 1) == 0)
    if wide_tile and not all(by_hand):
        raise ValueError(
            f"a wide tile attends pages the kernel copies itself; a DMA "
            f"cuts no page out of {k_pages.dtype}{list(k_pages.shape)}")
    if heads_in_rows:
        pools = [a.reshape(a.shape[0], block_s * kh, a.shape[-1])
                 for a in pools]
    tile_spec = pl.BlockSpec(
        (qt, kh, g, d), tile_map, memory_space=pltpu.VMEM)
    out_spec = pl.BlockSpec(
        (wq, kh, g, dv), tile_map, memory_space=pltpu.VMEM)
    in_specs, operands = [tile_spec], [qf]
    if merged:
        spread = _lane_spread(qf, wq, pack)
        in_specs, operands = [pl.BlockSpec(
            (1,) + spread.shape[1:], tile_map,
            memory_space=pltpu.VMEM)], [spread]
    rows = kh * qt * g
    if sink is not None:
        # one logit a query head, in the order of the score sheet's rows
        # (kv head, token of the tile, head of the group)
        in_specs.append(pl.BlockSpec(
            (rows, 1), lambda ti, j, meta_ref, tables_ref: (0, 0),
            memory_space=pltpu.VMEM))
        operands.append(jnp.broadcast_to(
            sink.astype(jnp.float32).reshape(kh, 1, g), (kh, qt, g)
        ).reshape(rows, 1))
    for a, hand in zip(pools, by_hand):
        if hand:
            in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
            operands.append(a)
        else:
            in_specs += [page_spec(p, (1,) + a.shape[1:])
                         for p in range(pages)]
            operands += [a] * pages
    scratch = [
        pltpu.VMEM((sub * rows, 1), jnp.float32),
        pltpu.VMEM((sub * rows, 1), jnp.float32),
        pltpu.VMEM((sub * rows, max(v_pack, 1) * dv), jnp.float32),
        # two halves of a group of pages for each array copied by hand
        *[pltpu.VMEM((2, pages) + a.shape[1:], a.dtype)
          for a, hand in zip(pools, by_hand) if hand],
    ]
    if any(by_hand):
        scratch += [pltpu.SemaphoreType.DMA((2,)),  # one a half
                    pltpu.SMEM((1,), jnp.int32)]  # whose turn it is
    out = pl.pallas_call(
        functools.partial(
            _ragged_kernel, scale=scale, softcap=logit_softcap,
            quantized=quantized, kv_heads=kh, group=g, block_s=block_s,
            q_tile=qt, head_dim=d, pages=pages, mb=mb, by_hand=by_hand,
            pack=pack, value_dim=dv, v_pack=v_pack,
            has_sink=sink is not None, has_base=block0 is not None,
            heads_in_rows=heads_in_rows, wide=wide_tile,
        ),
        out_shape=jax.ShapeDtypeStruct((t, kh, g, dv), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(nt, steps),
            in_specs=in_specs,
            out_specs=out_spec,
            scratch_shapes=scratch,
        ),
        interpret=interpret,
        **_wide_compiler_params(wide_tile, kh, g, d, dv, block_s, pages,
                                k_pages.dtype),
    )(meta, tables.reshape(-1).astype(jnp.int32), *operands)
    return out.reshape(t, h, dv)


def ragged_paged_attention_xla(
    q: jnp.ndarray,
    k_pages: jnp.ndarray,
    v_pages: jnp.ndarray,
    tables: jnp.ndarray,
    tok_row: jnp.ndarray,
    tok_slot: jnp.ndarray,
    tok_live: jnp.ndarray,
    pads: jnp.ndarray,
    window: jnp.ndarray,
    *,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    scale: float,
    logit_softcap: float | None = None,
    sink: jnp.ndarray | None = None,
    block0: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """XLA reference/fallback for ``ragged_paged_attention`` (per-TOKEN
    metadata instead of per-tile): gathers each engine row's blocks into
    a contiguous view and runs the standard masked GQA attention with
    every packed token as its own batch row — the mixed-step analogue of
    the engine's gather decode path.  Materializes [T, S_max, K, D], so
    it is the PROBE-FAILURE fallback and the parity oracle, not the fast
    path."""
    t, h, d = q.shape
    block_s = k_pages.shape[1]
    # (merged pages [NB, BS, K * D]: the gathered view takes the heads apart)
    kh = k_pages.shape[2] // d if k_pages.ndim == 3 else k_pages.shape[2]
    mb = tables.shape[1]
    s_max = mb * block_s

    def gathered(pages, scales):
        # (a value head may be narrower than a key head)
        view = pages[tables].reshape(tables.shape[0], s_max, kh, -1)
        if scales is None:
            return view
        sv = scales[tables].reshape(tables.shape[0], s_max, kh)
        from llm_np_cp_tpu.quant import dequantize_kv

        return dequantize_kv(view, sv, q.dtype)

    k_rows = gathered(k_pages, k_scale)
    v_rows = gathered(v_pages, v_scale)
    k_t = k_rows[tok_row]  # [T, S_max, K, D]
    v_t = v_rows[tok_row]
    kv_idx = jnp.arange(s_max, dtype=jnp.int32)[None, :]
    if block0 is not None:  # the table's column 0 is that logical block
        kv_idx = kv_idx + (block0[tok_row] * block_s)[:, None]
    lower = jnp.maximum(pads[tok_row], tok_slot - window + 1)[:, None]
    mask = (
        (kv_idx >= lower) & (kv_idx <= tok_slot[:, None])
        & tok_live[:, None]
    )  # [T, S_max]
    from llm_np_cp_tpu.ops.attention import gqa_attention

    out = gqa_attention(
        q[:, None], k_t, v_t, mask[:, None, :],
        scale=scale, logit_softcap=logit_softcap, sink=sink,
    )
    return out[:, 0]


@functools.partial(
    jax.jit,
    static_argnames=("scale", "logit_softcap", "block_s", "interpret"),
)
def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    k_scale: jnp.ndarray | None = None,
    v_scale: jnp.ndarray | None = None,
    scale: float,
    logit_softcap: float | None = None,
    block_s: int = 512,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One-token GQA attention against the cache.

    q [B, 1, H, D], k/v [B, S, K, D], mask [B, S] bool (True = visible)
    → [B, 1, H, D].  Equivalent to ``gqa_attention(q, k, v, mask[:,None,:])``
    — verified against it in tests.

    int8 cache mode: pass k/v as int8 with ``k_scale``/``v_scale``
    [B, S, K] (quant.quantize_kv layout); the kernel streams 1-byte
    values from HBM and dequantizes in VMEM — the combination that would
    otherwise materialize full dequantized slabs per step.

    interpret=None auto-selects: compiled on TPU, interpreter elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = k_scale is not None
    if (
        quantized != (k.dtype == jnp.int8)
        or quantized != (v.dtype == jnp.int8)
        or quantized != (v_scale is not None)
    ):
        raise ValueError(
            "int8 k AND v require both k_scale and v_scale (and vice "
            f"versa); got k={k.dtype}, v={v.dtype}, "
            f"k_scale={'set' if k_scale is not None else None}, "
            f"v_scale={'set' if v_scale is not None else None}"
        )
    b, one, h, d = q.shape
    assert one == 1, f"decode_attention is q_len=1 only, got {one}"
    _, s, kh, _ = k.shape
    g = h // kh
    out_dtype = q.dtype

    # ZERO-COPY contract: decode is HBM-bound on streaming the cache slab,
    # so the kernel reads K/V in their NATIVE [B, S, K, D] layout via 4-D
    # BlockSpecs whose trailing (K, D) dims are the FULL array dims — no
    # transpose/pad materialization of the slabs, and Mosaic's trailing-
    # dims alignment rule is satisfied for any K/D.  q's head split
    # [B,1,H,D]→[B,K,G,D] is a free reshape.
    qf = q.reshape(b, kh, g, d)  # [B, K, G, D]

    try:
        block_s = select_block_s(
            s, kh, d, jnp.dtype(k.dtype).itemsize, block_s, quantized
        )
    except ValueError:
        # no aligned divisor and too large for one block: PAD the cache
        # axis to the alignment and mask the tail off (the r4 fdec fix —
        # a few dead slots beat a Mosaic rejection at first dispatch)
        s_pad = -(-s // _BLOCK_S_ALIGN) * _BLOCK_S_ALIGN
        pad = [(0, 0), (0, s_pad - s), (0, 0), (0, 0)]
        k = jnp.pad(k, pad)
        v = jnp.pad(v, pad)
        mask = jnp.pad(mask, [(0, 0), (0, s_pad - s)])  # False = invisible
        if quantized:
            k_scale = jnp.pad(k_scale, [(0, 0), (0, s_pad - s), (0, 0)])
            v_scale = jnp.pad(v_scale, [(0, 0), (0, s_pad - s), (0, 0)])
        s = s_pad
        block_s = select_block_s(
            s, kh, d, jnp.dtype(k.dtype).itemsize, block_s, quantized
        )
    mask3 = mask[:, :, None]  # [B, S, 1]: trailing dims (block_s, 1)
    n_blocks = s // block_s
    bounds = _block_bounds(mask, block_s, n_blocks)

    # kv blocks clamp into the row's visible range: a clamped (repeated)
    # index skips the DMA, so invisible blocks are never streamed
    def _kv_map(bi, j, bounds_ref):
        jj = jnp.minimum(bounds_ref[0, bi] + j, bounds_ref[1, bi] - 1)
        return (bi, jj, 0, 0)

    def _kv3_map(bi, j, bounds_ref):
        jj = jnp.minimum(bounds_ref[0, bi] + j, bounds_ref[1, bi] - 1)
        return (bi, jj, 0)

    in_specs = [
        pl.BlockSpec((1, kh, g, d), lambda bi, j, bounds_ref: (bi, 0, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_s, kh, d), _kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_s, kh, d), _kv_map, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_s, 1), _kv3_map, memory_space=pltpu.VMEM),
    ]
    operands = [qf, k, v, mask3]
    if quantized:
        scale_spec = pl.BlockSpec(
            (1, block_s, kh), _kv3_map, memory_space=pltpu.VMEM
        )
        in_specs += [scale_spec, scale_spec]
        operands += [k_scale, v_scale]
    out = pl.pallas_call(
        functools.partial(
            _decode_kernel, scale=scale, softcap=logit_softcap,
            quantized=quantized, kv_heads=kh, group=g,
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), out_dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_blocks),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, kh, g, d), lambda bi, j, bounds_ref: (bi, 0, 0, 0),
                memory_space=pltpu.VMEM,
            ),
            scratch_shapes=[
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, d), jnp.float32),
            ],
        ),
        interpret=interpret,
    )(bounds, *operands)

    return out.reshape(b, 1, h, d)
