"""Fused Pallas decode-step attention == the XLA path (VERDICT r2 task 8).

The kernel is mask-driven, so the parity matrix covers exactly the decode
features the mask encodes: cache validity (partial fill), ragged left-pad
holes, sliding windows, GQA grouping, and attention-logit softcapping.
Runs in interpreter mode on CPU (same kernel logic the TPU compiles).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.ops.attention import gqa_attention
from llm_np_cp_tpu.ops.pallas.decode_attention import decode_attention


def _rand(rng, shape):
    return jnp.asarray(rng.standard_normal(shape, dtype=np.float32))


@pytest.mark.parametrize("h,kh", [(4, 4), (8, 2), (4, 1)])
@pytest.mark.parametrize("s", [7, 64, 200])
def test_matches_xla_gqa(h, kh, s):
    rng = np.random.default_rng(h * s)
    b, d = 3, 16
    q = _rand(rng, (b, 1, h, d))
    k = _rand(rng, (b, s, kh, d))
    v = _rand(rng, (b, s, kh, d))
    # partially-filled cache with ragged holes
    mask = jnp.asarray(rng.random((b, s)) > 0.3)
    mask = mask.at[:, 0].set(True)  # every row sees something
    want = gqa_attention(q, k, v, mask[:, None, :], scale=d**-0.5)
    got = decode_attention(q, k, v, mask, scale=d**-0.5, block_s=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_softcap_parity():
    rng = np.random.default_rng(0)
    b, s, h, kh, d = 2, 33, 4, 2, 8
    q = _rand(rng, (b, 1, h, d)) * 3
    k = _rand(rng, (b, s, kh, d)) * 3
    v = _rand(rng, (b, s, kh, d))
    mask = jnp.ones((b, s), bool)
    want = gqa_attention(q, k, v, mask[:, None, :], scale=0.5, logit_softcap=20.0)
    got = decode_attention(q, k, v, mask, scale=0.5, logit_softcap=20.0, block_s=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


# ---------------------------------------------------------------------------
# Block-shape validation/padding (the BENCH_TPU_LIVE_r4 fdec warm-log
# divisibility failure): a partial block_s must satisfy Mosaic's
# strictest sublane tile among the streamed operands — the 1-byte bool
# mask needs 32 — or the kernel must pad, never hand Mosaic an
# unaligned partial block.  Interpret mode hides the rejection, so the
# regression is pinned on the SELECTION and on padded-path numerics.
# ---------------------------------------------------------------------------

def test_select_block_s_partial_blocks_are_32_aligned():
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        _BLOCK_S_ALIGN,
        select_block_s,
    )

    # the offending class: s with 8-aligned-but-not-32-aligned divisors
    # only (528 = 16*33; the old selector picked 264 under a small
    # request/VMEM cap — a bool-mask block Mosaic rejects on hardware)
    # 8/16 hints were valid pre-32 and must clamp up, not mis-raise on a
    # perfectly divisible cache with an empty candidate range
    for s, req in ((528, 264), (384, 512), (200, 64), (4224, 2048),
                   (264, 64), (1001, 512), (16384, 8), (1024, 16)):
        got = select_block_s(s, kv_heads=2, head_dim=64, kv_itemsize=2,
                             requested=req, quantized=False)
        assert got == s or (got % _BLOCK_S_ALIGN == 0 and s % got == 0), (
            f"s={s}: block_s={got} is a partial block Mosaic would reject"
        )


def test_decode_attention_pads_unaligned_oversized_cache(monkeypatch):
    """A cache length with no aligned divisor AND too large for one
    VMEM block used to raise; now decode_attention pads the cache axis
    and masks the tail — results must match the XLA reference exactly."""
    import llm_np_cp_tpu.ops.pallas.decode_attention as da

    # shrink the VMEM budget so s=1000 (8*125, no 32-aligned divisor)
    # cannot be a single block — forcing the pad path
    monkeypatch.setattr(da, "_VMEM_BUDGET_BYTES", 64 * 1024)
    rng = np.random.default_rng(3)
    b, s, h, kh, d = 2, 1000, 4, 2, 16
    with pytest.raises(ValueError, match="aligned divisor"):
        da.select_block_s(s, kh, d, 4, 512, False)
    q = _rand(rng, (b, 1, h, d))
    k = _rand(rng, (b, s, kh, d))
    v = _rand(rng, (b, s, kh, d))
    mask = jnp.asarray(rng.random((b, s)) > 0.3)
    mask = mask.at[:, 0].set(True)
    want = gqa_attention(q, k, v, mask[:, None, :], scale=d**-0.5)
    got = da.decode_attention(q, k, v, mask, scale=d**-0.5, block_s=512)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5)


def test_block_bounds_cover_exactly_the_visible_blocks():
    """_block_bounds must include every block containing a visible slot
    (correctness) and exclude fully-invisible prefix/suffix blocks (the
    DMA-skip win); fully-masked rows degrade to one block."""
    from llm_np_cp_tpu.ops.pallas.decode_attention import _block_bounds

    block_s, n_blocks = 8, 4
    cases = [
        (np.r_[np.zeros(16, bool), np.ones(8, bool), np.zeros(8, bool)], 2, 3),
        (np.ones(32, bool), 0, 4),                   # all visible
        (np.zeros(32, bool), 0, 1),                  # nothing visible
        (np.r_[np.ones(1, bool), np.zeros(31, bool)], 0, 1),   # first slot only
        (np.r_[np.zeros(31, bool), np.ones(1, bool)], 3, 4),   # last slot only
    ]
    mask = jnp.asarray(np.stack([c[0] for c in cases]))
    bounds = np.asarray(_block_bounds(mask, block_s, n_blocks))
    for i, (_, want_start, want_nb) in enumerate(cases):
        assert bounds[0, i] == want_start, f"case {i} start"
        assert bounds[1, i] == want_nb, f"case {i} nb"


def test_middle_band_mask_parity():
    """A visibility band in the middle of the slab (blocks skipped on both
    sides) must still match the oracle — guards the clamp arithmetic."""
    rng = np.random.default_rng(5)
    b, s, h, kh, d = 2, 256, 4, 2, 16
    q = _rand(rng, (b, 1, h, d))
    k = _rand(rng, (b, s, kh, d))
    v = _rand(rng, (b, s, kh, d))
    mask = np.zeros((b, s), bool)
    mask[0, 100:140] = True   # spans blocks 1-2 of 4 at block_s=64
    mask[1, 250:] = True      # last block only
    mask = jnp.asarray(mask)
    want = gqa_attention(q, k, v, mask[:, None, :], scale=d**-0.5)
    got = decode_attention(q, k, v, mask, scale=d**-0.5, block_s=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_decode_loop_token_parity():
    """Full fused decode loop with attn_impl='flash_decode' emits the same
    greedy tokens as the XLA loop, from the same prefilled cache."""
    from llm_np_cp_tpu.config import tiny_config
    from llm_np_cp_tpu.generate import Generator
    from llm_np_cp_tpu.models.transformer import init_params
    from llm_np_cp_tpu.ops.sampling import Sampler

    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, (14,))

    a = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.float32).generate(prompt, 10).tokens
    b = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.float32,
                  decode_attn="flash_decode").generate(prompt, 10).tokens
    np.testing.assert_array_equal(a, b)


def test_decode_loop_gemma2_sliding_parity():
    """Sliding-window layers reach the kernel through the mask."""
    from llm_np_cp_tpu.config import tiny_config
    from llm_np_cp_tpu.generate import Generator
    from llm_np_cp_tpu.models.transformer import init_params
    from llm_np_cp_tpu.ops.sampling import Sampler

    cfg = tiny_config("gemma2")
    assert cfg.sliding_window is not None
    params = init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)
    prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, (11,))

    a = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.float32).generate(prompt, 8).tokens
    b = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.float32,
                  decode_attn="flash_decode").generate(prompt, 8).tokens
    np.testing.assert_array_equal(a, b)


def test_fully_masked_row_yields_zeros():
    """A row with nothing visible emits zeros, not the mean of V (the
    p-re-zeroing path: with m == NEG_INF, exp(s - m) would be 1)."""
    rng = np.random.default_rng(9)
    b, s, h, kh, d = 2, 16, 2, 1, 8
    q = _rand(rng, (b, 1, h, d))
    k = _rand(rng, (b, s, kh, d))
    v = _rand(rng, (b, s, kh, d))
    mask = jnp.zeros((b, s), bool).at[1].set(True)  # row 0 fully masked
    got = np.asarray(decode_attention(q, k, v, mask, scale=1.0, block_s=8))
    assert np.all(got[0] == 0.0)
    want = gqa_attention(q[1:2], k[1:2], v[1:2],
                         mask[1:2, None, :], scale=1.0)
    np.testing.assert_allclose(got[1:2], np.asarray(want), atol=2e-5)


def test_generator_rejects_unknown_decode_impl():
    from llm_np_cp_tpu.config import tiny_config
    from llm_np_cp_tpu.generate import Generator
    from llm_np_cp_tpu.models.transformer import init_params

    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    with pytest.raises(ValueError, match="decode_attn"):
        Generator(params, cfg, decode_attn="pallas")


def test_decode_loop_under_tp_mesh_parity():
    """flash_decode inside a TP=4-sharded decode loop emits the same
    tokens as single-device XLA (JAX reshards around the pallas_call;
    whether that's FAST is the bench's question, correctness is ours)."""
    from llm_np_cp_tpu.config import tiny_config
    from llm_np_cp_tpu.generate import Generator
    from llm_np_cp_tpu.models.transformer import init_params
    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.parallel.sharding import (
        MeshPlan, make_mesh, shard_params,
    )

    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    prompt = np.random.default_rng(1).integers(0, cfg.vocab_size, (14,))
    want = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                     cache_dtype=jnp.float32).generate(prompt, 8).tokens

    plan = MeshPlan(model=4)
    mesh = make_mesh(plan)
    p_sh = shard_params(params, cfg, plan, mesh)
    with jax.set_mesh(mesh):
        got = Generator(p_sh, cfg, sampler=Sampler(kind="greedy"),
                        cache_dtype=jnp.float32,
                        decode_attn="flash_decode").generate(prompt, 8).tokens
    np.testing.assert_array_equal(want, got)


def test_ragged_batch_parity():
    """Left-padded ragged batches: pad holes are invisible via the mask."""
    from llm_np_cp_tpu.config import tiny_config
    from llm_np_cp_tpu.generate import Generator
    from llm_np_cp_tpu.models.transformer import init_params
    from llm_np_cp_tpu.ops.sampling import Sampler

    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(4), cfg, dtype=jnp.float32)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n in (5, 9, 12)]

    a = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.float32).generate_ragged(prompts, 6).tokens
    b = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.float32,
                  decode_attn="flash_decode").generate_ragged(prompts, 6).tokens
    np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Ragged variant: the kernel the served tick runs (ServeEngine.mixed_step),
# over the serving pool (serve/block_pool.py layout): ``ragged_paged_attention``
# against BOTH its XLA twin (the probe-failure fallback the engine degrades
# to) and an independent per-token ``gqa_attention`` reference, then a
# matrix of row mixes the packer produces: decode rows, prefill chunks and
# speculative verify slices in one packed batch, dead lanes included.
# Contract (kernel docstring): token i of a tile sits at cache slot
# ``qpos0 + i`` of its row and sees slots [max(pad, slot - window + 1), slot].
# ---------------------------------------------------------------------------

_NO_WINDOW = 1 << 30


def _pack_segments(segs, dead_tiles=0, wide=0):
    """``[(row, qpos0, n_tokens)]`` → the kernel's per-TILE metadata, the
    XLA twin's per-TOKEN metadata and the packed width, laid out the way
    the engine's packer does: each segment on its own q tiles, the tail
    of its last tile dead, a zero-token segment one dead tile where it
    stands, ``dead_tiles`` whole dead tiles at the end (the bucket's
    padding).  ``wide``: a segment's whole tiles of ``wide`` tokens come
    FIRST, each named by the first of its ``wide / 8`` tile entries (the
    others dead), and what is left of it where it stood; the width is
    rounded up to whole wide tiles by dead ones.  A segment ``(row,
    qpos0, -n)`` is ONE wide tile of ``n < wide`` tokens."""
    from llm_np_cp_tpu.ops.pallas.decode_attention import RAGGED_Q_TILE as qt

    tile_row, tile_qpos0, tile_qlen = [], [], []
    tok_row, tok_slot, tok_live = [], [], []
    rest = []
    for row, qpos0, n in segs if wide else ():
        # the tokens of the segment that go into wide tiles (``-n``: all)
        whole = n // wide * wide if n > 0 else -n
        for i in range(0, whole, wide):
            live = min(wide, whole - i)
            tile_row += [row] * (wide // qt)
            tile_qpos0 += [qpos0 + i] + [0] * (wide // qt - 1)
            tile_qlen += [live] + [0] * (wide // qt - 1)
            tok_row += [row] * wide
            tok_slot += [qpos0 + i + j if j < live else 0 for j in range(wide)]
            tok_live += [j < live for j in range(wide)]
        if n == 0 or 0 <= whole < n:  # what is left of it (or its dead tile)
            rest.append((row, qpos0 + whole, n - whole))
    for row, qpos0, n in rest if wide else segs:
        if n == 0:  # a dead tile BETWEEN live ones (a row that left)
            tile_row.append(row), tile_qpos0.append(0), tile_qlen.append(0)
            tok_row += [row] * qt
            tok_slot += [0] * qt
            tok_live += [False] * qt
        for i in range(0, n, qt):
            live = min(qt, n - i)
            tile_row.append(row)
            tile_qpos0.append(qpos0 + i)
            tile_qlen.append(live)
            for j in range(qt):
                tok_row.append(row)
                tok_slot.append(qpos0 + i + j if j < live else 0)
                tok_live.append(j < live)
    if wide:
        dead_tiles += -(len(tile_row) + dead_tiles) % (wide // qt)
    for _ in range(dead_tiles):
        tile_row.append(0), tile_qpos0.append(0), tile_qlen.append(0)
        tok_row += [0] * qt
        tok_slot += [0] * qt
        tok_live += [False] * qt
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    return ((i32(tile_row), i32(tile_qpos0), i32(tile_qlen)),
            (i32(tok_row), i32(tok_slot), jnp.asarray(tok_live)),
            len(tok_row))


def _ragged_reference(q, pages_k, pages_v, tables, tok, pads, window, *,
                      scale, logit_softcap=None, sink=None):
    """One ``gqa_attention`` call a live token over its row's blocks
    gathered contiguous; dead lanes stay zero."""
    tok_row, tok_slot, tok_live = (np.asarray(a) for a in tok)
    bs, kh, d = pages_k.shape[1:]
    out = np.zeros(q.shape, np.float32)
    for t in np.flatnonzero(tok_live):
        row, slot = int(tok_row[t]), int(tok_slot[t])
        gk = pages_k[tables[row]].reshape(1, -1, kh, d)
        gv = pages_v[tables[row]].reshape(1, -1, kh, d)
        pos = jnp.arange(gk.shape[1])
        lo = max(int(pads[row]), slot - window + 1)
        mask = ((pos >= lo) & (pos <= slot))[None, None, :]
        out[t] = np.asarray(gqa_attention(
            q[t][None, None], gk, gv, mask, scale=scale,
            logit_softcap=logit_softcap, sink=sink))[0, 0]
    return out


def _merge(pages):
    """``[NB, BS, K, D]`` → the same pool contents as the serve pool
    stores a ``head_dim``-64 page: ``[NB, BS, K * D]``, heads side by
    side (serve/block_pool.py)."""
    return pages.reshape(pages.shape[:2] + (-1,))


def _check_ragged(q, pages_k, pages_v, tables, segs, pads, *, scale,
                  window=_NO_WINDOW, logit_softcap=None, dead_tiles=0,
                  scales=None, float_pages=None, merged=False, wide=0,
                  sink=None):
    """Kernel == XLA twin == reference on the live lanes; the kernel's
    dead lanes are exactly zero (what the step scatters to scratch and
    the host discards must never be NaN or another row's output).
    ``merged``: kernel and twin get the pages ``[NB, BS, K * D]``; the
    reference keeps the 4-D pool they were made from.  ``wide``: the
    segments' whole wide tiles first (``_pack_segments``)."""
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        ragged_paged_attention,
        ragged_paged_attention_xla,
    )

    tile, tok, width = _pack_segments(segs, dead_tiles, wide)
    assert q.shape[0] == width
    kw = dict(scale=scale, logit_softcap=logit_softcap, sink=sink)
    if scales is not None:
        kw.update(k_scale=scales[0], v_scale=scales[1])
    win = jnp.asarray(window, jnp.int32)
    kernel_k, kernel_v = ((_merge(pages_k), _merge(pages_v)) if merged
                          else (pages_k, pages_v))
    got = np.asarray(ragged_paged_attention(
        q, kernel_k, kernel_v, tables, *tile, pads, win, wide_tile=wide,
        **kw))
    twin = np.asarray(ragged_paged_attention_xla(
        q, kernel_k, kernel_v, tables, *tok, pads, win, **kw))
    fk, fv = float_pages if float_pages is not None else (pages_k, pages_v)
    want = _ragged_reference(q, fk, fv, tables, tok, pads, window,
                             scale=scale, logit_softcap=logit_softcap,
                             sink=sink)
    live = np.asarray(tok[2])
    assert live.any() and (dead_tiles == 0 or not live.all())
    np.testing.assert_allclose(got[live], want[live], atol=2e-5)
    np.testing.assert_allclose(twin[live], want[live], atol=2e-5)
    assert np.all(got[~live] == 0.0)
    return got


def _decode_segs(lengths):
    """One decode row a sequence: its one token sits at the last slot."""
    return [(r, int(n) - 1, 1) for r, n in enumerate(lengths)]


def _packed_q(rng, segs, h, d, dead_tiles=0, wide=0):
    width = _pack_segments(segs, dead_tiles, wide)[2]
    return _rand(rng, (width, h, d))


def _by_token(out, segs, dead_tiles=0, wide=0):
    """A packed result's live lanes in the order (row, cache slot): what
    two layouts of the same segments have in common."""
    tok_row, tok_slot, live = (
        np.asarray(a) for a in _pack_segments(segs, dead_tiles, wide)[1])
    lanes = np.flatnonzero(live)
    return out[lanes[np.lexsort((tok_slot[lanes], tok_row[lanes]))]]


_PAGE_FORMS = pytest.mark.parametrize(
    "merged", [False, True], ids=["4d", "merged"])


@_PAGE_FORMS
@pytest.mark.parametrize("h,kh", [(4, 4), (8, 2), (4, 1)])
def test_ragged_matches_gathered_contiguous(h, kh, merged):
    rng = np.random.default_rng(h * 7 + kh)
    d, nbp, bs = 16, 8, 16
    pages_k = _rand(rng, (nbp, bs, kh, d))
    pages_v = _rand(rng, (nbp, bs, kh, d))
    # permuted tables with scratch-0 padding past each row's allocation
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [7, 6, 5, 4]], jnp.int32)
    segs = _decode_segs([40, 17, 64])  # mid-block, 1-past, full
    pads = jnp.asarray([3, 0, 10], jnp.int32)
    _check_ragged(_packed_q(rng, segs, h, d), pages_k, pages_v, tables,
                  segs, pads, scale=d**-0.5, merged=merged)


def test_ragged_softcap_parity():
    rng = np.random.default_rng(0)
    h, kh, d, nbp, bs = 4, 2, 8, 6, 8
    pages_k = _rand(rng, (nbp, bs, kh, d)) * 3
    pages_v = _rand(rng, (nbp, bs, kh, d))
    tables = jnp.asarray([[5, 1, 2], [3, 4, 0]], jnp.int32)
    segs = _decode_segs([24, 9])
    pads = jnp.asarray([2, 0], jnp.int32)
    _check_ragged(_packed_q(rng, segs, h, d) * 3, pages_k, pages_v, tables,
                  segs, pads, scale=0.5, logit_softcap=20.0)


def test_ragged_int8_pool_matches_dequantized_gather():
    """int8 pool blocks + scale pages through the ragged kernel must
    match the gathered-dequantized oracle in f32 (``--cache-dtype int8``
    serves through this path)."""
    from llm_np_cp_tpu.quant import dequantize_kv, quantize_kv

    rng = np.random.default_rng(21)
    h, kh, d, nbp, bs = 8, 2, 16, 8, 16
    kq, ks = quantize_kv(_rand(rng, (nbp, bs, kh, d)))
    vq, vs = quantize_kv(_rand(rng, (nbp, bs, kh, d)))
    tables = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [7, 6, 5, 4]], jnp.int32)
    segs = _decode_segs([40, 17, 64])
    pads = jnp.asarray([3, 0, 10], jnp.int32)
    _check_ragged(
        _packed_q(rng, segs, h, d), kq, vq, tables, segs, pads,
        scale=d**-0.5, scales=(ks, vs),
        float_pages=(dequantize_kv(kq, ks, jnp.float32),
                     dequantize_kv(vq, vs, jnp.float32)),
    )


def test_ragged_int8_requires_both_scales():
    """int8 pages without scale pages (or scales with float pages) must
    refuse rather than misread quantized blocks as floats."""
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        RAGGED_Q_TILE,
        ragged_paged_attention,
    )

    q = jnp.zeros((RAGGED_Q_TILE, 4, 8))
    pages = jnp.zeros((2, 8, 2, 8), jnp.int8)
    scales = jnp.zeros((2, 8, 2), jnp.float32)
    one = jnp.zeros((1,), jnp.int32)
    args = (jnp.zeros((1, 1), jnp.int32), one, one + 3, one + 1, one,
            jnp.asarray(_NO_WINDOW, jnp.int32))
    with pytest.raises(ValueError, match="k_scale"):
        ragged_paged_attention(q, pages, pages, *args, scale=0.35)
    with pytest.raises(ValueError, match="k_scale"):
        ragged_paged_attention(
            q, pages, pages, *args, k_scale=scales, scale=0.35
        )
    with pytest.raises(ValueError, match="k_scale"):
        ragged_paged_attention(
            q, pages.astype(jnp.float32), pages.astype(jnp.float32), *args,
            k_scale=scales, v_scale=scales, scale=0.35,
        )


def test_ragged_leading_block_skip_parity():
    """Rows whose left pads span WHOLE blocks (start = pads // BS > 0):
    the kernel's grid clamp and the scalar-prefetch index map both begin
    at the first visible block — and the cells' geometry (prefill_chunk =
    2 * block_size) routinely produces pads >= BS."""
    rng = np.random.default_rng(42)
    h, kh, d, nbp, bs = 8, 2, 16, 10, 8
    pages_k = _rand(rng, (nbp, bs, kh, d))
    pages_v = _rand(rng, (nbp, bs, kh, d))
    tables = jnp.asarray(
        [[1, 2, 3, 4], [5, 6, 7, 0], [9, 8, 7, 6]], jnp.int32
    )
    # start blocks 1, 2, 3: mid-block pad, exact-boundary pad, and a row
    # whose single visible block is its LAST
    segs = _decode_segs([30, 24, 32])
    pads = jnp.asarray([9, 16, 25], jnp.int32)
    _check_ragged(_packed_q(rng, segs, h, d), pages_k, pages_v, tables,
                  segs, pads, scale=d**-0.5)


# row mixes of one packed batch, as (row, first slot, tokens): what a
# steady decode tick, an admission tick and a speculating tick pack
_RAGGED_MIXES = {
    "decode-rows": ([(0, 39, 1), (1, 16, 1), (2, 63, 1)], 0),
    # one 19-token chunk mid-prompt: three tiles, the last 3 of 8 live
    "prefill-chunk": ([(1, 5, 19)], 1),
    # decode rows + a prefill chunk + a verify slice (its own token and
    # 3 drafts) + two dead tiles of bucket padding
    "decode+prefill+verify4": (
        [(0, 39, 1), (3, 8, 13), (2, 50, 4), (1, 16, 1)], 2),
}


@_PAGE_FORMS
@pytest.mark.parametrize("h,kh", [(4, 4), (8, 2), (4, 1)])
@pytest.mark.parametrize("mix", list(_RAGGED_MIXES))
def test_ragged_row_mixes(mix, h, kh, merged):
    """Every token of a multi-token segment attends causally INSIDE its
    own freshly written segment (the step scatters the whole packed
    batch before attending), under a sliding window narrower than the
    context, and lanes no segment owns come back zero."""
    segs, dead_tiles = _RAGGED_MIXES[mix]
    rng = np.random.default_rng(len(mix) * 31 + h + kh)
    d, nbp, bs = 16, 12, 16
    pages_k = _rand(rng, (nbp, bs, kh, d))
    pages_v = _rand(rng, (nbp, bs, kh, d))
    tables = jnp.asarray(
        [[1, 2, 3, 0], [4, 5, 0, 0], [7, 6, 5, 4], [8, 9, 10, 11]], jnp.int32)
    pads = jnp.asarray([3, 0, 10, 6], jnp.int32)
    q = _packed_q(rng, segs, h, d, dead_tiles)
    full = _check_ragged(q, pages_k, pages_v, tables, segs, pads,
                         scale=d**-0.5, dead_tiles=dead_tiles, merged=merged)
    windowed = _check_ragged(q, pages_k, pages_v, tables, segs, pads,
                             scale=d**-0.5, window=12,
                             dead_tiles=dead_tiles, merged=merged)
    # the window bit: contexts here are longer than 12 slots
    assert not np.allclose(full, windowed, atol=1e-3)


# ---------------------------------------------------------------------------
# A kv grid step is a GROUP of P pages (PERF.md §6, PR 33).  The cases
# above fit one group; these walk several, at block size 64 where P = 8:
# rows that end one page short of a group, on its edge and one past it, a
# table width P does not divide, dead tiles between live ones, a window
# that starts mid-group, one row under 16 tiles, verify slices, the int8
# pool, and the page shapes of the benchmark's other configurations.
# ---------------------------------------------------------------------------

_BS = 64

# name: (h, kh, d, mb, segs, pads per row, window, int8 pool, pages copied
# by hand).  Whole rows of 128 lanes are what a DMA can slice: head_dim
# 128 runs the Qwen cells' path, the narrow heads above the blocked one.
_GROUP_CASES = {
    "rows-of-P-1-P-P+1-pages": (
        8, 2, 128, 16, _decode_segs([7 * _BS, 8 * _BS, 8 * _BS + 1, 449]),
        [0, 70, 3, 0], _NO_WINDOW, False, True),
    "mb-42-not-a-multiple-of-P": (
        8, 2, 128, 42, _decode_segs([42 * _BS, 41 * _BS - 6, 1100, 30]),
        [5, 0, 200, 0], _NO_WINDOW, False, True),
    "dead-tiles-between-live-ones": (
        4, 1, 128, 16,
        [(0, 500, 1), (3, 0, 0), (1, 70, 1), (0, 0, 0), (2, 0, 0),
         (2, 900, 3)],
        [0, 9, 130, 0], _NO_WINDOW, False, True),
    "window-starts-mid-group": (
        8, 2, 128, 16, [(0, 999, 1), (1, 640, 1), (2, 350, 5), (3, 700, 12)],
        [0, 500, 0, 64], 300, False, True),
    "prefill-chunk-of-16-tiles-on-one-row": (
        4, 2, 128, 16, [(0, 410, 1), (1, 600, 128)], [0, 17], _NO_WINDOW,
        False, True),
    "verify-tiles-of-2-to-5": (
        8, 2, 128, 16, [(0, 520, 2), (1, 300, 3), (2, 62, 4), (3, 510, 5)],
        [0, 0, 7, 129], _NO_WINDOW, False, True),
    # two int8 kv heads fill half a tile: K/V and scales all blocked
    "int8-pool-kh2-blocked": (
        8, 2, 128, 16, [(0, 515, 1), (1, 800, 11), (2, 40, 1)], [3, 64, 0],
        _NO_WINDOW, True, False),
    # four do: K/V copied by hand, the scale pages blocked beside them
    "int8-pool-kh4-copied": (
        8, 4, 128, 16, [(0, 515, 1), (1, 800, 11), (2, 40, 1)], [3, 64, 0],
        _NO_WINDOW, True, True),
    # head_dim 64 is half a row of lanes: blocked
    "lfm2-page-kh8-d64-blocked": (
        32, 8, 64, 16, [(0, 600, 1), (1, 447, 1), (2, 512, 3)], [0, 0, 66],
        _NO_WINDOW, False, False),
    "qwen3b-page-g8-d128": (
        16, 2, 128, 16, [(0, 1023, 1), (1, 100, 9), (2, 512, 1)], [0, 3, 0],
        _NO_WINDOW, False, True),
}


def _group_pool(rng, kh, d, mb, rows):
    """A pool and a table of distinct pages a row, block 0 left as the
    scratch block no table names."""
    nbp = rows * mb + 1
    tables = (rng.permutation(nbp - 1) + 1).reshape(rows, mb)
    return (_rand(rng, (nbp, _BS, kh, d)), _rand(rng, (nbp, _BS, kh, d)),
            jnp.asarray(tables, jnp.int32))


@pytest.mark.parametrize("case", list(_GROUP_CASES))
def test_ragged_groups_of_pages(case):
    from llm_np_cp_tpu.quant import dequantize_kv, quantize_kv
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        ragged_pages_per_step,
    )

    h, kh, d, mb, segs, pads, window, int8, _ = _GROUP_CASES[case]
    rng = np.random.default_rng(len(case) * 13 + h)
    pages_k, pages_v, tables = _group_pool(rng, kh, d, mb, len(pads))
    kw = {}
    if int8:
        (pages_k, ks), (pages_v, vs) = quantize_kv(pages_k), quantize_kv(pages_v)
        kw = dict(scales=(ks, vs),
                  float_pages=(dequantize_kv(pages_k, ks, jnp.float32),
                               dequantize_kv(pages_v, vs, jnp.float32)))
    # the case means what its name says only while a step is 8 pages
    # (4 where four int8 heads are dequantized in VMEM)
    p = ragged_pages_per_step(mb, _BS, kh, d, pages_k.dtype, int8)
    assert p == (4 if int8 and kh == 4 else 8) and -(-mb // p) > 1
    dead = 1
    _check_ragged(_packed_q(rng, segs, h, d, dead), pages_k, pages_v, tables,
                  segs, jnp.asarray(pads, jnp.int32), scale=d**-0.5,
                  window=window, dead_tiles=dead, **kw)


def test_ragged_wholly_dead_batch_is_zeros():
    """A program dispatched with no live tile (every row left between
    plan and pack) streams nothing and returns zeros, not NaN and not a
    page's contents."""
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        RAGGED_Q_TILE,
        ragged_paged_attention,
    )

    rng = np.random.default_rng(5)
    pages_k, pages_v, tables = _group_pool(rng, 2, 16, 16, 2)
    nt = 4
    zeros = jnp.zeros((nt,), jnp.int32)
    out = ragged_paged_attention(
        _rand(rng, (nt * RAGGED_Q_TILE, 8, 16)), pages_k * jnp.nan, pages_v,
        tables, zeros, zeros + 700, zeros, jnp.zeros((2,), jnp.int32),
        jnp.asarray(_NO_WINDOW, jnp.int32), scale=0.25)
    assert np.all(np.asarray(out) == 0.0)


@pytest.mark.parametrize("case", list(_GROUP_CASES))
def test_ragged_group_cases_take_the_path_they_name(case):
    """Which pool arrays the kernel copies page by page itself and which
    ride the automatic pipeline is read off their shapes
    (``_dma_slices_pages``): the cases above cover both."""
    from llm_np_cp_tpu.ops.pallas.decode_attention import _dma_slices_pages

    _, kh, d, _, _, _, _, int8, by_hand = _GROUP_CASES[case]
    page = jax.ShapeDtypeStruct(
        (9, _BS, kh, d), jnp.int8 if int8 else jnp.float32)
    assert _dma_slices_pages(page) is by_hand
    if int8:  # a scale a head: never whole lanes
        assert not _dma_slices_pages(
            jax.ShapeDtypeStruct((9, _BS, kh), jnp.float32))


# ---------------------------------------------------------------------------
# MERGED pages (PERF.md section 6, PR 38): a float pool whose head_dim is
# short of a row of lanes is stored ``[NB, BS, K * D]``.  The kernel takes
# such pages as they lie: a DMA can cut one out, the heads that share a row
# of 128 lanes are scored by one dot, and each head's lanes are its own.
# Same pool contents, both forms: the merged kernel == the 4-D kernel == the
# XLA twin (on merged pages) == the reference.
# ---------------------------------------------------------------------------

# name: (h, kh, d, mb, segs, pads per row, window)
_MERGED_CASES = {
    # LFM2 / Llama-3.2-1B pages, K * D = 512: decode rows that end one page
    # short of a group, on its edge, one past it and mid-group
    "kd512-rows-of-P-1-P-P+1-pages": (
        32, 8, 64, 16, _decode_segs([7 * _BS, 8 * _BS, 8 * _BS + 1, 449]),
        [0, 70, 3, 0], _NO_WINDOW),
    # K * D = 256: a prefill chunk of 16 tiles on one row beside a decode row
    "kd256-prefill-chunk-of-16-tiles": (
        8, 4, 64, 16, [(0, 410, 1), (1, 600, 128)], [0, 17], _NO_WINDOW),
    # K * D = 128 (Qwen2.5-0.5B, one row of lanes): a sliding window that
    # starts mid-group
    "kd128-window-starts-mid-group": (
        14, 2, 64, 16, [(0, 999, 1), (1, 640, 1), (2, 350, 5), (3, 700, 12)],
        [0, 500, 0, 64], 300),
    "kd128-dead-tiles-between-live-ones": (
        4, 2, 64, 16,
        [(0, 500, 1), (3, 0, 0), (1, 70, 1), (0, 0, 0), (2, 0, 0),
         (2, 900, 3)],
        [0, 9, 130, 0], _NO_WINDOW),
    "kd512-verify-tiles-of-2-to-5": (
        32, 8, 64, 16, [(0, 520, 2), (1, 300, 3), (2, 62, 4), (3, 510, 5)],
        [0, 0, 7, 129], _NO_WINDOW),
    # a table width P does not divide
    "kd512-mb-42-not-a-multiple-of-P": (
        16, 8, 64, 42, _decode_segs([42 * _BS, 41 * _BS - 6, 1100, 30]),
        [5, 0, 200, 0], _NO_WINDOW),
    # four heads of 32 to a row of lanes
    "kd128-four-heads-of-32": (
        8, 4, 32, 16, [(0, 600, 1), (1, 447, 1), (2, 512, 3)], [0, 0, 66],
        _NO_WINDOW),
    # heads that fill their own rows (nothing to take apart): still merged
    "kd256-heads-of-128": (
        8, 2, 128, 16, [(0, 1023, 1), (1, 100, 9), (2, 512, 1)], [0, 3, 0],
        _NO_WINDOW),
    # WIDE tiles (PERF.md section 6, PR 57): a prompt chunk's tokens 64 /
    # 32 / 16 to a tile, each of which walks its row's pages once.  A
    # chunk of 150 = two tiles of 64 + three of 8 lanes, beside a decode
    # row: the causal edge lies inside every wide tile, the row's pad
    # before the first
    "kd256-wide64-causal-edge-inside-the-tile": (
        8, 4, 64, 16, [(0, 410, 1), (1, 600, 150)], [0, 17], _NO_WINDOW,
        dict(wide=64)),
    # a window of 40 positions starts INSIDE each wide tile's own span;
    # one of 700 starts some groups of pages back, mid-group
    "kd256-wide64-window-starts-inside-the-tile": (
        8, 4, 64, 16, [(0, 410, 1), (1, 600, 150)], [0, 17], 40,
        dict(wide=64)),
    "kd256-wide64-window-starts-mid-group": (
        8, 4, 64, 16, [(0, 999, 1), (1, 820, 130)], [0, 17], 700,
        dict(wide=64)),
    # chunks whose lengths no width divides, two rows' worth, between
    # one-token decode tiles and a verify slice
    "kd512-wide32-chunks-of-70-and-45-beside-decode-rows": (
        32, 8, 64, 16,
        [(0, 999, 1), (1, 300, 70), (2, 62, 4), (3, 130, 45)],
        [0, 0, 7, 129], _NO_WINDOW, dict(wide=32)),
    "kd128-wide16-four-heads-of-32": (
        8, 4, 32, 16, [(0, 600, 1), (1, 447, 37), (2, 512, 3)], [0, 0, 66],
        _NO_WINDOW, dict(wide=16)),
    # heads of 128 (a head is a page's row of lanes) under learned sinks,
    # behind a pad of more than a page
    "kd256-wide64-heads-of-128-with-sinks": (
        12, 2, 128, 16, [(0, 1023, 1), (1, 100, 200), (2, 512, 1)],
        [0, 70, 0], 300, dict(wide=64, sink=True)),
    # ONE wide tile that is not full (50 of 64 lanes live): the packer
    # lays none, the kernel masks its dead rows as it does a tile's
    "kd256-wide64-a-tile-of-50-tokens": (
        8, 4, 64, 16, [(0, 410, 1), (1, 600, -50)], [0, 17], _NO_WINDOW,
        dict(wide=64)),
}


@pytest.mark.parametrize("case", list(_MERGED_CASES))
def test_ragged_merged_pages(case):
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        _dma_slices_pages,
        _lane_pack,
        ragged_pages_per_step,
        ragged_wide_tile,
    )

    h, kh, d, mb, segs, pads, window, *more = _MERGED_CASES[case]
    wide, with_sink = (more[0].get(k, 0) for k in ("wide", "sink")) if more else (0, 0)
    rng = np.random.default_rng(len(case) * 17 + h)
    pages_k, pages_v, tables = _group_pool(rng, kh, d, mb, len(pads))
    assert (kh * d) % 128 == 0 and f"kd{kh * d}" in case
    # several groups a row, copied by the kernel's own DMAs
    p = ragged_pages_per_step(mb, _BS, kh, d, pages_k.dtype, False, merged=True)
    assert p == 8 and -(-mb // p) > 1
    assert _dma_slices_pages(_merge(pages_k))
    assert _lane_pack(kh, d) == max(128 // d, 1)
    dead = 1
    q = _packed_q(rng, segs, h, d, dead, wide)
    sink = _rand(rng, (h,)) * 3 if with_sink else None
    kw = dict(scale=d**-0.5, window=window, dead_tiles=dead, sink=sink)
    pads = jnp.asarray(pads, jnp.int32)
    got = _check_ragged(q, pages_k, pages_v, tables, segs, pads, merged=True,
                        wide=wide, **kw)
    if not wide:
        plain = _check_ragged(q, pages_k, pages_v, tables, segs, pads, **kw)
        np.testing.assert_allclose(got, plain, atol=2e-5)
        return
    assert f"wide{wide}" in case and wide <= ragged_wide_tile(
        kh, h // kh, d, d, pages_k.dtype, True)
    # the same tokens in tiles of 8 lanes alone (a tile of fewer than
    # ``wide`` tokens: as a segment of that many): the same result
    segs = [(r, p0, abs(n)) for r, p0, n in segs]
    _, (row8, slot8, live8), width8 = _pack_segments(segs, dead)
    lanes = np.flatnonzero(np.asarray(live8))
    q8 = np.zeros((width8, h, d), np.float32)
    q8[lanes[np.lexsort((np.asarray(slot8)[lanes],
                         np.asarray(row8)[lanes]))]] = _by_token(
        np.asarray(q), _MERGED_CASES[case][4], dead, wide)
    plain = _check_ragged(jnp.asarray(q8), pages_k, pages_v, tables, segs,
                          pads, merged=True, **kw)
    np.testing.assert_allclose(
        _by_token(got, _MERGED_CASES[case][4], dead, wide),
        _by_token(plain, segs, dead), atol=2e-5)


def test_ragged_merged_pages_refuse_what_they_cannot_be():
    """int8 pages stay beside their scale pages, and a merged page is whole
    heads of ``q``'s ``head_dim``."""
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        RAGGED_Q_TILE,
        ragged_paged_attention,
    )

    one = jnp.zeros((1,), jnp.int32)
    args = (jnp.zeros((1, 1), jnp.int32), one, one + 3, one + 1, one,
            jnp.asarray(_NO_WINDOW, jnp.int32))
    q = jnp.zeros((RAGGED_Q_TILE, 4, 8))
    scales = jnp.zeros((2, 8, 2), jnp.float32)
    with pytest.raises(ValueError, match="merged pages"):
        ragged_paged_attention(
            q, jnp.zeros((2, 8, 16), jnp.int8), jnp.zeros((2, 8, 16), jnp.int8),
            *args, k_scale=scales, v_scale=scales, scale=0.35)
    with pytest.raises(ValueError, match="merged pages"):
        ragged_paged_attention(
            q, jnp.zeros((2, 8, 12)), jnp.zeros((2, 8, 12)), *args, scale=0.35)


# ---------------------------------------------------------------------------
# A tile of ONE live token (PERF.md section 6, PR 44): a decode row, or the
# one-token tail of a prefill segment, attends that token's K x G score
# rows alone — the kernel branches on the tile's own ``tile_qlen``, and
# every form it has takes the branch.  Each case names the form; all are
# held against the XLA twin, and against the per-token reference where the
# reference knows the form (no sink, no table base, one head width).
# ---------------------------------------------------------------------------

# name: (h, kh, dk, dv, merged, segs, pads, window, softcap, int8, sink,
# block0 a row)
_ONE_TOKEN_CASES = {
    # decode-only batches at (H, K) of the benchmark's five configurations
    "decode-only-qwen1.5b-h12-k2": (
        12, 2, 128, 128, False, _decode_segs([449, 64, 65, 1000]),
        [0, 3, 0, 70], _NO_WINDOW, None, False, False, None),
    "decode-only-qwen3b-h16-k2": (
        16, 2, 128, 128, False, _decode_segs([512, 513, 30]),
        [0, 0, 5], _NO_WINDOW, None, False, False, None),
    "decode-only-falcon-h1-h20-k4": (
        20, 4, 128, 128, False, _decode_segs([600, 447, 64]),
        [0, 66, 0], _NO_WINDOW, None, False, False, None),
    "decode-only-lfm2-h32-k8-merged-pack2": (
        32, 8, 64, 64, True, _decode_segs([7 * _BS, 8 * _BS + 1, 449]),
        [0, 70, 3], _NO_WINDOW, None, False, False, None),
    "decode-only-mimo-global-h64-k4-dk192-dv128-merged": (
        64, 4, 192, 128, True, _decode_segs([900, 129]),
        [0, 0], _NO_WINDOW, None, False, False, None),
    "decode-only-mimo-window-h64-k8-sink-block0": (
        64, 8, 192, 128, True, _decode_segs([900, 300]),
        [0, 0], 128, None, False, True, [12, 2]),
    # decode and prefill tiles alternating in one call
    "decode-and-prefill-tiles-alternating": (
        12, 2, 128, 128, False,
        [(0, 519, 1), (1, 96, 8), (2, 700, 1), (3, 20, 16), (0, 0, 0),
         (1, 300, 1)],
        [0, 0, 130, 3], _NO_WINDOW, None, False, False, None),
    # a prefill segment of 8 n + 1 tokens: its tail tile holds one token,
    # at a ``qpos0`` in mid-row (and mid-page), behind its own fresh K/V
    "prefill-segment-of-8n+1-tokens": (
        16, 2, 128, 128, False, [(0, 500, 17), (1, 61, 9), (2, 63, 1)],
        [0, 9, 0], _NO_WINDOW, None, False, False, None),
    "prefill-segment-of-8n+1-tokens-merged": (
        32, 8, 64, 64, True, [(0, 500, 17), (1, 61, 9), (2, 63, 1)],
        [0, 9, 0], _NO_WINDOW, None, False, False, None),
    # a row with drafts beside plain decode rows: tiles of 2-8 tokens take
    # the tile's whole sheet as before
    "rows-with-drafts-qlen-2-to-8": (
        20, 4, 128, 128, False,
        [(0, 520, 2), (1, 300, 1), (2, 62, 5), (3, 510, 8), (1, 10, 1)],
        [0, 0, 7, 129], _NO_WINDOW, None, False, False, None),
    "window-starts-mid-group": (
        12, 2, 128, 128, False,
        [(0, 999, 1), (1, 640, 1), (2, 350, 9), (3, 700, 1)],
        [0, 500, 0, 64], 300, None, False, False, None),
    "softcap": (
        16, 2, 128, 128, False, [(0, 515, 1), (1, 800, 9), (2, 40, 1)],
        [3, 64, 0], _NO_WINDOW, 20.0, False, False, None),
    # two int8 kv heads: every pool array on blocked operands
    "int8-pool-kh2-blocked": (
        12, 2, 128, 128, False, [(0, 515, 1), (1, 800, 9), (2, 40, 1)],
        [3, 64, 0], _NO_WINDOW, None, True, False, None),
    # four: K / V copied by hand, the scale pages blocked beside them
    "int8-pool-kh4-copied": (
        20, 4, 128, 128, False, [(0, 515, 1), (1, 800, 9), (2, 40, 1)],
        [3, 64, 0], _NO_WINDOW, None, True, False, None),
    # a sink on a head_dim-64 pool, merged, under a window from a base
    "sink-merged-64-window-block0": (
        16, 4, 64, 64, True, [(0, 700, 1), (1, 200, 17), (2, 131, 1)],
        [0, 0, 0], 130, None, False, True, [8, 1, 0]),
}


def _one_token_run(case):
    """→ (kernel's result, twin's, reference's or None, live lanes, each
    tile's live tokens)."""
    from llm_np_cp_tpu.quant import dequantize_kv, quantize_kv
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        ragged_paged_attention,
        ragged_paged_attention_xla,
    )

    (h, kh, dk, dv, merged, segs, pads, window, softcap, int8, sink,
     block0) = _ONE_TOKEN_CASES[case]
    rng = np.random.default_rng(len(case) * 19 + h)
    rows, mb = len(pads), 16
    nbp = rows * mb + 1
    pages_k = _rand(rng, (nbp, _BS, kh, dk))
    pages_v = _rand(rng, (nbp, _BS, kh, dv))
    tables = np.asarray((rng.permutation(nbp - 1) + 1).reshape(rows, mb))
    if block0 is not None:
        # the table's column 0 is logical block ``block0[row]``: shift
        # each row's pages so that position p still finds its own page
        full = tables.copy()
        for r, b0 in enumerate(block0):
            tables[r] = np.roll(full[r], -b0)
    tables = jnp.asarray(tables, jnp.int32)
    dead = 1
    tile, tok, width = _pack_segments(segs, dead)
    q = _rand(rng, (width, h, dk))
    kw = dict(scale=dk ** -0.5, logit_softcap=softcap)
    float_k, float_v = pages_k, pages_v
    if int8:
        (pages_k, ks), (pages_v, vs) = quantize_kv(pages_k), quantize_kv(pages_v)
        kw.update(k_scale=ks, v_scale=vs)
        float_k = dequantize_kv(pages_k, ks, jnp.float32)
        float_v = dequantize_kv(pages_v, vs, jnp.float32)
    if sink:
        kw["sink"] = jnp.asarray(3 + rng.standard_normal(h), jnp.float32)
    if block0 is not None:
        kw["block0"] = jnp.asarray(block0, jnp.int32)
    if merged:
        pages_k, pages_v = _merge(pages_k), _merge(pages_v)
    pads = jnp.asarray(pads, jnp.int32)
    win = jnp.asarray(window, jnp.int32)
    got = np.asarray(ragged_paged_attention(
        q, pages_k, pages_v, tables, *tile, pads, win, **kw))
    twin = np.asarray(ragged_paged_attention_xla(
        q, pages_k, pages_v, tables, *tok, pads, win, **kw))
    want = None
    # (the reference attends a token at a time: where the twin is itself
    # held to it above, in this file's other ragged tests, a few will do)
    if (not sink and block0 is None and dk == dv
            and int(np.asarray(tok[2]).sum()) <= 12):
        want = _ragged_reference(q, float_k, float_v, tables, tok, pads,
                                 window, scale=dk ** -0.5,
                                 logit_softcap=softcap)
    return got, twin, want, np.asarray(tok[2]), np.asarray(tile[2])


@pytest.mark.parametrize("case", list(_ONE_TOKEN_CASES))
def test_ragged_one_token_tiles(case):
    got, twin, want, live, tile_qlen = _one_token_run(case)
    # the case drives the branch, and (but for the decode-only ones) the
    # tile's whole sheet beside it in the same call
    assert (tile_qlen == 1).any() and (tile_qlen == 0).any()
    assert case.startswith("decode-only") or (tile_qlen > 1).any()
    h, kh, dk, dv = _ONE_TOKEN_CASES[case][:4]
    assert got.shape == (live.size, h, dv)
    np.testing.assert_allclose(got[live], twin[live], atol=3e-5)
    if want is not None:
        np.testing.assert_allclose(got[live], want[live], atol=3e-5)
    assert np.all(got[~live] == 0.0)


def test_ragged_dead_lanes_are_zeros_in_both_branches():
    """A one-token tile's seven dead lanes and a three-token tile's five
    come back as exact zeros — not a masked token's softmax over nothing,
    not another tile's leftovers in the scratch — whatever the pages hold:
    the lanes the branch never touches keep what the tile's first step
    gave them."""
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        RAGGED_Q_TILE,
        ragged_paged_attention,
    )

    rng = np.random.default_rng(44)
    h, kh, d, mb = 12, 2, 128, 16
    pages_k, pages_v, tables = _group_pool(rng, kh, d, mb, 3)
    # a full tile first, so the scratch holds live rows in every lane when
    # the next tiles start; then a decode tile, a verify tile, a tail tile
    segs = [(0, 200, 8), (1, 700, 1), (2, 530, 3), (0, 208, 9)]
    tile, tok, width = _pack_segments(segs, 1)
    live = np.asarray(tok[2])
    out = np.asarray(ragged_paged_attention(
        _rand(rng, (width, h, d)) * 4, pages_k * 50, pages_v, tables, *tile,
        jnp.zeros((3,), jnp.int32), jnp.asarray(_NO_WINDOW, jnp.int32),
        scale=d ** -0.5))
    per_tile = out.reshape(-1, RAGGED_Q_TILE, h, d)
    qlen = np.asarray(tile[2])
    assert list(qlen) == [8, 1, 3, 8, 1, 0]
    for ti, n in enumerate(qlen):
        assert np.all(per_tile[ti, n:] == 0.0), ti
        assert np.all(np.abs(per_tile[ti, :n]).sum(axis=(1, 2)) > 0.0), ti
    assert np.isfinite(out).all() and np.all(out[~live] == 0.0)
