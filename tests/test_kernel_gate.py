"""Mosaic compile gate + 128-aligned cache capacities (r4 hardening).

The r3 decode kernel passed every interpret-mode test and was rejected
by Mosaic at first hardware compile; these tests pin the two defences:
selection downgrades to XLA instead of dying, and Generator-sized caches
are always 128-aligned so the kernel's kv-block search never collapses.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.generate import Generator
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.pallas import support
from llm_np_cp_tpu.ops.pallas.decode_attention import select_block_s
from llm_np_cp_tpu.ops.sampling import Sampler


@pytest.fixture(scope="module")
def tiny_model():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    return cfg, params


@pytest.fixture
def clean_probe_cache():
    support._probe.cache_clear()
    yield
    support._FORCE_FAIL = False
    support._probe.cache_clear()


def test_forced_compile_failure_degrades_to_xla(tiny_model, clean_probe_cache, caplog):
    """A kernel that Mosaic rejects must downgrade with a warning and
    produce IDENTICAL tokens via the XLA path."""
    cfg, params = tiny_model
    prompt = jnp.asarray(np.arange(1, 9)[None, :], jnp.int32)

    base = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                     cache_dtype=jnp.float32)
    ref = np.asarray(base.generate(prompt, max_new_tokens=12, seed=0).tokens)

    support._FORCE_FAIL = True
    support._probe.cache_clear()
    with caplog.at_level("WARNING", logger="llm_np_cp_tpu"):
        gated = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                          cache_dtype=jnp.float32,
                          decode_attn="flash_decode",
                          prefill_attn_impl="flash")
    assert "falling back to the XLA attention path" in caplog.text
    out = np.asarray(gated.generate(prompt, max_new_tokens=12, seed=0).tokens)
    np.testing.assert_array_equal(out, ref)


def test_gate_passes_impl_through_when_supported(clean_probe_cache):
    # CPU backend: kernels run the interpreter, so the gate is a no-op
    assert support.gate_attn_impl("flash_decode") == "flash_decode"
    assert support.gate_attn_impl("flash") == "flash"
    assert support.gate_attn_impl("xla") == "xla"
    assert support.gate_attn_impl("ring") == "ring"


def test_cache_capacity_rounded_to_128(tiny_model):
    cfg, params = tiny_model
    gen = Generator(params, cfg, cache_dtype=jnp.float32)
    assert gen._init_cache(1, 383).k.shape[2] == 384
    assert gen._init_cache(1, 1).k.shape[2] == 128
    assert gen._init_cache(1, 256).k.shape[2] == 256


def test_odd_request_shapes_match_explicit_capacity(tiny_model):
    """prompt 7 + 9 new tokens (derived capacity 16 → 128) must match a
    run with a much larger explicit capacity token-for-token."""
    cfg, params = tiny_model
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                    cache_dtype=jnp.float32)
    prompt = jnp.asarray(np.arange(1, 8)[None, :], jnp.int32)
    a = np.asarray(gen.generate(prompt, max_new_tokens=9, seed=0).tokens)
    b = np.asarray(
        gen.generate(prompt, max_new_tokens=9, max_seq_len=384, seed=0).tokens
    )
    np.testing.assert_array_equal(a, b)


def test_select_block_s_alignment():
    # aligned capacity: full 32-aligned divisor wins (32 = the 1-byte
    # mask operand's sublane tile, the r4 fdec warm-log fix — 8-aligned
    # partial blocks compile for the K/V specs and die on the mask spec)
    assert select_block_s(384, 1, 64, 4, 512, False) == 384
    assert select_block_s(1024, 8, 64, 2, 512, False) == 512
    # prime capacity, small enough for one block: whole-s fallback
    assert select_block_s(383, 1, 64, 4, 512, False) == 383
    # prime capacity too large for VMEM: loud failure, not block_s=1
    # (decode_attention catches this and pads the cache axis instead)
    with pytest.raises(ValueError, match="aligned divisor"):
        select_block_s(100003, 8, 128, 4, 512, False)


def test_block_s_respects_vmem_budget():
    # kh=8, d=128, f32: row = 8*128*4*2 = 8 KiB → cap ≈ 8 MiB/16 KiB = 512
    got = select_block_s(4096, 8, 128, 4, 512, False)
    assert got <= 512 and got % 8 == 0 and 4096 % got == 0
    # int8 cache halves the stream → larger blocks allowed at same budget
    got8 = select_block_s(4096, 8, 128, 1, 512, True)
    assert got8 >= got


@pytest.mark.parametrize("kernel", [
    "ragged_paged_attention", "ragged_paged_attention_int8",
    "ragged_latent_attention"])
def test_ragged_probe_case_holds_a_one_token_tile(kernel):
    """What the start-up probe compiles and runs on the chip holds every
    kind of tile the kernels branch on — a whole tile, a partial one, a
    tile of ONE live token (a decode row: its own update since PR 44 / PR
    41) and a dead one — at the page form that takes the new path
    (``[64, 2, 128]`` bf16 pages, attended as they lie), so a chip that
    cannot lower a branch degrades at start-up and not in a tick (the
    case against its XLA twin: ``chip_smoke.py --kernels`` on the chip,
    ``tests/test_decode_attention.py`` in interpret mode)."""
    from llm_np_cp_tpu.ops.pallas import decode_attention as da

    latent = kernel == "ragged_latent_attention"
    shape = support.LATENT_PROBE_SHAPE if latent else support.PROBE_SHAPE
    make_args, _, _ = support.kernel_case(
        kernel, shape, support.SERVE_BLOCK_SIZES[0], interpret=True)
    args = make_args()
    qt = da.RAGGED_Q_TILE
    tile_qlen = np.asarray(args[5 if latent else 4])
    assert tile_qlen.shape == (6,) and tile_qlen.dtype == np.int32
    assert {0, 1, qt} <= set(tile_qlen.tolist())
    assert any(1 < n < qt for n in tile_qlen)
    if not latent:
        pages = args[6]
        assert pages.shape[1:] == (64, 2, 128)
        int8 = kernel.endswith("_int8")
        assert pages.dtype == (jnp.int8 if int8 else jnp.bfloat16)
        # (int8 pages keep their scale pages beside them: the per-head form)
        assert da._dma_slices_pages(pages) is not int8
