"""int8 KV-cache quantization (extension beyond the reference: halves
cache HBM traffic for long-context decode; reference has no cache
compression of any kind).

Error model: per-token-per-head symmetric absmax int8 ⇒ elementwise error
≤ absmax/254 per value.  Tests pin the roundtrip bound, full-forward
logits proximity, greedy-decode agreement on a tiny model, rollback
(truncate) scale preservation, the ragged/speculative per-row write
path, and sharding under a mesh.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.cache import (
    KVCache,
    truncate,
    update_layer_quantized,
)
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.generate import Generator
from llm_np_cp_tpu.models.transformer import forward, init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.quant import dequantize_kv, quantize_kv


@pytest.fixture(scope="module")
def model():
    config = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), config, dtype=jnp.float32)
    return config, params


def test_quantize_roundtrip_bound():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 9, 3, 16), dtype=np.float32) * 5)
    q, s = quantize_kv(x)
    assert q.dtype == jnp.int8 and s.shape == (2, 9, 3)
    back = dequantize_kv(q, s, jnp.float32)
    bound = np.asarray(jnp.max(jnp.abs(x), axis=-1))[..., None] / 254 + 1e-6
    assert (np.abs(np.asarray(back - x)) <= bound).all()


def test_quantize_zero_row_safe():
    q, s = quantize_kv(jnp.zeros((1, 2, 1, 8)))
    assert np.all(np.asarray(q) == 0)
    back = dequantize_kv(q, s, jnp.float32)
    assert np.all(np.asarray(back) == 0.0) and np.isfinite(np.asarray(back)).all()


def test_int8_cache_prefill_matches_f32(model):
    """Prefill logits through the int8 cache track the f32-cache logits,
    and the dequantized slab contents track the f32 slabs within the
    per-head quantization bound."""
    config, params = model
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, config.vocab_size, (2, 12)), jnp.int32)

    logits_f, cache_f = forward(
        params, ids, config, KVCache.init(config, 2, 20, dtype=jnp.float32)
    )
    logits_q, cache_q = forward(
        params, ids, config, KVCache.init(config, 2, 20, dtype=jnp.int8)
    )
    assert cache_q.k.dtype == jnp.int8 and cache_q.quantized
    np.testing.assert_allclose(
        np.asarray(logits_q), np.asarray(logits_f), atol=0.05, rtol=0.05
    )
    back = np.asarray(dequantize_kv(cache_q.k, cache_q.k_scale, jnp.float32))
    want = np.asarray(cache_f.k, dtype=np.float32)
    # layer 0's inputs are identical between the two runs, so its slab
    # error is PURE quantization error (≤ absmax/254 per element); deeper
    # layers add propagated divergence and only get a loose check
    bound = np.abs(want[0]).max(axis=-1, keepdims=True) / 250 + 1e-5
    assert (np.abs(back[0] - want[0]) <= bound)[:, :12].all()
    np.testing.assert_allclose(back[:, :, :12], want[:, :, :12], atol=0.05)


def test_int8_cache_greedy_decode_matches(model):
    """Greedy decode through the int8 cache emits the same tokens as the
    f32 cache on the tiny model (errors are far below argmax margins)."""
    config, params = model
    prompt = np.random.default_rng(2).integers(0, config.vocab_size, (10,))
    a = Generator(params, config, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.float32).generate(prompt, 12).tokens
    b = Generator(params, config, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.int8).generate(prompt, 12).tokens
    np.testing.assert_array_equal(a, b)


def test_int8_cache_gemma2_sliding(model):
    cfg = tiny_config("gemma2")
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    prompt = np.random.default_rng(3).integers(0, cfg.vocab_size, (9,))
    a = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.float32).generate(prompt, 8).tokens
    b = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.int8).generate(prompt, 8).tokens
    np.testing.assert_array_equal(a, b)


def test_truncate_preserves_scales(model):
    config, _ = model
    cache = KVCache.init(config, 2, 16, dtype=jnp.int8)
    out = truncate(cache, jnp.asarray(4, jnp.int32))
    assert out.k_scale is not None and out.v_scale is not None
    assert out.k_scale.shape == cache.k_scale.shape


def test_per_row_offsets_write(model):
    """The batched-speculative per-row write path updates values AND
    scales at each row's own offset."""
    config, _ = model
    L, B, S, K, D = 1, 2, 8, config.num_key_value_heads, config.head_dim
    k_l = jnp.zeros((B, S, K, D), jnp.int8)
    v_l = jnp.zeros((B, S, K, D), jnp.int8)
    ks_l = jnp.zeros((B, S, K), jnp.float32)
    vs_l = jnp.zeros((B, S, K), jnp.float32)
    rng = np.random.default_rng(4)
    k_new = jnp.asarray(rng.standard_normal((B, 2, K, D)), jnp.float32)
    v_new = jnp.asarray(rng.standard_normal((B, 2, K, D)), jnp.float32)
    offs = jnp.asarray([1, 4], jnp.int32)
    k2, v2, ks2, vs2 = update_layer_quantized(
        k_l, v_l, ks_l, vs_l, k_new, v_new, offs
    )
    back0 = dequantize_kv(k2[0, 1:3], ks2[0, 1:3], jnp.float32)
    back1 = dequantize_kv(k2[1, 4:6], ks2[1, 4:6], jnp.float32)
    np.testing.assert_allclose(np.asarray(back0), np.asarray(k_new[0]), atol=0.02)
    np.testing.assert_allclose(np.asarray(back1), np.asarray(k_new[1]), atol=0.02)
    assert np.all(np.asarray(ks2[0, 3:]) == 0) and np.all(np.asarray(ks2[1, :4]) == 0)


def test_int8_cache_under_tp_mesh(model):
    from llm_np_cp_tpu.parallel.sharding import (
        MeshPlan, make_mesh, shard_cache, shard_params,
    )

    config, params = model
    rng = np.random.default_rng(5)
    ids = jnp.asarray(rng.integers(0, config.vocab_size, (2, 8)), jnp.int32)
    want, _ = forward(
        params, ids, config, KVCache.init(config, 2, 12, dtype=jnp.int8)
    )

    plan = MeshPlan(data=2, model=2)
    mesh = make_mesh(plan)
    p_sh = shard_params(params, config, plan, mesh)
    c_sh = shard_cache(
        KVCache.init(config, 2, 12, dtype=jnp.int8), config, plan, mesh
    )
    with jax.set_mesh(mesh):
        got, got_cache = jax.jit(
            lambda p, i, c: forward(p, i, config, c)
        )(p_sh, ids, c_sh)
    assert got_cache.k.dtype == jnp.int8
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-4, rtol=1e-4
    )


def test_int8_cache_flash_decode_parity(model):
    """The decode kernel reads the int8 cache natively (1-byte HBM stream,
    in-VMEM dequant) and emits the same greedy tokens as the XLA path
    over the same int8 cache."""
    config, params = model
    prompt = np.random.default_rng(7).integers(0, config.vocab_size, (11,))
    a = Generator(params, config, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.int8).generate(prompt, 10).tokens
    b = Generator(params, config, sampler=Sampler(kind="greedy"),
                  cache_dtype=jnp.int8,
                  decode_attn="flash_decode").generate(prompt, 10).tokens
    np.testing.assert_array_equal(a, b)


def test_decode_attention_int8_kernel_matches_dequant():
    """Kernel-level: int8+scales input == dequantize-then-attend."""
    from llm_np_cp_tpu.ops.pallas.decode_attention import decode_attention

    rng = np.random.default_rng(8)
    b, s, h, kh, d = 2, 48, 4, 2, 16
    q = jnp.asarray(rng.standard_normal((b, 1, h, d), dtype=np.float32))
    kf = jnp.asarray(rng.standard_normal((b, s, kh, d), dtype=np.float32))
    vf = jnp.asarray(rng.standard_normal((b, s, kh, d), dtype=np.float32))
    kq, ks = quantize_kv(kf)
    vq, vs = quantize_kv(vf)
    mask = jnp.asarray(rng.random((b, s)) > 0.2)
    mask = mask.at[:, 0].set(True)

    want = decode_attention(
        q, dequantize_kv(kq, ks, jnp.float32), dequantize_kv(vq, vs, jnp.float32),
        mask, scale=d**-0.5, block_s=16,
    )
    got = decode_attention(
        q, kq, vq, mask, k_scale=ks, v_scale=vs, scale=d**-0.5, block_s=16,
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_decode_attention_int8_requires_both_scales():
    from llm_np_cp_tpu.ops.pallas.decode_attention import decode_attention

    q = jnp.zeros((1, 1, 2, 8))
    kq = jnp.zeros((1, 4, 1, 8), jnp.int8)
    with pytest.raises(ValueError, match="k_scale and v_scale"):
        decode_attention(q, kq, kq, jnp.ones((1, 4), bool),
                         k_scale=jnp.ones((1, 4, 1)), scale=1.0)


def test_int8_cache_speculative(model):
    """Speculative decoding (rollback + per-row lengths) over an int8
    cache is still exact w.r.t. its own greedy target semantics."""
    from llm_np_cp_tpu.speculative import SpeculativeGenerator

    config, params = model
    prompt = np.random.default_rng(6).integers(0, config.vocab_size, (8,))
    want = Generator(params, config, sampler=Sampler(kind="greedy"),
                     cache_dtype=jnp.int8).generate(prompt, 10).tokens[0]
    spec = SpeculativeGenerator(
        params, config, gamma=2, sampler=Sampler(kind="greedy"),
        cache_dtype=jnp.int8,
    )
    got = spec.generate(prompt, 10).tokens
    np.testing.assert_array_equal(want, got)
