"""Deviceless TPU lowering AND compile of the Pallas kernels at REAL
model shapes.

Interpret-mode tests validate kernel math but not Mosaic's layout rules
(r3 postmortem: a kernel that passed every CPU test was rejected by
Mosaic at first hardware compile).  Two levels, neither needs a chip:

- ``jax.export`` with ``platforms=["tpu"]`` runs the Pallas→Mosaic
  serialization, where the block-shape/trailing-dims rules live;
- with libtpu installed, ``jax.experimental.topologies`` describes a
  v5e host the process does not have, and ``jit(...).lower(avals on
  that topology).compile()`` runs the WHOLE TPU compile — Mosaic's
  layout passes, scoped-VMEM limits and all — so a refusal (the softmax
  kernel's 16 MiB scoped-VMEM overrun at vocab width was found this
  way) fails HERE instead of on the chip.

What only the chip can say is whether the compiled kernel computes the
right numbers: ``chip_smoke.py --kernels`` runs the same cases there
against their XLA twins.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax import export

from llm_np_cp_tpu.ops.pallas.decode_attention import decode_attention
from llm_np_cp_tpu.ops.pallas.flash_attention import flash_attention


def _export_tpu(fn, *args):
    exp = export.export(jax.jit(fn), platforms=["tpu"])(*args)
    assert exp.platforms == ("tpu",)


# llama-3.2-1B headline decode shape: bs=8, 512-slot cache, 32 q heads
B, S, H, KH, D = 8, 512, 32, 8, 64


def test_decode_attention_lowers_for_tpu():
    q = jax.ShapeDtypeStruct((B, 1, H, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((B, S, KH, D), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((B, S), jnp.bool_)
    _export_tpu(
        functools.partial(decode_attention, scale=0.125, interpret=False),
        q, kv, kv, mask,
    )


def test_decode_attention_int8_lowers_for_tpu():
    q = jax.ShapeDtypeStruct((B, 1, H, D), jnp.bfloat16)
    kv8 = jax.ShapeDtypeStruct((B, S, KH, D), jnp.int8)
    mask = jax.ShapeDtypeStruct((B, S), jnp.bool_)
    sc = jax.ShapeDtypeStruct((B, S, KH), jnp.float32)
    fn = functools.partial(decode_attention, scale=0.125, interpret=False)
    _export_tpu(
        lambda q_, k_, v_, m_, ks_, vs_: fn(
            q_, k_, v_, m_, k_scale=ks_, v_scale=vs_
        ),
        q, kv8, kv8, mask, sc, sc,
    )


@pytest.mark.parametrize(
    "window,softcap", [(None, None), (4096, 50.0)],
    ids=["causal", "gemma2_window_softcap"],
)
def test_flash_attention_8k_lowers_for_tpu(window, softcap):
    s = 8192
    q = jax.ShapeDtypeStruct((1, s, H, D), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, s, KH, D), jnp.bfloat16)
    _export_tpu(
        functools.partial(
            flash_attention, scale=0.125, window=window,
            logit_softcap=softcap, interpret=False,
        ),
        q, kv, kv,
    )


@pytest.mark.parametrize("cache_dtype", ["bf16", "int8"])
def test_full_fdec_decode_loop_lowers_for_tpu(cache_dtype):
    """The ENTIRE fused decode loop with the Pallas kernel inside the
    layer scan (the program the fdec bench configs dispatch), at the real
    llama-1B headline shape — integration-level Mosaic serialization, not
    just the kernel alone."""
    from llm_np_cp_tpu.cache import KVCache, align_capacity
    from llm_np_cp_tpu.config import LLAMA_3_2_1B
    from llm_np_cp_tpu.generate import make_decode_loop_fn
    from llm_np_cp_tpu.models.transformer import init_params
    from llm_np_cp_tpu.ops.sampling import Sampler

    cfg = LLAMA_3_2_1B
    params = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    )
    cap = align_capacity(128 + 256 + 8)
    cdt = jnp.int8 if cache_dtype == "int8" else jnp.bfloat16
    cache = jax.eval_shape(lambda: KVCache.init(cfg, 8, cap, dtype=cdt))
    loop = make_decode_loop_fn(
        cfg, Sampler(kind="greedy"), attn_impl="flash_decode"
    )
    tok = jax.ShapeDtypeStruct((8,), jnp.int32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    _export_tpu(jax.jit(lambda p, t, c, k: loop(p, t, c, k, 8)),
                params, tok, cache, key)


def test_gemma2_decode_shape_lowers_for_tpu():
    # Gemma-2-2B: 8 q heads over 4 KV heads of 256 dim — the wide-head
    # layout class (trailing dims (4, 256))
    q = jax.ShapeDtypeStruct((8, 1, 8, 256), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((8, 512, 4, 256), jnp.bfloat16)
    mask = jax.ShapeDtypeStruct((8, 512), jnp.bool_)
    _export_tpu(
        functools.partial(
            decode_attention, scale=0.0625, logit_softcap=50.0,
            interpret=False,
        ),
        q, kv, kv, mask,
    )


# ----------------------------------------------------------------------
# full deviceless compile for a v5e topology (libtpu, no chip)
# ----------------------------------------------------------------------

@pytest.fixture(scope="module")
def v5e_sharding():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            topology_name="v5e:2x2", platform="tpu")
    except Exception as e:  # noqa: BLE001 — no libtpu, nothing to compile with
        pytest.skip(f"no deviceless TPU topology here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile_case(sharding, kernel, shape, block_size):
    from llm_np_cp_tpu.ops.pallas import support

    make_args, run, _ = support.kernel_case(kernel, shape, block_size or 64)
    avals = [
        jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding)
        for a in jax.eval_shape(make_args)
    ]
    jax.jit(run).lower(*avals).compile()


def _case_id(case):
    kernel, shape, bs = case
    return f"{kernel}-{shape.name}" + (f"-bs{bs}" if bs else "")


def _default_path(case):
    # the kernels the CLI's default serve path selects, at the probe
    # shapes and at the smoke's model (Qwen2.5-1.5B), CLI block size
    kernel, shape, bs = case
    return (kernel in ("ragged_paged_attention", "sample_epilogue")
            and shape.name in ("probe", "probe/untied", "qwen2.5-1.5b")
            and bs in (None, 64))


def _all_cases():
    from llm_np_cp_tpu.ops.pallas import support

    return list(support.kernel_cases())


@pytest.mark.parametrize(
    "case", [c for c in _all_cases() if _default_path(c)], ids=_case_id)
def test_default_path_kernels_compile_for_v5e(v5e_sharding, case):
    _compile_case(v5e_sharding, *case)


@pytest.mark.slow
@pytest.mark.parametrize(
    "case", [c for c in _all_cases() if not _default_path(c)], ids=_case_id)
def test_every_kernel_case_compiles_for_v5e(v5e_sharding, case):
    """The whole on-chip matrix (every kernel in support.KERNELS at the
    probe and the three family shapes, both serve block sizes)."""
    _compile_case(v5e_sharding, *case)
