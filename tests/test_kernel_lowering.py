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

import contextlib
import functools
import json
import pathlib
import re
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import export

from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models import init_params
from llm_np_cp_tpu.models.transformer import (
    HYBRID_SCOPES,
    SCOPE_KV_WRITE,
    STEP_SCOPES,
)
from llm_np_cp_tpu.ops.pallas.decode_attention import decode_attention
from llm_np_cp_tpu.ops.pallas.flash_attention import flash_attention
from llm_np_cp_tpu.serve import ServeEngine, opmap
from llm_np_cp_tpu.serve.block_pool import PagedKV, PageForm


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
    if kernel in ("ragged_latent_attention", "sparse_latent_attention"):
        return bs == 64  # one shape each: the published row / GLM-5's layer
    if kernel == "grouped_matmul":  # the cells' own shapes: further down
        return shape.name == "probe"
    if kernel in ("ssm_state_update", "kda_state_update",
                  "retention_state_update"):
        return True  # the probe's state and the cell's
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


# ----------------------------------------------------------------------
# the unified tick must not move the KV pool (whole step, for a v5e)
# ----------------------------------------------------------------------
#
# ``_make_mixed_step`` carries the pool through the layer scan (flat over
# layer and block) and scatters this tick's K/V into it in place.  The
# form it replaced handed the pool to ``lax.scan`` as ``xs`` / ``ys``:
# each layer's slab was sliced out, scattered into, stacked back into a
# NEW pool-sized array and that copied onto the donated buffer — 41 / 36 /
# 58 % of the step's device time in the benchmark's three cells (PERF.md
# §6, PR 25), and a second pool held as a temporary.
#
# Whether the compiler updates in place is decided by the TPU compiler,
# not by the jaxpr, so this compiles the step for a DESCRIBED v5e (libtpu
# is installed; no chip — the way benchmark/tick_memory.py sizes a cell,
# in THIS file because one process of a test run may load libtpu, so one
# file describes the topology) and reads the result: its
# temporaries, and every operation whose result is shaped like the pool
# or one layer's slab of it, by serve/opmap.py's own parse.

# Qwen2.5-shaped heads (12 q over 2 kv heads of 128), three layers, and a
# pool whose one-layer slab (8 MiB of bf16 K) dwarfs every activation of
# the step: a temporary that size can only be the pool
SLOTS, BLOCKS, BLOCK, CHUNK = 4, 256, 64, 64


def _compile_widest_bucket(sharding, cache_dtype, blocks=BLOCKS, slots=SLOTS,
                           program=None, cfg=None, chunk=CHUNK):
    """The unified step of a small Qwen2.5-shaped engine (or of ``cfg``),
    compiled for a described v5e as ``program`` (its widest by default)."""
    cfg = cfg or tiny_config(
        "qwen2", num_hidden_layers=3, hidden_size=1536,
        intermediate_size=1024, num_attention_heads=12,
        num_key_value_heads=2, head_dim=128, vocab_size=2048,
    )
    abstract = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    engine = ServeEngine(
        abstract, cfg, max_slots=slots, num_blocks=blocks, block_size=BLOCK,
        max_seq_len=BLOCK * 8, prefill_chunk=chunk, cache_dtype=cache_dtype,
    )
    assert engine.mixed and engine.ragged_attn_impl == "pallas"

    def aval(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    avals = [jax.tree.map(aval, abstract),
             jax.tree.map(aval, engine.pool.pages)]
    program = program or engine.mixed_buckets[-1]
    assert program in engine.mixed_buckets
    avals.append(aval(engine._dead_mixed_operands(*program)))
    with _traced_for_a_tpu():
        compiled = engine._mixed_step.lower(*avals).compile()
    return engine, compiled


@contextlib.contextmanager
def _traced_for_a_tpu():
    """The kernels pick interpret mode from the backend they see: show
    them the one they are being compiled for (and the expert layers,
    which ask the grouped matmul's probe as they are traced, a verdict
    no CPU can give)."""
    from llm_np_cp_tpu.ops.pallas import support

    real, real_error = jax.default_backend, support.kernel_error
    jax.default_backend = lambda: "tpu"
    support.kernel_error = lambda kernel: None
    try:
        yield
    finally:
        jax.default_backend, support.kernel_error = real, real_error


def _pool_ops(engine, compiled):
    """serve/opmap.py's parse of the compiled text: every operation that
    runs, as [scope, result shape, "pool" | "slab" | ""]."""
    pool = opmap.pool_shapes(
        (a.dtype.name, a.shape) for a in jax.tree.leaves(engine.pool.pages))
    return opmap.op_map_from_hlo(compiled.as_text(), STEP_SCOPES, pool)


@pytest.mark.parametrize("cache_dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_compiled_tick_writes_the_pool_in_place(v5e_sharding, cache_dtype):
    engine, compiled = _compile_widest_bucket(v5e_sharding, cache_dtype)
    pages = engine.pool.pages
    k_slab_bytes = int(np.prod(pages.k.shape[1:])) * pages.k.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < k_slab_bytes, (
        f"the tick holds {temp} B of temporaries, one layer's K slab is "
        f"{k_slab_bytes} B: a copy of (part of) the pool is back")

    # besides params and pool the program takes ONE operand: the packed
    # int32 vector of the widest program (one transfer a tick)
    text = compiled.as_text()
    ints = re.findall(r"= ((?:[su]\d+|pred)\[[\d,]*\])\S* parameter\(",
                      text[text.index("\nENTRY"):])
    assert ints == [opmap.hlo_shape("int32", (
        engine._mixed_layouts[engine.mixed_buckets[-1]][1],))]

    ops = _pool_ops(engine, compiled)
    assert {scope for scope, _, _ in ops.values()} >= (
        set(STEP_SCOPES) - set(HYBRID_SCOPES))
    slabs = {n: v for n, v in ops.items() if v[2] == "slab"}
    assert not slabs, f"a layer's slab is sliced out or rebuilt: {slabs}"
    moved = {n: v for n, v in ops.items()
             if v[2] == "pool" and v[0] != SCOPE_KV_WRITE}
    assert not moved, f"the pool moves outside the K/V write: {moved}"
    # what is left is the write itself: one scatter for K and one for V,
    # in the layer loop's body, on the carried (flat) pool
    writes = [v for v in ops.values() if v[2] == "pool"]
    flat = opmap.hlo_shape(
        pages.k.dtype.name,
        (pages.k.shape[0] * pages.k.shape[1],) + pages.k.shape[2:])
    assert [v[1] for v in writes] == [flat, flat]


_FLOATS = re.compile(r"\b(?:bf16|f32)\[([\d,]*)\]")


def _ragged_kernel_call(hlo_text):
    """The ragged kernel as a compiled module calls it: (result shape,
    operand layout constraints, the Mosaic module's text with its debug
    locations dropped — they name the Python frames of the caller)."""
    import base64
    import json

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib.mlir import ir

    line, = (ln for ln in hlo_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "ragged_paged_attention" in ln.split("=", 1)[0])
    result = line.split("=", 1)[1].split("custom-call(", 1)[0].strip()
    operands = re.search(r"operand_layout_constraints=\{(.*?)\}, \w+=",
                         line).group(1)
    cfg = line[line.index("backend_config=") + len("backend_config="):]
    cfg = json.loads(cfg[:cfg.rindex("}") + 1])
    ctx = jax_mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True  # ("stable_mosaic", serialized)
    with ctx:
        module = ir.Module.parse(
            base64.b64decode(cfg["custom_call_config"]["body"]))
        return result, operands, module.operation.get_asm(
            enable_debug_info=False)


def test_decode_program_is_dense_outside_attention(v5e_sharding):
    """The steady decode tick of 64 rows, as a v5e runs it — the program
    ``(512 tile lanes, 64 tokens)``.  The step's token axis is 64 wide:
    the MLP's matmuls are ``[64, ffn]``, the K/V scatter writes 64 rows,
    and the 512 lanes of the rows' query tiles exist only under scope
    ``attn``, between the two row gathers (at 512 the matmuls were
    compute-bound on lanes that hold nothing: PERF.md §6, PR 30 / 31).
    The pool is still the donated buffer written in place.  And the
    ragged kernel is the function's own call at the same 64 tiles, to
    the byte: what this program changed is around it."""
    t_w, d_w, slots = 512, 64, 64
    engine, compiled = _compile_widest_bucket(
        v5e_sharding, jnp.bfloat16, blocks=600, slots=slots,
        program=(t_w, d_w))
    assert (t_w, d_w) == min(p for p in engine.mixed_buckets if p[0] == t_w)
    cfg, pages = engine.config, engine.pool.pages
    text = compiled.as_text()
    ops = _pool_ops(engine, compiled)

    def floats(shape):
        return [tuple(int(x) for x in dims.split(",") if x)
                for dims in _FLOATS.findall(shape)]

    by_scope = {}
    for name, (scope, shape, _) in ops.items():
        if not scope and name.startswith("slice-"):
            # the fused tail streams the tied head in vocabulary tiles of
            # 512 rows: a weight slice, not a token axis
            assert floats(shape)[-1] == (512, cfg.hidden_size)
            continue
        by_scope.setdefault(scope, []).extend(
            (name, dims) for dims in floats(shape))
    # the tiled width: under ``attn`` (the gathered q, the kernel, its
    # result) and nowhere else
    assert any(t_w in dims for _, dims in by_scope["attn"])
    tiled_outside = {
        scope: [(n, dims) for n, dims in arrays if t_w in dims]
        for scope, arrays in by_scope.items() if scope != "attn"}
    assert not any(tiled_outside.values()), tiled_outside
    # the matmuls: every array as wide as the MLP's or a projection's
    # output has the dense width beside it
    f, h = cfg.intermediate_size, cfg.hidden_size
    for scope, feature in (("mlp", f), ("mlp", h), ("o_proj", h),
                           ("qkv", h)):
        wide = [dims for _, dims in by_scope[scope]
                if feature in dims and len(dims) > 1]  # (not a norm's scale)
        assert wide, (scope, feature)
        assert all(set(dims) - {1} == {d_w, feature} for dims in wide), (
            scope, wide)
    # the scatter: ``max_slots`` rows of fresh K (and V) a layer
    kh, hd = cfg.num_key_value_heads, cfg.head_dim
    scatters = [ln for ln in text.splitlines()
                if " scatter(" in ln and "kv_write" in ln]
    assert len(scatters) == 2, scatters
    for ln in scatters:  # scatter(pool, indices, updates): by definition
        shapes = [re.search(rf"{re.escape(arg)} = (\w+\[[\d,]*\])", text)
                  .group(1) for arg in re.findall(r"%[\w.-]+", ln.split(
                      " scatter(", 1)[1].split(")", 1)[0])]
        assert shapes[1:] == [
            opmap.hlo_shape("int32", (slots, 2)),  # (block, slot) a row
            opmap.hlo_shape(pages.k.dtype.name, (slots, kh, hd))], shapes
    # ...into the pool, which is still the result: nothing pool- or
    # slab-sized beside it, nothing pool-shaped outside the write
    k_slab_bytes = int(np.prod(pages.k.shape[1:])) * pages.k.dtype.itemsize
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < min(k_slab_bytes, 1 << 20)
    assert mem.alias_size_in_bytes >= sum(
        a.nbytes for a in pages if a is not None)
    assert {v[2] for v in ops.values() if v[0] != SCOPE_KV_WRITE} == {""}

    # the function alone: q tile-aligned all the way, ``[512, H, D]``
    # straight into the kernel, on the same (flat) pool and tables
    assert cfg.attn_scale == hd ** -0.5 and not cfg.attn_logit_softcapping
    alone = _lower_ragged(
        v5e_sharding, nt=t_w // engine._q_tile, mb=engine.max_blocks_per_seq,
        h=cfg.num_attention_heads, kh=kh, d=hd, dtype=pages.k.dtype,
        blocks=pages.k.shape[0] * pages.k.shape[1], rows=slots)
    assert _ragged_kernel_call(text) == alone


# ----------------------------------------------------------------------
# a kv grid step of the ragged kernel is a group of P pages (PR 33)
# ----------------------------------------------------------------------

def _lower_ragged(sharding, *, nt, mb, h, kh, d, dtype=jnp.bfloat16,
                  block_s=64, blocks=1026, rows=64, merged=False,
                  whole=False):
    """``ragged_paged_attention`` alone, compiled for the described v5e at
    a cell's geometry → ``_ragged_kernel_call`` of the compiled text (or,
    ``whole``, the compiled program).  ``merged``: pages ``[BS, K * D]``."""
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        ragged_paged_attention,
    )

    def aval(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    i32 = jnp.int32
    page = aval((blocks, block_s) + ((kh * d,) if merged else (kh, d)), dtype)
    args = [aval((nt * 8, h, d), jnp.bfloat16), page, page,
            aval((rows, mb), i32), aval((nt,), i32), aval((nt,), i32),
            aval((nt,), i32), aval((rows,), i32), aval((), i32)]
    kw = {}
    if dtype == jnp.int8:
        kw = dict(k_scale=aval((blocks, block_s, kh), jnp.float32),
                  v_scale=aval((blocks, block_s, kh), jnp.float32))
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = jax.jit(functools.partial(
            ragged_paged_attention, scale=d ** -0.5)).lower(
                *args, **kw).compile()
    finally:
        jax.default_backend = real
    return compiled if whole else _ragged_kernel_call(compiled.as_text())


def _kernel_grid(module_text):
    bounds = re.search(r"iteration_bounds = array<i64: ([\d, ]+)>",
                       module_text).group(1)
    return tuple(int(x) for x in bounds.split(","))


def _kernel_vmem_scratch(module_text, rank=5):
    """Every VMEM scratch array of the kernel's signature holding pages
    (rank 5: half, slot, block, heads, dim; rank 4 where the pages are
    merged) as (shape, dtype name)."""
    sig = next(ln for ln in module_text.splitlines() if "^bb0(" in ln)
    return [(tuple(int(x) for x in dims.rstrip("x").split("x")), dt)
            for dims, dt in re.findall(
                r"memref<((?:\d+x){%d})(\w+), #tpu.memory_space<vmem>>"
                % rank, sig)]


@pytest.mark.parametrize("nt,mb,grid", [
    # the closed cells: 64 decode tiles under a 16-block table — 1,024
    # one-page steps before PR 33
    (64, 16, (64, 2)),
    (96, 16, (96, 2)),
    # chat-open's table is 42 wide: 6 steps a tile where it walked 42
    (8, 42, (8, 6)),
    (64, 42, (64, 6)),
], ids=["closed-512", "closed-768", "chat-open-64", "chat-open-512"])
def test_ragged_kernel_grid_is_tiles_by_groups_of_pages(
        v5e_sharding, nt, mb, grid):
    """What PR 33 is for: the lowered call's grid is ``(tiles, ceil(mb /
    P))`` with P = 8 pages of 64 positions, the pool stays in HBM (the
    kernel's own copies fetch it: two halves of P pages for K and for
    V), and those buffers are what the P rule budgeted."""
    from llm_np_cp_tpu.ops.pallas import decode_attention as da

    h, kh, d = 12, 2, 128  # Qwen2.5-1.5B
    result, operands, module = _lower_ragged(
        v5e_sharding, nt=nt, mb=mb, h=h, kh=kh, d=d,
        blocks=28 * 64, rows=64)
    assert _kernel_grid(module) == grid
    p = da.ragged_pages_per_step(mb, 64, kh, d, jnp.bfloat16, False)
    assert p == 8 and grid[1] == -(-mb // p)
    # (since PR 44 a page comes [BS * K, D], its kv heads on consecutive
    # rows: the same 32,768 bytes; rank 4 are also q's and the result's
    # tile, ahead of the two buffers)
    scratch = _kernel_vmem_scratch(module, rank=4)[-2:]
    assert scratch == [((2, p, 64 * kh, d), "bf16")] * 2, scratch
    held = sum(da._vmem_bytes(shape, jnp.bfloat16) for shape, _ in scratch)
    assert held == 2 * 2 * p * 32768 <= da._VMEM_BUDGET_BYTES
    # the pool operands are taken as they lie (no window of them is
    # pipelined): memory space "any", whole
    assert module.count("#tpu.memory_space<any>") >= 2
    assert f"{28 * 64}x{64 * kh}x{d}xbf16" in module


@pytest.mark.parametrize("kh,d,dtype,mb,block_s,want,merged", [
    (kh, d, dtype, mb, block_s, want, False) for kh, d, dtype, mb, block_s, want in [
    # hand arithmetic (bytes in VMEM: the last two dims in whole tiles).
    # Qwen2.5-1.5B / 3B / the 7B's shard: a [64, 2, 128] bf16 page is
    # 32,768 B, K + V in two halves 131,072 B a slot: 64 would fit 8 MiB;
    # 512 positions / 64 = 8 decides
    (2, 128, jnp.bfloat16, 16, 64, 8),
    (2, 128, jnp.bfloat16, 42, 64, 8),
    # LFM2: [64, 8, 64] bf16 pads 64 lanes to 128: 131,072 B a page,
    # 524,288 a slot, 16 fit; 8
    (8, 64, jnp.bfloat16, 16, 64, 8),
    # Gemma-2 2B: [64, 4, 256] bf16 = 131,072 B: the same
    (4, 256, jnp.bfloat16, 16, 64, 8),
    # int8 Qwen page: [64, 2, 128] int8 pads 2 rows to 4: 32,768 B x 4,
    # scale pages [64, 2] f32 32,768 B x 4, dequantized 12 x 65,536:
    # 1,048,576 B a slot, 8 fit
    (2, 128, jnp.int8, 16, 64, 8),
    # int8 Gemma page: 65,536 x 4 + 32,768 x 4 + 12 x 262,144 =
    # 3,538,944 B a slot: 2 fit
    (4, 256, jnp.int8, 16, 64, 2),
    # block size 128: 512 / 128 = 4; a table narrower than that: mb
    (2, 128, jnp.bfloat16, 16, 128, 4),
    (2, 128, jnp.bfloat16, 3, 64, 3),
    # a page so large that two halves of one slot fill the budget:
    # [64, 32, 256] f32 = 2 MiB, K + V x 2 halves = 8 MiB: 1
    (32, 256, jnp.float32, 16, 64, 1),
]] + [
    # merged pages [BS, K * D] take what they hold.  LFM2 as it is stored
    # since PR 38: [64, 512] bf16 = 65,536 B, 262,144 a slot, 32 fit; 8
    (8, 64, jnp.bfloat16, 16, 64, 8, True),
    # 32 float32 heads of 64: [64, 32, 64] pads to 1 MiB a page, K + V in
    # two halves 4 MiB: 2 fit - merged [64, 2048] is 512 KiB: 4
    (32, 64, jnp.float32, 16, 64, 2, False),
    (32, 64, jnp.float32, 16, 64, 4, True),
], ids=["qwen-closed", "qwen-chat-open", "lfm2-unmerged", "gemma2",
        "qwen-int8", "gemma2-int8", "block-128", "narrow-table", "huge-page",
        "lfm2", "f32-32x64-unmerged", "f32-32x64-merged"])
def test_pages_per_step_by_hand(kh, d, dtype, mb, block_s, want, merged):
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        ragged_pages_per_step,
    )

    assert ragged_pages_per_step(
        mb, block_s, kh, d, dtype, dtype == jnp.int8, merged=merged) == want


@pytest.mark.parametrize("h,kh,d,dtype,merged", [
    (16, 2, 128, jnp.bfloat16, False),  # Qwen2.5-3B: groups of 8
    # LFM2 / Llama-3.2-1B as the pool stores them: [64, 512] pages, which
    # a DMA can cut - the kernel's own copies, at head_dim 64
    (32, 8, 64, jnp.bfloat16, True),
    (8, 4, 256, jnp.bfloat16, False),   # Gemma-2 2B
    (12, 2, 128, jnp.int8, False),      # int8 pool: every array blocked
    (8, 4, 256, jnp.int8, False),       # int8 K / V copied, scales blocked
    (4, 1, 128, jnp.bfloat16, False),   # one bf16 kv head: half a tile, blocked
    (32, 8, 64, jnp.bfloat16, False),   # [64, 8, 64] pages: blocked operands
    (14, 2, 64, jnp.bfloat16, True),    # Qwen2.5-0.5B: one row of lanes, g = 7
    (16, 4, 32, jnp.float32, True),     # four float32 heads to a row of lanes
], ids=["qwen3b", "lfm2", "gemma2", "qwen-int8", "gemma2-int8", "kh1",
        "lfm2-unmerged", "qwen0.5b", "f32-4x32"])
def test_ragged_kernel_compiles_at_every_page_shape(
        v5e_sharding, h, kh, d, dtype, merged):
    """The other page shapes the kernel serves, at the closed cells'
    geometry: each compiles for the v5e inside its scoped VMEM."""
    from llm_np_cp_tpu.ops.pallas import decode_attention as da

    _, _, module = _lower_ragged(
        v5e_sharding, nt=64, mb=16, h=h, kh=kh, d=d, dtype=dtype,
        merged=merged)
    p = da.ragged_pages_per_step(
        16, 64, kh, d, dtype, dtype == jnp.int8, merged=merged)
    assert _kernel_grid(module) == (64, -(-16 // p))
    # BOTH updates in the one program (PR 44), the branch taken from the
    # tile's own ``tile_qlen``: the whole tile's sheet and a one-token
    # tile's rows, each with its dots — one for K and one for V where
    # [BS, K, D] float pages are attended as they lie, else one a kv
    # head (a row of lanes of a merged page)
    as_they_lie = (not merged and dtype != jnp.int8 and kh & (kh - 1) == 0
                   and da._dma_slices_pages(jax.ShapeDtypeStruct(
                       (1026, 64, kh, d), dtype)))
    per_update = 2 if as_they_lie else 2 * kh // (
        da._lane_pack(kh, d) if merged else 1)
    assert module.count("tpu.matmul") == 2 * per_update, (
        module.count("tpu.matmul"), per_update)
    if as_they_lie:
        name = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
        scratch = _kernel_vmem_scratch(module, rank=4)[-2:]
        assert scratch == [((2, p, 64 * kh, d), name)] * 2, scratch
        assert f"1026x{64 * kh}x{d}x{name}" in module
    if merged:
        # the pool stays in HBM as it lies (memory space "any", whole) and
        # the kernel's own DMAs fetch it: two halves of P merged pages for
        # K and for V, each taking in VMEM exactly what it holds
        name = {jnp.bfloat16: "bf16", jnp.float32: "f32"}[dtype]
        # (rank 4 are also q's and the result's tile, ahead of them)
        scratch = _kernel_vmem_scratch(module, rank=4)[-2:]
        assert scratch == [((2, p, 64, kh * d), name)] * 2, scratch
        assert module.count("#tpu.memory_space<any>") >= 2
        assert f"1026x64x{kh * d}x{name}" in module


@pytest.mark.parametrize("h,kh", [(12, 2), (16, 2), (20, 4)],
                         ids=["qwen1.5b", "qwen3b", "falcon-h1"])
def test_ragged_kernel_takes_4d_pages_as_they_lie_by_a_bitcast(
        v5e_sharding, h, kh):
    """PR 44: ``[NB, 64, K, 128]`` bf16 pages go to the kernel ``[NB,
    64 * K, 128]``.  On a v5e that is the same bytes in the same order
    (two bf16 rows to a 32-bit row either way): the compiled call holds
    a bitcast of each pool, no copy of one, and no temporary."""
    blocks = 28 * 1026
    compiled = _lower_ragged(
        v5e_sharding, nt=64, mb=16, h=h, kh=kh, d=128, blocks=blocks,
        whole=True)
    text = compiled.as_text()
    flat = opmap.hlo_shape("bfloat16", (blocks, 64 * kh, 128))
    casts = [ln for ln in text.splitlines()
             if f"= {flat}" in ln and " bitcast(" in ln]
    assert len(casts) == 2, casts
    pool = opmap.hlo_shape("bfloat16", (blocks, 64, kh, 128))
    ops = opmap.op_map_from_hlo(text, STEP_SCOPES, {pool: "pool", flat: "pool"})
    assert [n for n, v in ops.items() if v[2] == "pool"] == []
    assert compiled.memory_analysis().temp_size_in_bytes == 0


def test_ragged_kernel_on_merged_pages_copies_no_pool(v5e_sharding):
    """Why the pool is stored merged (PERF.md section 6, PR 38): handed a
    ``[NB, 64, 8, 64]`` bf16 pool, which a v5e does not keep in that order,
    the compiled call relays BOTH arrays out whole first (a copy of K and
    one of V: two pools of temporaries); handed the same pages ``[NB, 64,
    512]`` it copies nothing pool-shaped and holds no temporary."""
    blocks = 4 * 1026
    found = {}
    for merged in (False, True):
        compiled = _lower_ragged(
            v5e_sharding, nt=64, mb=16, h=32, kh=8, d=64, blocks=blocks,
            merged=merged, whole=True)
        pool = opmap.hlo_shape("bfloat16", (blocks, 64) + (
            (512,) if merged else (8, 64)))
        ops = opmap.op_map_from_hlo(
            compiled.as_text(), STEP_SCOPES, {pool: "pool"})
        found[merged] = (
            sorted(n for n, v in ops.items() if v[2] == "pool"),
            compiled.memory_analysis().temp_size_in_bytes)
    pool_bytes = blocks * 64 * 512 * 2
    copies, temp = found[False]
    assert len(copies) == 2 and all(n.startswith("copy") for n in copies)
    assert temp >= 2 * pool_bytes
    assert found[True] == ([], 0), found[True]


def test_int8_pool_on_a_v5e_is_why_the_slab_form_stays(
        v5e_sharding, monkeypatch):
    """A v5e keeps ``s8[.., 64, 2, 128]`` pages and their ``f32[.., 64,
    2]`` scale pages with the dimensions permuted (two int8 kv heads
    would fill half a tile), which the ragged kernel cannot read.  The
    engine sees that on the pool it allocated (``_pool_is_row_major``)
    and keeps the per-layer slab form there.  This pool lives on the CPU,
    so the choice is forced each way and the two programs compared as the
    v5e compiler sees them: carried, the whole pool is relaid out in and
    out (entry-computation copies, a padded pool of temporaries — 14 GiB
    at ``chat-open``'s pool size against the slab form's 3, PERF.md §4).
    The day this fails the slab form has lost its reason: delete it."""
    from llm_np_cp_tpu.serve import engine as engine_mod

    temps = {}
    for carried in (True, False):
        monkeypatch.setattr(
            engine_mod, "_pool_is_row_major", lambda pages, c=carried: c)
        engine, compiled = _compile_widest_bucket(
            v5e_sharding, jnp.int8, blocks=512)
        temps[carried] = compiled.memory_analysis().temp_size_in_bytes
        # which form this is: what the K/V write writes into (how the
        # compiler cuts the carried form's whole-pool relayout is its own
        # business: in three slab-sized slices, with one packed operand)
        written = {v[2] for v in _pool_ops(engine, compiled).values()
                   if v[0] == SCOPE_KV_WRITE and v[2]}
        assert written == {"pool" if carried else "slab"}
    pool_bytes = sum(a.nbytes for a in engine.pool.pages.pool_arrays())
    assert temps[False] < pool_bytes < temps[True], temps


class _Laid:
    """An array as ``_pool_is_row_major`` looks at it."""

    def __init__(self, *major_to_minor):
        self.ndim = len(major_to_minor)
        self.format = types.SimpleNamespace(layout=types.SimpleNamespace(
            major_to_minor=major_to_minor))


@pytest.mark.parametrize("pages,carried", [
    # what a v5e reports (compiled for a described one): bf16 pages of
    # two kv heads x 128 lie as their shape says; int8 pages and every
    # scale page, and pages of head_dim 64, do not
    (PagedKV(_Laid(0, 1, 2, 3, 4), _Laid(0, 1, 2, 3, 4)), True),
    (PagedKV(_Laid(0, 1, 3, 2, 4), _Laid(0, 1, 3, 2, 4),
             _Laid(0, 2, 3, 1), _Laid(0, 2, 3, 1)), False),
    (PagedKV(_Laid(0, 1, 2, 3, 4), _Laid(0, 1, 2, 3, 4),
             _Laid(0, 2, 3, 1), _Laid(0, 2, 3, 1)), False),
    (PagedKV(_Laid(0, 2, 3, 4, 1), _Laid(0, 2, 3, 4, 1)), False),
    # ...which is why such pages are stored merged, [BS, K * D] (PR 38)
    (PagedKV(_Laid(0, 1, 2, 3), _Laid(0, 1, 2, 3), form=PageForm(64)), True),
], ids=["bf16-2x128", "int8-2x128", "int8-4x128", "bf16-8x64",
        "bf16-64x512-merged"])
def test_the_step_carries_the_pool_where_the_device_keeps_it_row_major(
        pages, carried):
    from llm_np_cp_tpu.serve.engine import _pool_is_row_major

    assert _pool_is_row_major(pages) is carried


@pytest.mark.parametrize("shape,dtype,row_major", [
    # the LFM2 cell's pool as it was and as it is stored (PR 38)
    ((4, 1026, 64, 8, 64), jnp.bfloat16, False),
    ((4, 1026, 64, 512), jnp.bfloat16, True),
    # float32 pages of head_dim 64 are permuted too: the rule is shapes
    ((4, 1026, 64, 8, 64), jnp.float32, False),
    ((4, 1026, 64, 512), jnp.float32, True),
    # one row of lanes (Qwen2.5-0.5B: 2 x 64)
    ((4, 1026, 64, 2, 64), jnp.bfloat16, False),
    ((4, 1026, 64, 128), jnp.bfloat16, True),
    # the pools that stay as they are: Qwen, Falcon-H1, Gemma-2
    ((28, 1026, 64, 2, 128), jnp.bfloat16, True),
    ((6, 1026, 64, 4, 128), jnp.bfloat16, True),
    ((26, 1026, 64, 4, 256), jnp.bfloat16, True),
], ids=["bf16-8x64", "bf16-512-merged", "f32-8x64", "f32-512-merged",
        "bf16-2x64", "bf16-128-merged", "qwen", "falcon-h1", "gemma2"])
def test_a_v5e_keeps_a_merged_page_in_the_order_of_its_shape(
        v5e_sharding, shape, dtype, row_major):
    """What ``Array.format`` of a pool says on the chip, asked of the
    compiler for a described v5e: the layout it gives an array of this
    shape as a program's argument and result.  ``block_pool.merges_pages``
    states the same rule from the shapes alone."""
    from llm_np_cp_tpu.serve.block_pool import merges_pages

    compiled = jax.jit(lambda a: a.at[0, 0, 0].set(1)).lower(
        jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_sharding)).compile()
    kept = compiled.input_formats[0][0].layout.major_to_minor
    assert (kept == tuple(range(len(shape)))) is row_major, kept
    assert compiled.output_formats.layout.major_to_minor == kept
    if len(shape) == 5:  # what the pool's rule makes of such heads
        assert merges_pages(*shape[3:], False) is not row_major


# ----------------------------------------------------------------------
# MiMo-V2's two page classes (PR 43): keys 192 wide, values 128
# ----------------------------------------------------------------------

@pytest.mark.parametrize("shape,row_major", [
    # K heads of 192 are a lane and a half: [.., BS, K, 192] is permuted,
    # its 128-wide V beside it is not
    ((2, 1026, 64, 4, 192), False),
    ((2, 1026, 64, 4, 128), True),
    # ... stored merged, every one of the four is kept as its shape says:
    # global K / V, window K / V (768 / 512 / 1,536 / 1,024 columns)
    ((2, 1026, 64, 768), True),
    ((2, 1026, 64, 512), True),
    ((5, 769, 64, 1536), True),
    ((5, 769, 64, 1024), True),
], ids=["k-4x192", "v-4x128", "global-k", "global-v", "window-k", "window-v"])
def test_a_v5e_keeps_mimo_v2s_pages_in_the_order_of_their_shape_merged(
        v5e_sharding, shape, row_major):
    compiled = jax.jit(lambda a: a.at[0, 0, 0].set(1)).lower(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e_sharding)
    ).compile()
    kept = compiled.input_formats[0][0].layout.major_to_minor
    assert (kept == tuple(range(len(shape)))) is row_major, kept


@pytest.mark.parametrize("kh,mb,blocks,sink,base,wide", [
    (8, 12, 5 * 769, True, True, 0),     # a window layer: its ring's table
    (4, 78, 2 * 4994, False, False, 0),  # a global layer: the growing chain
    # ... with chunks in tiles of 32 lanes: what the kernel CAN take over
    # such pages and no packer lays (``ragged_wide_tile``: 0)
    (8, 12, 5 * 769, True, True, 32),
    (4, 78, 2 * 4994, False, False, 32),
], ids=["window", "global", "window-wide", "global-wide"])
def test_ragged_kernel_compiles_at_mimo_v2s_page_classes(
        v5e_sharding, kh, mb, blocks, sink, base, wide):
    """64 query heads of 192 over ``kh`` kv heads, V pages 128 wide, at
    the cell's widest program (136 tiles; 128 with wide tiles): the
    kernel lowers for the v5e inside its scoped VMEM with a sink operand
    and a table whose column 0 is not position 0, K and V copied by its
    own DMAs out of merged pages of their own widths; without either it
    has neither operand.  ``wide``: the call may hold wide tiles — a
    block of ``q`` is 32 lanes, the scratch 32 tokens' score rows, and
    what it takes of VMEM fits the limit the call asks for.  The kernel
    takes such a call; the engine makes none: a tile of 8 tokens over
    these pages (two heads of 192 to a 384-deep dot, a group of 8 / 16) is
    bound by its arithmetic already, and at a tile of 32 the cell's prompt
    tick ran 1.8 ms LONGER (PERF.md section 6, PR 57)."""
    from llm_np_cp_tpu.ops.pallas import decode_attention as da

    def aval(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e_sharding)

    nt, h, d, dv, i32 = 128 if wide else 136, 64, 192, 128, jnp.int32
    shape = (kh, h // kh, d, dv, jnp.bfloat16, True)
    assert (da._wide_tile_limit(*shape), da.ragged_wide_tile(*shape)) == (32, 0)
    args = [aval((nt * 8, h, d), jnp.bfloat16),
            aval((blocks, 64, kh * d), jnp.bfloat16),
            aval((blocks, 64, kh * dv), jnp.bfloat16),
            aval((64, mb), i32), aval((nt,), i32), aval((nt,), i32),
            aval((nt,), i32), aval((64,), i32), aval((), i32)]
    kw = {}
    if sink:
        kw["sink"] = aval((h,), jnp.float32)
    if base:
        kw["block0"] = aval((64,), i32)
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        compiled = jax.jit(functools.partial(
            da.ragged_paged_attention, scale=d ** -0.5,
            wide_tile=wide)).lower(*args, **kw).compile()
    finally:
        jax.default_backend = real
    _, _, module = _ragged_kernel_call(compiled.as_text())
    p = da.ragged_pages_per_step(mb, 64, kh, d, jnp.bfloat16, False,
                                 merged=True, wide=bool(wide))
    # (a call with wide tiles walks 1,024 positions a step — as many pages
    # as the buffers' budget holds of the window class's wider K page)
    assert p == (min(8, mb) if not wide else 10 if kh == 8 else 16)
    assert _kernel_grid(module) == (nt, -(-mb // p))
    # two heads of 192 share three whole rows of lanes; a value head of
    # 128 is a row of its own
    assert (da._lane_pack(kh, d), da._lane_pack(kh, dv)) == (2, 1)
    scratch = _kernel_vmem_scratch(module, rank=4)[-2:]
    assert scratch == [((2, p, 64, kh * d), "bf16"),
                       ((2, p, 64, kh * dv), "bf16")], scratch
    # the whole tile's update and a one-token tile's, each a dot a pair of
    # key heads and one a value head; a wide tile's a dot a key head and
    # block of 512 score rows and one a value head
    blocks_w = max(wide * (h // kh) // da._WIDE_SHEET_ROWS, 1)
    assert module.count("tpu.matmul") == 2 * (kh // 2 + kh) + (
        2 * kh * blocks_w if wide else 0)
    # the sink: one float32 a score-sheet row (kv head, token, group
    # head) beside the running maximum and the denominator (of a wide
    # tile's tokens where the call has one)
    sig = next(ln for ln in module.splitlines() if "^bb0(" in ln)
    assert sig.count(f"memref<{8 * h}x1xf32") == (
        (1 if sink else 0) if wide else (3 if sink else 2))
    assert sig.count(f"memref<{wide * h}x1xf32") == (2 if wide else 0)
    assert f"{blocks}x64x{kh * d}xbf16" in module


@pytest.mark.parametrize("mb,blocks,base,merged,wide", [
    (78, 4 * 2497, True, True, 0),     # a window layer: its ring's table
    (140, 4482, False, True, 0),       # the global layer: the growing chain
    (140, 4482, False, False, 0),      # ... as [BS, K, D] pages: refused
    # ... in the program a prompt tick runs: chunks in tiles of 64 lanes
    (78, 4 * 2497, True, True, 64),
    (140, 4482, False, True, 64),
], ids=["window", "global", "global-heads-in-rows", "window-wide",
        "global-wide"])
def test_ragged_kernel_at_afmoes_page_classes(
        v5e_sharding, mb, blocks, base, merged, wide):
    """48 query heads of 128 over 8 kv heads (Trinity: K 8, G 6) at the
    cell's widest program (128 tiles): stored MERGED, a head is a whole
    row of a page's lanes and the kernel lowers for the v5e with a table
    whose column 0 is not position 0 (the window class) or without (the
    global one).  As ``[BS, K, D]`` pages the kernel would attend all 8
    heads in one ``[K * rows, BS * K]`` sheet of which a row keeps an
    eighth, and the compiler refuses it: why a pool of two classes stores
    both merged whatever their heads (serve/block_pool.py).  ``wide``: the
    call may hold wide tiles of 64 lanes (what such pages have): ``q``'s
    block is 64 lanes, the scratch 64 tokens' 3,072 score rows, the pages'
    buffers what they were, and the whole fits the scoped VMEM the call
    asks for (the compile is the proof; PR 57)."""
    from llm_np_cp_tpu.ops.pallas import decode_attention as da

    def aval(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e_sharding)

    nt, h, kh, d, i32 = 128, 48, 8, 128, jnp.int32
    assert da.ragged_wide_tile(
        kh, h // kh, d, d, jnp.bfloat16, merged) == (64 if merged else 0)
    page = (64, kh * d) if merged else (64, kh, d)
    args = [aval((nt * 8, h, d), jnp.bfloat16),
            aval((blocks,) + page, jnp.bfloat16),
            aval((blocks,) + page, jnp.bfloat16),
            aval((32, mb), i32), aval((nt,), i32), aval((nt,), i32),
            aval((nt,), i32), aval((32,), i32), aval((), i32)]
    kw = {"block0": aval((32,), i32)} if base else {}
    real = jax.default_backend
    jax.default_backend = lambda: "tpu"
    try:
        lowered = jax.jit(functools.partial(
            da.ragged_paged_attention, scale=d ** -0.5,
            wide_tile=wide)).lower(*args, **kw)
        if not merged:
            with pytest.raises(Exception, match="vmem"):
                lowered.compile()
            return
        compiled = lowered.compile()
    finally:
        jax.default_backend = real
    _, _, module = _ragged_kernel_call(compiled.as_text())
    p = da.ragged_pages_per_step(mb, 64, kh, d, jnp.bfloat16, False,
                                 merged=True, wide=bool(wide))
    # (1,024 positions a step in a call with wide tiles: most of such a
    # call's grid steps are dead ones)
    assert p == (16 if wide else 8)
    assert _kernel_grid(module) == (nt, -(-mb // p))
    assert (da._lane_pack(kh, d), da._dma_slices_pages(
        jnp.zeros((2,) + page, jnp.bfloat16))) == (1, True)
    scratch = _kernel_vmem_scratch(module, rank=4)[-2:]
    assert scratch == [((2, p, 64, kh * d), "bf16")] * 2, scratch
    # the whole tile's update and a one-token tile's, a dot a head for
    # the scores and one for the values — and a wide tile's (64 tokens x
    # 6 group heads: one block of score rows a head)
    assert module.count("tpu.matmul") == (3 if wide else 2) * 2 * kh
    assert f"{blocks}x64x{kh * d}xbf16" in module
    sig = next(ln for ln in module.splitlines() if "^bb0(" in ln)
    rows = (wide or 8) * h
    assert sig.count(f"memref<{rows}x1xf32") == 2  # maximum, denominator
    assert f"memref<{rows}x{d}xf32" in sig         # the accumulator
    # the limit: the 16 MiB a kernel gets unasked, or what the call counts
    limit = da._wide_compiler_params(wide, kh, h // kh, d, d, 64, p,
                                     jnp.bfloat16)
    assert bool(limit) == bool(wide)
    if wide:
        asked = limit["compiler_params"].vmem_limit_bytes
        assert 16 * 2**20 < asked <= 64 * 2**20


def test_a_stack_with_one_page_class_compiles_the_parents_tick(v5e_sharding):
    """The second table exists only for a pool with a window class: the
    Qwen-shaped engine's and a Gemma-2-shaped engine's widest programs
    take the operand, the arguments and the temporaries they took at the
    parent (PR 42; measured there and here with this file's compile, PR
    43), and their operand has the parent's sections and no other."""
    engine, compiled = _compile_widest_bucket(v5e_sharding, jnp.bfloat16)
    program = engine.mixed_buckets[-1]
    layout, words = engine._mixed_layouts[program]
    assert list(layout) == [
        "tokens", "positions", "tok_blk", "tok_off", "tok_row", "tok_slot",
        "tok_live", "tok_lane", "lane_tok", "tile_row", "tile_qpos0",
        "tile_qlen", "tables", "pads", "last_idx", "sample_pos", "seeds",
        "verify_len"]
    assert (program, words, len(engine._mixed_geometry)) == ((160, 136), 1360, 4)
    mem = compiled.memory_analysis()
    assert (mem.temp_size_in_bytes, mem.argument_size_in_bytes) == (
        806400, 118018048)
    gemma = tiny_config(
        "gemma2", hidden_size=256, intermediate_size=512,
        num_attention_heads=8, num_key_value_heads=4, head_dim=128,
        vocab_size=2048, num_hidden_layers=4, sliding_window=128)
    engine, compiled = _compile_widest_bucket(
        v5e_sharding, jnp.bfloat16, cfg=gemma, blocks=1026)
    mem = compiled.memory_analysis()
    assert (engine._mixed_layouts[engine.mixed_buckets[-1]][1],
            mem.temp_size_in_bytes, mem.argument_size_in_bytes) == (
        1360, 451584, 548422144)


def test_mimo_v2_tick_on_a_v5e_reads_both_classes_where_they_lie(v5e_sharding):
    """The unified step of a MiMo-V2-shaped stack at the published
    attention widths (64 heads of 192 over 4 / 8 kv heads, values 128, a
    sink, two page classes), its widest program compiled for the described
    v5e: one ragged kernel call a layer under ``attn_global`` /
    ``attn_window``, both classes written in place (nothing pool- or
    slab-shaped made but the ``kv_write`` scatters), the experts in the
    grouped matmul."""
    cfg = tiny_config(
        "mimo_v2", hidden_size=256, intermediate_size=512,
        num_attention_heads=64, num_key_value_heads=4,
        swa_num_key_value_heads=8, head_dim=192, v_head_dim=128, rope_dim=64,
        sliding_window=128, moe_intermediate_size=128, num_experts_held=4,
        first_expert=4, vocab_size=2048)
    # (1,026 blocks and 64 slots' rings: pools the compiler cannot park
    # in VMEM whole, as no served pool can be)
    engine, compiled = _compile_widest_bucket(
        v5e_sharding, jnp.bfloat16, cfg=cfg, blocks=1026, slots=64, chunk=128)
    pages = engine.pool.pages
    assert pages.k.shape == (2, 1026, BLOCK, 768)
    assert pages.v.shape == (2, 1026, BLOCK, 512)
    # (the default budget, 64 + 2 x 128: a ring of 127 + 320 slots + 1)
    assert engine.window_blocks == 8
    assert [a.shape for a in pages.window] == [
        (3, 513, BLOCK, 1536), (3, 513, BLOCK, 1024)]
    ops = _pool_ops(engine, compiled)
    scopes = {v[0] for v in ops.values()}
    assert {"qkv", "kv_write", "attn_global", "attn_window", "o_proj", "mlp",
            "moe_route", "moe_experts"} <= scopes
    text = compiled.as_text()
    assert len(re.findall(r"%ragged_paged_attention[.\d]* = ", text)) == 5
    # every pool-shaped result is a scatter under kv_write, in place
    moved = {name: v for name, v in ops.items()
             if v[2] and v[0] != "kv_write"}
    assert not moved, moved
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= sum(
        a.nbytes for a in pages.pool_arrays())
    _assert_experts_run_the_kernel(ops, layers=4, weights=[
        (4, 256, 128), (4, 128, 256)],
        pairs=(engine.mixed_buckets[-1][1], cfg.num_experts_per_tok, 256))


def _ragged_kernel_modules(hlo_text):
    """The Mosaic module of every ragged kernel call of a compiled step,
    debug locations dropped (``_ragged_kernel_call`` for a stack whose
    layers are not one scan)."""
    lines = [ln for ln in hlo_text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln
             and "ragged_paged_attention" in ln.split("=", 1)[0]]
    return [_ragged_kernel_call(ln)[2] for ln in lines]


def test_wide_tiles_lie_in_the_chunk_rungs_and_a_decode_tick_keeps_its_call(
        v5e_sharding):
    """A Trinity-shaped stack (48 heads of 128 over 8 kv heads, merged pages
    in two classes: a wide tile of 64 lanes) at 32 slots: the program past
    the rung of 32 one-tile rows — the one only a tick with a prompt chunk
    runs — takes ``q`` and the result in blocks of 64 lanes and walks a
    row's pages 16 to a step; the steady decode tick's program ``(256, 32)``
    calls the kernel it called before there was a wide tile, to the byte:
    the function's own call at 32 tiles with no wide tile.  A MiMo-V2-shaped
    stack has none (``ragged_wide_tile``), in any program (PR 57)."""
    from llm_np_cp_tpu.ops.pallas import decode_attention as da

    cfg = tiny_config(
        "afmoe", hidden_size=256, intermediate_size=512,
        num_attention_heads=48, num_key_value_heads=8, head_dim=128,
        sliding_window=4096, moe_intermediate_size=128, vocab_size=2048)
    engine, decode = _compile_widest_bucket(
        v5e_sharding, jnp.bfloat16, cfg=cfg, blocks=1026, slots=32,
        chunk=128, program=(256, 32))
    assert engine._wide_tile == 64
    wide = {t_w: engine._wide_program(t_w) for t_w, _ in engine.mixed_buckets}
    assert wide == {t_w: 64 if t_w > 256 else 0 for t_w in wide}
    assert max(wide) == 512 and len(engine.mixed_buckets) == 8
    h, mb = cfg.num_attention_heads, engine.max_blocks_per_seq
    modules = _ragged_kernel_modules(decode.as_text())
    assert len(modules) == cfg.num_hidden_layers
    for module in modules:
        sig = next(ln for ln in module.splitlines() if "^bb0(" in ln)
        assert f"memref<{8 * h}x1xf32" in sig  # a tile's score rows
        assert f"memref<{64 * h}x1xf32" not in sig

    def aval(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e_sharding)

    pages, i32 = engine.pool.pages, jnp.int32
    nb = pages.k.shape[0] * pages.k.shape[1]
    args = [aval((256, h, 128), jnp.bfloat16),
            aval((nb,) + pages.k.shape[2:], jnp.bfloat16),
            aval((nb,) + pages.v.shape[2:], jnp.bfloat16),
            aval((32, mb), i32), aval((32,), i32), aval((32,), i32),
            aval((32,), i32), aval((32,), i32), aval((), i32)]
    with _traced_for_a_tpu():
        alone = jax.jit(functools.partial(
            da.ragged_paged_attention, scale=cfg.attn_scale)).lower(
                *args).compile()
    assert _ragged_kernel_call(alone.as_text())[2] in modules
    # the widest program: every call may hold wide tiles
    _, widest = _compile_widest_bucket(
        v5e_sharding, jnp.bfloat16, cfg=cfg, blocks=1026, slots=32,
        chunk=128)
    modules = _ragged_kernel_modules(widest.as_text())
    assert len(modules) == cfg.num_hidden_layers
    for module in modules:
        sig = next(ln for ln in module.splitlines() if "^bb0(" in ln)
        assert sig.count(f"memref<{64 * h}x1xf32") == 2
        assert _kernel_grid(module)[0] == 512 // 8
    # MiMo-V2's pages: none, in any program
    mimo = tiny_config(
        "mimo_v2", hidden_size=256, intermediate_size=512,
        num_attention_heads=64, num_key_value_heads=4,
        swa_num_key_value_heads=8, head_dim=192, v_head_dim=128, rope_dim=64,
        sliding_window=128, moe_intermediate_size=128, num_experts_held=4,
        first_expert=4, vocab_size=2048)
    abstract = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), mimo, dtype=jnp.bfloat16))
    engine = ServeEngine(
        abstract, mimo, max_slots=64, num_blocks=1026, block_size=BLOCK,
        max_seq_len=BLOCK * 8, prefill_chunk=128, cache_dtype=jnp.bfloat16)
    assert engine._wide_tile == 0 and not any(
        engine._wide_program(t_w) for t_w, _ in engine.mixed_buckets)


@pytest.mark.parametrize("shape,row_major,held", [
    # the latent row as ONE array: the block axis becomes the minor one
    ((24, 3458, 64, 576), False, 6_341_787_648),
    # c' and k_pe as two arrays: c' lies as its shape says, k_pe does not,
    # neither by itself nor merged over the block
    ((24, 3458, 64, 512), True, 5_438_963_712),
    ((24, 3458, 64, 64), False, 704_643_072),
    ((24, 3458, 64 * 64), False, 679_870_464),
    # what the pool stores: whole rows of 128 lanes, zeros past 576
    ((24, 3458, 64, 640), True, 6_798_704_640),
    # k_pe two tokens to a row of lanes would lie as its shape says and
    # pad nothing (with c': 1,152 B a token): no kernel reads it yet
    ((24, 3458, 32, 128), True, 679_870_464),
], ids=["576", "c-512", "kpe-64", "kpe-merged-4096", "640-stored",
        "kpe-2-tokens-a-row"])
def test_which_latent_page_a_v5e_keeps_in_the_order_of_its_shape(
        v5e_sharding, shape, row_major, held):
    """The candidates for a page of latent rows ``[c' 512 | k_pe 64]`` at
    the benchmark cell's pool (24 layers x 3,458 blocks x 64 tokens, bf16;
    the algorithm needs 6,118,834,176 B), asked of the compiler for a
    described v5e: the order it keeps and the bytes it really holds.
    ``block_pool.latent_page_width`` states the choice."""
    from llm_np_cp_tpu.serve.block_pool import latent_page_width

    compiled = jax.jit(lambda a: a.at[0, 0, 0].set(1)).lower(
        jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=v5e_sharding)
    ).compile()
    kept = compiled.input_formats[0][0].layout.major_to_minor
    assert (kept == tuple(range(len(shape)))) is row_major, kept
    assert compiled.memory_analysis().argument_size_in_bytes == held
    assert latent_page_width(576) == 640
    assert 24 * 3458 * 64 * 576 * 2 == 6_118_834_176


# ----------------------------------------------------------------------
# the routed experts' grouped matmul at the cells' shapes (kernel alone)
# ----------------------------------------------------------------------
#
# (token, expert) pairs of a program, experts the router spreads them
# over, experts held, hidden, an expert's width: LFM2-8B-A1B's steady tick
# and widest program, Kanana-2's narrowest, steady and widest, and the
# rows of the benchmark's check (run.py ``check_reference``: 4 sequences
# padded to the mix's longest through ``models.forward``)
EXPERT_SHAPES = {
    "lfm2-steady-256": (64 * 4, 32, 32, 2048, 1792),
    "lfm2-widest-1280": (320 * 4, 32, 32, 2048, 1792),
    "lfm2-check-14336": (3584 * 4, 32, 32, 2048, 1792),
    "kanana-narrowest-48": (8 * 6, 128, 16, 2048, 768),
    "kanana-steady-576": (96 * 6, 128, 16, 2048, 768),
    "kanana-widest-2112": (352 * 6, 128, 16, 2048, 768),
    "kanana-check-52224": (8704 * 6, 128, 16, 2048, 768),
}


def _compile_expert_calls(v5e_sharding, rows, experts, held, h, inter, top_k):
    """A layer's two calls at ``rows`` pairs of ``rows // top_k`` tokens,
    in the form ``moe_dropless`` takes them there
    (``ops/moe.expert_rows_in_call``), compiled for the described v5e:
    ``(the calls' lines, the whole text, laid rows, moved in the calls)``."""
    from llm_np_cp_tpu.ops import moe
    from llm_np_cp_tpu.ops.pallas import grouped_matmul as gmm

    tokens = rows // top_k
    tm = gmm.row_tile(rows, experts)
    laid = gmm.tile_count(rows, held, tm) * tm
    in_call = moe.expert_rows_in_call(
        jax.ShapeDtypeStruct((held, h, inter), jnp.bfloat16), tokens, top_k, tm)

    def layer(x, w1, w3, w2, sizes, token, weight):
        layout = gmm.align_groups(sizes, rows, tm)
        kw = dict(act=jax.nn.silu, tm=tm, interpret=False)
        if in_call:
            return gmm.grouped_experts(
                x, w1, w3, w2, layout, token, weight, **kw)
        return gmm.grouped_experts(
            x.astype(w1.dtype)[token], w1, w3, w2, layout, **kw)[layout.dest]

    def aval(shape, dtype=jnp.bfloat16, **kw):
        return jax.ShapeDtypeStruct(shape, dtype, **kw)

    shapes = [((tokens, h), jnp.float32), ((held, h, inter),),
              ((held, h, inter),), ((held, inter, h),), ((held,), jnp.int32),
              ((laid,), jnp.int32), ((laid,), jnp.float32)]
    _export_tpu(layer, *(aval(*s) for s in shapes))
    text = jax.jit(layer).lower(
        *(aval(*s, sharding=v5e_sharding) for s in shapes)).compile().as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 2 and all("%grouped_matmul" in ln for ln in calls)
    return calls, text, laid, in_call


@pytest.mark.parametrize("case", EXPERT_SHAPES)
def test_grouped_matmul_compiles_at_the_cells_shapes(v5e_sharding, case):
    """Both calls of a layer, with the layout they multiply in, exported
    and compiled for the described v5e: the weights stay where they lie
    (operands of the calls as the program's arguments have them, no copy
    of one into VMEM), and the kernel's VMEM is its blocks'.  At a tick's
    rows the calls move the rows themselves — the tokens' float32 rows in,
    their weighted sums out, no gather and no array as long as the pairs
    around them; at the check's (a plain forward over thousands of
    tokens, whose rows do not fit) XLA gathers and un-sorts as before."""
    rows, experts, held, h, inter = EXPERT_SHAPES[case]
    top_k = 4 if case.startswith("lfm2") else 6
    calls, text, laid, in_call = _compile_expert_calls(
        v5e_sharding, rows, experts, held, h, inter, top_k)
    assert in_call == ("check" not in case)
    for shape in ((held, h, inter), (held, inter, h)):
        made = re.findall(
            rf"= {re.escape(opmap.hlo_shape('bfloat16', shape))}\S* "
            r"(?!parameter)[\w-]+\(", text)
        assert not made, f"the weights {shape} are made anew: {made}"
    out_rows = rows // top_k if in_call else laid
    assert f"bf16[{laid},{inter}]" in calls[0]
    assert f"f32[{out_rows},{h}]" in calls[1]
    if in_call:  # nothing but the two calls and the layout's arithmetic
        assert "gather" not in text and f"[{laid},{h}]" not in text
    for ln in calls:
        scope, = re.findall(
            r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"\d+",'
            r'"size":"(\d+)"', ln)
        assert int(scope) < (100 if in_call else 64) * 2**20, scope


# the four expert cells' programs (benchmark/cells/*.json: slots, the
# tick's token budget; ``ServeEngine._make_buckets``): the dense widths of
# the tile ladder's rungs and of the steady decode tick
EXPERT_PROGRAMS = {
    # experts, held, hidden, an expert's width, top k, dense widths
    "lfm2-8b-a1b": (32, 32, 2048, 1792, 4, (8, 16, 32, 64, 128, 256, 320)),
    "kanana-2-30b-a3b-ep8": (
        128, 16, 2048, 768, 6, (8, 16, 32, 64, 96, 128, 256, 352)),
    "mimo-v2.5-ep16": (
        256, 16, 4096, 2048, 8, (8, 16, 32, 64, 128, 256, 512, 576)),
    "ling-3.0-flash-ep4": (
        512, 128, 2560, 768, 8, (8, 16, 32, 64, 128, 256, 320)),
}


@pytest.mark.parametrize("config", EXPERT_PROGRAMS)
def test_expert_calls_move_the_rows_at_every_program_width(v5e_sharding, config):
    """Every program of the four expert cells: the first call holds the
    tokens' ``[T, H]`` float32 whole and the second a float32 block ``[T,
    tn]`` of its result beside their weight blocks, both inside the
    call's ``vmem_limit_bytes`` (the widest is MiMo-V2's, 576 x 4096)."""
    experts, held, h, inter, top_k, widths = EXPERT_PROGRAMS[config]
    for tokens in widths:
        calls, text, laid, in_call = _compile_expert_calls(
            v5e_sharding, tokens * top_k, experts, held, h, inter, top_k)
        assert in_call, tokens
        assert f"bf16[{laid},{inter}]" in calls[0], tokens
        assert f"f32[{tokens},{h}]" in calls[1], tokens
        assert "gather" not in text, tokens


def _assert_experts_run_the_kernel(ops, *, layers, weights, pairs):
    """The routed experts of a compiled step (serve/opmap.py's parse, no
    ``named=`` rescue): two calls of the grouped matmul a layer, each under
    ``moe_experts`` by the ``op_name`` it was traced under, no
    ``ragged-dot`` left, and no operation that gives back an array shaped
    like a layer's expert weights (``weights``: ``[E_held, K, N]`` — a
    copy, a pad or a transpose of them; with the run's leading 1 too).
    The calls move the rows themselves (PR 49): nothing under
    ``moe_experts`` is as long as the program's ``pairs`` (tokens x k) and
    a hidden vector wide — no gather of the laid rows, no un-sort, no
    ``[T, k, H]`` for a masked sum — and the second call of a layer gives
    the tokens' ``[T, H]`` float32."""
    tokens, top_k, hidden = pairs
    long_rows = {}
    for name, (scope, shape, _) in ops.items():
        for dims in re.findall(r"\w+\[([\d,]+)\]", shape):
            dims = [int(d) for d in dims.split(",")]
            if scope == "moe_experts" and dims[-1] == hidden and (
                    np.prod(dims[:-1]) >= tokens * top_k
                    and not name.startswith("grouped_matmul")):
                long_rows[name] = shape
    assert not long_rows, f"rows of every pair under moe_experts: {long_rows}"
    by_token = opmap.hlo_shape("float32", (tokens, hidden))
    assert sum(v[1] == by_token and n.startswith("grouped_matmul")
               for n, v in ops.items()) == layers
    calls = [n for n in ops if n.startswith("grouped_matmul")]
    assert len(calls) == 2 * layers, sorted(ops)
    assert all(ops[n][0] == "moe_experts" for n in calls)
    assert not [n for n in ops if "ragged-dot" in n]
    shaped = {opmap.hlo_shape("bfloat16", lead + tuple(perm))
              for shape in weights for lead in ((), (1,))
              for perm in (shape, (shape[0], shape[2], shape[1]))}
    moved = {n: v[1] for n, v in ops.items() if v[1] in shaped}
    assert not moved, f"expert weights copied, padded or transposed: {moved}"


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_latent_kernel_at_the_tiny_presets_row_compiles_for_v5e(
        v5e_sharding, dtype):
    """``tiny_config("deepseek_v3")``'s row (4 heads over 32 + 8 values,
    stored 128 wide) is what ``chip_smoke.py --latent`` serves on the
    chip: the kernel's own copies of a token's rows must cut whole rows
    of lanes out of ``q`` and out of its result, which is 32 wide there
    (Mosaic: "Slice shape along dimension 2 must be aligned to tiling
    (128), but is 32" - the interpreter checks no alignment)."""
    from llm_np_cp_tpu.ops.pallas.latent_attention import (
        ragged_latent_attention,
    )

    def aval(shape, dt=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=v5e_sharding)

    def run(q, pool, tables, row, qpos0, qlen, tok, pads):
        return ragged_latent_attention(
            q, pool, tables, row, qpos0, qlen, tok, pads, scale=40 ** -0.5,
            rank=32, interpret=False)

    compiled = jax.jit(run).lower(
        aval((64, 4, 128), dtype), aval((40, 16, 128), dtype), aval((4, 8)),
        *[aval((16,))] * 4, aval((4,))).compile()
    assert "ragged_latent_attention" in compiled.as_text()


def test_latent_tick_on_a_v5e_reads_the_pool_where_it_lies(v5e_sharding):
    """The unified step of a latent-attention stack at the published
    attention widths (32 heads over rows of 512 + 64, stored 640 wide; a
    leading dense block, then expert layers with shared experts), its
    widest program compiled for the described v5e: the latent kernel
    lowers inside the step under its own name, the pool (one array, flat
    over layer and block) comes back in place, nothing pool- or
    slab-shaped is made but the ``kv_write`` scatters, and all the step's
    temporaries together are smaller than one layer's slab of rows."""
    cfg = tiny_config(
        "deepseek_v3", hidden_size=256, intermediate_size=512,
        num_attention_heads=32, num_key_value_heads=32, head_dim=64,
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, moe_intermediate_size=128,
        shared_expert_intermediate_size=256, num_experts_held=4,
        first_expert=2, vocab_size=2048)
    # (1,026 blocks: a pool of 63 MB the compiler parks in VMEM whole, a
    # copy in and a copy out that no served pool is small enough for)
    engine, compiled = _compile_widest_bucket(
        v5e_sharding, jnp.bfloat16, cfg=cfg, blocks=1026)
    pages = engine.pool.pages
    assert pages.latent and pages.k.shape == (3, 1026, BLOCK, 640)
    assert pages.v is None
    ops = _assert_tick_copies_no_pool(engine, compiled)
    text = compiled.as_text()
    # a call a layer, under the kernel's own name, and no other's
    assert len(re.findall(r"%ragged_latent_attention[.\d]* = ", text)) == 3
    assert "%ragged_paged_attention" not in text
    # the kernel reads the queries and writes its result on the DENSE
    # token axis: nothing is spread over the tile-aligned one and nothing
    # gathered back from it (PR 48), so the query and the result exist in
    # their dense forms alone
    t_w, d_w = engine.mixed_buckets[-1]
    assert t_w > d_w
    for width in (640, 512):
        assert f"[{t_w},32,{width}]" not in text
        assert f"bf16[{d_w},32,{width}]" in text
    # ... and what it holds in VMEM at the published row (a tile's score
    # rows in float32, both halves of pages, queries and results) is
    # inside the budget the page buffer alone is sized by
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        _VMEM_BUDGET_BYTES,
        _vmem_bytes,
    )
    from llm_np_cp_tpu.ops.pallas.latent_attention import (
        latent_pages_per_step,
        latent_vmem_scratch,
    )

    held = sum(_vmem_bytes(shape, dtype) for shape, dtype in
               latent_vmem_scratch(
                   32, 640, 512,
                   latent_pages_per_step(8, BLOCK, 640, jnp.bfloat16), BLOCK,
                   jnp.bfloat16, jnp.bfloat16))
    assert 3 * 2**20 < held < _VMEM_BUDGET_BYTES
    assert {"qkv", "kv_write", "attn", "o_proj", "mlp", "moe_route",
            "moe_experts", "moe_shared"} <= {v[0] for v in ops.values()}
    # two expert layers x two calls (gate and up in one, down) over the
    # four experts held, where they lie
    _assert_experts_run_the_kernel(ops, layers=2, weights=[
        (4, 256, 128), (4, 128, 256)], pairs=(d_w, 2, 256))


def test_a_pool_on_the_cpu_is_row_major():
    from llm_np_cp_tpu.serve.block_pool import BlockPool
    from llm_np_cp_tpu.serve.engine import _pool_is_row_major

    for dtype in (jnp.float32, jnp.int8):
        pool = BlockPool(tiny_config("llama"), 8, 8, dtype=dtype)
        assert _pool_is_row_major(pool.pages)


def _assert_tick_copies_no_pool(engine, compiled):
    """The compiled unified step of a stack whose layer loop carries the
    pool flat over (layer, block): the donated arrays come back as the
    result, every temporary of the step together is smaller than ONE
    layer's slab of K, and no operation gives back something shaped like
    the pool or like a layer's slab of it except the K/V write itself."""
    pages = engine.pool.pages
    assert engine.pool_carried
    text = compiled.as_text()
    assert "input_output_alias" in text
    k_slab_bytes = int(np.prod(pages.k.shape[1:])) * pages.k.dtype.itemsize
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < k_slab_bytes, (temp, k_slab_bytes)
    pool = opmap.pool_shapes(
        (a.dtype.name, a.shape) for a in pages.pool_arrays())
    ops = opmap.op_map_from_hlo(text, STEP_SCOPES, pool)
    moved = {n: v for n, v in ops.items()
             if v[2] and v[0] != SCOPE_KV_WRITE}
    assert not moved, f"pool- or slab-shaped outside the K/V write: {moved}"
    # what is left is the write: scatters on the carried (flat) pool
    flat = opmap.hlo_shape(
        pages.k.dtype.name,
        (pages.k.shape[0] * pages.k.shape[1],) + pages.k.shape[2:])
    writes = [v[1] for v in ops.values() if v[2]]
    assert writes and set(writes) == {flat}, writes
    return ops


@pytest.mark.parametrize("form", ["flat-bf16-8x64", "whole-int8"])
def test_hybrid_tick_on_a_v5e_names_its_experts_and_rebuilds_no_pool(
        v5e_sharding, monkeypatch, form):
    """A stack of conv / attention layers over dense / expert feed-forwards
    (LFM2-MoE's shapes in small: 8 kv heads of 64).  Two things its
    compiler did to earlier forms of the step (PERF.md section 6, PR 32): a
    scan over stacked expert layers copied a layer's expert tensors out of
    the stack every step, and concatenating per-layer slabs became
    whole-pool pad + maximum passes.  And the one PR 38 removed: the pool
    ``[.., 64, 8, 64]``, which a v5e permutes, was relaid out whole on the
    way in and out of every tick and a layer's slab copied for the kernel —
    stored ``[.., 64, 512]`` it lies as its shape says, the loop carries it
    flat (nothing forces ``_pool_is_row_major`` here: the CPU's pool and
    the described v5e agree) and the tick copies nothing pool-shaped.  An
    int8 pool is still permuted (pages and scale pages): that stack keeps
    the whole-pool carry, written at [layer, block, slot]."""
    import llm_np_cp_tpu.serve.engine as engine_mod

    flat = form == "flat-bf16-8x64"
    if not flat:
        # what Array.format says of an int8 pool on the chip; the CPU's
        # says yes
        monkeypatch.setattr(
            engine_mod, "_pool_is_row_major", lambda pages: False)
    cfg = tiny_config(
        "lfm2_moe", hidden_size=256, intermediate_size=512,
        moe_intermediate_size=256, num_attention_heads=16,
        num_key_value_heads=8, head_dim=64, vocab_size=2048)
    engine, compiled = _compile_widest_bucket(
        v5e_sharding, jnp.bfloat16 if flat else jnp.int8, cfg=cfg,
        program=(SLOTS * 8, 8))
    text = compiled.as_text()
    assert "input_output_alias" in text  # the pool and the state come back in place
    assert engine.pool.pages.merged is flat
    if flat:
        assert engine.pool.pages.k.shape[2:] == (BLOCK, 512)
        _assert_tick_copies_no_pool(engine, compiled)
    pool = opmap.pool_shapes(
        [(a.dtype.name, a.shape) for a in engine.pool.pages.pool_arrays()]
        + [(a.dtype.name, a.shape)
           for a in jax.tree.leaves(engine.pool.pages.state)])
    ops = opmap.op_map_from_hlo(text, STEP_SCOPES, pool)
    # 8 expert layers x 2 calls, each under the scope it was traced in
    _assert_experts_run_the_kernel(ops, layers=8, weights=[
        (8, 256, 256)], pairs=(8, 2, 256))
    assert {"conv", "moe_route", "moe_experts", "attn", "mlp"} <= {
        v[0] for v in ops.values()}
    # (that an expert layer is never stacked, so that no scan slices a
    # layer's experts out of a stack, is config.layer_groups' rule:
    # tests/test_lfm2_moe.py)
    # the pool is neither padded nor concatenated back together
    rebuilt = [n for n, v in ops.items() if v[2] == "pool"
               and re.match(r"(pad|concatenate|maximum)", n)]
    assert not rebuilt, rebuilt
    # nor is the conv state: it is carried whole and written in place at
    # [layer, row] - no run's rows are sliced out, stacked or concatenated
    state = engine.pool.pages.state["conv"]
    whole = opmap.hlo_shape(state.dtype.name, state.shape)
    # (an asynchronous copy-start / copy-done pair is the compiler
    # prefetching these 8 KB into faster memory, not a rebuild)
    moved = [n for n, v in ops.items() if v[1] == whole
             and re.match(r"(copy|pad|concatenate|slice|gather)(\.\d+)?$", n)]
    assert not moved, moved
    assert any(v[1] == whole for v in ops.values()), "the state is not written"


def _falcon_h1_program(sharding, program):
    import json
    from pathlib import Path

    from llm_np_cp_tpu.config import ModelConfig

    cfg = ModelConfig.from_hf_dict(json.loads((
        Path(__file__).resolve().parents[1] / "benchmark" / "configs"
        / "falcon-h1-34b-6l.json").read_text()))
    return _compile_widest_bucket(
        sharding, jnp.bfloat16, cfg=cfg, slots=64, blocks=1026,
        chunk=128, program=program)


@pytest.fixture(scope="module")
def falcon_h1_tick(v5e_sharding):
    """The benchmark's state-space configuration at its published shapes,
    its widest program compiled for the described v5e: (engine, compiled)."""
    return _falcon_h1_program(v5e_sharding, (768, 320))


def test_state_space_tick_on_a_v5e_copies_no_slab_of_the_pool(falcon_h1_tick):
    """Falcon-H1's pages (``[64, 4, 128]`` bf16) a v5e keeps row-major, and
    the whole-pool carry still handed the kernel ``pool[layer]``: a copy of
    a layer's 67 MB K and V slab, six layers a tick
    (``dynamic-slice_bitcast_fusion bf16[1026,64,4,128]``, PERF.md section
    6, PR 38).  Carried flat, the scan over its six ``attn_ssm`` layers
    scatters in place and the kernel reads the pool where it lies."""
    engine, compiled = falcon_h1_tick
    pages = engine.pool.pages
    assert not pages.merged and pages.k.shape == (6, 1026, 64, 4, 128)
    _assert_tick_copies_no_pool(engine, compiled)


def test_state_space_tick_on_a_v5e_updates_the_state_in_place_row_by_row(
        falcon_h1_tick):
    """The benchmark's state-space configuration AT ITS PUBLISHED SHAPES (6
    layers of a Mamba-2 mixer beside attention, 64 slots: a recurrent state
    of 6 x 64 rows of 4 MiB float32, 1.5 GiB), its widest program - decode
    rows' rank-one updates and a prefill chunk's passes in one step.  What
    the compiler may not do with the state, each of which it did to an
    earlier form of the step (PERF.md section 6, PR 34): copy it whole
    (carried through the layer scan it is donated and aliased), materialise
    a layer's rows beside it (a 256 MiB slab: the chunk form over all rows
    did), or gather a state row per TOKEN.  A gather of four rows out of the
    whole state was compiled as a pass over ALL of it (+1.5 GiB of
    temporaries): a prefill chunk's rows are sliced one at a time."""
    engine, compiled = falcon_h1_tick
    assert engine.epilogue_impl == "fused"  # a head of 5,120 x 261,120
    text = compiled.as_text()
    assert "input_output_alias" in text
    state = engine.pool.pages.state["ssm"]
    assert state.shape == (6, 64, 32, 128, 256) and state.dtype == jnp.float32
    slab_bytes = state.nbytes // 6  # one layer's rows: 256 MiB
    # every temporary of the step together is less than ONE layer's rows
    # (measured: 100 MiB): no whole-state copy, no slab beside the state, no
    # [tokens, 32, 128, 256] gather (320 tokens: 1.25 GiB)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < slab_bytes, temp
    ops = opmap.op_map_from_hlo(text, STEP_SCOPES, opmap.pool_shapes(
        [(a.dtype.name, a.shape) for a in jax.tree.leaves(engine.pool.pages)]))
    whole = opmap.hlo_shape("float32", state.shape)
    rows = {opmap.hlo_shape("float32", (n,) + state.shape[1:]) for n in (1, 6)} | {
        opmap.hlo_shape("float32", state.shape[1:])}
    # whatever gives the whole state back (alone, or beside another result)
    # is an in-place update under its scope: the state-update kernel's call
    # for every row's first token (beside it the ``[64, 128, 32]`` it read
    # out of the state), and the chunk loop's write of ONE row, a fusion;
    # the two loops only carry it.  Nothing gives a layer's rows back at all
    writes = [n for n, v in ops.items()
              if whole in v[1] and not n.startswith("while")]
    assert writes and all(ops[n][0] == "ssm_scan" for n in writes), writes
    kernel = [n for n in writes if n.startswith("ssm_state_update")]
    assert len(kernel) == 1 and len(writes) == 2, writes
    assert opmap.hlo_shape("float32", (64, 128, 32)) in ops[kernel[0]][1]
    assert all("fusion" in n for n in writes if n not in kernel), writes
    assert not [n for n, v in ops.items() if v[1] in rows - {whole}]
    assert {"ssm_proj", "ssm_scan", "attn", "mlp", "tail"} <= {
        v[0] for v in ops.values()}



def _takes(text, shape, beside):
    """``{name: opcode}`` of the instructions that take a value shaped
    ``shape`` as an operand, in the computation that holds the instruction
    named ``beside*`` (serve/opmap.py's own reading of a line)."""
    comps, current = {}, None
    for line in text.splitlines():
        if not line.startswith((" ", "\t")):
            m = opmap._COMPUTATION.match(line)
            current = comps.setdefault(m.group(2), []) if m else None
            continue
        m = opmap._INSTRUCTION.match(opmap._LAYOUT.sub("", line))
        if m and current is not None:
            current.append((*m.groups(), line[m.end():]))
    body, = [c for c in comps.values() if any(n.startswith(beside) for n, *_ in c)]
    holds = [n for n, result, _, _ in body if result == shape]
    return {n: opcode for n, _, opcode, operands in body
            if any(re.search(rf"%{re.escape(h)}\b", operands) for h in holds)}


def test_steady_state_space_tick_reads_the_state_once_in_the_kernel(v5e_sharding):
    """The program most ticks run (64 decode rows, ``512 x 64``): in a layer
    the state is an operand of the kernel's call and of the tuple that
    hands it to the chunk loop (no trip in such a tick), and of nothing
    else — the compiler's two passes over all 64 rows (``add_dynamic-
    update-slice_fusion f32[6,64,32,128,256]`` and the second read,
    ``multiply_reduce_fusion f32[64,32,128]``: 0.87 s of a 3 s profile,
    PERF.md section 6, PR 45) are not there, and the step still keeps
    nothing the size of a layer's rows beside the state."""
    engine, compiled = _falcon_h1_program(v5e_sharding, (512, 64))
    text = compiled.as_text()
    state = engine.pool.pages.state["ssm"]
    whole = opmap.hlo_shape("float32", state.shape)
    takers = _takes(text, whole, "ssm_state_update")
    kernel = [n for n in takers if n.startswith("ssm_state_update")]
    assert len(kernel) == 1 and takers[kernel[0]] == "custom-call", takers
    assert set(takers.values()) <= {"custom-call", "tuple"}, takers
    ops = opmap.op_map_from_hlo(text, STEP_SCOPES, {})
    assert ops[kernel[0]][0] == "ssm_scan"
    gone = [n for n, v in ops.items() if v[0] == "ssm_scan" and (
        n.startswith("add_dynamic-update-slice_fusion")
        or v[1] == opmap.hlo_shape("float32", state.shape[1:]))]
    assert not gone, gone
    assert "input_output_alias" in text
    assert compiled.memory_analysis().temp_size_in_bytes < state.nbytes // 6


def test_ling_v3_steady_tick_on_a_v5e_moves_the_matrix_state_in_the_kernel(
        v5e_sharding):
    """The benchmark's delta-rule configuration AT ITS PUBLISHED SHAPES (6
    KDA layers of 32 heads x 128 x 128 float32 a slot beside one latent
    layer, 64 slots: a matrix state of 768 MiB), the program most ticks run
    (64 decode rows, ``512 x 64``): the state is an operand of each KDA
    layer's kernel call and of the tuple that hands it to that layer's chunk
    loop (no trip in such a tick), and of nothing else; the calls sit under
    ``kda_scan``; the state and the pool come back in place; and the step
    keeps nothing the size of a layer's rows beside the state."""
    import json
    from pathlib import Path

    from llm_np_cp_tpu.config import ModelConfig

    cfg = ModelConfig.from_hf_dict(json.loads((
        Path(__file__).resolve().parents[1] / "benchmark" / "configs"
        / "ling-3.0-flash-7l-ep4.json").read_text()))
    engine, compiled = _compile_widest_bucket(
        v5e_sharding, jnp.bfloat16, cfg=cfg, slots=64, blocks=1026,
        chunk=128, program=(512, 64))
    assert engine.epilogue_impl == "fused"  # a head of 2,560 x 157,184
    pages = engine.pool.pages
    assert pages.latent and pages.k.shape == (1, 1026, 64, 640)
    state = pages.state["kda"]
    assert state.shape == (6, 64, 32, 128, 128) and state.dtype == jnp.float32
    text = compiled.as_text()
    assert "input_output_alias" in text
    whole = opmap.hlo_shape("float32", state.shape)
    takers = _takes(text, whole, "kda_state_update")
    kernel = [n for n in takers if n.startswith("kda_state_update")]
    # (every KDA layer is a run of its own: six calls in the one step)
    assert len(kernel) == 6 and all(
        takers[n] == "custom-call" for n in kernel), takers
    assert set(takers.values()) <= {"custom-call", "tuple"}, takers
    ops = opmap.op_map_from_hlo(text, STEP_SCOPES, {})
    assert all(ops[n][0] == "kda_scan" for n in kernel)
    assert {"kda_proj", "kda_scan", "moe_route", "moe_experts", "moe_shared",
            "attn", "tail"} <= {v[0] for v in ops.values()}
    # no operation gives back a layer's rows (128 MiB), or one layer of
    # the state: nothing the size of a slab lies beside the state
    rows = {opmap.hlo_shape("float32", (n,) + state.shape[1:]) for n in (1,)} | {
        opmap.hlo_shape("float32", state.shape[1:])}
    assert not [n for n, v in ops.items() if v[1] in rows]
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * state.nbytes // 6


def test_brumby_steady_tick_on_a_v5e_moves_the_state_in_the_kernel(
        v5e_sharding):
    """The benchmark's attention-free configuration AT ITS PUBLISHED WIDTHS
    (5 power-retention layers, 8 kv heads of 8,704 x 128 + 128 x 128 float32
    a slot; 2 slots here: the state is host memory in this build), a
    decode-only tick: a pool with NO page class compiles; each of the state's
    two leaves is an operand of the layer scan's kernel call and of what
    hands it on, and of nothing that computes; the call sits under
    ``retention_scan``; the state comes back in place; the operand is the
    dense axis and the rows' sections alone; and the step keeps nothing the
    size of a slot's state beside it (``phi`` is never in HBM: 2 MiB of
    temporaries)."""
    import json
    from pathlib import Path

    from llm_np_cp_tpu.config import ModelConfig

    cfg = ModelConfig.from_hf_dict(json.loads((
        Path(__file__).resolve().parents[1] / "benchmark" / "configs"
        / "brumby-14b-5l.json").read_text()))
    engine, compiled = _compile_widest_bucket(
        v5e_sharding, jnp.bfloat16, cfg=cfg, slots=2, blocks=0, chunk=128,
        program=(8, 8))
    assert engine.epilogue_impl == "fused"  # a head of 5,120 x 151,936
    pages = engine.pool.pages
    assert pages.k.size == 0 and engine.pool.num_blocks == 0
    s, z = pages.state["retention"], pages.state["retention_z"]
    assert s.shape == (5, 2, 8, 8704, 128) and z.shape == (5, 2, 8, 128, 128)
    text = compiled.as_text()
    assert "input_output_alias" in text
    ints = re.findall(r"= ((?:[su]\d+|pred)\[[\d,]*\])\S* parameter\(",
                      text[text.index("\nENTRY"):])
    assert ints == [opmap.hlo_shape("int32", (4 * 8 + 5 * 2,))]
    ops = opmap.op_map_from_hlo(text, STEP_SCOPES, {})
    kernel = [n for n in ops if n.startswith("retention_state_update")]
    assert kernel and all(ops[n][0] == "retention_scan" for n in kernel)
    scopes = {v[0] for v in ops.values()}
    assert {"retention_proj", "retention_scan", "mlp", "tail"} <= scopes
    assert not {"attn", "qkv", "kv_write"} & scopes
    for leaf in (s, z):
        takers = _takes(text, opmap.hlo_shape("float32", leaf.shape),
                        "retention_state_update")
        assert any(n.startswith("retention_state_update") for n in takers)
        assert set(takers.values()) <= {
            "custom-call", "tuple", "get-tuple-element", "while"}, takers
    assert compiled.memory_analysis().temp_size_in_bytes < s.nbytes // 10 // 8


# ----------------------------------------------------------------------
# no weight is re-laid-out inside a tick (whole step, for a v5e)
# ----------------------------------------------------------------------
#
# ``jax.jit`` hands a program its arguments in the device's default layout
# and the compiler may not change an entry parameter's: where a dot wants
# its weight the other way round it copies the WHOLE weight, every
# execution.  The engine asks the compiler which layout the steady decode
# program reads each weight in (``ServeEngine.step_weight_formats``) and
# puts the weights there when it is built; every program is then compiled
# for the weights as they lie.  This compiles both ends of the ladder that
# way for a described v5e, at the cells' published widths, and reads the
# compiled text for what is left (``serve/opmap.weight_relayouts``).

_CONFIGS = pathlib.Path(__file__).resolve().parent.parent / "benchmark/configs"

# (configuration, the cell's slots and token budget, the keys that cut its
# depth to one or two layers a kind: every width as published)
LAYOUT_CASES = [
    ("qwen2.5-1.5b", 64, None, dict(num_hidden_layers=2)),
    ("qwen2.5-3b", 64, None, dict(num_hidden_layers=2)),
    ("lfm2-8b-a1b-16l", 64, None, dict(
        num_hidden_layers=3, layer_types=["conv", "full_attention", "conv"])),
    ("falcon-h1-34b-6l", 64, None, dict(num_hidden_layers=1)),
    ("kanana-2-30b-a3b-24l-ep8", 96, None, dict(num_hidden_layers=2)),
    ("mimo-v2.5-7l-ep16", 64, 576, dict(
        num_hidden_layers=3, hybrid_layer_pattern=[0, 1, 0],
        moe_layer_freq=[0, 1, 1])),
    ("ling-3.0-flash-7l-ep4", 64, None, dict(
        num_hidden_layers=2, layer_group_size=2,
        expert_swiglu_limit_list=[0, 0],
        share_expert_swiglu_limit_list=[0, 0])),
    ("trinity-large-5l-ep8", 32, 800, dict(
        num_hidden_layers=2,
        layer_types=["sliding_attention", "full_attention"])),
]


@pytest.mark.parametrize("name,slots,budget,cut", LAYOUT_CASES,
                         ids=[c[0] for c in LAYOUT_CASES])
def test_no_program_of_the_tick_re_lays_out_a_weight(
        v5e_sharding, name, slots, budget, cut):
    """The steady decode program and the widest program of every
    configuration a cell uses hold NO entry-level ``copy`` / ``transpose``
    / loop fusion whose operand is a WEIGHT parameter (or a ``bitcast`` /
    ``copy-done`` of one) and whose result is as large (>= 1 M elements)
    - told by operand, not by size: a wide program's activations are as
    large, and the pool, ``kv_write`` and the recurrent / conv state are
    other arguments.

    Before the engine laid its weights out (the parent of PR 54, weights
    in the default layout), one tick's program held, at ENTRY level, at
    the cells' full depth (blocks 1026, chunk 128; bytes WRITTEN a tick):

    - ``mimo-v2.5-7l-ep16``: 7 x ``%copy bf16[1,4096,12288]{1,2,0}
      copy(params['layers'][i]['q_proj'])`` + 5 x ``k_proj [1,4096,1536]``
      + 2 x ``k_proj [1,4096,768]``: 780 MB (the chip: 2.2 ms of 21);
    - ``kanana-2-30b-a3b-24l-ep8``: 24 x ``q_proj [1,2048,6144]`` + 24 x
      ``kv_b_proj [1,512,8192]``: 805 MB;
    - ``ling-3.0-flash-7l-ep4``: 6 x ``copy bf16[4096,2560]`` under
      ``kda_proj/bsh,ho->bso/dot_general`` + ``q_proj [1,2560,6144]`` +
      ``kv_b_proj [1,512,8192]``: 166 MB;
    - ``trinity-large-5l-ep8``: 1 x ``copy bf16[3072,25024]
      copy(params['lm_head'])``: 154 MB;
    - both Qwen, ``lfm2-8b-a1b-16l``, ``falcon-h1-34b-6l``: none of a
      weight at ENTRY level.

    Each a transposing copy of a weight PARAMETER where the projection's
    result is split into heads straight after the dot (64 x 192, the
    absorbed ``kv_b_proj``) and the compiler folds the split into the
    dot.  Those four cases fail on that parent, at this test's depth
    too (327 / 67 / 61 / 154 MB) - and so does ``falcon-h1``: its ONE
    layer here is no loop, and ``%copy bf16[512,5120]`` of ``v_proj``
    (5 MB) stands at ENTRY level where the cell's six scanned layers
    keep it in the loop's body (``%copy bf16[1,5120,512]{1,2,0}`` under
    ``while/body/dynamic_slice``), out of this parse's sight."""
    from llm_np_cp_tpu.config import ModelConfig

    spec = json.loads((_CONFIGS / f"{name}.json").read_text())
    spec.update(cut)
    cfg = ModelConfig.from_hf_dict(spec)
    abstract = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    engine = ServeEngine(
        abstract, cfg, max_slots=slots, num_blocks=258, block_size=BLOCK,
        max_seq_len=BLOCK * 16, prefill_chunk=128, cache_dtype=jnp.bfloat16,
        tick_token_budget=budget)
    assert engine._weight_formats is None  # shapes alone: nothing to put

    def aval(x, sharding=v5e_sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    params, pages = jax.tree.map(aval, abstract), jax.tree.map(
        aval, engine.pool.pages)
    steady = engine._pick_bucket(slots * engine._q_tile, slots)
    with _traced_for_a_tpu():
        formats = engine.step_weight_formats(params, pages)
        # the weights as the engine's build leaves them
        laid = jax.tree.map(
            lambda a, f: a if f.layout is None else aval(a, f),
            params, formats)
        for program in (steady, engine.mixed_buckets[-1]):
            text = engine._mixed_step.lower(
                laid, pages, aval(engine._dead_mixed_operands(*program)),
            ).compile().as_text()
            left = opmap.weight_relayouts(text)
            assert not left, (
                f"program {program} writes {sum(n for _, _, n, _ in left)} B "
                f"of weights out again every tick: {left[:4]}")
