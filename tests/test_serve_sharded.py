"""Mesh-sharded ServeEngine (mesh_plan=...) on the 8-device CPU mesh.

The acceptance bar is output invisibility: a TP-sharded engine — params
column/row-sharded, pool slabs kv-head-partitioned, block tables
replicated — must reproduce the single-chip engine's token streams
EXACTLY (int8 pools, prefix sharing,
gemma sliding windows, abort, supervised recovery), with zero compiles
across ticks once warm (the static-shape contract extended to
placement) and the slabs actually partitioned (pinned by inspecting
the committed shardings, not trusted from the spec).

Unlike tests/test_sharding.py these tests do NOT need ``jax.set_mesh``
— the serve path commits every operand explicitly.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.generate import Generator
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.parallel.sharding import MeshPlan, paged_kv_specs
from llm_np_cp_tpu.serve import ServeEngine, poisson_trace
from tools.compile_counter import (
    CompileCounter,
    assert_serve_compiles_bounded,
)

pytestmark = pytest.mark.mesh


def shardable_tiny(model_type="llama", **kw):
    # dims divisible by model=4: heads 8, kv 4, I 128, V 256
    kw.setdefault("num_attention_heads", 8)
    kw.setdefault("num_key_value_heads", 4)
    kw.setdefault("head_dim", 8)
    kw.setdefault("hidden_size", 64)
    return tiny_config(model_type, **kw)


@pytest.fixture(scope="module")
def tiny():
    cfg = shardable_tiny()
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, plan=None, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                       mesh_plan=plan, **kw)


def _tokens(engine):
    return {r.req_id: r.generated for r in engine.scheduler.finished}


def _trace(cfg, n=32, seed=0, **kw):
    rng = np.random.default_rng(seed)
    kw.setdefault("prompt_len_range", (3, 14))
    kw.setdefault("max_new_tokens", 6)
    return poisson_trace(rng, n, rate_rps=40.0,
                         vocab_size=cfg.vocab_size, **kw)


# ---------------------------------------------------------------------------
# The acceptance criterion: 32-request token parity, TP vs single chip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tp", [2, 4])
def test_tp_trace_parity_32_requests(tiny, tp):
    cfg, params = tiny
    trace = _trace(cfg)

    def run(plan):
        engine = _engine(cfg, params, plan)
        snap = engine.replay_trace(trace)
        assert snap["finished"] == 32
        return engine

    single = run(None)
    sharded = run(MeshPlan(model=tp))
    assert sharded.mesh is not None and sharded._kv_sharded
    assert _tokens(sharded) == _tokens(single)
    # the unified tick keeps its Pallas ragged kernel under the mesh
    # (shard_map harness; interpret mode on CPU, Mosaic on TPU)
    assert sharded.mixed and sharded.ragged_attn_impl == "pallas"


def test_tp_offline_parity_and_int8(tiny):
    """Sharded serving == offline generate_ragged, and int8 pools keep
    parity with their kv-head-sharded scale pages."""
    cfg, params = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (6, 11, 4)]

    for dtype in (jnp.float32, jnp.int8):
        engine = _engine(cfg, params, MeshPlan(model=2),
                         cache_dtype=dtype, max_slots=3, num_blocks=32)
        for j, p in enumerate(prompts):
            engine.submit(p, 5, seed=j)
        engine.run_until_complete()
        gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                        cache_dtype=dtype)
        for req in engine.scheduler.finished:
            res = gen.generate_ragged([req.prompt], 5, seed=req.seed)
            want = [int(t) for t in np.asarray(res.tokens)[0][:5]]
            assert req.generated == want, f"dtype={dtype} diverged"
        if dtype == jnp.int8:
            assert engine.pool.pages.quantized
            spec = engine.pool.pages.k_scale.sharding.spec
            assert "model" in tuple(spec), (
                "int8 scale pages must shard with the kv heads"
            )


def test_gemma_sliding_window_kv_replicated_parity():
    """Gemma-2-style kv heads (2) < TP degree (4): the slabs replicate
    (TP+GQA hard part), the engine drops to the partitionable XLA
    attention paths, and tokens still match the single chip."""
    cfg = shardable_tiny("gemma2", num_key_value_heads=2)
    params = init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)
    assert cfg.sliding_window is not None
    trace = _trace(cfg, n=8, seed=3)

    def run(plan):
        engine = _engine(cfg, params, plan)
        engine.replay_trace(trace)
        return engine

    single, sharded = run(None), run(MeshPlan(model=4))
    assert sharded.mesh is not None and not sharded._kv_sharded
    assert sharded.ragged_attn_impl == "xla"  # no shard_map harness
    assert _tokens(sharded) == _tokens(single)
    # replicated slabs: one shard's bytes == the whole slab
    st = sharded.pool.stats()
    assert st["kv_shards"] == 1
    assert st["kv_bytes_shard"] == st["kv_bytes_total"]


def test_tp_prefix_sharing_parity_and_hits(tiny):
    """Prefix-cache sharing works unchanged over sharded slabs — the
    registry is host-side block ids, which are shard-invariant."""
    cfg, params = tiny
    trace = _trace(cfg, n=24, seed=5, prompt_len_range=(18, 30),
                   distinct_prompts=4)

    def run(plan):
        engine = _engine(cfg, params, plan, enable_prefix_cache=True,
                         num_blocks=64)
        snap = engine.replay_trace(trace)
        return engine, snap

    single, snap_s = run(None)
    sharded, snap_m = run(MeshPlan(model=2))
    assert _tokens(sharded) == _tokens(single)
    assert snap_m["prefix_blocks_hit"] > 0
    assert snap_m["prefix_blocks_hit"] == snap_s["prefix_blocks_hit"]


def test_tp_abort_and_recovery_parity(tiny):
    """Abort mid-flight and supervised recovery (clone_fresh + recover)
    behave identically under the mesh, sharing the sharded compiled
    steps."""
    cfg, params = tiny
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (7, 12, 5)]

    engine = _engine(cfg, params, MeshPlan(model=2))
    # warm every packed-width bucket up front so the zero-compile claim
    # below isolates restart/recovery (a recovery's teacher-forced
    # prefill may pack a bucket ordinary traffic never hit)
    engine.warmup([int(p.size) for p in prompts], max_new_tokens=6)
    live = [engine.submit(p, 6, seed=j) for j, p in enumerate(prompts)]
    engine.step()
    assert engine.abort(live[1].req_id)
    engine.step()
    rebuilt = engine.clone_fresh()
    with CompileCounter().watch() as counter:
        for r in (live[0], live[2]):
            if r.req_id in engine._requests:
                rebuilt.recover(
                    r.prompt, r.max_new_tokens, request_id=r.req_id,
                    seed=r.seed, generated=list(r.generated),
                )
        rebuilt.run_until_complete()
    assert counter.count == 0, (
        f"sharded restart/recovery recompiled: {counter.events}"
    )
    # token parity for the survivors vs uninterrupted single chip
    single = _engine(cfg, params)
    for j, p in enumerate(prompts):
        if j != 1:
            single.submit(p, 6, seed=j)
    single.run_until_complete()
    want = {tuple(r.generated) for r in single.scheduler.finished}
    got = {
        tuple(r.generated)
        for e in (engine, rebuilt)
        for r in e.scheduler.finished
    }
    assert got == want
    assert rebuilt.pool.stats()["request_held"] == 0


# ---------------------------------------------------------------------------
# The placement contract: really sharded, really stable
# ---------------------------------------------------------------------------

def test_slabs_partitioned_and_operands_replicated(tiny):
    """The in-aval pin, inspected at runtime: pool slabs carry the
    kv-head 'model' sharding (per-shard bytes really shrink), and the
    slab sharding is a FIXED POINT across ticks — the spelled spec the
    engine commits equals the spec GSPMD returns, which is what keeps
    tick N+1 on the compiled program (no mid-graph resharding)."""
    cfg, params = tiny
    plan = MeshPlan(model=4)
    engine = _engine(cfg, params, plan)
    want_spec = tuple(paged_kv_specs(cfg, plan).k)
    assert tuple(engine.pool.pages.k.sharding.spec) == want_spec
    st = engine.pool.stats()
    assert st["kv_shards"] == 4
    assert st["kv_bytes_shard"] * 4 == st["kv_bytes_total"]

    for t in _trace(cfg, n=6, seed=7):
        engine.submit(t["prompt"], t["max_new_tokens"])
    for _ in range(3):
        engine.step()
        assert tuple(engine.pool.pages.k.sharding.spec) == want_spec, (
            "slab sharding drifted across a tick — in-avals not pinned"
        )
    engine.run_until_complete()


def test_zero_compiles_across_sharded_ticks(tiny):
    """After warmup, composition churn (prefill-heavy, decode-only,
    prefix hits, varied lengths) triggers ZERO compiles under the mesh
    — the compile-counter acceptance criterion."""
    cfg, params = tiny
    engine = _engine(cfg, params, MeshPlan(model=2),
                     enable_prefix_cache=True, num_blocks=64)
    trace = _trace(cfg, n=24, seed=13, prompt_len_range=(3, 30),
                   distinct_prompts=6)
    engine.warmup([int(t["prompt"].size) for t in trace],
                  max_new_tokens=6)
    with CompileCounter().watch() as counter:
        engine.replay_trace(trace)
    assert counter.count == 0, f"sharded ticks compiled: {counter.events}"
    assert_serve_compiles_bounded(engine)


def test_mesh_plan_rejects_non_tp_axes(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="tensor-parallel only"):
        _engine(cfg, params, MeshPlan(data=2, model=2))
    with pytest.raises(ValueError, match="not divisible"):
        _engine(cfg, params, MeshPlan(model=3))
