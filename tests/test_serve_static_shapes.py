"""The serve/ static-shape lint (tools/compile_counter.py).

A recompile inside the serving tick loop is a multi-second stall for
every queued request, so the engine's contract is: after one warm pass
over the workload's phase shapes, further traffic triggers ZERO backend
compiles.  Two independent probes pin it — the engine's own per-program
jit cache sizes, and a process-wide ``jax.monitoring`` listener that
would also catch an accidentally-unjitted (retracing) code path.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import ServeEngine
from tools.compile_counter import CompileCounter, assert_serve_compiles_bounded


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    """The default engine — the tick ``cli serve`` serves."""
    kw.setdefault("num_blocks", 24)
    kw.setdefault("max_slots", 2)
    return ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32, **kw,
    )


def _drive(engine, cfg, lens, max_new=5, seed0=0):
    rng = np.random.default_rng(seed0)
    for i, n in enumerate(lens):
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), max_new,
                      seed=seed0 + i)
    engine.run_until_complete()


def test_steady_state_ticks_compile_nothing():
    """Warm pass covers the phase shapes; a second batch of requests
    reusing those shapes (different lengths, same block-count buckets)
    must run with zero new backend compiles."""
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    engine = _engine(cfg, params)
    # warm (block_size=8, chunk=8, budget 18): two prompts in one tick
    # on the widest rung (4 + 12) and on the middle one (5 + 4), two
    # decode rows, then a lone prompt and a lone decode row
    _drive(engine, cfg, lens=(4, 12, 5, 4, 6), seed0=0)
    warm_counts = dict(engine.compile_counts())

    counter = CompileCounter()
    with counter.watch():
        _drive(engine, cfg, lens=(6, 3, 10, 15, 7), seed0=100)
    assert counter.count == 0, (
        f"steady-state serving compiled: {counter.events}"
    )
    assert engine.compile_counts() == warm_counts


def test_prefix_steady_state_ticks_compile_nothing():
    """The served tick with prefix sharing: after ``warmup`` (one compile
    a packed-width bucket) repeated traffic — prompt-length buckets,
    prefix hits, refcount churn — triggers ZERO backend compiles and
    ``mixed_step`` stays the only program."""
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)
    engine = _engine(cfg, params, num_blocks=32, enable_prefix_cache=True)
    assert engine.mixed
    engine.warmup([4, 12], max_new_tokens=5)
    warm_counts = dict(engine.compile_counts())
    assert warm_counts == {"mixed_step": len(engine.mixed_buckets)}

    counter = CompileCounter()
    with counter.watch():
        for _ in range(3):  # rounds 2+ hit the prefix cache
            _drive(engine, cfg, lens=(4, 12, 4, 12, 4), seed0=0)
    assert counter.count == 0, (
        f"prefix steady-state serving compiled: {counter.events}"
    )
    assert engine.compile_counts() == warm_counts
    assert engine.metrics.prefix_blocks_hit > 0


def test_compile_counts_bounded_by_buckets():
    """The per-program contract of the served tick: ONE program,
    ``mixed_step``, compiled at most once a packed-width bucket however
    many requests, prompt lengths or ticks ran — and with no warm-up,
    only the buckets the traffic actually packed."""
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(1), cfg, dtype=jnp.float32)
    engine = _engine(cfg, params)
    assert engine.mixed
    _drive(engine, cfg, lens=(3, 5, 9, 14, 2, 11, 8, 16), seed0=0)
    assert engine.scheduler.n_preemptions == 0
    assert_serve_compiles_bounded(engine)
    counts = engine.compile_counts()
    assert set(counts) == {"mixed_step"}
    assert 1 <= counts["mixed_step"] <= len(engine.mixed_buckets)
    assert engine.metrics.n_ticks > counts["mixed_step"]


def _parent_rungs(engine):
    """How many programs the parent warmed at this geometry: one a rung
    of its packed-width ladder (q-tile multiples doubling up to the
    worst aligned total)."""
    qb = engine._q_tile
    worst = engine.tick_token_budget + engine.scheduler.max_slots * (qb - 1)
    n, t = 1, qb
    while t < worst:
        n, t = n + 1, t * 2
    return n


# the benchmark cells' geometry (budget 320: nine programs where the
# parent warmed eight), 128 slots, and a small engine every program of
# which a tick can reach
PROGRAM_GEOMETRIES = {
    "cells-64-slots": (dict(max_slots=64, num_blocks=64 * 6 + 8, block_size=64,
                            max_seq_len=384, prefill_chunk=128), 9),
    "128-slots": (dict(max_slots=128, num_blocks=128 * 2 + 8, block_size=64,
                       max_seq_len=128, prefill_chunk=128), 10),
    "4-slots": (dict(max_slots=4, num_blocks=48, block_size=8, max_seq_len=64,
                     prefill_chunk=16), 5),
}


@pytest.mark.parametrize("geometry", sorted(PROGRAM_GEOMETRIES))
def test_warmup_pays_for_one_program_more_than_the_parent(geometry):
    """The program set is part of the design (a warm program costs
    0.7-1 s of every start: PERF.md §6, PR 30 / 31): at most ONE more
    than the parent's ladder has rungs, warm-up compiles exactly that
    many, and a replay that visits every program compiles nothing."""
    kw, n_programs = PROGRAM_GEOMETRIES[geometry]
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    engine = ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                         cache_dtype=jnp.float32, **kw)
    assert engine.mixed
    assert len(engine.mixed_buckets) == n_programs == _parent_rungs(engine) + 1
    if geometry == "128-slots":
        return  # the count is the claim; the small engines replay it
    engine.warmup([4, 12], max_new_tokens=3)
    warm = dict(engine.compile_counts())
    assert warm == {"mixed_step": n_programs}
    assert engine._mixed_step._cache_size() == n_programs

    pack, picked = engine._pack_mixed, set()

    def recording_pack(decode_rows, prefill_segs):
        packed = pack(decode_rows, prefill_segs)
        picked.add(packed[1])
        return packed

    engine._pack_mixed = recording_pack
    slots, chunk = kw["max_slots"], kw["prefill_chunk"]
    rng = np.random.default_rng(17)

    def submit(n, new, seed):
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), new, seed=seed)

    counter = CompileCounter()
    with counter.watch():
        # every slot prefills at once, decodes as one full batch (the
        # steady decode tick) and drains row by row down the ladder
        for i in range(slots):
            submit(4, 3 + i % 12, i)
        engine.run_until_complete()
        # most of a batch decoding when a prompt of a chunk and more
        # arrives: more tiles than the slots' rung holds
        for i in range(slots - max(slots // 8, 1)):
            submit(4, 8, 100 + i)
        engine.step()
        engine.step()
        submit(chunk + 2, 2, 99)
        engine.run_until_complete()
        # ...and a mix of arrivals beside decoding rows
        for i in range(2 * min(slots, 8)):
            submit(int(rng.integers(1, chunk + 8)),
                   int(rng.integers(2, 9)), 200 + i)
            for _ in range(int(rng.integers(0, 3))):
                engine.step()
        engine.run_until_complete()
        submit(3, 3, 999)  # ...and one request alone
        engine.run_until_complete()
    assert counter.count == 0, f"a tick compiled: {counter.events}"
    assert engine.compile_counts() == warm
    assert picked == set(engine.mixed_buckets), (
        sorted(set(engine.mixed_buckets) - picked))
    assert_serve_compiles_bounded(engine)


@pytest.fixture(scope="module")
def cells_engine():
    """An engine at the benchmark cells' geometry, never dispatched: its
    program set and ``_pick_bucket`` are host arithmetic."""
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    kw, _ = PROGRAM_GEOMETRIES["cells-64-slots"]
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                       cache_dtype=jnp.float32, **kw)


# (tile lanes, tokens) of a tick → the program it runs
PICKS = {
    "one-decode-row": ((8, 1), (8, 8)),
    "the-steady-decode-tick": ((512, 64), (512, 64)),
    "63-rows-and-a-chunk": ((63 * 8 + 128, 63 + 128), (768, 320)),
    "a-chunk-alone": ((128, 128), (128, 128)),
    "the-whole-budget": ((320 + 64 * 7, 320), (768, 320)),
    "one-token-past-the-steady-width": ((512, 65), (512, 320)),
}


@pytest.mark.parametrize("tick", sorted(PICKS))
def test_a_tick_takes_the_narrowest_program_that_holds_it(cells_engine, tick):
    """``_pick_bucket``: the first program, in ``(tile lanes, dense
    width)`` order, that holds the tick's tiles AND its tokens — so the
    steady decode tick (64 one-tile rows) runs 64 lanes wide, and one
    token more falls back on the rung's capacity."""
    (lanes, tokens), want = PICKS[tick]
    engine = cells_engine
    assert engine._pick_bucket(lanes, tokens) == want
    assert want in engine.mixed_buckets
    held = [p for p in engine.mixed_buckets
            if p[0] >= lanes and p[1] >= tokens]
    assert want == min(held)
    with pytest.raises(AssertionError, match="budget accounting"):
        engine._pick_bucket(engine.mixed_buckets[-1][0] + 8, 1)


# (moved here from test_serve_mixed.py, PR 46: the compile-count contract
# of the default engine, and a third of that file's time — one xdist worker
# runs a file whole)

@pytest.mark.mesh
@pytest.mark.parametrize("mesh", [None, 2], ids=["one-device", "model=2"])
@pytest.mark.parametrize("prefix", [False, True], ids=["noprefix", "prefix"])
@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8],
                         ids=["f32", "int8"])
def test_default_engine_names_only_mixed_step(tiny, cache_dtype, prefix, mesh):
    """Whatever the pool's dtype, with or without the prefix cache, on
    one device or tensor-parallel: the default engine is the unified
    tick, its one program is ``mixed_step`` (plus the host tier's two
    where a tier is attached), warm-up compiles it once a bucket and
    traffic afterwards — prefix hits included — compiles nothing."""
    from llm_np_cp_tpu.parallel.sharding import MeshPlan
    from llm_np_cp_tpu.serve.host_tier import HostTier

    cfg, params = tiny
    tier = HostTier(8 << 20) if prefix else None
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
        num_blocks=32, block_size=8, max_seq_len=64,
        cache_dtype=cache_dtype, enable_prefix_cache=prefix,
        host_tier=tier,
        mesh_plan=MeshPlan(model=mesh) if mesh else None,
    )
    assert engine.mixed
    want = {"mixed_step"} | ({"restore_block", "slice_block"} if prefix
                             else set())
    assert set(engine.compile_counts()) == want
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (19, 6)]
    engine.warmup([19, 6], max_new_tokens=4)
    warm = engine.compile_counts()
    assert warm["mixed_step"] == len(engine.mixed_buckets)
    with CompileCounter().watch() as counter:
        for _ in range(2):
            for j, p in enumerate(prompts):
                engine.submit(p, 4, seed=j)
            engine.run_until_complete()
    assert counter.count == 0, counter.events
    assert engine.compile_counts() == warm
    assert_serve_compiles_bounded(engine)
    assert (engine.metrics.prefix_blocks_hit > 0) == prefix
    if tier is not None:
        tier.close()


def test_mixed_zero_compiles_across_ragged_composition_churn(tiny):
    """After warmup compiles every packed-width bucket, ticks whose
    prefill:decode row mix churns arbitrarily (fresh prompts, varied
    lengths and budgets-worth of chunk slices, decode-only tails) must
    trigger ZERO backend compiles."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=4, num_blocks=48)
    rng = np.random.default_rng(4)
    lens = (3, 26, 7, 14, 9, 21)
    engine.warmup([int(n) for n in lens], max_new_tokens=8)
    warm = dict(engine.compile_counts())
    assert warm["mixed_step"] == len(engine.mixed_buckets)

    counter = CompileCounter()
    with counter.watch():
        for rep in range(3):
            for i, n in enumerate(lens):
                engine.submit(rng.integers(1, cfg.vocab_size, size=n),
                              3 + (i % 5), seed=rep * 10 + i)
            engine.run_until_complete()
    assert counter.count == 0, (
        f"composition churn compiled: {counter.events}"
    )
    assert engine.compile_counts() == warm
