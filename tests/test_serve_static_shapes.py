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


def _engine(cfg, params, **kw):
    """The default engine — the tick ``cli serve`` serves; the tests of
    the phase-split tick's own programs ask for it (``mixed_step="off"``)."""
    kw.setdefault("num_blocks", 24)
    return ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=2, block_size=8, max_seq_len=64,
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
    # warm: 1-block and 2-block prefills (block_size=8, chunk=8)
    _drive(engine, cfg, lens=(4, 12), seed0=0)
    warm_counts = dict(engine.compile_counts())

    counter = CompileCounter()
    with counter.watch():
        _drive(engine, cfg, lens=(6, 3, 10, 15, 7), seed0=100)
    assert counter.count == 0, (
        f"steady-state serving compiled: {counter.events}"
    )
    assert engine.compile_counts() == warm_counts


def test_paged_prefix_steady_state_ticks_compile_nothing():
    """The paged decode path with prefix sharing: after a warm pass over
    the phase shapes (prompt-length buckets AND shared-prefix depths),
    repeated traffic — including prefix hits and refcount churn — must
    trigger ZERO backend compiles, and decode must have compiled exactly
    once."""
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)
    engine = _engine(cfg, params, num_blocks=32, decode_attn_impl="paged",
                     mixed_step="off", enable_prefix_cache=True)
    assert not engine.mixed and engine.decode_attn_impl == "paged"
    # warm: both block-count buckets, then a repeat so the prefix-hit
    # path (gather_prefix per shared depth) compiles too
    _drive(engine, cfg, lens=(4, 12), seed0=0)
    _drive(engine, cfg, lens=(4, 12), seed0=0)
    warm_counts = dict(engine.compile_counts())
    assert warm_counts["decode_step"] == 1

    counter = CompileCounter()
    with counter.watch():
        _drive(engine, cfg, lens=(4, 12, 4, 12, 4), seed0=0)
    assert counter.count == 0, (
        f"paged+prefix steady-state serving compiled: {counter.events}"
    )
    assert engine.compile_counts() == warm_counts
    assert engine.metrics.prefix_blocks_hit > 0


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
    assert_serve_compiles_bounded(engine, distinct_prefill_shapes=0)
    counts = engine.compile_counts()
    assert set(counts) == {"mixed_step"}
    assert 1 <= counts["mixed_step"] <= len(engine.mixed_buckets)
    assert engine.metrics.n_ticks > counts["mixed_step"]


def test_compile_counts_bounded_by_phase_shapes():
    """The phase-split tick's per-program contract: decode/sample/prefill
    compile once (the temp prefill cache has a fixed capacity), scatter
    at most once per distinct prefill block count, regardless of how
    many requests or ticks ran."""
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(1), cfg, dtype=jnp.float32)
    engine = _engine(cfg, params, mixed_step="off")
    lens = (3, 5, 9, 14, 2, 11, 8, 16)
    _drive(engine, cfg, lens=lens, seed0=0)
    chunk = engine.prefill_chunk
    shapes = {
        engine.pool.blocks_for(-(-r.prompt_len // chunk) * chunk)
        for r in engine.scheduler.finished
    }
    assert engine.scheduler.n_preemptions == 0
    assert_serve_compiles_bounded(engine, distinct_prefill_shapes=len(shapes))
    counts = engine.compile_counts()
    assert counts["decode_step"] == 1
    assert counts["sample_first"] == 1
    assert counts["prefill_step"] == 1


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
    assert_serve_compiles_bounded(engine, distinct_prefill_shapes=0)
