"""ServeEngine end-to-end: continuous batching must be output-invisible.

The whole serving layer (queueing, paged pool, packed decode, eviction)
is legitimate only if a request cannot tell it shared the machine: every
request's greedy tokens must equal ``Generator.generate_ragged`` run
offline on the same prompt (the acceptance criterion for the serve/
subsystem), whether its KV lived in contiguous slabs or scattered
blocks, bf16/f32 or int8, interrupted by preemption or not.

CPU backend, tiny fixture; the compile-counter assertions ride along so
the parity traffic doubles as the jit-stability evidence.
"""

import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.generate import Generator
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import ServeEngine, poisson_trace
from tools.compile_counter import assert_serve_compiles_bounded


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _offline_tokens(gen: Generator, req) -> list[int]:
    res = gen.generate_ragged([req.prompt], req.max_new_tokens, seed=req.seed)
    return [int(t) for t in np.asarray(res.tokens)[0][: req.max_new_tokens]]


def _assert_parity(engine: ServeEngine, cfg, params, cache_dtype) -> None:
    gen = Generator(
        params, cfg, sampler=Sampler(kind="greedy"), cache_dtype=cache_dtype
    )
    assert engine.scheduler.finished, "nothing finished — bad test setup"
    for req in engine.scheduler.finished:
        assert req.generated == _offline_tokens(gen, req), (
            f"request {req.req_id} (preempted {req.n_preemptions}x) diverged "
            "from the offline run"
        )


@pytest.mark.parametrize(
    "tick_kw", [{}, {"mixed_step": "off"}], ids=["unified", "split"])
def test_trace_parity_32_requests_and_bounded_compiles(tiny, tick_kw):
    """The acceptance criterion: a 32-request Poisson trace through the
    engine produces per-request greedy tokens identical to offline
    ``generate_ragged``, and the jitted steps compile once per packed
    width bucket (the default engine: the tick that is served) or once
    per distinct phase shape (the phase-split tick) — never per tick."""
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=4, num_blocks=48, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32, **tick_kw,
    )
    assert engine.mixed == (not tick_kw)
    rng = np.random.default_rng(0)
    trace = poisson_trace(
        rng, 32, rate_rps=40.0, prompt_len_range=(3, 14),
        max_new_tokens=6, vocab_size=cfg.vocab_size,
    )
    snap = engine.replay_trace(trace)
    assert snap["finished"] == 32
    _assert_parity(engine, cfg, params, jnp.float32)

    # distinct prefill shapes == distinct block allocations at prefill
    # time (no preemptions here, so each request prefilled its prompt
    # rounded up to whole chunks)
    chunk = engine.prefill_chunk
    shapes = {
        engine.pool.blocks_for(-(-r.prompt_len // chunk) * chunk)
        for r in engine.scheduler.finished
    }
    assert engine.scheduler.n_preemptions == 0
    assert_serve_compiles_bounded(engine, distinct_prefill_shapes=len(shapes))
    counts = engine.compile_counts()
    if engine.mixed:
        assert set(counts) == {"mixed_step"}
        assert 1 <= counts["mixed_step"] <= len(engine.mixed_buckets)
        assert snap["ticks"] > counts["mixed_step"]
        return
    assert counts["decode_step"] == 1
    assert snap["ticks"] > counts["decode_step"] + counts["prefill_step"]


def test_eviction_requeue_parity(tiny):
    """A pool too small for the running set forces evict→requeue; the
    re-prefilled (teacher-forced) request must still produce the exact
    uninterrupted token sequence."""
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=2, num_blocks=6, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32,
    )
    rng = np.random.default_rng(7)
    for n in (4, 5, 3):
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), 20)
    engine.run_until_complete()
    assert engine.scheduler.n_preemptions > 0, (
        "pool was not tight enough to exercise eviction"
    )
    assert len(engine.scheduler.finished) == 3
    _assert_parity(engine, cfg, params, jnp.float32)
    # preempted blocks all returned
    assert engine.pool.free_list.num_allocated == 0


def test_int8_block_pool_parity(tiny):
    """int8 pool blocks (quantize on write, dequantize on gather — the
    cache.quantize_kv discipline) must decode exactly like the
    contiguous int8 ``KVCache``: same greedy tokens on the tiny
    fixture."""
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=3, num_blocks=16, block_size=8, max_seq_len=64,
        cache_dtype=jnp.int8,
    )
    assert engine.pool.pages.quantized
    rng = np.random.default_rng(11)
    for n in (6, 11, 4):
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), 5)
    engine.run_until_complete()
    assert len(engine.scheduler.finished) == 3
    _assert_parity(engine, cfg, params, jnp.int8)


def test_streaming_callbacks_per_request(tiny):
    """Each generated token reaches the request's callback in order, and
    detokenized deltas concatenate to the full decoded text."""
    cfg, params = tiny

    class Tok:
        def decode(self, ids, skip_special_tokens=True):
            return "".join(chr(97 + (int(i) % 26)) for i in ids)

    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=2, num_blocks=16, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32, tokenizer=Tok(),
    )
    got: dict[int, list] = {}
    text: dict[int, str] = {}

    def cb(req, token, delta):
        got.setdefault(req.req_id, []).append(token)
        if delta:
            text[req.req_id] = text.get(req.req_id, "") + delta

    rng = np.random.default_rng(2)
    reqs = [
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), 4, callback=cb)
        for n in (3, 7)
    ]
    engine.run_until_complete()
    for req in reqs:
        assert got[req.req_id] == req.generated
        assert text[req.req_id] == Tok().decode(req.generated)


def test_submit_rejects_impossible_requests(tiny):
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, max_slots=1, num_blocks=4, block_size=8, max_seq_len=24,
        cache_dtype=jnp.float32,
    )
    with pytest.raises(ValueError, match="max_seq_len"):
        engine.submit(np.arange(1, 20, dtype=np.int32), 30)
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit(np.zeros(0, np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(np.asarray([5], np.int32), 0)


def test_submit_rejects_unadmittable_request(tiny):
    """The submit check must mirror the scheduler's admission rule
    (prefill need + decode reserve): with prefill_chunk=100 over 64-slot
    blocks, a 150-token prompt fits max_seq_len and the raw pool, but
    its 200-wide prefill needs 4 blocks + 1 reserve > 4 allocatable —
    it would starve the FIFO head forever if accepted."""
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, max_slots=1, num_blocks=5, block_size=64,
        max_seq_len=256, prefill_chunk=100, cache_dtype=jnp.float32,
    )
    with pytest.raises(ValueError, match="pool capacity"):
        engine.submit(np.arange(1, 151, dtype=np.int32), 1)
    # a request whose worst-case admission leaves the reserve free is in
    engine.submit(np.arange(1, 11, dtype=np.int32), 2)


# ---------------------------------------------------------------------------
# attn_impl="paged": the zero-gather decode path.  Same acceptance bar as
# the gather path — offline parity, one decode compile — plus a structural
# assertion that the [L, B, S_max] gathered view never exists in the traced
# program.
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr):
    """Every eqn in ``jaxpr`` and all nested sub-jaxprs (pjit/scan/...)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            yield from _iter_param_eqns(v)


def _iter_param_eqns(v):
    if isinstance(v, jax.extend.core.ClosedJaxpr):
        yield from _iter_eqns(v.jaxpr)
    elif isinstance(v, jax.extend.core.Jaxpr):
        yield from _iter_eqns(v)
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _iter_param_eqns(x)


def _decode_step_shapes(engine: ServeEngine) -> set[tuple[int, ...]]:
    """Output shapes of every eqn in the traced decode step."""
    b = engine.scheduler.max_slots
    mb = engine.max_blocks_per_seq
    args = (
        engine.params, engine.pool.pages,
        jnp.zeros((b, mb), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.int32), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), jnp.uint32),
    )
    jaxpr = jax.make_jaxpr(lambda *a: engine._decode_step(*a))(*args)
    return {
        tuple(eqn_var.aval.shape)
        for eqn in _iter_eqns(jaxpr.jaxpr)
        for eqn_var in eqn.outvars
        if hasattr(eqn_var.aval, "shape")
    }


def test_paged_trace_parity_32_requests_and_bounded_compiles(tiny):
    """The gather-path acceptance criterion, re-run under
    attn_impl='paged' (CPU interpret mode runs the same kernel logic the
    TPU compiles): 32-request trace == offline generate_ragged, decode
    compiles ONCE."""
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=4, num_blocks=48, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32, decode_attn_impl="paged", mixed_step="off",
    )
    assert engine.decode_attn_impl == "paged"
    rng = np.random.default_rng(0)
    trace = poisson_trace(
        rng, 32, rate_rps=40.0, prompt_len_range=(3, 14),
        max_new_tokens=6, vocab_size=cfg.vocab_size,
    )
    snap = engine.replay_trace(trace)
    assert snap["finished"] == 32
    _assert_parity(engine, cfg, params, jnp.float32)
    counts = engine.compile_counts()
    assert counts["decode_step"] == 1
    # the paged path streams less cache than the gather view per tick
    assert 0 < snap["kv_bytes_tick_mean"]


def test_paged_int8_pool_parity(tiny):
    """int8 pool blocks flow through the paged kernel (quantize on the
    in-scan write, scale pages streamed) with the same greedy tokens as
    the gather path's dequantize-on-gather."""
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=3, num_blocks=16, block_size=8, max_seq_len=64,
        cache_dtype=jnp.int8, decode_attn_impl="paged", mixed_step="off",
    )
    assert engine.decode_attn_impl == "paged"
    rng = np.random.default_rng(11)
    for n in (6, 11, 4):
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), 5)
    engine.run_until_complete()
    assert len(engine.scheduler.finished) == 3
    _assert_parity(engine, cfg, params, jnp.int8)


def test_paged_gemma2_sliding_window_parity():
    """Gemma-2's alternating sliding layers reach the paged kernel as an
    effective left pad (row_pads = max(pads, vis - window)) instead of a
    mask tensor — tokens must match the gather path exactly, or the
    per-layer window math is off by one."""
    cfg = tiny_config("gemma2")
    assert cfg.sliding_window is not None
    params = init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)

    def run(impl):
        engine = ServeEngine(
            params, cfg, sampler=Sampler(kind="greedy"),
            max_slots=2, num_blocks=32, block_size=8, max_seq_len=64,
            cache_dtype=jnp.float32, decode_attn_impl=impl, mixed_step="off",
        )
        rng = np.random.default_rng(5)
        # long decodes so visible length crosses the window bound and
        # several block boundaries on both layer kinds
        for n in (9, 13):
            engine.submit(rng.integers(1, cfg.vocab_size, size=n), 16)
        engine.run_until_complete()
        return {r.req_id: r.generated for r in engine.scheduler.finished}

    assert run("xla") == run("paged")


def test_paged_decode_step_has_no_materialized_gather(tiny):
    """Structural zero-gather assertion: the gathered cache view
    [L, B, S_max, K, D] (or its per-layer [B, S_max, K, D] slice) exists
    in the gather step's jaxpr and in NO eqn of the paged step's."""
    cfg, params = tiny

    def build(impl):
        return ServeEngine(
            params, cfg, sampler=Sampler(kind="greedy"),
            max_slots=4, num_blocks=16, block_size=8, max_seq_len=64,
            cache_dtype=jnp.float32, decode_attn_impl=impl, mixed_step="off",
        )

    l = cfg.num_hidden_layers
    kh, d = cfg.num_key_value_heads, cfg.head_dim
    b, s_max = 4, 64
    gathered = {(l, b, s_max, kh, d), (b, s_max, kh, d)}

    gather_shapes = _decode_step_shapes(build("xla"))
    assert gathered & gather_shapes, (
        "control failed: the gather step no longer materializes the "
        "gathered view — update this test's shape expectations"
    )
    paged_shapes = _decode_step_shapes(build("paged"))
    hit = gathered & paged_shapes
    assert not hit, (
        f"attn_impl='paged' materialized a gathered cache view {hit} — "
        "the zero-gather contract is broken"
    )


def test_engine_rejects_unknown_decode_impl(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="decode_attn_impl"):
        ServeEngine(params, cfg, decode_attn_impl="pallas")


def test_paged_falls_back_to_xla_when_probe_fails(tiny, monkeypatch):
    """The hardware gate: when Mosaic rejects the paged kernel the
    engine downgrades to the gather path with a warning instead of dying
    at first dispatch."""
    import llm_np_cp_tpu.ops.pallas.support as support

    monkeypatch.setattr(support, "_FORCE_FAIL", True)
    support._probe.cache_clear()
    try:
        cfg, params = tiny
        engine = ServeEngine(
            params, cfg, max_slots=2, num_blocks=16, block_size=8,
            max_seq_len=64, cache_dtype=jnp.float32,
            decode_attn_impl="paged", mixed_step="off",
        )
        assert engine.decode_attn_impl == "xla"
    finally:
        support._probe.cache_clear()


# ---------------------------------------------------------------------------
# Refcounted prefix sharing: identical prompts reuse prompt blocks; a hit
# must skip prefill chunks without changing a single output token.
# ---------------------------------------------------------------------------

def _count_prefill_calls(engine):
    calls = [0]
    orig = engine._prefill_step

    def counting(*a, **k):
        calls[0] += 1
        return orig(*a, **k)

    engine._prefill_step = counting
    return calls


@pytest.mark.parametrize("impl", ["xla", "paged"])
def test_prefix_sharing_parity_and_fewer_prefill_dispatches(tiny, impl):
    """4 repeats of 2 distinct prompts: the shared run must emit the
    exact tokens of the unshared run (and offline), dispatch strictly
    fewer prefill chunks, and report the hit rate in the metrics."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (20, 17)]

    def run(prefix: bool):
        engine = ServeEngine(
            params, cfg, sampler=Sampler(kind="greedy"),
            max_slots=4, num_blocks=48, block_size=8, max_seq_len=64,
            cache_dtype=jnp.float32, decode_attn_impl=impl, mixed_step="off",
            enable_prefix_cache=prefix,
        )
        calls = _count_prefill_calls(engine)
        for rep in range(4):
            for j, p in enumerate(prompts):
                engine.submit(p, 4, seed=j)
        engine.run_until_complete()
        tokens = {r.req_id: r.generated for r in engine.scheduler.finished}
        return tokens, calls[0], engine

    base_tokens, base_calls, _ = run(prefix=False)
    shared_tokens, shared_calls, engine = run(prefix=True)
    assert shared_tokens == base_tokens
    assert shared_calls < base_calls, (
        f"prefix sharing dispatched {shared_calls} prefill chunks, "
        f"expected strictly fewer than the unshared {base_calls}"
    )
    snap = engine.metrics.snapshot()
    assert snap["prefix_blocks_hit"] > 0
    assert 0 < snap["prefix_hit_rate"] <= 1
    _assert_parity(engine, cfg, params, jnp.float32)
    # every request's references were released; only the cache's own
    # remain, and they are all reclaimable
    fl = engine.pool.free_list
    assert fl.num_free + fl.num_allocated == fl.capacity
    assert fl.num_allocated == len(engine.pool.prefix_cache)
    assert engine.pool.prefix_cache.n_reclaimable == fl.num_allocated


def test_prefix_sharing_eviction_stress_parity(tiny):
    """Interleave evict-on-OOM with shared prefixes on a pool too small
    for the running set: refcounted eviction must never free a block a
    live request still references (FreeList would raise on the resulting
    double free) and every request must still match the offline run."""
    cfg, params = tiny
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (9, 9, 5)]
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=2, num_blocks=8, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32, enable_prefix_cache=True,
    )
    for rep in range(3):
        for j, p in enumerate(prompts):
            engine.submit(p, 12, seed=j)
    engine.run_until_complete()
    assert len(engine.scheduler.finished) == 9
    assert engine.scheduler.n_preemptions > 0, (
        "pool was not tight enough to exercise eviction"
    )
    _assert_parity(engine, cfg, params, jnp.float32)
    fl = engine.pool.free_list
    assert fl.num_free + fl.num_allocated == fl.capacity
    assert fl.num_allocated == len(engine.pool.prefix_cache)


def test_metrics_snapshot_shape(tiny):
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=2, num_blocks=16, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32,
    )
    rng = np.random.default_rng(4)
    trace = poisson_trace(
        rng, 5, rate_rps=100.0, prompt_len_range=(2, 10),
        max_new_tokens=3, vocab_size=cfg.vocab_size,
    )
    snap = engine.replay_trace(trace)
    assert snap["submitted"] == snap["finished"] == 5
    assert snap["total_generated_tokens"] == 15
    assert snap["throughput_tok_s"] > 0
    assert snap["ttft_s_p50"] > 0
    assert 0 <= snap["occupancy_p99"] <= 1
    assert "tok/s" in engine.metrics.format()
