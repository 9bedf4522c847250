"""Unified ragged prefill+decode tick (ServeEngine mixed_step).

The acceptance bar for the tick is output invisibility — every
request's greedy tokens must equal an offline run of the same prompt (the
parity oracle, ``_offline_tokens``) on the identical workload (int8 pools, prefix sharing,
gemma sliding windows, eviction, abort, and chaos-style recovery replays
included) — plus its two structural claims: ONE device dispatch per
tick, and one ``mixed_step`` compile per packed-width bucket with ZERO
compiles across ticks while the prefill:decode composition churns.

CPU backend; the Pallas ragged kernel runs in interpret mode (same
kernel logic the TPU compiles), the XLA fallback is exercised via the
probe-failure hook.
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
from llm_np_cp_tpu.serve import ServeEngine, poisson_trace
from llm_np_cp_tpu.serve.engine import (
    mixed_operand_layout,
    mixed_operand_program,
    split_mixed_operands,
)
from tools.compile_counter import (
    CompileCounter,
    assert_serve_compiles_bounded,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"), **kw)


def _sections(engine, ops):
    """The sections of a packed operand of any of the engine's programs."""
    program = mixed_operand_program(
        ops.shape[0], engine.mixed_buckets, *engine._mixed_geometry)
    return split_mixed_operands(ops, engine._mixed_layouts[program][0])


def _tokens(engine):
    return {r.req_id: r.generated for r in engine.scheduler.finished}


# The parity oracle, one a (weights, cache dtype) of this module, and what
# it has answered (tests repeat prompts, and their spec / plain cases serve
# the same workload).  A float32 cache is held to the PLAIN FORWARD: greedy
# continuation by ``models.forward`` over the whole sequence, no cache at
# all — one program at one width serves every length, because a causal
# model's logits at a position do not see what follows it.  An int8 cache
# is held to the offline ``Generator`` over its int8 ``KVCache`` (the same
# quantization on both sides), whose programs compile once a prompt length
# and budget.
_ORACLES: dict = {}
_ORACLE_WIDTH = 64


def _offline_tokens(cfg, params, cache_dtype, prompt, n, seed=0):
    from llm_np_cp_tpu.models import forward

    prompt = np.asarray(prompt, np.int32)
    plain = (jnp.dtype(cache_dtype) == jnp.float32
             and prompt.size + n <= _ORACLE_WIDTH)
    key = (id(params), "forward" if plain else jnp.dtype(cache_dtype).name)
    if key not in _ORACLES:
        oracle = (jax.jit(lambda ids: forward(params, ids, cfg)[0]) if plain
                  else Generator(params, cfg, sampler=Sampler(kind="greedy"),
                                 cache_dtype=cache_dtype))
        # (``params`` is kept: its id stays this tree's)
        _ORACLES[key] = (oracle, params, {})
    oracle, _, memo = _ORACLES[key]
    asked = (prompt.tobytes(), int(n), int(seed))
    if asked in memo:
        return memo[asked]
    if plain:
        ids = [int(t) for t in prompt]
        for _ in range(n):
            padded = np.zeros((1, _ORACLE_WIDTH), np.int32)
            padded[0, :len(ids)] = ids
            ids.append(int(jnp.argmax(oracle(padded)[0, len(ids) - 1])))
        memo[asked] = ids[prompt.size:]
    else:
        res = oracle.generate_ragged([prompt], n, seed=seed)
        memo[asked] = [int(t) for t in np.asarray(res.tokens)[0][:n]]
    return memo[asked]


def _assert_offline_parity(engine, cfg, params, cache_dtype):
    assert engine.scheduler.finished, "nothing finished — bad test setup"
    for req in engine.scheduler.finished:
        want = _offline_tokens(cfg, params, cache_dtype, req.prompt,
                               req.max_new_tokens, req.seed)
        assert req.generated == want, (
            f"request {req.req_id} (preempted {req.n_preemptions}x) "
            "diverged from the offline run"
        )


# ---------------------------------------------------------------------------
# The acceptance criterion: 32-request offline parity
# ---------------------------------------------------------------------------

def test_mixed_trace_parity_32_requests_vs_offline(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(0)
    trace = poisson_trace(
        rng, 32, rate_rps=40.0, prompt_len_range=(3, 14),
        max_new_tokens=6, vocab_size=cfg.vocab_size,
    )

    mixed = _engine(cfg, params)
    assert mixed.replay_trace(trace)["finished"] == 32
    assert mixed.mixed and mixed.ragged_attn_impl == "pallas"
    _assert_offline_parity(mixed, cfg, params, jnp.float32)
    assert_serve_compiles_bounded(mixed)
    counts = mixed.compile_counts()
    assert set(counts) == {"mixed_step"}
    assert counts["mixed_step"] <= len(mixed.mixed_buckets)
    # the unified tick's budget accounting is visible in the metrics
    snap = mixed.metrics.snapshot()
    assert snap["mixed_decode_tokens"] == snap["total_generated_tokens"] - 32
    assert snap["mixed_prefill_tokens"] > 0


def test_mixed_int8_pool_parity(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (6, 11, 4)]

    mixed = _engine(cfg, params, max_slots=3, num_blocks=16,
                    cache_dtype=jnp.int8)
    for j, p in enumerate(prompts):
        mixed.submit(p, 5, seed=j)
    mixed.run_until_complete()
    assert mixed.mixed and mixed.pool.pages.quantized
    _assert_offline_parity(mixed, cfg, params, jnp.int8)


def _force_pool_form(monkeypatch, carried):
    """Build engines as if the device reported the pool row-major
    (carried through the layer scan) or not (per-layer slabs)."""
    from llm_np_cp_tpu.serve import engine as engine_mod

    monkeypatch.setattr(
        engine_mod, "_pool_is_row_major", lambda pages: carried)


@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8],
                         ids=["f32", "int8"])
@pytest.mark.parametrize("carried", [True, False], ids=["carried", "slabs"])
def test_mixed_tick_writes_each_layer_at_its_own_blocks_only(
        monkeypatch, carried, cache_dtype):
    """Two layers' writes must not meet: after one tick the pool differs
    from the pool before it ONLY at ``[l, tok_blk, tok_off]``, in every
    layer ``l`` — a wrong ``layer * NB`` offset into the flat carried
    pool that token parity on a tiny model could miss through block 0."""
    from llm_np_cp_tpu.serve.block_pool import PagedKV

    _force_pool_form(monkeypatch, carried)
    cfg = tiny_config("llama", num_hidden_layers=2)
    params = init_params(jax.random.PRNGKey(3), cfg, dtype=jnp.float32)
    engine = _engine(cfg, params, max_slots=3, num_blocks=12,
                     cache_dtype=cache_dtype)
    rng = np.random.default_rng(5)

    def noise(a):  # nothing a tick would write there
        if a.dtype == jnp.int8:
            return rng.integers(-100, 100, a.shape)
        return rng.normal(size=a.shape) + 7.0

    engine.pool.pages = PagedKV(*(
        None if a is None else jnp.asarray(noise(a), dtype=a.dtype)
        for a in engine.pool.pages))
    lanes = []
    step = engine._mixed_step

    def spy(params_, pages, ops):
        sec = _sections(engine, np.asarray(ops))
        lanes.append((sec["tok_blk"], sec["tok_off"], sec["tok_live"] != 0))
        return step(params_, pages, ops)

    engine._mixed_step = spy
    for j, n in enumerate((13, 5)):
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), 4, seed=j)
    n_blocks = engine.pool.pages.num_blocks
    for _ in range(4):  # prefill chunks, then decode rows beside them
        before = [None if a is None else np.asarray(a)
                  for a in engine.pool.pages]
        engine.step()
        tok_blk, tok_off, tok_live = lanes.pop()
        assert not lanes and tok_live.any()
        # dead lanes write (block 0, slot 0) of every layer
        may = np.zeros((n_blocks, engine.block_size), bool)
        may[tok_blk, tok_off] = True
        must = np.zeros_like(may)
        must[tok_blk[tok_live], tok_off[tok_live]] = True
        assert tok_blk[tok_live].min() > 0  # block 0 is the scratch block
        for old, new in zip(before, engine.pool.pages):
            if old is None:
                continue
            changed = (np.asarray(new) != old).reshape(
                old.shape[:3] + (-1,)).any(axis=-1)  # [L, NB, BS]
            for layer in range(cfg.num_hidden_layers):
                assert not (changed[layer] & ~may).any(), layer
                assert (changed[layer] | ~must).all(), layer


@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8],
                         ids=["f32", "int8"])
def test_mixed_slab_form_parity(tiny, monkeypatch, cache_dtype):
    """Where the device does not keep the pool row-major the step hands
    each layer its slab instead of carrying the pool: same tokens."""
    cfg, params = tiny
    rng = np.random.default_rng(17)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (12, 3, 19)]

    def run(carried):
        _force_pool_form(monkeypatch, carried)
        engine = _engine(cfg, params, max_slots=3,
                         cache_dtype=cache_dtype)
        for j, p in enumerate(prompts):
            engine.submit(p, 6, seed=j)
        engine.run_until_complete()
        return engine

    slabs = run(False)
    assert _tokens(slabs) == _tokens(run(True))
    _assert_offline_parity(slabs, cfg, params, cache_dtype)


def test_mixed_gemma2_sliding_window_parity():
    """Gemma-2's alternating sliding layers reach the ragged kernel as a
    traced per-layer window bound — long decodes crossing the window and
    several block boundaries must match the offline run exactly."""
    cfg = tiny_config("gemma2")
    assert cfg.sliding_window is not None
    params = init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (9, 13)]

    engine = _engine(cfg, params, max_slots=2, num_blocks=32)
    for j, p in enumerate(prompts):
        engine.submit(p, 16, seed=j)
    engine.run_until_complete()
    _assert_offline_parity(engine, cfg, params, jnp.float32)


def test_mixed_prefix_sharing_parity_and_zero_copy(tiny):
    """Prefix hits under the unified tick: covered chunks consume no
    budget and no copy program runs (shared blocks are attended in
    place) — tokens still match the unshared run and the offline run,
    and the hit-rate metrics flow."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (20, 17)]

    def run(prefix):
        engine = _engine(cfg, params, enable_prefix_cache=prefix)
        for rep in range(4):
            for j, p in enumerate(prompts):
                engine.submit(p, 4, seed=j)
        engine.run_until_complete()
        return engine

    shared, cold = run(True), run(False)
    assert _tokens(shared) == _tokens(cold)
    snap = shared.metrics.snapshot()
    assert snap["prefix_blocks_hit"] > 0
    assert 0 < snap["prefix_hit_rate"] <= 1
    # covered content consumed no budget: the shared run planned fewer
    # prefill tokens than the cold run
    assert (snap["mixed_prefill_tokens"]
            < cold.metrics.snapshot()["mixed_prefill_tokens"])
    _assert_offline_parity(shared, cfg, params, jnp.float32)
    fl = shared.pool.free_list
    assert fl.num_free + fl.num_allocated == fl.capacity
    assert fl.num_allocated == len(shared.pool.prefix_cache)


def test_mixed_eviction_requeue_parity(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (4, 5, 3)]

    mixed = _engine(cfg, params, max_slots=2, num_blocks=6)
    for j, p in enumerate(prompts):
        mixed.submit(p, 20, seed=j)
    mixed.run_until_complete()
    assert mixed.scheduler.n_preemptions > 0, "pool not tight enough"
    _assert_offline_parity(mixed, cfg, params, jnp.float32)
    assert mixed.pool.free_list.num_allocated == 0


def test_mixed_abort_mid_prefill_and_mid_decode(tiny):
    """Abort in every unified-tick state: a request mid-prefill (budget
    small enough that prefill spans ticks), one mid-decode, one queued —
    blocks all return."""
    cfg, params = tiny
    rng = np.random.default_rng(9)
    long_p = rng.integers(1, cfg.vocab_size, size=24)
    short_p = rng.integers(1, cfg.vocab_size, size=5)
    engine = _engine(cfg, params, max_slots=2, tick_token_budget=10)
    r_long = engine.submit(long_p, 6, seed=0)
    r_short = engine.submit(short_p, 6, seed=1)
    engine.step()
    assert not r_long.prefilled and r_long.prefill_done > 0, (
        "budget did not split the long prefill across ticks"
    )
    assert engine.abort(r_long.req_id)          # mid-prefill
    engine.step()
    assert engine.abort(r_short.req_id) or r_short.finish_reason  # mid-decode
    r_q = engine.submit(long_p, 4, seed=2)
    queued_before_abort = r_q.state.value == "queued"
    assert engine.abort(r_q.req_id)
    assert queued_before_abort
    engine.run_until_complete()
    assert engine.pool.stats()["request_held"] == 0
    snap = engine.metrics.snapshot()
    assert snap["finish_reasons"]["aborted"] >= 2


def test_mixed_recovery_replay_parity_zero_recompiles(tiny):
    """The supervisor contract under the unified tick: clone_fresh
    SHARES the compiled mixed_step, teacher-forced recovery replays are
    token-identical to an uninterrupted run, and the rebuild+replay
    compiles NOTHING new."""
    cfg, params = tiny
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (24, 5, 9)]
    engine = _engine(cfg, params, max_slots=2, tick_token_budget=10)
    engine.warmup([int(p.size) for p in prompts], max_new_tokens=8)
    live = [engine.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
    for _ in range(3):
        engine.step()  # some mid-prefill, some mid-decode
    warm = dict(engine.compile_counts())

    counter = CompileCounter()
    with counter.watch():
        rebuilt = engine.clone_fresh()
        assert rebuilt._mixed_step is engine._mixed_step
        for r in live:
            rebuilt.recover(r.prompt, r.max_new_tokens,
                            request_id=r.req_id, seed=r.seed,
                            generated=list(r.generated))
        rebuilt.run_until_complete()
    assert counter.count == 0, (
        f"restart + recovery replay compiled: {counter.events}"
    )
    assert rebuilt.compile_counts() == warm

    _assert_offline_parity(rebuilt, cfg, params, jnp.float32)
    assert rebuilt.pool.stats()["request_held"] == 0


# ---------------------------------------------------------------------------
# One dispatch a tick + compile stability (the CPU-measurable acceptance)
# ---------------------------------------------------------------------------

def test_mixed_one_dispatch_a_tick_on_long_prefill_mix(tiny):
    """A long-prefill-heavy trace with decode overlap: the tick must
    issue AT MOST ONE device dispatch per tick, whatever it admits."""
    cfg, params = tiny
    rng = np.random.default_rng(1)
    trace = poisson_trace(
        rng, 12, rate_rps=30.0, prompt_len_range=(16, 30),
        max_new_tokens=(2, 8), vocab_size=cfg.vocab_size,
    )

    mixed = _engine(cfg, params, num_blocks=64, max_seq_len=64)
    msnap = mixed.replay_trace(trace)
    assert msnap["finished"] == 12
    assert 0 < mixed.n_dispatches <= msnap["ticks"], (
        "the tick issued more than one dispatch per tick"
    )


# ---------------------------------------------------------------------------
# Gating, fallbacks, validation
# ---------------------------------------------------------------------------

# what the phase-split engine kept on a ServeEngine (spelt in halves: the
# repository's grep for the deleted names finds nothing)
_GONE = tuple("_%s_%s" % pair for pair in (
    ("decode", "step"), ("prefill", "step"), ("scatter", "prefill"),
    ("gather", "prefix"), ("sample", "first"))) + ("decode_attn" + "_impl",)


def _fail_probes(monkeypatch):
    """Every kernel probe of this test reports failure (conftest clears
    the cached verdicts afterwards)."""
    import llm_np_cp_tpu.ops.pallas.support as support

    monkeypatch.setattr(support, "_FORCE_FAIL", True)
    support._probe.cache_clear()


def test_a_failed_probe_falls_back_to_the_xla_twin_and_keeps_speculation(
        tiny, monkeypatch, caplog):
    """No spelling of the engine builds anything but the unified tick: a
    failed ragged probe takes ``ragged_paged_attention_xla``, says so in
    ONE warning a process, and a spec engine keeps its verifier."""
    _fail_probes(monkeypatch)
    cfg, params = tiny
    with caplog.at_level("WARNING", logger="llm_np_cp_tpu"):
        engines = [_engine(cfg, params), _engine(cfg, params, spec_k=3),
                   _engine(cfg, params, **{"mixed_step": "on"})]
    for engine in engines:
        assert engine.mixed and engine.ragged_attn_impl == "xla"
        assert set(engine.compile_counts()) == {"mixed_step"}
    assert engines[1].spec_k == 3
    said = [r.getMessage() for r in caplog.records
            if "ragged_paged_attention_xla" in r.getMessage()]
    assert len(said) == 1 and "ragged_paged_attention " in said[0]


def test_mixed_step_off_names_the_engine_that_is_gone(tiny):
    """The keyword outlives the phase-split tick only for three scripts
    under benchmark/: ``"off"`` is refused by name, at once."""
    cfg, params = tiny
    with pytest.raises(ValueError, match="PR 46.*one tick"):
        _engine(cfg, params, **{"mixed_step": "off"})
    for gone in _GONE + ("_step_" + "split",):
        assert not hasattr(ServeEngine, gone)


def test_auto_and_on_build_the_same_engine(tiny):
    """``mixed_step`` chooses nothing: no keyword, ``"auto"`` and ``"on"``
    give the same programs, the same compile-count keys and the same
    resolution."""
    cfg, params = tiny
    built = [_engine(cfg, params)] + [
        _engine(cfg, params, **{"mixed_step": name}) for name in ("auto", "on")]
    facts = [(e.mixed, e.ragged_attn_impl, e.epilogue_impl, e.mixed_buckets,
              e.tick_token_budget, sorted(e.compile_counts()))
             for e in built]
    assert facts[0] == facts[1] == facts[2]
    assert facts[0][:2] == (True, "pallas")


def _xla_twin_case(case):
    """(config, params, engine keywords, cache dtype) of a page form."""
    from llm_np_cp_tpu.parallel.sharding import MeshPlan

    kw, dtype, cfg_kw, family = {}, jnp.float32, {}, "llama"
    if case == "f32-merged":
        cfg_kw = dict(num_key_value_heads=2, head_dim=64,
                      num_attention_heads=4, num_hidden_layers=2)
    elif case == "int8":
        dtype = jnp.int8
    elif case == "gemma2-window":
        family = "gemma2"
    elif case == "model=2-replicated-kv":
        cfg_kw = dict(num_key_value_heads=1)
        kw = dict(mesh_plan=MeshPlan(model=2))
    elif case == "spec_k=3":
        kw = dict(spec_k=3)
    cfg = tiny_config(family, **cfg_kw)
    params = init_params(jax.random.PRNGKey(4), cfg, dtype=jnp.float32)
    return cfg, params, kw, dtype


@pytest.mark.mesh
@pytest.mark.parametrize("case", [
    "f32-4d", "f32-merged", "int8", "gemma2-window",
    "model=2-replicated-kv", "spec_k=3"])
def test_a_failed_probe_serves_the_xla_twin_token_for_token(
        case, monkeypatch):
    """What a failed probe used to hide behind the split engine:
    with the ragged probe refused, every page form the XLA twin reads
    serves the offline run's tokens, one dispatch a tick."""
    cfg, params, kw, dtype = _xla_twin_case(case)
    _fail_probes(monkeypatch)
    engine = _engine(cfg, params, max_slots=3, cache_dtype=dtype, **kw)
    assert engine.ragged_attn_impl == "xla" and engine.epilogue_impl == "xla"
    assert engine.pool.pages.merged == (case == "f32-merged")
    rng = np.random.default_rng(21)
    for j, n in enumerate((14, 5)):
        # a repeating prompt, so that a spec engine's drafts are taken
        prompt = np.resize(rng.integers(1, cfg.vocab_size, size=4), n)
        engine.submit(prompt, 10, seed=j, speculative=bool(engine.spec_k))
    engine.run_until_complete()
    _assert_offline_parity(engine, cfg, params, dtype)
    snap = engine.metrics.snapshot()
    assert engine.n_dispatches <= snap["ticks"]
    if engine.spec_k:
        assert snap["spec_accepted_tokens"] > 0


@pytest.mark.parametrize("arch", [
    "qwen2", "gemma2", "lfm2_moe", "falcon_h1", "deepseek_v3", "mimo_v2",
    "brumby"])
def test_every_architecture_builds_the_one_tick(arch):
    """Each of seven of the benchmark's architectures (the last with no
    layer that has pages: a pool with no page class), at its tiny preset: the
    engine is the unified tick, ``compile_counts()`` names ``mixed_step``
    alone after a short trace, and nothing of the phase-split engine is
    left on it."""
    # (the two deepest presets cut to one layer of each of their kinds)
    cfg = tiny_config(arch, **{
        "lfm2_moe": dict(num_hidden_layers=4, layer_types=(
            "conv", "conv", "full_attention", "conv")),
        "mimo_v2": dict(num_hidden_layers=3, window_pattern=(0, 1, 0)),
    }.get(arch, {}))
    params = init_params(jax.random.PRNGKey(6), cfg, dtype=jnp.float32)
    engine = _engine(cfg, params, max_slots=2, num_blocks=24)
    assert engine.mixed and engine.ragged_attn_impl == "pallas"
    # (one request: its prefill tick and its decode ticks are one tile
    # each, so one program compiles)
    req = engine.submit(np.arange(1, 6, dtype=np.int32), 3, seed=6)
    engine.run_until_complete()
    assert req.finish_reason == "length" and len(req.generated) == 3
    counts = engine.compile_counts()
    assert set(counts) == {"mixed_step"}
    assert 1 <= counts["mixed_step"] <= len(engine.mixed_buckets)
    for gone in _GONE:
        assert not hasattr(engine, gone), gone
    assert engine.pool.stats()["request_held"] == 0
    assert (engine.pool.num_blocks == 0) == (arch == "brumby")


def test_mixed_xla_fallback_parity(tiny, monkeypatch):
    """With the kernel rejected the tick runs the XLA ragged fallback —
    still one dispatch per tick, still the Pallas tick's tokens."""
    cfg, params = tiny
    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (14, 5, 9)]

    def run(engine):
        for j, p in enumerate(prompts):
            engine.submit(p, 6, seed=j)
        engine.run_until_complete()
        return _tokens(engine)

    want = run(_engine(cfg, params))
    _fail_probes(monkeypatch)
    xla = _engine(cfg, params)
    assert xla.ragged_attn_impl == "xla"
    assert run(xla) == want
    assert xla.n_dispatches <= xla.metrics.snapshot()["ticks"]


def test_mixed_runtime_degradation_to_xla_fallback(tiny):
    """A ragged-kernel dispatch fault mid-traffic degrades to the XLA
    fallback for the process and retries the same tick — requests still
    finish with the offline run's tokens."""
    from llm_np_cp_tpu.serve import FaultInjector

    cfg, params = tiny
    rng = np.random.default_rng(30)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (9, 6)]
    engine = _engine(cfg, params, fault_injector=FaultInjector("decode@2"))
    assert engine.ragged_attn_impl == "pallas"
    for j, p in enumerate(prompts):
        engine.submit(p, 6, seed=j)
    engine.run_until_complete()
    assert engine.ragged_attn_impl == "xla"
    assert engine.decode_degraded is not None
    _assert_offline_parity(engine, cfg, params, jnp.float32)


def test_degraded_engine_rebuilds_as_the_tick_it_is(tiny):
    """After a kernel fault degraded the engine (the probe now reports
    the kernel unavailable, process-wide) a supervisor's ``clone_fresh``
    comes back over the XLA twins with the degraded step shared: nothing
    compiles cold in mid-traffic."""
    from llm_np_cp_tpu.serve import FaultInjector

    cfg, params = tiny
    engine = _engine(cfg, params, fault_injector=FaultInjector("decode@2"))
    assert engine.mixed and engine.ragged_attn_impl == "pallas"
    live = engine.submit(np.arange(1, 12, dtype=np.int32), 8, seed=3)
    for _ in range(4):
        engine.step()
    assert engine.ragged_attn_impl == "xla" and live.generated
    engine.publish_owed()
    rebuilt = engine.clone_fresh()
    assert (rebuilt.ragged_attn_impl, rebuilt.epilogue_impl) \
        == ("xla", "xla")
    assert rebuilt._mixed_step is engine._mixed_step
    assert set(rebuilt.compile_counts()) == {"mixed_step"}
    rebuilt.faults = None
    rebuilt.recover(live.prompt, live.max_new_tokens,
                    request_id=live.req_id, seed=live.seed,
                    generated=list(live.generated))
    rebuilt.run_until_complete()
    # a grandchild shares the step too
    assert rebuilt.clone_fresh()._mixed_step is engine._mixed_step
    _assert_offline_parity(rebuilt, cfg, params, jnp.float32)


def _iter_eqns(jaxpr, *, into_pallas=False):
    """Every eqn of ``jaxpr`` and of its sub-jaxprs (pjit / scan / ...),
    the body of a Pallas kernel left out unless asked for."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "pallas_call" and not into_pallas:
            continue
        stack = list(eqn.params.values())
        while stack:
            v = stack.pop()
            if isinstance(v, jax.extend.core.ClosedJaxpr):
                yield from _iter_eqns(v.jaxpr, into_pallas=into_pallas)
            elif isinstance(v, jax.extend.core.Jaxpr):
                yield from _iter_eqns(v, into_pallas=into_pallas)
            elif isinstance(v, (tuple, list)):
                stack.extend(v)


def test_mixed_step_has_no_materialized_gather(tiny, monkeypatch):
    """Structural zero-gather assertion on the served program: the
    gathered cache view the XLA ragged attention builds — every row's
    blocks contiguous, ``[rows, S_max, K, D]``, and one copy a packed
    token, ``[D, S_max, K, Dh]`` on the dense axis the XLA twin is
    handed — is in the fallback step's jaxpr (detector sanity) and in NO
    eqn of the Pallas step's outside the kernel, on either of its axes,
    in any of its programs."""
    import llm_np_cp_tpu.ops.pallas.support as support

    cfg, params = tiny
    kh, d = cfg.num_key_value_heads, cfg.head_dim

    def shapes(engine, program):
        jaxpr = jax.make_jaxpr(lambda ops: engine._mixed_step(
            engine.params, engine.pool.pages, ops
        ))(jnp.asarray(engine._dead_mixed_operands(*program)))
        return {tuple(v.aval.shape) for eqn in _iter_eqns(jaxpr.jaxpr)
                for v in eqn.outvars if hasattr(v.aval, "shape")}

    pallas = _engine(cfg, params)
    assert pallas.ragged_attn_impl == "pallas"
    monkeypatch.setattr(support, "_FORCE_FAIL", True)
    support._probe.cache_clear()  # conftest clears it again afterwards
    xla = _engine(cfg, params)
    assert xla.ragged_attn_impl == "xla"
    rows, s_max = pallas.scheduler.max_slots, pallas.max_seq_len
    assert xla.mixed_buckets == pallas.mixed_buckets
    for t_w, d_w in pallas.mixed_buckets:
        gathered = {(rows, s_max, kh, d), (d_w, s_max, kh, d)}
        assert gathered <= shapes(xla, (t_w, d_w)), (
            "control failed: the XLA ragged attention no longer "
            "materializes the gathered view — update the shapes here"
        )
        hit = (gathered | {(t_w, s_max, kh, d)}) & shapes(pallas, (t_w, d_w))
        assert not hit, (
            f"the Pallas step materialized a gathered cache view {hit} "
            f"in program {t_w}x{d_w} — the zero-gather contract is broken"
        )


def test_mixed_rejects_bad_config(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="mixed_step"):
        _engine(cfg, params, **{"mixed_step": "yes"})
    with pytest.raises(ValueError, match="tick_token_budget"):
        _engine(cfg, params, max_slots=4, tick_token_budget=3)


# ----------------------------------------------------------------------
# the step's ONE operand (mixed_operand_layout) and the packer's two ways
# of filling it
# ----------------------------------------------------------------------

@pytest.mark.parametrize("window", [0, 3], ids=["one-class", "window-3"])
@pytest.mark.parametrize("spec_w", [1, 4], ids=["w1", "w4"])
@pytest.mark.parametrize("program", [(8, 8), (64, 16), (200, 72)],
                         ids=["8x8", "64x16", "200x72"])
def test_packed_operand_round_trips(program, spec_w, window):
    """What the host writes through the layout's views is what the
    jitted step's slices read, section by section, in the shapes and
    dtypes the 16 separate operands had — ``tok_live`` a bool, ``seeds``
    a uint32 above 2**31 included — with the token-level sections on the
    dense width, the tile metadata on the tiled one and an index map
    each way between them; and the vector's length names the program
    among a step's programs.  A pool with a window class adds its second
    table and nothing else: the one-class operand is a prefix of it."""
    t_w, d_w = program
    # q_tile, slots, blocks a row, W (, window blocks a slot)
    geometry = (8, 5, 6, spec_w) + ((window,) if window else ())
    layout, size = mixed_operand_layout(t_w, d_w, *geometry)
    programs = [(8, 8), (16, 16), (64, 16), (64, 64), (200, 72)]
    assert mixed_operand_program(size, programs, *geometry) == program
    with pytest.raises(ValueError, match="none among the step's programs"):
        mixed_operand_program(size + 1, programs, *geometry)
    with pytest.raises(ValueError, match="among the step's programs"):
        mixed_operand_program(size, programs + [program], *geometry)
    sizes = [mixed_operand_layout(*p, *geometry)[1] for p in programs]
    assert sizes == sorted(set(sizes))  # one aval a program

    shapes = {"tokens": (d_w,), "tok_live": (d_w,), "tok_lane": (d_w,),
              "lane_tok": (t_w,), "tile_qlen": (t_w // 8,),
              "tables": (5, 6), "pads": (5,), "last_idx": (5, spec_w),
              "sample_pos": (5, spec_w), "seeds": (5,), "verify_len": (5,)}
    if window:
        shapes.update(wtables=(5, window), wfirst=(5,))
        plain, plain_size = mixed_operand_layout(t_w, d_w, *geometry[:4])
        assert {k: layout[k] for k in plain} == plain
        assert size == plain_size + 5 * window + 5
    assert len(layout) == (20 if window else 18)
    assert {k: layout[k][1] for k in shapes} == shapes
    # every token-level section is dense, every tile-level one tiled
    assert {layout[k][1] for k in (
        "tokens", "positions", "tok_blk", "tok_off", "tok_row", "tok_slot",
        "tok_live", "tok_lane")} == {(d_w,)}
    assert {layout[k][1] for k in (
        "tile_row", "tile_qpos0", "tile_qlen")} == {(t_w // 8,)}

    rng = np.random.default_rng(t_w * 10 + spec_w)
    ops = np.zeros(size, np.int32)
    host = split_mixed_operands(ops, layout)
    want = {}
    for name, view in host.items():
        assert view.base is not None and view.shape == layout[name][1]
        if name == "seeds":
            assert view.dtype == np.uint32
            want[name] = rng.integers(2**31, 2**32, view.shape, np.uint32)
            want[name][0] = 2**32 - 1
        elif name == "tok_live":
            want[name] = rng.integers(0, 2, view.shape, np.int32)
        else:
            want[name] = rng.integers(-2**31, 2**31, view.shape, np.int32)
        view[...] = want[name]
    # the sections tile the vector: no word unwritten, none written twice
    assert sum(v.size for v in host.values()) == size
    for name, view in host.items():
        np.testing.assert_array_equal(view, want[name], err_msg=name)

    dev = jax.jit(lambda o: split_mixed_operands(o, layout))(jnp.asarray(ops))
    assert dev["tok_live"].dtype == jnp.bool_
    assert dev["seeds"].dtype == jnp.uint32
    for name, got in dev.items():
        if name not in ("tok_live", "seeds"):
            assert got.dtype == jnp.int32, name
        np.testing.assert_array_equal(
            np.asarray(got),
            want[name] != 0 if name == "tok_live" else want[name],
            err_msg=name)


def _parent_pack(engine, decode_rows, prefill_segs):
    """The 16 operands as the packer built them while the step's whole
    token axis was tile-aligned (every section ``t_w`` lanes wide, a
    token at its tile lane), row by row and field by field, at the rung
    of the tile ladder that packer picked: the reference both of
    ``_pack_mixed``'s paths are held to — the dense operand must be
    that tick, token for token, seen through its two index maps
    (``_assert_packs_the_parents_tick``)."""
    qb, bs = engine._q_tile, engine.block_size
    b, mb, w_v = (engine.scheduler.max_slots, engine.max_blocks_per_seq,
                  engine._spec_w)
    segs = []
    for r in decode_rows:
        toks = [r.generated[-1]]
        if r.draft_len:
            toks.extend(int(t) for t in r.extra["spec_draft"][: r.draft_len])
        segs.append((r, np.asarray(toks, np.int32), r.cache_len - 1,
                     len(toks)))
    for r, n in prefill_segs:
        content = r.extra["prefill_content"]
        segs.append((
            r, np.asarray(content[r.prefill_done:r.prefill_done + n],
                          np.int32),
            r.pad + r.prefill_done,
            1 if r.prefill_done + n >= r.prefill_target else 0))
    aligned = sum(-(-t.size // qb) * qb for _, t, _, _ in segs)
    t_w = min(t for t, _ in engine.mixed_buckets if t >= max(aligned, qb))
    o = {k: np.zeros(t_w, np.int32) for k in (
        "tokens", "positions", "tok_blk", "tok_off", "tok_row", "tok_slot",
        "tok_live")}
    o.update({k: np.zeros(t_w // qb, np.int32)
              for k in ("tile_row", "tile_qpos0", "tile_qlen")})
    o.update(tables=np.zeros((b, mb), np.int32), pads=np.zeros(b, np.int32),
             last_idx=np.zeros((b, w_v), np.int32),
             sample_pos=np.zeros((b, w_v), np.int32),
             seeds=np.zeros(b, np.uint32), verify_len=np.zeros(b, np.int32))
    cur = 0
    for r, toks, start_slot, n_verify in segs:
        n, slot = toks.size, r.slot
        o["tables"][slot, :len(r.block_ids)] = r.block_ids
        o["pads"][slot] = r.pad
        o["seeds"][slot] = np.uint32(r.seed)
        sl = start_slot + np.arange(n, dtype=np.int32)
        o["tokens"][cur:cur + n] = toks
        o["positions"][cur:cur + n] = sl - r.pad
        o["tok_blk"][cur:cur + n] = np.asarray(r.block_ids, np.int32)[sl // bs]
        o["tok_off"][cur:cur + n] = sl % bs
        o["tok_row"][cur:cur + n] = slot
        o["tok_slot"][cur:cur + n] = sl
        o["tok_live"][cur:cur + n] = 1
        n_tiles = -(-n // qb)
        for k in range(n_tiles):
            o["tile_row"][cur // qb + k] = slot
            o["tile_qpos0"][cur // qb + k] = start_slot + k * qb
            o["tile_qlen"][cur // qb + k] = min(qb, n - k * qb)
        for j in range(n_verify):
            o["verify_len"][slot] = n_verify
            o["last_idx"][slot, j] = cur + n - n_verify + j
            o["sample_pos"][slot, j] = start_slot + n - n_verify + j - r.pad
        cur += n_tiles * qb
    return o


TOKEN_SECTIONS = ("tokens", "positions", "tok_blk", "tok_off", "tok_row",
                  "tok_slot", "tok_live")


def _assert_packs_the_parents_tick(engine, sec, program, parent, n_tok):
    """The dense operand ``sec`` of ``program`` is the parent's
    tile-aligned tick ``parent``: the same attention rung, the same tile
    metadata and row sections word for word, every live token in one
    dense lane (the first ``n_tok``, consecutive) carrying what its tile
    lane carried, the two index maps inverse on live lanes, dead tile
    lanes masked and reading token 0, and the sample slots naming the
    dense lanes of the tokens the parent's named by tile lane."""
    qb = engine._q_tile
    t_w, d_w = program
    assert program in engine.mixed_buckets
    assert parent["tokens"].size == t_w, "a wider attention rung than today's"
    assert set(sec) == set(parent) | {"tok_lane", "lane_tok"}
    for name in ("tile_row", "tile_qpos0", "tile_qlen", "tables", "pads",
                 "seeds", "verify_len", "sample_pos"):
        assert sec[name].dtype == parent[name].dtype, name
        np.testing.assert_array_equal(sec[name], parent[name], err_msg=name)
    live = sec["tok_live"].astype(bool)
    assert live.shape == (d_w,) and n_tok <= d_w
    assert live[:n_tok].all() and not live[n_tok:].any()
    lane_of, tok_of = sec["tok_lane"], sec["lane_tok"]
    for name in TOKEN_SECTIONS:
        np.testing.assert_array_equal(
            sec[name][:n_tok], parent[name][lane_of[:n_tok]], err_msg=name)
        assert not sec[name][n_tok:].any(), name  # padding: scratch, dead
    lanes = np.arange(t_w)
    tile_live = parent["tok_live"].astype(bool)
    assert tile_live.sum() == n_tok
    np.testing.assert_array_equal(
        tile_live, lanes % qb < sec["tile_qlen"][lanes // qb])
    np.testing.assert_array_equal(tok_of[lane_of[:n_tok]], np.arange(n_tok))
    np.testing.assert_array_equal(lane_of[tok_of[tile_live]], lanes[tile_live])
    assert not tok_of[~tile_live].any() and not lane_of[n_tok:].any()
    n_v = sec["verify_len"]
    cols = np.arange(sec["last_idx"].shape[1])[None, :] < n_v[:, None]
    np.testing.assert_array_equal(
        lane_of[sec["last_idx"]][cols], parent["last_idx"][cols])
    assert not sec["last_idx"][~cols].any()


# what a tick must have held for the case to have been packed at all:
# (plain decode rows, speculating rows, prefill segments, longest prefill
# segment, decode rows' slots out of order) of one tick, at least
PACK_CASES = {
    "decode-only": dict(lens=(5, 3, 7), new=6),
    "decode+prefill-chunk": dict(lens=(5, 6, 4, 7, 3), new=5, stagger=True),
    "multi-tile-prefill": dict(lens=(4, 29, 21), new=6, stagger=True,
                               prefill_chunk=16),
    "spec-rows": dict(lens=(12, 9, 5), new=10, spec_k=3, stagger=True),
    "slots-reused-out-of-order": dict(
        lens=(4, 6, 3, 5, 4, 7, 3, 6), new=(2, 9, 4, 7, 3, 5, 8, 2),
        max_slots=3),
}


@pytest.mark.parametrize("case", sorted(PACK_CASES))
def test_array_path_packs_what_the_segment_path_packs(tiny, case):
    """Every verdict the planner hands the packer in a run is packed
    three ways — as the tick does it (plain decode rows by whole-array
    writes), with every row through the per-segment code (the identical
    vector), and by the parent's tile-aligned packer: the same tick, on
    the same attention rung, token for token."""
    cfg, params = tiny
    kw = dict(PACK_CASES[case])
    lens, new, stagger = kw.pop("lens"), kw.pop("new"), kw.pop("stagger", 0)
    engine = _engine(cfg, params, **kw)
    pack, fill_rows = engine._pack_mixed, engine._fill_decode_rows
    seen = []

    def by_segment(sec, rows, curs, lanes):
        for r, cur, lane in zip(rows, curs, lanes):
            engine._fill_segment(
                sec, r, np.asarray([r.generated[-1]], np.int32),
                r.cache_len - 1, 1, cur, lane)

    def checking_pack(decode_rows, prefill_segs):
        ops, program, n_array = pack(decode_rows, prefill_segs)
        engine._fill_decode_rows = by_segment
        try:
            slow, slow_program, _ = pack(decode_rows, prefill_segs)
        finally:
            engine._fill_decode_rows = fill_rows
        assert slow_program == program
        np.testing.assert_array_equal(ops, slow)
        _assert_packs_the_parents_tick(
            engine, _sections(engine, ops), program,
            _parent_pack(engine, decode_rows, prefill_segs),
            sum(1 + r.draft_len for r in decode_rows)
            + sum(n for _, n in prefill_segs))
        plain = [r for r in decode_rows if not r.draft_len]
        assert n_array == len(plain)
        slots = [r.slot for r in plain]
        seen.append(dict(
            plain=len(plain), spec=len(decode_rows) - len(plain),
            prefill=len(prefill_segs),
            longest=max([n for _, n in prefill_segs], default=0),
            unordered=slots != sorted(slots)))
        return ops, program, n_array

    engine._pack_mixed = checking_pack
    rng = np.random.default_rng(41)
    news = new if isinstance(new, tuple) else (new,) * len(lens)
    for j, (n, m) in enumerate(zip(lens, news)):
        if kw.get("spec_k"):  # a tiled prompt: drafts get proposed
            prompt = np.resize(rng.integers(1, cfg.vocab_size, size=3), n)
        else:
            prompt = rng.integers(1, cfg.vocab_size, size=n)
        # seeds on both sides of 2**31: the section carries uint32 bits
        engine.submit(prompt, m, seed=(2**32 - 1 - j) if j % 2 else j,
                      speculative=bool(kw.get("spec_k")))
        if stagger:
            engine.step()  # later prompts arrive beside decoding rows
    engine.run_until_complete()
    assert len(engine.scheduler.finished) == len(lens)

    qb = engine._q_tile
    want = {
        "decode-only": lambda t: t["plain"] >= 2 and not t["prefill"],
        "decode+prefill-chunk": lambda t: t["plain"] and t["prefill"],
        "multi-tile-prefill": lambda t: t["longest"] > qb and t["plain"],
        "spec-rows": lambda t: t["spec"] and t["plain"],
        "slots-reused-out-of-order": lambda t: t["unordered"],
    }[case]
    assert any(want(t) for t in seen), seen


# ----------------------------------------------------------------------
# the dense token axis: the same tokens as the tile-wide step, and a
# program set warm-up can afford
# ----------------------------------------------------------------------

def _run_as_the_parent_did(engine):
    """Turn ``engine`` into the step it replaced: ONE width — every
    program's dense axis is its tile axis, a token at its tile lane —
    packed by the parent's packer (``_parent_pack``) with identity index
    maps, so qkv, the K/V scatter, o_proj and the MLP run ``t_w`` lanes
    wide as they did.  Same engine, same weights, same sampler: the
    streams it serves are today's."""
    ladder = sorted({t for t, _ in engine.mixed_buckets})
    engine.mixed_buckets = tuple((t, t) for t in ladder)
    engine._mixed_layouts = {
        p: mixed_operand_layout(*p, *engine._mixed_geometry)
        for p in engine.mixed_buckets}
    engine._mixed_step = engine._make_mixed_step()

    def pack(decode_rows, prefill_segs):
        parent = _parent_pack(engine, decode_rows, prefill_segs)
        t_w = parent["tokens"].size
        layout, size = engine._mixed_layouts[t_w, t_w]
        ops = np.zeros(size, np.int32)
        sec = split_mixed_operands(ops, layout)
        for name, section in parent.items():
            sec[name][...] = section
        sec["tok_lane"][...] = sec["lane_tok"][...] = np.arange(t_w)
        return ops, (t_w, t_w), sum(not r.draft_len for r in decode_rows)

    engine._pack_mixed = pack
    return engine


# (model family, engine keywords, prompt lengths, new tokens, staggered
# submits, what some tick of the run must have held)
DENSE_CASES = {
    "decode-only": (  # four one-tile rows: 32 lanes, a dense axis of 8
        "llama", dict(), (5, 3, 7, 4), 6, False,
        lambda t: t["decode"] == 4 and t["program"] == (32, 8)),
    "chunk-only": (
        "llama", dict(), (21, 13), 3, False,
        lambda t: not t["decode"] and len(t["prefill"]) == 2),
    "decode+two-chunks": (
        "llama", dict(prefill_chunk=16, tick_token_budget=40), (4, 30, 27),
        8, "late", lambda t: t["decode"] and len(t["prefill"]) == 2
        and t["program"][1] < t["program"][0]),
    "speculating-row": (
        "llama", dict(spec_k=3), (12, 9, 5), 10, False,
        lambda t: t["spec"] and t["program"][1] < t["program"][0]),
    "int8-pool": (
        "llama", dict(cache_dtype=jnp.int8), (5, 19, 4, 11), 6, True,
        lambda t: t["decode"] and t["prefill"]
        and t["program"][1] < t["program"][0]),
    "sliding-window": (
        "gemma2", dict(), (9, 13, 6, 5), 24, True,
        lambda t: t["decode"] == 4 and t["program"] == (32, 8)),
}


def _serve(engine, cfg, lens, new, stagger, spec, seen=None):
    """Serve one fixed workload; ``seen`` collects what each tick held."""
    if seen is not None:
        pack = engine._pack_mixed

        def recording_pack(decode_rows, prefill_segs):
            packed = pack(decode_rows, prefill_segs)
            seen.append(dict(
                decode=len(decode_rows), program=packed[1],
                spec=sum(bool(r.draft_len) for r in decode_rows),
                prefill=[n for _, n in prefill_segs]))
            return packed

        engine._pack_mixed = recording_pack
    rng = np.random.default_rng(43)
    for j, n in enumerate(lens):
        if spec:  # a tiled prompt: drafts get proposed
            prompt = np.resize(rng.integers(1, cfg.vocab_size, size=3), n)
        else:
            prompt = rng.integers(1, cfg.vocab_size, size=n)
        engine.submit(prompt, new, seed=j, speculative=spec)
        if stagger is True or (stagger == "late" and j == 0):
            engine.step()  # later prompts arrive beside decoding rows
    engine.run_until_complete()
    assert len(engine.scheduler.finished) == len(lens)
    return _tokens(engine)


@pytest.mark.parametrize("case", sorted(DENSE_CASES))
def test_dense_step_serves_the_tile_wide_steps_tokens(case):
    """A dense row goes through the same arithmetic as its tile lane
    did: the streams of the dense step are, token for token, those of
    the step it replaced (every section and every matmul ``t_w`` lanes
    wide, the parent's packing) — for decode rows alone, a lone chunk,
    decode rows beside two chunks, a verify slice, an int8 pool with its
    scale pages, and Gemma-2's sliding-window layers across the window
    and several block boundaries."""
    family, kw, lens, new, stagger, want = DENSE_CASES[case]
    cfg = tiny_config(family)
    assert (cfg.sliding_window is not None) == (family == "gemma2")
    params = init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)
    spec = bool(kw.get("spec_k"))
    seen = []
    dense = _serve(_engine(cfg, params, **kw), cfg, lens, new,
                   stagger, spec, seen)
    assert any(want(t) for t in seen), seen
    tile_wide = _serve(
        _run_as_the_parent_did(_engine(cfg, params, **kw)),
        cfg, lens, new, stagger, spec)
    assert dense == tile_wide
    if family == "gemma2":  # long enough to cross the window
        assert max(lens) + new > cfg.sliding_window


def _parent_ladder(engine):
    """The packed-width buckets the parent's ``_make_buckets`` built for
    this engine's geometry — ONE width a program, a doubling ladder of
    q-tile multiples capped by the worst aligned total — and the rung
    its ``_pick_bucket`` gave a tick."""
    qb, budget = engine._q_tile, engine.tick_token_budget
    a_max = -(-(budget + engine.scheduler.max_slots * (qb - 1)) // qb) * qb
    ladder, t = [], qb
    while t < a_max:
        ladder.append(t)
        t *= 2
    return sorted({*ladder, a_max})


# the benchmark cells' geometry (64 slots, chunks of 128: budget 320),
# a small engine, and 128 slots — each with and without verify lanes
GEOMETRIES = {
    "cells-64-slots": dict(max_slots=64, num_blocks=64 * 6 + 8, block_size=64,
                           max_seq_len=384, prefill_chunk=128),
    "4-slots": dict(max_slots=4),
    "128-slots": dict(max_slots=128, num_blocks=128 * 2 + 8, block_size=64,
                      max_seq_len=128, prefill_chunk=128),
    "4-slots-spec3": dict(max_slots=4, spec_k=3),
    "64-slots-spec7": dict(max_slots=64, num_blocks=64 * 2 + 8, block_size=64,
                           max_seq_len=128, prefill_chunk=128, spec_k=7),
    # 8 * (216 - 128) tokens = 11 * (128 - 64) tiles: the decode program
    # (1024, 128) would be as long as (512, 216)
    "128-slots-same-length": dict(
        max_slots=128, num_blocks=128 * 2 + 8, block_size=64, max_seq_len=128,
        prefill_chunk=44, tick_token_budget=216),
}


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_every_tick_has_a_program_on_todays_attention_rung(tiny, geometry):
    """Over every tick the planner can emit — any number of tile lanes up
    to the worst aligned total, with any token count those lanes and the
    budget allow (a superset of every rows-and-segments mix) — the
    program found holds the tick, its attention rung is EXACTLY the one
    the parent's packer picked (never wider: the kernel's time follows
    its grid), the operand's length names exactly one program, and the
    set has at most ONE program more than the parent's ladder has rungs:
    the steady decode tick's, ``max_slots`` one-tile rows at the width
    of their tokens."""
    cfg, params = tiny
    engine = _engine(cfg, params, **GEOMETRIES[geometry])
    qb, budget = engine._q_tile, engine.tick_token_budget
    slots, spec_w = engine.scheduler.max_slots, engine._spec_w
    ladder = _parent_ladder(engine)
    programs = engine.mixed_buckets
    assert list(programs) == sorted(set(programs))
    assert sorted({t for t, _ in programs}) == ladder  # today's rungs
    assert len(programs) <= len(ladder) + 1
    sizes = {p: engine._mixed_layouts[p][1] for p in programs}
    assert len(set(sizes.values())) == len(programs)
    for p, size in sizes.items():
        assert mixed_operand_program(
            size, programs, *engine._mixed_geometry) == p
        assert engine._dead_mixed_operands(*p).shape == (size,)
    for n_lanes in range(qb, ladder[-1] + 1, qb):
        rung = min(t for t in ladder if t >= n_lanes)
        # at least a token a tile, at most a lane's worth or the budget
        for n_tokens in range(n_lanes // qb, min(n_lanes, budget) + 1):
            t_w, d_w = engine._pick_bucket(n_lanes, n_tokens)
            assert t_w == rung and d_w >= n_tokens, (n_lanes, n_tokens)
            assert (t_w, d_w) == min(
                p for p in programs if p[0] == rung and p[1] >= n_tokens)
    with pytest.raises(AssertionError, match="budget accounting"):
        engine._pick_bucket(ladder[-1] + qb, 1)
    # the steady decode tick: every slot one tile, one token (or one
    # verify slice that fits a tile) — as wide as its tokens, no wider
    full = slots * min(spec_w, qb)
    t_w, d_w = engine._pick_bucket(slots * qb, full)
    bumped = geometry == "128-slots-same-length"  # one q tile wider
    assert d_w == -(-full // qb) * qb + (qb if bumped else 0) <= t_w
    if geometry == "cells-64-slots":
        assert programs == (
            (8, 8), (16, 16), (32, 32), (64, 64), (128, 128), (256, 256),
            (512, 64), (512, 320), (768, 320))
        assert (t_w, d_w) == (512, 64)
        # 64 rows and a chunk or two: 320 rows of matmul, not 768
        assert engine._pick_bucket(512 + 128, 64 + 128) == (768, 320)
        # a chunk-heavy tick on the 64-tile rung keeps that rung
        assert engine._pick_bucket(40 * qb + 128, 40 + 128) == (512, 320)
    if geometry == "64-slots-spec7":
        # the rows' tokens fill their tiles: no second program
        assert len(programs) == len(ladder)


# ---------------------------------------------------------------------------
# accept / publish (PR 35): tick N's tokens and terminals reach the
# callbacks behind tick N+1's dispatch — per request every token once, in
# order, the terminal after the last token; nothing waits across an idle
# engine
# ---------------------------------------------------------------------------

class _HoldBackTok:
    """One letter a token; an even number of tokens ends in a half-merged
    character, so the detokenizer holds the tail back for the terminal
    event's ``final_text_delta``."""

    def decode(self, ids, skip_special_tokens=True):
        text = "".join(chr(97 + int(i) % 26) for i in ids)
        return text + ("�" if len(ids) and len(ids) % 2 == 0 else "")


def _serve_logged(engine, prompts, budgets, *, spec=False, stagger=True):
    """Serve the workload with logging callbacks and hold every
    ``step()`` to its contract.  → ``{rid: [("token", tok, delta)...,
    ("end", reason, final_text_delta)]}``."""
    log = {}

    def cb(req, tok, delta):
        log[req.req_id].append(("token", tok, delta))

    def on_event(req, event):
        if event in ("length", "stop", "aborted"):
            log[req.req_id].append(
                ("end", event, req.extra.get("final_text_delta")))

    reqs = []

    def handed(r):
        return [t for kind, t, _ in log[r.req_id] if kind == "token"]

    def check(more):
        for r in reqs:
            assert r.generated[:len(handed(r))] == handed(r)
        if not more:
            # no tick follows: nothing is owed, nothing was held back
            assert not engine._owed
            assert all(handed(r) == r.generated for r in reqs)

    for j, (p, n) in enumerate(zip(prompts, budgets)):
        log[j] = []
        reqs.append(engine.submit(p, n, seed=j, request_id=j, callback=cb,
                                  on_event=on_event, speculative=spec))
        if stagger:
            check(engine.step())
    while True:
        more = engine.step()
        check(more)
        if not more:
            break
    assert len(engine.scheduler.finished) == len(prompts)
    return log


def _tiled(rng, vocab, lens):
    return [np.resize(rng.integers(1, vocab, size=3), n) for n in lens]


def _assert_streams(log, cfg, params, prompts, budgets, tokenizer=None):
    """The shape of a callback stream, per request: every token of the
    offline run once and in order, then the terminal, last; with a
    tokenizer the text deltas and the detokenizer's held-back tail (it
    rides the terminal) join to the decoded text."""
    for j, (p, n) in enumerate(zip(prompts, budgets)):
        want = _offline_tokens(cfg, params, jnp.float32, p, n, j)
        *tokens, end = log[j]
        assert [k for k, _, _ in tokens] == ["token"] * n
        assert [t for _, t, _ in tokens] == want, f"request {j}"
        assert end[:2] == ("end", "length")
        if tokenizer is None:
            assert all(d is None for _, _, d in tokens) and end[2] is None
            continue
        # an even budget ends on the held-back character: the tail rides
        # the terminal, and the text is whole
        assert (end[2] is not None) == (n % 2 == 0)
        text = "".join(d or "" for _, _, d in tokens) + (end[2] or "")
        assert text == tokenizer.decode(want)


@pytest.mark.parametrize("spec_k", [0, 3], ids=["plain", "spec3"])
def test_publish_hands_out_every_token_once_in_order(tiny, spec_k):
    """The tick's callbacks, per request: every token of the offline run
    once, in order, each with its text delta, then the terminal with the
    detokenizer's held-back tail — with verify rounds on and off."""
    cfg, params = tiny
    rng = np.random.default_rng(35)
    prompts = _tiled(rng, cfg.vocab_size, (9, 12, 7, 10, 8))
    budgets = (6, 7, 5, 8, 6)
    kw = dict(tokenizer=_HoldBackTok(), max_slots=4)
    mixed = _engine(cfg, params, spec_k=spec_k, **kw)
    got = _serve_logged(mixed, prompts, budgets, spec=bool(spec_k))
    _assert_streams(got, cfg, params, prompts, budgets, _HoldBackTok())
    snap = mixed.metrics.snapshot()
    assert snap["total_generated_tokens"] == sum(budgets)
    assert snap["finished"] == len(budgets)
    # the mechanism ran: most ticks' tokens went out behind a dispatch
    assert snap["publish_overlapped_ticks"] > snap["publish_immediate_ticks"] >= 1
    if spec_k:
        assert snap["spec_accepted_tokens"] > 0, "no verify round paid off"


def test_an_abort_that_empties_the_engine_publishes_what_others_are_owed(tiny):
    """A finished at accept (its last token and its terminal are owed), B
    still running: aborting B leaves no work, so no tick follows — A's
    items go out with the abort, not after an idle wait."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=2)
    events = []
    a = engine.submit(np.arange(1, 7), 2, on_event=lambda r, e: events.append(
        (r.req_id, e)), callback=lambda r, t, d: events.append((r.req_id, t)))
    b = engine.submit(np.arange(2, 9), 20, on_event=lambda r, e: events.append(
        (r.req_id, e)), callback=lambda r, t, d: events.append((r.req_id, t)))
    while a.finish_reason is None:
        assert engine.step()
    assert (a.req_id, "length") not in events and engine._owed
    assert engine.scheduler.has_work  # B
    assert engine.abort(b.req_id)
    assert not engine.scheduler.has_work and not engine._owed
    assert [e for e in events if e[0] == a.req_id] == [
        (a.req_id, a.generated[0]), (a.req_id, a.generated[1]),
        (a.req_id, "length")]
    of_b = [e for e in events if e[0] == b.req_id]
    assert of_b[-1] == (b.req_id, "aborted")
    assert [t for _, t in of_b[:-1]] == b.generated
    assert engine.pool.stats()["request_held"] == 0


def test_a_tick_that_dispatches_nothing_publishes_on_the_spot(tiny):
    cfg, params = tiny
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    got = []
    req = engine.submit(np.arange(1, 9), 6,
                        callback=lambda r, t, d: got.append(t))
    assert engine.step() and engine.step()
    assert engine._owed and len(got) < len(req.generated)
    plan = engine.scheduler.plan_tick
    engine.scheduler.plan_tick = lambda *a, **k: ([], [])  # nothing planned
    fetches = engine.n_host_fetches
    assert engine.step()
    engine.scheduler.plan_tick = plan
    assert engine.n_host_fetches == fetches  # nothing was dispatched
    assert not engine._owed and got == req.generated
    tick = [e for e in tracer.events() if e.get("cat") == "tick"][-1]
    assert tick["args"]["publish_rows"] >= 1
    assert tick["args"]["publish_overlapped"] == 0
    assert tick["args"]["packed_width"] == 0
    engine.run_until_complete()
    assert got == req.generated and len(got) == 6


@pytest.mark.parametrize("spec_k", [0, 7], ids=["plain", "spec7"])
def test_64_slot_streams_are_the_offline_runs_streams(tiny, spec_k):
    """The benchmark cells' shape — 64 slots, chunks of 128 — with 64
    requests in flight, prefill chunks beside decode rows: every
    request's callback stream is, token for token and in order, the
    offline run's, with speculation on and off."""
    cfg, params = tiny
    rng = np.random.default_rng(64)
    # (few distinct shapes: the offline reference compiles one program a
    # prompt length and budget)
    lens = rng.choice((5, 14, 27, 39), size=64)
    prompts = _tiled(rng, cfg.vocab_size, lens)
    budgets = [int(n) for n in rng.choice((10, 14), size=64)]
    geometry = dict(max_slots=64, num_blocks=64 * 3 + 8, block_size=64,
                    max_seq_len=192, prefill_chunk=128)
    mixed = _engine(cfg, params, spec_k=spec_k, **geometry)
    got = _serve_logged(mixed, prompts, budgets, spec=bool(spec_k),
                        stagger=False)
    assert max(mixed.metrics.active_slots) == 64
    _assert_streams(got, cfg, params, prompts, budgets)
