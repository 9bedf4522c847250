"""Tick-tail fusion: fused sampling epilogue + AMLA rescaling + the
one-fetch host sync (ops/pallas/sample_epilogue.py, engine packed sync).

The acceptance bar is the PR 6/11 output-invisibility contract applied
to the tick's tail: an engine whose final-norm → lm_head → sample chain
runs as ONE Pallas kernel over vocab tiles (logits never materialized),
whose ragged/paged attention uses AMLA additive-max rescaling, and
whose tick makes ONE packed device→host transfer must be
TOKEN-IDENTICAL to the XLA ``final_logits``+Sampler tail
(``sample_epilogue="off"`` — the oracle) AND to offline
``generate_ragged`` — across bf16 pools, int8 pools, int8 lm-head
payloads, prefix sharing, speculative k=4 verify lanes, gemma-2 sliding
window + softcap, eviction-requeue, and teacher-forced recovery.  Plus
the structural claims: no ``[R, W, V]`` logits array in the fused mixed
step's jaxpr (the PR 2 zero-gather pattern), exactly one device fetch
per tick (trace-verified, readable via summarize_trace's host_sync
column), zero recompiles across composition churn, and the telemetry
byte model billing no phantom logits traffic on the fused path.

CPU backend; the Pallas kernels run in interpret mode (same kernel
logic the TPU compiles — Mosaic-compiling the epilogue on hardware is
recorded live-TPU debt).
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
from llm_np_cp_tpu.models.transformer import (
    final_logits,
    head_quant_mode,
    init_params,
)
from llm_np_cp_tpu.ops.pallas import support
from llm_np_cp_tpu.ops.pallas.sample_epilogue import sample_epilogue
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.quant import quantize_array, quantize_params
from llm_np_cp_tpu.serve import ServeEngine, TraceRecorder, poisson_trace
from tools.compile_counter import assert_serve_compiles_bounded


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, epilogue="auto", **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("sampler", Sampler(kind="greedy"))
    return ServeEngine(params, cfg, sample_epilogue=epilogue, **kw)


def _tokens(engine):
    return {r.req_id: r.generated for r in engine.scheduler.finished}


def _assert_offline_parity(engine, cfg, params, cache_dtype, limit=None):
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                    cache_dtype=cache_dtype)
    finished = list(engine.scheduler.finished)
    assert finished, "nothing finished — bad test setup"
    for req in finished[:limit]:
        res = gen.generate_ragged([req.prompt], req.max_new_tokens,
                                  seed=req.seed)
        want = [int(t) for t in np.asarray(res.tokens)[0][: req.max_new_tokens]]
        assert req.generated == want, (
            f"request {req.req_id} diverged from the offline run"
        )


# ---------------------------------------------------------------------------
# The kernel itself vs the XLA oracle (final_logits + greedy argmax)
# ---------------------------------------------------------------------------

def _head_cfg(v, h, *, tied, softcap=None, unit_offset=False):
    return tiny_config(
        "llama", vocab_size=v, hidden_size=h, tie_word_embeddings=tied,
        final_logit_softcapping=softcap, rms_norm_unit_offset=unit_offset,
    )


def _oracle_argmax(cfg, pdict, x):
    lg = final_logits(pdict, x[:, None, :], cfg, last_only=True)
    return np.asarray(jnp.argmax(lg[:, -1], axis=-1), np.int32)


@pytest.mark.parametrize("tied", [True, False])
@pytest.mark.parametrize("softcap,unit_offset", [(None, False), (30.0, True)])
def test_epilogue_kernel_matches_oracle_float(tied, softcap, unit_offset):
    """Multi-tile vocab with a ragged tail (300 = 2x128 + 44), non-tile
    row count: the fused draw equals argmax over final_logits bit for
    bit, both head layouts, with and without gemma-style softcap +
    unit-offset norm."""
    v, h, n = 300, 64, 5
    rng = np.random.default_rng(0)
    cfg = _head_cfg(v, h, tied=tied, softcap=softcap,
                    unit_offset=unit_offset)
    x = jnp.asarray(rng.standard_normal((n, h)), jnp.float32)
    gamma = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    shape = (v, h) if tied else (h, v)
    w = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    pdict = {"final_norm": gamma,
             ("embed_tokens" if tied else "lm_head"): w}
    got = np.asarray(sample_epilogue(
        x, gamma, w, tied=tied, eps=cfg.rms_norm_eps,
        unit_offset=unit_offset, logit_softcap=softcap, block_v=128,
    ))
    np.testing.assert_array_equal(got, _oracle_argmax(cfg, pdict, x))


@pytest.mark.parametrize("tied", [True, False])
def test_epilogue_kernel_matches_oracle_int8(tied):
    """int8 lm-head payloads (quant.py "q" + per-vocab-column scales)
    stream through the kernel and reproduce the quant_einsum oracle's
    argmax exactly."""
    v, h, n = 300, 64, 4
    rng = np.random.default_rng(1)
    cfg = _head_cfg(v, h, tied=tied)
    x = jnp.asarray(rng.standard_normal((n, h)), jnp.float32)
    gamma = jnp.asarray(rng.standard_normal((h,)), jnp.float32)
    shape = (v, h) if tied else (h, v)
    w = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    q = quantize_array(w, axis=(-1 if tied else -2))
    pdict = {"final_norm": gamma,
             ("embed_tokens" if tied else "lm_head"): q}
    got = np.asarray(sample_epilogue(
        x, gamma, q["q"], w_scale=q["s"].reshape(1, -1), tied=tied,
        eps=cfg.rms_norm_eps, block_v=128,
    ))
    np.testing.assert_array_equal(got, _oracle_argmax(cfg, pdict, x))


def test_epilogue_kernel_first_occurrence_tie_breaking():
    """Cross-tile argmax ties resolve to the FIRST occurrence, exactly
    like jnp.argmax over the full row: duplicate the winning vocab
    column into a LATER tile and the early index must still win.
    Softcap saturation makes exact ties a real production case."""
    v, h, n = 300, 64, 3
    rng = np.random.default_rng(2)
    # constant rows → a column of all-tens is the unambiguous winner
    x = jnp.ones((n, h), jnp.float32)
    gamma = jnp.ones((h,), jnp.float32)
    w = np.asarray(rng.standard_normal((v, h)), np.float32)
    w[7] = 10.0          # a clear winner in tile 0...
    w[131] = w[7]        # ...duplicated EXACTLY in tile 1
    w[299] = w[7]        # ...and in the ragged tail tile
    w = jnp.asarray(w)
    got = np.asarray(sample_epilogue(
        x, gamma, w, tied=True, eps=1e-6, block_v=128,
    ))
    cfg = _head_cfg(v, h, tied=True)
    pdict = {"final_norm": gamma, "embed_tokens": w}
    want = _oracle_argmax(cfg, pdict, x)
    np.testing.assert_array_equal(got, want)
    assert set(got) == {7}, "tie did not resolve to the first occurrence"


def test_epilogue_kernel_single_tile_vocab(tiny):
    """v <= block_v collapses the grid to one step (the tiny-model serve
    shape) — init/emit on the same grid step must still work."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((6, cfg.hidden_size)), jnp.float32)
    got = np.asarray(sample_epilogue(
        x, params["final_norm"], params["embed_tokens"], tied=True,
        eps=cfg.rms_norm_eps,
    ))
    pdict = {"final_norm": params["final_norm"],
             "embed_tokens": params["embed_tokens"]}
    np.testing.assert_array_equal(got, _oracle_argmax(cfg, pdict, x))


def test_epilogue_kernel_rejects_bad_args():
    x = jnp.zeros((2, 64), jnp.float32)
    g = jnp.zeros((64,), jnp.float32)
    w = jnp.zeros((128, 64), jnp.float32)
    with pytest.raises(ValueError, match="block_v"):
        sample_epilogue(x, g, w, tied=True, eps=1e-6, block_v=100)
    with pytest.raises(ValueError, match="w_scale"):
        sample_epilogue(x, g, w.astype(jnp.int8), tied=True, eps=1e-6)
    with pytest.raises(ValueError, match="w_scale"):
        sample_epilogue(x, g, w, w_scale=jnp.ones((1, 128)), tied=True,
                        eps=1e-6)
    with pytest.raises(ValueError, match="hidden"):
        sample_epilogue(x, g, jnp.zeros((128, 32), jnp.float32),
                        tied=True, eps=1e-6)


# ---------------------------------------------------------------------------
# Gate resolution (engine + offline Generator share one rule)
# ---------------------------------------------------------------------------

def test_engine_gate_resolution(tiny):
    cfg, params = tiny
    assert _engine(cfg, params).epilogue_impl == "fused"
    assert _engine(cfg, params, epilogue="off").epilogue_impl == "xla"
    # non-greedy samplers keep the XLA tail (the fused draw is only
    # bit-identical for greedy) — even under "on", with a warning
    stoch = _engine(cfg, params, epilogue="on",
                    sampler=Sampler(kind="top_p", top_p=0.9))
    assert stoch.epilogue_impl == "xla"
    with pytest.raises(ValueError, match="sample_epilogue"):
        _engine(cfg, params, epilogue="sometimes")


def test_gate_covers_head_quant_modes(tiny):
    cfg, params = tiny
    qparams = quantize_params(params)
    assert head_quant_mode(params, cfg) == "float"
    assert head_quant_mode(qparams, cfg) == "int8"
    # int4-style head payloads are outside the kernel's coverage → the
    # gate reports None and the engine keeps the XLA tail
    q4 = dict(qparams)
    q4["embed_tokens"] = dict(
        q4=np.zeros((cfg.vocab_size, cfg.hidden_size // 2), np.uint8),
        s=np.ones((cfg.vocab_size, 1), np.float32),
    )
    assert head_quant_mode(q4, cfg) is None


def test_offline_generator_fused_tail_parity(tiny):
    """The offline Generator gates on the same probe and its fused
    decode tail must emit the same tokens as the XLA tail (forced via
    the probe-failure hook)."""
    cfg, params = tiny
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 11, 8)]
    fused = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                      cache_dtype=jnp.float32)
    assert fused.epilogue_impl == "fused"
    support._FORCE_FAIL = True
    support._probe.cache_clear()
    try:
        xla = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                        cache_dtype=jnp.float32)
        assert xla.epilogue_impl == "xla"
    finally:
        support._FORCE_FAIL = False
        support._probe.cache_clear()
    for p in prompts:
        a = np.asarray(fused.generate_ragged([p], 8, seed=3).tokens)
        b = np.asarray(xla.generate_ragged([p], 8, seed=3).tokens)
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# The acceptance criterion: 32-request parity, fused vs oracle vs offline
# ---------------------------------------------------------------------------

def test_fused_trace_parity_32_requests_bf16(tiny):
    """The headline suite: one 32-request Poisson trace through the
    fused engine and the sample_epilogue="off" oracle engine on a bf16
    pool — token-identical, one fetch per tick, zero compiles across
    the composition churn, offline generate_ragged ground truth."""
    cfg, params = tiny
    rng = np.random.default_rng(0)
    trace = poisson_trace(
        rng, 32, rate_rps=40.0, prompt_len_range=(3, 14),
        max_new_tokens=8, vocab_size=cfg.vocab_size,
    )

    def run(epilogue):
        engine = _engine(cfg, params, epilogue=epilogue,
                         cache_dtype=jnp.bfloat16)
        snap = engine.replay_trace(trace)
        assert snap["finished"] == 32
        return engine

    fused, oracle = run("auto"), run("off")
    assert fused.epilogue_impl == "fused"
    assert oracle.epilogue_impl == "xla"
    assert _tokens(fused) == _tokens(oracle)
    assert_serve_compiles_bounded(fused)
    _assert_offline_parity(fused, cfg, params, jnp.bfloat16, limit=6)


def test_fused_int8_pool_parity(tiny):
    """int8 KV pool: the fused tail sits downstream of the dequantized
    hidden states, and the int8 ragged kernel's AMLA rescaling must not
    move a single token."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (9, 14, 6)]

    def run(epilogue):
        engine = _engine(cfg, params, epilogue=epilogue, max_slots=3,
                         num_blocks=24, cache_dtype=jnp.int8)
        for j, p in enumerate(prompts):
            engine.submit(p, 7, seed=j)
        engine.run_until_complete()
        return engine

    fused = run("auto")
    assert fused.pool.pages.quantized
    assert fused.epilogue_impl == "fused"
    assert _tokens(fused) == _tokens(run("off"))
    _assert_offline_parity(fused, cfg, params, jnp.int8)


def test_fused_int8_head_parity(tiny):
    """int8-quantized params (embed/lm_head as quant.py "q" payloads):
    the gate selects the sample_epilogue_int8 kernel and the engine
    matches the XLA quant_einsum tail and the offline run exactly."""
    cfg, params = tiny
    qparams = quantize_params(params)
    rng = np.random.default_rng(6)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (8, 12)]

    def run(epilogue):
        engine = _engine(cfg, qparams, epilogue=epilogue, max_slots=2,
                         num_blocks=32)
        for j, p in enumerate(prompts):
            engine.submit(p, 6, seed=j)
        engine.run_until_complete()
        return engine

    fused = run("auto")
    assert fused.epilogue_impl == "fused"
    assert head_quant_mode(qparams, cfg) == "int8"
    assert _tokens(fused) == _tokens(run("off"))
    _assert_offline_parity(fused, cfg, qparams, jnp.float32)


def test_fused_prefix_sharing_parity(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (20, 17)]

    def run(epilogue):
        engine = _engine(cfg, params, epilogue=epilogue,
                         enable_prefix_cache=True)
        for rep in range(3):
            for j, p in enumerate(prompts):
                engine.submit(p, 5, seed=j)
        engine.run_until_complete()
        return engine

    fused = run("auto")
    assert _tokens(fused) == _tokens(run("off"))
    assert fused.metrics.snapshot()["prefix_blocks_hit"] > 0
    fl = fused.pool.free_list
    assert fl.num_free + fl.num_allocated == fl.capacity


def test_fused_speculative_verify_lane_parity(tiny):
    """spec k=4: verify lanes sample through the fused kernel ([R, W]
    rows flattened into its packed row axis) and the in-graph accept
    walk must keep the streams identical to the XLA-tail spec engine
    AND the plain fused engine."""
    cfg, params = tiny
    rng = np.random.default_rng(8)
    prompts = []
    for n in (16, 13, 11):  # repetitive: the prompt-lookup win case
        base = rng.integers(1, cfg.vocab_size, size=4, dtype=np.int64)
        prompts.append(np.resize(base.astype(np.int32), n))

    def run(epilogue, spec_k):
        engine = _engine(cfg, params, epilogue=epilogue, spec_k=spec_k)
        for j, p in enumerate(prompts):
            engine.submit(p, 10, seed=j, speculative=bool(spec_k))
        engine.run_until_complete()
        return engine

    fused_spec = run("auto", 4)
    assert fused_spec.epilogue_impl == "fused"
    toks = _tokens(fused_spec)
    assert toks == _tokens(run("off", 4))
    assert toks == _tokens(run("auto", 0))
    assert fused_spec.metrics.snapshot().get("spec_accepted_tokens", 0) > 0


def test_fused_gemma2_softcap_sliding_window_parity():
    """Gemma-2 exercises every numerics branch at once: final-logit
    softcap + unit-offset norm in the epilogue kernel, sliding-window
    bounds + attn softcap in the AMLA-rescaled ragged kernel."""
    cfg = tiny_config("gemma2")
    assert cfg.sliding_window is not None
    assert cfg.final_logit_softcapping is not None
    params = init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (9, 13)]

    def run(epilogue):
        engine = _engine(cfg, params, epilogue=epilogue, max_slots=2,
                         num_blocks=32, max_seq_len=96)
        for j, p in enumerate(prompts):
            engine.submit(p, 24, seed=j)  # decode crosses the window
        engine.run_until_complete()
        return engine

    fused = run("auto")
    assert fused.epilogue_impl == "fused"
    assert _tokens(fused) == _tokens(run("off"))
    _assert_offline_parity(fused, cfg, params, jnp.float32)


def test_fused_eviction_requeue_parity(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(10)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (4, 5, 3)]

    def run(epilogue):
        engine = _engine(cfg, params, epilogue=epilogue, max_slots=2,
                         num_blocks=6)
        for j, p in enumerate(prompts):
            engine.submit(p, 20, seed=j)
        engine.run_until_complete()
        return engine

    fused = run("auto")
    assert fused.scheduler.n_preemptions > 0, "pool not tight enough"
    assert _tokens(fused) == _tokens(run("off"))
    assert fused.pool.free_list.num_allocated == 0


def test_fused_teacher_forced_recovery_parity(tiny):
    """Kill-and-replay across the fused tail: requests interrupted
    mid-decode resume on a FRESH fused engine with their tokens
    teacher-forced, and the continuation matches the oracle engine's
    uninterrupted stream."""
    cfg, params = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (7, 10)]
    first = _engine(cfg, params)
    reqs = [first.submit(p, 12, seed=j) for j, p in enumerate(prompts)]
    for _ in range(6):  # partway into decode, then "crash"
        first.step()
    assert any(r.generated for r in reqs)
    second = _engine(cfg, params)
    assert second.epilogue_impl == "fused"
    for r in reqs:
        second.recover(r.prompt, r.max_new_tokens, request_id=r.req_id,
                       seed=r.seed, generated=list(r.generated))
    second.run_until_complete()
    oracle = _engine(cfg, params, epilogue="off")
    for j, p in enumerate(prompts):
        oracle.submit(p, 12, seed=j, request_id=100 + j)
    oracle.run_until_complete()
    got = _tokens(second)
    want = _tokens(oracle)
    for j, r in enumerate(reqs):
        assert got[r.req_id] == want[100 + j], (
            "teacher-forced continuation diverged from the oracle"
        )


# ---------------------------------------------------------------------------
# Structural pins: no materialized logits, one fetch per tick
# ---------------------------------------------------------------------------

def _iter_eqns(jaxpr, *, skip_pallas):
    for eqn in jaxpr.eqns:
        if skip_pallas and eqn.primitive.name == "pallas_call":
            # VMEM-resident tiles inside the kernel body are the whole
            # point — only HBM-shaped arrays OUTSIDE the kernel count
            continue
        yield eqn
        for v in eqn.params.values():
            yield from _iter_param_eqns(v, skip_pallas=skip_pallas)


def _iter_param_eqns(v, *, skip_pallas):
    if isinstance(v, jax.extend.core.ClosedJaxpr):
        yield from _iter_eqns(v.jaxpr, skip_pallas=skip_pallas)
    elif isinstance(v, jax.extend.core.Jaxpr):
        yield from _iter_eqns(v, skip_pallas=skip_pallas)
    elif isinstance(v, (tuple, list)):
        for x in v:
            yield from _iter_param_eqns(x, skip_pallas=skip_pallas)


def _mixed_step_shapes(engine, program, *, skip_pallas):
    jaxpr = jax.make_jaxpr(lambda ops: engine._mixed_step(
        engine.params, engine.pool.pages, ops
    ))(jnp.asarray(engine._dead_mixed_operands(*program)))
    return {
        tuple(v.aval.shape)
        for eqn in _iter_eqns(jaxpr.jaxpr, skip_pallas=skip_pallas)
        for v in eqn.outvars
        if hasattr(v.aval, "shape")
    }


def test_fused_mixed_step_never_materializes_logits(tiny):
    """The zero-gather pattern applied to the tail: NO eqn outside the
    Pallas kernel body produces a vocab-wide logits array — neither the
    [R, W, V] block the XLA tail materializes nor its flattened
    [R*W(+pad), V] form — while the oracle engine's jaxpr contains it
    (detector sanity)."""
    cfg, params = tiny
    v = cfg.vocab_size

    def logits_shapes(engine):
        shapes = _mixed_step_shapes(
            engine, engine.mixed_buckets[0], skip_pallas=True)
        return {s for s in shapes
                if len(s) >= 2 and s[-1] == v and s[-2] != v}

    fused = _engine(cfg, params, spec_k=4)
    assert fused.epilogue_impl == "fused"
    leaked = logits_shapes(fused)
    assert not leaked, f"fused step materializes logits-shaped {leaked}"

    oracle = _engine(cfg, params, spec_k=4, epilogue="off")
    b, w = oracle.scheduler.max_slots, oracle._spec_w
    assert (b, w, v) in logits_shapes(oracle), (
        "detector failed to see the oracle's [R, W, V] logits"
    )


def test_one_fetch_per_tick_and_summarize_host_sync(tiny, tmp_path):
    """The one-fetch contract, trace-verified on BOTH tick paths: every
    dispatching tick reports exactly one device→host transfer in its
    args, and tools/summarize_trace.py renders the host_sync column
    (mean/p99/share + fetch ceiling) from a dumped fixture."""
    from tools.summarize_trace import (
        format_summary,
        load_trace,
        mixed_utilization,
    )

    cfg, params = tiny
    rng = np.random.default_rng(12)
    trace = poisson_trace(rng, 8, rate_rps=50.0, prompt_len_range=(3, 12),
                          max_new_tokens=6, vocab_size=cfg.vocab_size)

    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    assert engine.replay_trace(trace)["finished"] == 8
    args = [
        e["args"] for e in tracer.events()
        if e.get("ph") == "X" and e.get("cat") == "tick"
        and "host_fetches" in (e.get("args") or {})
    ]
    assert args, "no tick args recorded"
    assert all(a["host_fetches"] <= 1 for a in args)
    dispatching = [a for a in args
                   if a["prefill_tokens"] + a["decode_tokens"] > 0]
    assert dispatching
    assert all(a["host_fetches"] == 1 for a in dispatching), (
        "a dispatching tick made more (or fewer) than ONE device fetch"
    )
    assert all(a["host_sync_us"] >= 0.0 for a in args)

    # summarize_trace's host_sync column off a dumped fixture
    path = tmp_path / "fused_trace.json"
    tracer.dump(str(path))
    loaded = load_trace(str(path))
    util = mixed_utilization(loaded)
    assert util is not None
    assert util["host_fetches_max"] == 1
    assert util["host_sync_us_p99"] >= util["host_sync_us_mean"] >= 0.0
    assert 0.0 <= util["host_sync_share"] <= 1.0
    out = format_summary(loaded, top=3)
    assert "host_sync:" in out and "fetch/tick" in out


def test_telemetry_bills_no_phantom_logits_when_fused(tiny):
    """The byte model must not bill the [rows, V] logits traffic the
    fused kernel retired: identical workloads, telemetry attached, the
    fused leg's weight-byte ledger is smaller than the oracle leg's by
    EXACTLY rows x V x 8 bytes per dispatch."""
    from llm_np_cp_tpu.serve.telemetry import TelemetryModel

    cfg, params = tiny
    model = TelemetryModel(cfg, params)
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (6, 9)]

    def run(epilogue):
        engine = _engine(cfg, params, epilogue=epilogue, max_slots=2,
                         num_blocks=32, telemetry=model)
        for j, p in enumerate(prompts):
            engine.submit(p, 5, seed=j)
        engine.run_until_complete()
        snap = engine.metrics.snapshot()
        return engine, snap["weight_bytes_total"]

    fused_eng, fused_bytes = run("auto")
    oracle_eng, oracle_bytes = run("off")
    assert _tokens(fused_eng) == _tokens(oracle_eng)
    assert fused_eng.n_dispatches == oracle_eng.n_dispatches
    per_dispatch = (fused_eng.scheduler.max_slots * fused_eng._spec_w
                    * cfg.vocab_size * 4 * 2)
    want_delta = oracle_eng.n_dispatches * per_dispatch
    assert oracle_bytes - fused_bytes == pytest.approx(want_delta), (
        "telemetry billed phantom logits traffic on the fused path"
    )
