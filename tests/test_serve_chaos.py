"""Fault injection + supervised recovery (serve/faults.py, the
EngineRunner supervisor, and the runtime Pallas→XLA fallback).

The contract being pinned: a crash is a blip, not an outage.  Under a
seeded chaos schedule — tick-thread crash mid-decode, a kernel
dispatch fault, transient 429s — every stream still completes, recovered
requests are TOKEN-IDENTICAL to a fault-free offline run (the
evict-requeue teacher-forcing discipline applied across an engine
rebuild), ``/healthz`` walks ok→degraded→ok, and the restart never
recompiles a step program.  With chaos off, the injection points are
``is None`` checks — the clean-path tests elsewhere in the suite run
through them constantly.
"""

import asyncio
import json
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.generate import Generator
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.pallas import support
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import FaultInjected, FaultInjector, ServeEngine
from llm_np_cp_tpu.serve.faults import install, parse_chaos_spec
from llm_np_cp_tpu.serve.http.client import astream_completion, http_get
from llm_np_cp_tpu.serve.http.server import HttpServer
from tools.compile_counter import CompileCounter

pytestmark = pytest.mark.chaos


@pytest.fixture(autouse=True)
def _clean_chaos_globals():
    """Chaos leaves process-wide marks on purpose (the runtime-disabled
    kernel ledger, the global injector); tests must not leak them into
    the rest of the suite."""
    yield
    support._RUNTIME_DISABLED.clear()
    install(None)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"), **kw)


def _offline(cfg, params, prompt, max_tokens):
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                    cache_dtype=jnp.float32)
    res = gen.generate_ragged([np.asarray(prompt, np.int32)], max_tokens)
    return [int(t) for t in np.asarray(res.tokens)[0][:max_tokens]]


# ---------------------------------------------------------------------------
# FaultInjector units (no engine)
# ---------------------------------------------------------------------------

def test_chaos_spec_grammar():
    events = parse_chaos_spec("decode@3;tick_hang@2:4=1.5, http_429%0.25=0")
    assert [(e.site, e.start, e.count, e.prob, e.arg) for e in events] == [
        ("decode", 3, 1, None, 1.0),
        ("tick_hang", 2, 4, None, 1.5),
        ("http_429", None, 1, 0.25, 0.0),
    ]
    assert parse_chaos_spec("") == []
    for bad in ("nope@1", "decode", "decode@0", "decode@1:0",
                "decode%1.5", "decode@x"):
        with pytest.raises(ValueError, match="bad chaos event"):
            parse_chaos_spec(bad)
    # FaultInjector.from_spec: None for empty (the zero-overhead default)
    assert FaultInjector.from_spec(None) is None
    assert FaultInjector.from_spec("  ") is None


def test_injector_deterministic_window_and_counters():
    inj = FaultInjector("decode@3:2=7.5;prefill@1")
    fired = [inj.trip("decode") for _ in range(6)]
    assert fired == [None, None, 7.5, 7.5, None, None]
    assert inj.trip("prefill") == 1.0 and inj.trip("prefill") is None
    assert inj.hits["decode"] == 6 and inj.injected["decode"] == 2
    assert inj.injected_total == 3
    assert inj.snapshot()["injected_total"] == 3


def test_injector_probabilistic_schedule_replays_with_seed():
    runs = []
    for _ in range(2):
        inj = FaultInjector("decode%0.3", seed=42)
        runs.append([inj.trip("decode") is not None for _ in range(200)])
    assert runs[0] == runs[1], "same seed must replay the same schedule"
    assert 20 < sum(runs[0]) < 100  # ~0.3 of 200, loosely
    assert FaultInjected("decode").site == "decode"


def test_injector_probabilistic_sites_have_independent_streams():
    """Sites are hit from different threads, so each site draws from its
    own (seed, site)-keyed RNG — hit interleaving across sites must not
    change any site's schedule (the replayability guarantee)."""
    a = FaultInjector("decode%0.4;http_429%0.4", seed=3)
    interleaved = [(s, a.trip(s) is not None)
                   for _ in range(50) for s in ("decode", "http_429")]
    b = FaultInjector("decode%0.4;http_429%0.4", seed=3)
    decode_only = [b.trip("decode") is not None for _ in range(50)]
    h429_only = [b.trip("http_429") is not None for _ in range(50)]
    assert [f for s, f in interleaved if s == "decode"] == decode_only
    assert [f for s, f in interleaved if s == "http_429"] == h429_only


# ---------------------------------------------------------------------------
# Runtime kernel degradation (a dispatch fault → the XLA twins)
# ---------------------------------------------------------------------------

def test_decode_fault_on_the_xla_tick_propagates(tiny, monkeypatch):
    """The served tick's floor: a process whose Mosaic probes fail
    resolves to the unified tick over the XLA ragged attention and the
    XLA tail, and there a dispatch fault has
    nothing left to degrade to — it surfaces for the supervisor."""
    monkeypatch.setattr(support, "_FORCE_FAIL", True)
    support._probe.cache_clear()  # conftest clears it again afterwards
    cfg, params = tiny
    engine = _engine(cfg, params,
                     fault_injector=FaultInjector("decode@1"))
    assert engine.mixed
    assert (engine.ragged_attn_impl, engine.epilogue_impl) == ("xla", "xla")
    engine.submit(np.asarray([3, 5, 7], np.int32), 4)
    with pytest.raises(FaultInjected):
        engine.run_until_complete()
    assert engine.decode_degraded is None


def test_decode_fault_degrades_fused_epilogue_then_propagates(tiny):
    """The floor: the FIRST decode fault degrades the tick's kernels —
    ragged attention and the fused epilogue, as a unit — to their XLA
    twins (process-wide, requests finish token-identically); once fully
    on XLA the next fault propagates."""
    cfg, params = tiny
    inj = FaultInjector("decode@2")
    engine = _engine(cfg, params, fault_injector=inj)
    assert engine.epilogue_impl == "fused"
    rng = np.random.default_rng(4)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (5, 9)]
    reqs = [engine.submit(p, 5, seed=i) for i, p in enumerate(prompts)]
    try:
        engine.run_until_complete()
        assert engine.epilogue_impl == "xla"
        assert engine.decode_degraded and "injected" in engine.decode_degraded
        assert support.kernel_error("sample_epilogue") is not None
        for req, p in zip(reqs, prompts):
            assert req.generated == _offline(cfg, params, p, 5)
        # nothing left below the XLA twins: the next fault surfaces
        engine.faults = FaultInjector("decode@1")
        engine.submit(prompts[0], 3)
        with pytest.raises(FaultInjected):
            engine.run_until_complete()
    finally:
        # surgical: other tests in this file rely on their own
        # kernels' process-wide disable state
        support._RUNTIME_DISABLED.pop("sample_epilogue", None)
        support._RUNTIME_DISABLED.pop("sample_epilogue_int8", None)


def test_prefill_fault_raises(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params,
                     fault_injector=FaultInjector("prefill@1"))
    engine.submit(np.asarray([3, 5, 7], np.int32), 4)
    with pytest.raises(FaultInjected):
        engine.run_until_complete()


# ---------------------------------------------------------------------------
# Engine rebuild + teacher-forced recovery (the supervisor's core move)
# ---------------------------------------------------------------------------

def test_restart_recovery_token_identical_and_zero_recompiles(tiny):
    """clone_fresh + recover IS the supervised restart, minus the HTTP
    machinery: kill an engine mid-flight, rebuild, replay every live
    request with its delivered tokens teacher-forced — full streams match
    the fault-free offline run and NOTHING recompiles (the rebuilt engine
    shares the compiled step programs)."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=4)
    # (every program warm, as a server's are before it listens: the replay
    # packs ticks the first four never did, and the counter below counts a
    # program's first compile like any other)
    engine.warmup([6, 11, 17], max_new_tokens=8)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (6, 11, 17)]
    reqs = [engine.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
    for _ in range(4):
        engine.step()
    snap = {r.req_id: list(r.generated) for r in reqs}
    assert all(0 < len(t) < 8 for t in snap.values()), "mid-flight please"

    rebuilt = engine.clone_fresh()
    assert rebuilt.pool.stats()["allocated"] == 0  # fresh pool
    new_tokens: dict[int, list[int]] = {r.req_id: [] for r in reqs}
    for r in reqs:
        rebuilt.recover(
            r.prompt, r.max_new_tokens, request_id=r.req_id, seed=r.seed,
            generated=snap[r.req_id],
            callback=lambda req, tok, delta: new_tokens[req.req_id].append(tok),
        )
    counter = CompileCounter()
    with counter.watch():
        rebuilt.run_until_complete()
    assert counter.count == 0, (
        f"supervised restart recompiled: {counter.events}"
    )
    for r, p in zip(reqs, prompts):
        full = snap[r.req_id] + new_tokens[r.req_id]
        assert full == _offline(cfg, params, p, 8), (
            "recovered stream diverged from the fault-free run"
        )
    # the replayed tokens were never re-emitted through the callback
    assert all(len(new_tokens[r.req_id]) == 8 - len(snap[r.req_id])
               for r in reqs)
    snap_m = rebuilt.metrics.snapshot()
    assert snap_m["recovered"] == 3
    # metrics carried across the rebuild: submits counted once
    assert snap_m["submitted"] == 3


def test_recover_rejects_already_finished_request(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    with pytest.raises(ValueError, match="finish event"):
        engine.recover(np.asarray([1, 2], np.int32), 2, request_id=9,
                       generated=[4, 5])


# ---------------------------------------------------------------------------
# Supervised HTTP server (http marker: ephemeral loopback ports)
# ---------------------------------------------------------------------------

@pytest.mark.http
def test_watchdog_restarts_hung_tick_and_stream_completes(tiny):
    """A tick that sleeps past --tick-deadline is declared hung by the
    watchdog; the superseded thread exits silently when it wakes, the
    rebuilt engine replays the stream, and the client sees one complete,
    token-identical response."""
    cfg, params = tiny
    inj = FaultInjector("tick_hang@2=3.0")
    engine = _engine(cfg, params, fault_injector=inj)
    prompt, n = [5] * 6, 6
    # compile outside the watchdog's clock: a first-tick jit compile on
    # a slow host must not read as a hung engine
    engine.warmup([len(prompt)], max_new_tokens=n)

    async def main():
        # (a deadline an honest tick of a loaded sandbox stays well
        # under — 0.2 s read a 0.24 s tick as hung, PR 46's whole run —
        # and a hang three times as long: the count below stays exact)
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0,
                         tick_deadline=1.0, max_restarts=2,
                         restart_backoff_s=0.05)
        await srv.start("127.0.0.1", 0)
        res = await astream_completion(
            srv.host, srv.port,
            {"prompt": prompt, "max_tokens": n, "stream": True},
            timeout=60,
        )
        assert res["finish_reason"] == "length"
        assert res["token_ids"] == _offline(cfg, params, prompt, n)
        assert srv.runner.restarts == 1
        assert inj.injected["tick_hang"] == 1
        assert srv.runner.recovery_latency_s, "recovery latency recorded"
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


@pytest.mark.http
def test_restart_budget_exhaustion_goes_terminal(tiny):
    """Faults beyond max_restarts fall back to the pre-supervision
    contract: streams end cleanly, /healthz flips 503 crashed."""
    cfg, params = tiny
    inj = FaultInjector("tick_crash@2:10")  # crash every busy tick
    engine = _engine(cfg, params, fault_injector=inj)

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=5.0,
                         max_restarts=1, restart_backoff_s=0.02)
        await srv.start("127.0.0.1", 0)
        loop = asyncio.get_running_loop()
        res = await asyncio.wait_for(astream_completion(
            srv.host, srv.port,
            {"prompt": [5] * 6, "max_tokens": 40, "stream": True},
        ), timeout=60)
        assert res["finish_reason"] == "aborted"  # clean end, no hang
        assert srv.runner.restarts == 1
        st, body = await loop.run_in_executor(
            None, http_get, srv.host, srv.port, "/healthz")
        assert st == 503 and json.loads(body)["status"] == "crashed"
        srv.begin_drain()
        await asyncio.wait_for(srv.serve_until_shutdown(), timeout=30)

    asyncio.run(asyncio.wait_for(main(), timeout=120))


# ---------------------------------------------------------------------------
# The acceptance scenario
# ---------------------------------------------------------------------------

@pytest.mark.http
def test_chaos_e2e_16_streams_crash_kernel_fault_and_429s(tiny):
    """16 concurrent HTTP streams under the seeded schedule the issue
    names: one tick-thread crash mid-decode, one step dispatch fault
    (runtime Pallas → XLA fallback), three transient 429s (clients retry with
    backoff).  Every request completes; recovered requests are
    token-identical to a fault-free offline ``generate_ragged``;
    /healthz transitions ok→degraded→ok; restarts_total and
    faults_injected_total appear in the Prometheus scrape."""
    cfg, params = tiny
    inj = FaultInjector("tick_crash@14;decode@6;http_429@2:3=0")
    engine = _engine(cfg, params, max_slots=4, num_blocks=64,
                     fault_injector=inj)
    assert engine.mixed and engine.ragged_attn_impl == "pallas"
    # compile outside the watchdog's clock (slow-host flake guard); the
    # chaos tick/decode hit counters only start with real traffic
    engine.warmup([19], max_new_tokens=12)
    assert inj.injected_total == 0
    rng = np.random.default_rng(7)
    reqs = [
        (rng.integers(1, cfg.vocab_size, size=int(rng.integers(6, 20)))
         .tolist(),
         int(rng.integers(8, 13)))
        for _ in range(16)
    ]
    health_states: set[str] = set()

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=30.0,
                         tick_deadline=5.0, max_restarts=3,
                         restart_backoff_s=0.4)
        await srv.start("127.0.0.1", 0)
        host, port = srv.host, srv.port
        loop = asyncio.get_running_loop()

        async def poll_health():
            while True:
                st, body = await loop.run_in_executor(
                    None, http_get, host, port, "/healthz")
                health_states.add(json.loads(body)["status"])
                await asyncio.sleep(0.005)

        poller = asyncio.create_task(poll_health())
        tasks = [
            asyncio.create_task(astream_completion(
                host, port, {"prompt": p, "max_tokens": m, "stream": True},
                timeout=120, retries=4, backoff_s=0.05,
            ))
            for p, m in reqs
        ]
        results = await asyncio.gather(*tasks)
        # recovery is long over once every stream finished; scrape while
        # the server is still up
        st, prom_raw = await loop.run_in_executor(
            None, http_get, host, port, "/metrics")
        assert st == 200
        poller.cancel()
        srv.begin_drain()
        await asyncio.wait_for(srv.serve_until_shutdown(), timeout=60)
        return srv, results, prom_raw.decode()

    srv, results, prom = asyncio.run(asyncio.wait_for(main(), timeout=300))

    # every request completed, token-identical to the fault-free run
    for (p, m), res in zip(reqs, results):
        assert res["status"] == 200, res
        assert res["finish_reason"] == "length"
        assert res["token_ids"] == _offline(cfg, params, p, m), (
            "a recovered stream diverged from the fault-free offline run"
        )
    # the schedule actually fired: 1 crash + 1 kernel fault + 3 429s
    assert srv.runner.restarts == 1
    assert inj.injected["tick_crash"] == 1
    assert inj.injected["decode"] == 1
    assert inj.injected["http_429"] == 3
    assert sum(r["retries"] for r in results) >= 3  # the 429s were retried
    # runtime degradation stuck: the live engine ended on the XLA twins
    assert srv.runner.engine.ragged_attn_impl == "xla"
    assert srv.runner.engine.epilogue_impl == "xla"
    # /healthz walked ok→degraded→ok
    assert {"ok", "degraded"} <= health_states
    # supervision observables in the Prometheus scrape
    restarts = float(re.search(
        r"^llm_serve_restarts_total (\S+)", prom, re.M).group(1))
    injected = float(re.search(
        r"^llm_serve_faults_injected_total (\S+)", prom, re.M).group(1))
    assert restarts == 1 and injected >= 5
    assert re.search(r"^llm_serve_requests_recovered_total (\S+)", prom, re.M)
    # and the rebuilt pool leaked nothing
    stats = srv.runner.engine.pool.stats()
    assert stats["request_held"] == 0
    snap = srv.runner.engine.metrics.snapshot()
    assert snap["finished"] == 16
    assert snap["recovered"] >= 1


@pytest.mark.http
@pytest.mark.parametrize("spec_k", [0, 3], ids=["plain", "spec3"])
def test_tick_crash_between_accept_and_publish_replays_identically(
        tiny, spec_k):
    """The ``tick_crash`` site fires between two ticks: the dead engine
    had ACCEPTED its last tick's tokens and published none of them.
    They were never handed out, so they are in no replay ledger; the
    rebuilt engine regenerates them with the same (seed, position) keys
    and every client reads one whole stream, equal to the uninterrupted
    run's, each token once."""
    cfg, params = tiny
    inj = FaultInjector("tick_crash@5")
    engine = _engine(cfg, params, max_slots=4, num_blocks=64, spec_k=spec_k,
                     fault_injector=inj)
    engine.warmup([12], max_new_tokens=10)
    rng = np.random.default_rng(35)
    reqs = [(np.resize(rng.integers(1, cfg.vocab_size, size=3), n).tolist(),
             10) for n in (7, 12, 9)]
    lost: list[int] = []
    clone = type(engine).clone_fresh

    def counting_clone(self, **kw):
        # what the dying engine still owed when the supervisor rebuilt it
        lost.append(sum(1 for kind, _, _ in self._owed if kind != 2))
        return clone(self, **kw)

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=30.0,
                         tick_deadline=5.0, max_restarts=2,
                         restart_backoff_s=0.05)
        await srv.start("127.0.0.1", 0)
        results = await asyncio.gather(*[
            astream_completion(
                srv.host, srv.port,
                {"prompt": p, "max_tokens": m, "stream": True,
                 "speculative": bool(spec_k)}, timeout=120)
            for p, m in reqs])
        srv.begin_drain()
        await asyncio.wait_for(srv.serve_until_shutdown(), timeout=60)
        return srv, results

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(type(engine), "clone_fresh", counting_clone)
        srv, results = asyncio.run(asyncio.wait_for(main(), timeout=300))
    assert srv.runner.restarts == 1 and inj.injected["tick_crash"] == 1
    assert lost and lost[0] >= 1, "the crash fell on an engine owing nothing"
    for (p, m), res in zip(reqs, results):
        assert res["status"] == 200 and res["finish_reason"] == "length"
        assert res["token_ids"] == _offline(cfg, params, p, m)
    live = srv.runner.engine
    assert live is not engine and not live._owed
    assert live.pool.stats()["request_held"] == 0
    snap = live.metrics.snapshot()
    assert snap["finished"] == 3 and snap["recovered"] >= 1


@pytest.mark.http
def test_http_reset_site_aborts_stream_and_client_survives(tiny):
    """The http_reset site: a mid-stream RST aborts the request
    server-side (blocks decref) and the client sees a connection error,
    not a hang."""
    cfg, params = tiny
    inj = FaultInjector("http_reset@3")
    engine = _engine(cfg, params, fault_injector=inj)

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        # the RST surfaces as ECONNRESET or, on loopback, sometimes as a
        # bare EOF — either way the stream ends promptly WITHOUT a
        # finish_reason/[DONE] (truncated), never hangs
        try:
            res = await asyncio.wait_for(astream_completion(
                srv.host, srv.port,
                {"prompt": [8] * 9, "max_tokens": 40, "stream": True},
            ), timeout=60)
        except (OSError, asyncio.IncompleteReadError):
            pass
        else:
            assert res["finish_reason"] is None
            assert len(res["token_ids"]) < 40
        deadline = time.time() + 20
        while time.time() < deadline:
            if (engine.metrics.snapshot()["aborted"] == 1
                    and engine.pool.stats()["request_held"] == 0):
                break
            await asyncio.sleep(0.02)
        assert engine.metrics.snapshot()["aborted"] == 1
        assert engine.pool.stats()["request_held"] == 0
        assert inj.injected["http_reset"] == 1
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


@pytest.mark.http
def test_client_retries_reset_before_first_token(tiny):
    """A connection reset AFTER the 200 status line but BEFORE the first
    token (a restart blip, or http_reset on the very first frame) is
    still transient: with retries the client resends — it must neither
    hang, nor report a bogus zero-token 'success', nor (ever) resend a
    stream that already delivered tokens."""
    cfg, params = tiny
    inj = FaultInjector("http_reset@1")
    engine = _engine(cfg, params, fault_injector=inj)
    prompt, n = [6, 2, 9], 4

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        res = await astream_completion(
            srv.host, srv.port,
            {"prompt": prompt, "max_tokens": n, "stream": True},
            retries=3, backoff_s=0.02,
        )
        assert res["status"] == 200 and res["retries"] >= 1
        assert res["finish_reason"] == "length"
        assert res["token_ids"] == _offline(cfg, params, prompt, n)
        assert inj.injected["http_reset"] == 1
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


@pytest.mark.http
def test_client_retries_injected_429_with_retry_after(tiny):
    cfg, params = tiny
    inj = FaultInjector("http_429@1:2=0")
    engine = _engine(cfg, params, fault_injector=inj)

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        res = await astream_completion(
            srv.host, srv.port,
            {"prompt": [4, 9, 2], "max_tokens": 3, "stream": True},
            retries=3, backoff_s=0.02,
        )
        assert res["status"] == 200 and res["retries"] == 2
        assert res["finish_reason"] == "length"
        assert inj.injected["http_429"] == 2
        # without retries the reject surfaces as-is
        res0 = await astream_completion(
            srv.host, srv.port,
            {"prompt": [4, 9, 2], "max_tokens": 3, "stream": True},
        )
        assert res0["status"] == 200  # schedule exhausted: no more 429s
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))
