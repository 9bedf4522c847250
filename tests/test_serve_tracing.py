"""Request-lifecycle tracing + tick-phase profiling (serve/tracing.py).

The tracing subsystem is only trustworthy if (a) every emitted event is
valid Chrome trace-event JSON that nests correctly, (b) the spans agree
with the metrics counters they shadow (a trace that disagrees with
/metrics is worse than no trace), and (c) turning tracing OFF costs
nothing — no recompiles, no hot-path allocations (the FaultInjector
is-None discipline, pinned by an AST lint).  The deadline-resume fix
for recovered requests and the Prometheus histogram promotion ride
along, plus tools/summarize_trace.py against a freshly recorded
fixture.
"""

import json
import re
import sys
from collections import Counter

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import ServeEngine, TraceRecorder, poisson_trace
from llm_np_cp_tpu.serve.tracing import MIXED_TICK_PHASES
from tools.compile_counter import (
    CompileCounter,
    assert_tracing_hooks_guarded,
)
from tools.summarize_trace import (
    format_summary,
    load_trace,
    phase_totals,
    request_table,
    slowest_ticks,
    tick_stats,
)

PROM_LINE = re.compile(
    r"[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.]+(e[+-]?[0-9]+)?"
)


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


@pytest.fixture(scope="module")
def traced_run(tiny):
    """One traced 8-request Poisson replay of the default engine (what
    ``cli serve`` serves), shared by the schema / coverage / summarize /
    histogram tests (each reads, none mutates)."""
    cfg, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    assert engine.mixed
    rng = np.random.default_rng(0)
    trace = poisson_trace(rng, 8, rate_rps=50.0, prompt_len_range=(3, 10),
                          max_new_tokens=5, vocab_size=cfg.vocab_size)
    snap = engine.replay_trace(trace)
    assert snap["finished"] == 8
    return engine, tracer, tracer.events()


# ---------------------------------------------------------------------------
# Trace schema: every event parses and nests
# ---------------------------------------------------------------------------

def test_trace_schema_validates_and_nests(traced_run, tmp_path):
    _, tracer, events = traced_run
    assert events, "traced replay recorded nothing"
    # the dump is loadable JSON in the Chrome wrapper shape
    path = tmp_path / "trace.json"
    tracer.dump(str(path))
    loaded = json.loads(path.read_text())
    assert isinstance(loaded["traceEvents"], list)
    assert len(loaded["traceEvents"]) == len(events)

    open_async: dict[tuple, float] = {}
    for ev in events:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in ("X", "i", "b", "e", "n", "M"), ev
        if ev["ph"] == "M":
            continue
        assert ev["ts"] >= 0.0, ev
        assert "pid" in ev and "tid" in ev
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0, ev
        elif ev["ph"] in ("b", "e", "n"):
            assert ev["cat"] == "request" and "id" in ev
            key = (ev["id"], ev["name"])
            if ev["ph"] == "b":
                assert key not in open_async, f"double-begin {key}"
                open_async[key] = ev["ts"]
            elif ev["ph"] == "e":
                t0 = open_async.pop(key, None)
                assert t0 is not None, f"end without begin {key}"
                assert ev["ts"] >= t0
    assert not open_async, f"unbalanced async spans: {open_async}"

    # every request walked queued → prefill → decode → finish
    table = request_table(events)
    assert len(table) == 8
    for rid, rec in table.items():
        assert rec["finish"] == "length", (rid, rec)
        for phase in ("queued", "prefill", "decode"):
            assert phase in rec["phases_us"], (rid, rec)


def test_tick_phase_spans_cover_tick_time(traced_run):
    """The acceptance invariant: a tick's phase slices are measured at
    consecutive timestamps, so they start where the tick starts, each
    ends where the next begins, and they sum to the tick up to the one
    clock read that closes its span.  (Asserted as such and not as a
    share of the tick: on the CPU a tick is 750 us and the recorder's own
    tail a varying part of it.)"""
    _, _, events = traced_run
    checked = 0
    i = 0
    while i < len(events):
        ev = events[i]
        i += 1
        if ev.get("cat") != "tick" or ev.get("ph") != "X":
            continue
        # the recorder appends a tick's phase slices atomically after it
        phases = events[i:i + len(MIXED_TICK_PHASES)]
        i += len(MIXED_TICK_PHASES)
        assert [p["name"] for p in phases] == list(MIXED_TICK_PHASES)
        assert phases[0]["ts"] == ev["ts"]
        for a, b in zip(phases, phases[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-6)
        end = phases[-1]["ts"] + phases[-1]["dur"]
        assert end <= ev["ts"] + ev["dur"] + 1e-6
        assert sum(p["dur"] for p in phases) == pytest.approx(
            end - ev["ts"], abs=1e-3)
        checked += 1
    assert checked > 0, "no tick was recorded — bad workload"


# ---------------------------------------------------------------------------
# Span-vs-metrics parity: the trace must agree with /metrics
# ---------------------------------------------------------------------------

def test_span_metrics_parity_32_requests_abort_evict_recover(tiny):
    """32-request Poisson trace through a pool tight enough to preempt,
    plus a deadline abort and a mid-flight engine rebuild with recovery
    replays: the span counts in the trace equal the finish-reason /
    preemption / recovery counters in the metrics snapshot."""
    cfg, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, num_blocks=6, tracer=tracer)
    rng = np.random.default_rng(5)
    trace = poisson_trace(rng, 32, rate_rps=60.0, prompt_len_range=(3, 6),
                          max_new_tokens=12, vocab_size=cfg.vocab_size)
    # one request doomed by its deadline: swept (aborted) on tick 1
    engine.submit(rng.integers(1, cfg.vocab_size, size=4), 12,
                  deadline_s=1e-6)
    snap = engine.replay_trace(trace)
    assert snap["finished"] == 32
    assert snap["aborted"] == 1
    preempts = engine.scheduler.n_preemptions
    assert preempts > 0, "pool was not tight enough to exercise eviction"

    # crash mid-flight: rebuild + teacher-forced recovery (the
    # supervisor path, minus the HTTP machinery)
    live = [engine.submit(rng.integers(1, cfg.vocab_size, size=4), 8,
                          seed=90 + i) for i in range(3)]
    for _ in range(3):
        engine.step()
    rebuilt = engine.clone_fresh()
    assert rebuilt.tracer is tracer  # the timeline survives the rebuild
    for r in live:
        rebuilt.recover(r.prompt, r.max_new_tokens, request_id=r.req_id,
                        seed=r.seed, generated=list(r.generated))
    rebuilt.run_until_complete()
    preempts += rebuilt.scheduler.n_preemptions

    final = rebuilt.metrics.snapshot()
    events = tracer.events()
    finishes = [ev for ev in events
                if ev.get("cat") == "request" and ev["ph"] == "n"
                and ev["name"] == "finish"]
    by_reason: dict[str, int] = {}
    for ev in finishes:
        r = ev["args"]["reason"]
        by_reason[r] = by_reason.get(r, 0) + 1
    assert by_reason == final["finish_reasons"], (
        f"span finishes {by_reason} != counters {final['finish_reasons']}"
    )
    evicts = sum(1 for ev in events
                 if ev.get("cat") == "request" and ev["ph"] == "n"
                 and ev["name"] == "evicted-requeued")
    assert evicts == preempts
    recovers = sum(1 for ev in events
                   if ev.get("cat") == "request" and ev["ph"] == "n"
                   and ev["name"] == "recovery-replay")
    assert recovers == final["recovered"] == 3


# ---------------------------------------------------------------------------
# Tracing OFF: zero recompiles, zero hot-path work (lint-pinned)
# ---------------------------------------------------------------------------

def test_tracing_off_and_on_add_zero_recompiles(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    assert engine.tracer is None  # the default IS off
    rng = np.random.default_rng(2)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (5, 9)]
    for p in prompts:
        engine.submit(p, 4)
    engine.run_until_complete()  # compile everything once

    counter = CompileCounter()
    with counter.watch():
        for p in prompts:
            engine.submit(p, 4)
        engine.run_until_complete()
    assert counter.count == 0, f"untraced ticks compiled: {counter.events}"

    # attaching a tracer is host-side only: the step jaxprs cannot see
    # it, so it must not trigger a single new compile either
    engine.tracer = TraceRecorder()
    with counter.watch():
        for p in prompts:
            engine.submit(p, 4)
        engine.run_until_complete()
    assert counter.count == 0, f"traced ticks compiled: {counter.events}"
    assert len(engine.tracer) > 0
    engine.tracer = None


def test_tracing_hooks_guarded_lint_and_detects_violations(tmp_path):
    """The hot-path modules pass the is-None discipline lint — and the
    lint actually bites: an unguarded tracer call in a synthetic module
    fails it (a lint that cannot fail pins nothing)."""
    assert_tracing_hooks_guarded()

    bad = tmp_path / "bad_hot_path.py"
    bad.write_text(
        "class Engine:\n"
        "    def step(self):\n"
        "        tr = self.tracer\n"
        "        tr.instant('tick')  # no is-None guard\n"
    )
    with pytest.raises(AssertionError, match="without an"):
        assert_tracing_hooks_guarded((str(bad),))
    direct = tmp_path / "bad_direct.py"
    direct.write_text(
        "class Engine:\n"
        "    def step(self):\n"
        "        self.tracer.instant('tick')  # unguarded attribute call\n"
    )
    with pytest.raises(AssertionError, match="without an"):
        assert_tracing_hooks_guarded((str(direct),))


# ---------------------------------------------------------------------------
# Deadline resume on recovery (the ROADMAP follow-up fix)
# ---------------------------------------------------------------------------

def test_recover_resumes_remaining_deadline_budget(tiny):
    """A recovered request keeps its ORIGINAL absolute deadline
    (deadline_at) instead of being granted a fresh window — and one
    whose budget ran out while the engine was down is swept on the first
    tick, exactly as if the engine had lived."""
    cfg, params = tiny
    now = [100.0]
    engine = _engine(cfg, params, clock=lambda: now[0])
    req = engine.submit(np.asarray([3, 5, 7], np.int32), 8, deadline_s=5.0)
    assert req.deadline == 105.0
    engine.step()  # mid-flight
    assert 0 < len(req.generated) < 8

    rebuilt = engine.clone_fresh()
    with pytest.raises(ValueError, match="not both"):
        rebuilt.recover(req.prompt, 8, request_id=req.req_id,
                        generated=list(req.generated),
                        deadline_s=5.0, deadline_at=req.deadline)
    rec = rebuilt.recover(req.prompt, 8, request_id=req.req_id,
                          seed=req.seed, generated=list(req.generated),
                          deadline_at=req.deadline)
    assert rec.deadline == 105.0, "recovery must not restart the window"

    # 3 virtual seconds of downtime already elapsed; 2 remain — still
    # live now, swept once the remaining budget runs out
    now[0] = 103.0
    rebuilt.step()
    assert rec.state.value in ("queued", "running")
    now[0] = 105.5
    rebuilt.step()
    assert rec.finish_reason == "aborted"
    assert rebuilt.metrics.snapshot()["finish_reasons"]["aborted"] == 1


def test_runner_ledger_records_absolute_deadline(tiny):
    """The EngineRunner's replay ledger stores deadline_at (the absolute
    deadline on the engine clock), which is what _rebuild_and_replay
    hands to recover — the end-to-end wiring of the fix."""
    from llm_np_cp_tpu.serve.http.server import EngineRunner

    cfg, params = tiny
    engine = _engine(cfg, params)
    runner = EngineRunner(engine, request_timeout=4.0)

    class Payload:
        prompt_ids = np.asarray([2, 4], np.int32)
        max_tokens = 4
        seed = 0
        timeout_s = None
        stream = False

    rid = runner.next_rid()
    runner._exec_inner(("submit", rid, Payload()), 0)
    rec = runner._inflight[rid]
    assert rec["deadline_at"] == engine._requests[rid].deadline
    assert rec["deadline_at"] is not None


# ---------------------------------------------------------------------------
# summarize_trace tool against a recorded fixture
# ---------------------------------------------------------------------------

def test_summarize_vocabulary_matches_recorder():
    """summarize_trace.py stays stdlib-only, so it carries its own copy
    of the lifecycle phase names — pinned equal to the recorder's here
    (plus the HTTP bracket span)."""
    from llm_np_cp_tpu.serve.tracing import REQUEST_PHASES
    from tools.summarize_trace import LIFECYCLE_COLUMNS

    assert LIFECYCLE_COLUMNS == REQUEST_PHASES + ("http",)


def test_summarize_trace_tool(traced_run, tmp_path):
    engine, tracer, events = traced_run
    path = tmp_path / "fixture_trace.json"
    tracer.dump(str(path))
    loaded = load_trace(str(path))
    assert len(loaded) == len(events)

    totals = phase_totals(loaded)
    for phase in MIXED_TICK_PHASES:
        assert phase in totals, f"missing phase {phase}"
        assert totals[phase]["count"] > 0
    # a prefill chunk is no dispatch of its own: it rides the tick's
    assert "prefill_chunk" not in totals

    stats = tick_stats(loaded)
    assert stats["ticks"] > 0
    assert 0.5 <= stats["phase_coverage"] <= 1.0 + 1e-9

    slow = slowest_ticks(loaded, 3)
    assert len(slow) == 3
    assert slow[0]["dur"] >= slow[-1]["dur"]

    table = request_table(loaded)
    assert len(table) == 8
    out = format_summary(loaded, top=3)
    assert "tick phases" in out and "requests" in out
    assert "mixed_dispatch" in out
    assert "length" in out  # finish reasons rendered
    # bare-list form loads too (both are valid Chrome trace JSON)
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps(loaded))
    assert len(load_trace(str(bare))) == len(loaded)


def test_mixed_tick_phases_and_summarize_utilization(tiny, tmp_path):
    """The unified tick's trace contract: every tick emits exactly the
    MIXED_TICK_PHASES slices at consecutive timestamps (sum-to-tick
    invariant preserved), tick args carry the prefill/decode token
    split, and tools/summarize_trace.py renders the mixed_step
    utilization line from a recorded fixture — budget totals in the
    summary equal the metrics counters."""
    from llm_np_cp_tpu.serve.tracing import MIXED_TICK_PHASES
    from tools.summarize_trace import mixed_utilization

    cfg, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer,
                     num_blocks=48)
    assert engine.mixed
    rng = np.random.default_rng(3)
    trace = poisson_trace(rng, 8, rate_rps=50.0, prompt_len_range=(3, 14),
                          max_new_tokens=5, vocab_size=cfg.vocab_size)
    snap = engine.replay_trace(trace)
    assert snap["finished"] == 8
    events = tracer.events()

    # phase slices: exact vocabulary, consecutive, nested in the tick
    i, checked = 0, 0
    while i < len(events):
        ev = events[i]
        i += 1
        if ev.get("cat") != "tick" or ev.get("ph") != "X":
            continue
        phases = events[i:i + len(MIXED_TICK_PHASES)]
        i += len(MIXED_TICK_PHASES)
        assert [p["name"] for p in phases] == list(MIXED_TICK_PHASES)
        for p in phases:
            assert p["ts"] >= ev["ts"] - 1e-6
            assert p["ts"] + p["dur"] <= ev["ts"] + ev["dur"] + 1e-6
        if ev["dur"] >= 200.0:
            cover = sum(p["dur"] for p in phases) / ev["dur"]
            assert cover >= 0.9
            checked += 1
    assert checked > 0

    # the summarize tool's utilization section, off a dumped fixture
    path = tmp_path / "mixed_trace.json"
    tracer.dump(str(path))
    loaded = load_trace(str(path))
    util = mixed_utilization(loaded)
    assert util is not None
    assert util["prefill_tokens"] == snap["mixed_prefill_tokens"] > 0
    assert util["decode_tokens"] == snap["mixed_decode_tokens"] > 0
    assert util["decode_tokens"] == snap["total_generated_tokens"] - 8, (
        "every token after each request's first is a decode-row token"
    )
    out = format_summary(loaded, top=3)
    assert "mixed_step utilization" in out
    assert "mixed_dispatch" in out
    totals = phase_totals(loaded)
    for phase in MIXED_TICK_PHASES:
        assert phase in totals, f"missing phase {phase}"
    # a phase-split trace has no utilization section
    assert mixed_utilization([]) is None


@pytest.mark.parametrize("budget", [1, 5], ids=["one-token", "five-tokens"])
def test_request_track_follows_the_publish_not_the_accept(tiny, budget):
    """A request's track is what the OUTSIDE saw: ``decode`` begins where
    its first token is emitted — the stamp ``first_emit_time``, taken just
    BEFORE the callback, so the frame's write can never precede it (the
    emit end of the first-write lag) —, inside the ``deliver`` phase of
    the tick AFTER the one that sampled it, and the ``finish`` instant
    follows the last token's callback — while the tick's own slices keep
    the new order and the sum-to-tick invariant."""
    from llm_np_cp_tpu.serve.tracing import MIXED_TICK_PHASES

    cfg, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer,
                     num_blocks=48)
    handed = []
    req = engine.submit(
        np.arange(1, 8), budget,
        callback=lambda r, t, d: handed.append(tracer.now_us()))
    engine.run_until_complete()
    assert len(handed) == budget == len(req.generated)
    events = tracer.events()
    track = [e for e in events if e.get("cat") == "request"
             and e.get("id") == req.req_id]
    names = [(e["name"], e["ph"]) for e in track]
    finish = next(e for e in track if e["name"] == "finish")
    assert finish["args"]["reason"] == "length"
    assert finish["ts"] >= handed[-1]
    if budget == 1:
        # finished on its first token: no decode span was ever opened
        assert ("decode", "b") not in names
    else:
        decode = next(e for e in track if (e["name"], e["ph"]) == ("decode", "b"))
        assert decode["ts"] == tracer.us_at(req.first_emit_time)
        assert req.first_token_time <= req.first_emit_time
        assert decode["ts"] <= handed[0] <= handed[1]
    ticks = [(e, events[i + 1:i + 1 + len(MIXED_TICK_PHASES)])
             for i, e in enumerate(events)
             if e.get("cat") == "tick" and e.get("ph") == "X"]
    for tick, phases in ticks:
        assert [p["name"] for p in phases] == list(MIXED_TICK_PHASES)
        for a, b in zip(phases, phases[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-6)
    # every callback ran inside a ``deliver`` slice — or, for the run's
    # last tick (no tick follows), inside its ``account`` slice
    slices = [(p["name"], p["ts"], p["ts"] + p["dur"])
              for _, phases in ticks for p in phases]
    where = [next(n for n, a, b in slices if a <= t <= b) for t in handed]
    assert where[-1] == "account" and set(where[:-1]) <= {"deliver"}
    # ...of the tick after the one whose fetch brought the token
    first_tick = next(i for i, (t, _) in enumerate(ticks)
                      if t["args"]["host_fetches"])
    deliver_of = {i: next(p for p in ph if p["name"] == "deliver")
                  for i, (_, ph) in enumerate(ticks)}
    if budget > 1:
        d = deliver_of[first_tick + 1]
        assert d["ts"] <= decode["ts"] <= handed[0] <= d["ts"] + d["dur"]


# ---------------------------------------------------------------------------
# Prometheus histograms + phase metrics (the scrape answers
# "queueing or compute?" without a trace file)
# ---------------------------------------------------------------------------

def test_prometheus_histograms_and_phase_metrics(traced_run):
    engine, _, _ = traced_run
    m = engine.metrics
    prom = m.prometheus()
    for line in prom.splitlines():
        assert line.startswith("# ") or PROM_LINE.fullmatch(line), line

    def buckets(name):
        pairs = re.findall(
            rf'^llm_serve_{name}_bucket{{le="([^"]+)"}} (\d+)$', prom, re.M)
        assert pairs, f"no {name} histogram in scrape"
        return pairs

    for name, values in (("ttft_seconds", m.ttft_s),
                         ("decode_tok_s", m.decode_tok_s)):
        pairs = buckets(name)
        counts = [int(c) for _, c in pairs]
        assert counts == sorted(counts), f"{name} buckets not cumulative"
        assert pairs[-1][0] == "+Inf"
        n = int(re.search(rf"^llm_serve_{name}_count (\d+)$",
                          prom, re.M).group(1))
        assert counts[-1] == n == len(values)
        total = float(re.search(rf"^llm_serve_{name}_sum (\S+)$",
                                prom, re.M).group(1))
        assert total == pytest.approx(sum(values), rel=1e-6)
        # cumulative bucket counts agree with the recorded samples
        for le_s, cum in pairs[:-1]:
            le = float(le_s)
            assert int(cum) == sum(1 for v in values if v <= le), (
                f"{name} bucket le={le_s} disagrees with samples"
            )

    # phase quantile gauges: queueing vs compute straight off the scrape
    for name in ("queue_wait_s_quantile", "prefill_s_quantile",
                 "ttft_s_quantile", "decode_tok_s_quantile"):
        assert re.search(rf'^llm_serve_{name}{{quantile="0.5"}} ', prom,
                         re.M), f"missing {name}"
    snap = m.snapshot()
    assert snap["queue_wait_s_p50"] >= 0.0
    assert snap["prefill_s_p50"] > 0.0


@pytest.mark.http
def test_debug_trace_endpoint(tiny):
    """GET /debug/trace serves the live ring buffer as Chrome trace JSON
    (incl. the http bracket span that starts at socket accept) when
    tracing is on, and 404s with an actionable message when off."""
    import asyncio

    from llm_np_cp_tpu.serve.http.client import http_get, post_completion
    from llm_np_cp_tpu.serve.http.server import HttpServer

    cfg, params = tiny
    tracer = TraceRecorder(ring=5000)
    engine = _engine(cfg, params, tracer=tracer)

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        host, port = srv.host, srv.port
        loop = asyncio.get_running_loop()
        st, obj = await loop.run_in_executor(
            None, post_completion, host, port,
            {"prompt": [4, 2, 9], "max_tokens": 3})
        assert st == 200
        st, body = await loop.run_in_executor(
            None, http_get, host, port, "/debug/trace")
        assert st == 200
        dump = json.loads(body)
        srv.begin_drain()
        await srv.serve_until_shutdown()
        return dump

    dump = asyncio.run(asyncio.wait_for(main(), timeout=120))
    events = dump["traceEvents"]
    names = {(e.get("cat"), e["name"], e["ph"]) for e in events}
    assert ("request", "http", "b") in names  # span starts at accept
    assert ("request", "queued", "b") in names
    assert ("tick", "tick", "X") in names
    # the http span opened BEFORE the engine saw the request
    t_http = min(e["ts"] for e in events
                 if e.get("cat") == "request" and e["name"] == "http"
                 and e["ph"] == "b")
    t_queued = min(e["ts"] for e in events
                   if e.get("cat") == "request" and e["name"] == "queued"
                   and e["ph"] == "b")
    assert t_http <= t_queued

    # tracing off → 404 with the how-to-enable hint
    engine_off = _engine(cfg, params)

    async def main_off():
        srv = HttpServer(engine_off, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        loop = asyncio.get_running_loop()
        st, body = await loop.run_in_executor(
            None, http_get, srv.host, srv.port, "/debug/trace")
        srv.begin_drain()
        await srv.serve_until_shutdown()
        return st, body

    st, body = asyncio.run(asyncio.wait_for(main_off(), timeout=120))
    assert st == 404 and b"--trace-ring" in body


@pytest.mark.http
@pytest.mark.chaos
def test_traced_chaos_poisson_covers_recovery(tiny):
    """The acceptance run: a 32-request Poisson workload over HTTP with
    a seeded tick-crash and tracing on — every request completes, the
    trace covers every request INCLUDING the recovery replays (finish
    instants == finished count, ≥1 recovery-replay span, a supervisor
    restart span), the tick phases keep their coverage invariant, and
    the dump is valid trace-event JSON end to end."""
    import asyncio

    from llm_np_cp_tpu.serve import FaultInjector
    from llm_np_cp_tpu.serve.http.client import astream_completion
    from llm_np_cp_tpu.serve.http.server import HttpServer

    cfg, params = tiny
    inj = FaultInjector("tick_crash@12")
    tracer = TraceRecorder()
    engine = _engine(cfg, params, max_slots=4, num_blocks=64,
                     fault_injector=inj, tracer=tracer)
    # compile outside the measured window (and outside the chaos
    # schedule — warmup suspends both injector and tracer)
    engine.warmup([12], max_new_tokens=5)
    # ...so the dummy request leaves no tick and no request track: all
    # there is are the set-up spans of the build and the warm-up of the
    # default engine — the unified tick: one bucket span a packed width,
    # the op map once (no compile watcher is attached here)
    assert engine.mixed
    assert Counter(
        (ev["cat"], ev["name"]) for ev in tracer.events() if ev["ph"] != "M"
    ) == Counter(("setup", name) for name in (
        "probe.ragged_attn", "pool_alloc",
        "probe.sample_epilogue", "engine_build", "warmup.request",
        *["warmup.bucket"] * len(engine.mixed_buckets), "warmup", "op_map",
    )), "warmup must not pollute the timeline"
    rng = np.random.default_rng(11)
    reqs = [
        (rng.integers(1, cfg.vocab_size,
                      size=int(rng.integers(3, 12))).tolist(),
         int(rng.integers(3, 6)))
        for _ in range(32)
    ]

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=30.0,
                         max_restarts=3, restart_backoff_s=0.05)
        await srv.start("127.0.0.1", 0)

        async def one(i, p, m):
            await asyncio.sleep(0.02 * i)  # staggered Poisson-ish ramp
            return await astream_completion(
                srv.host, srv.port,
                {"prompt": p, "max_tokens": m, "stream": True},
                timeout=120, retries=4, backoff_s=0.05,
            )

        results = await asyncio.gather(
            *(one(i, p, m) for i, (p, m) in enumerate(reqs)))
        srv.begin_drain()
        await asyncio.wait_for(srv.serve_until_shutdown(), timeout=60)
        return srv, results

    srv, results = asyncio.run(asyncio.wait_for(main(), timeout=300))
    assert all(r["status"] == 200 and r["finish_reason"] == "length"
               for r in results), results
    assert srv.runner.restarts >= 1
    assert inj.injected["tick_crash"] == 1

    events = tracer.events()
    snap = srv.runner.engine.metrics.snapshot()
    finishes = [ev for ev in events
                if ev.get("cat") == "request" and ev["ph"] == "n"
                and ev["name"] == "finish"]
    assert len(finishes) == snap["finished"] == 32
    finished_rids = {ev["id"] for ev in finishes}
    http_rids = {ev["id"] for ev in events
                 if ev.get("cat") == "request" and ev["name"] == "http"
                 and ev["ph"] == "b"}
    assert http_rids == finished_rids  # every accepted request resolved
    recovers = [ev for ev in events
                if ev.get("cat") == "request" and ev["ph"] == "n"
                and ev["name"] == "recovery-replay"]
    assert len(recovers) == snap["recovered"] >= 1
    sup = [ev for ev in events if ev.get("cat") == "supervisor"]
    assert any(ev["name"] == "engine-death" for ev in sup)
    assert any(ev["name"] == "restart" and ev["ph"] == "X" for ev in sup)

    # phase-coverage invariant holds across the crash + recovery
    stats = tick_stats(events)
    assert stats["ticks"] > 0
    assert stats["phase_coverage"] >= 0.9


def test_histograms_survive_sample_trimming(tiny):
    """max_samples trims the percentile windows; the histogram counters
    must stay exact anyway (they are maintained incrementally)."""
    from llm_np_cp_tpu.serve.metrics import ServeMetrics
    from llm_np_cp_tpu.serve.scheduler import Request

    m = ServeMetrics(max_samples=10)
    for i in range(100):
        req = Request(req_id=i, prompt=np.asarray([1], np.int32),
                      max_new_tokens=2)
        req.submit_time = 0.0
        req.first_token_time = 0.004 * (i + 1)
        req.finish_time = req.first_token_time + 0.01
        req.generated = [1, 2]
        m.on_finish(req)
    assert len(m.ttft_s) <= 10  # window trimmed...
    prom = m.prometheus()
    n = int(re.search(r"^llm_serve_ttft_seconds_count (\d+)$",
                      prom, re.M).group(1))
    assert n == 100  # ...histogram exact


# ----------------------------------------------------------------------
# the tick on one clock: a dispatch's number on the recorder's tick and on
# its two profiler annotations, the fetch cut with a recorder only, the
# ticks the host set, the collector as spans, both threads' CPU at the scrape
# ----------------------------------------------------------------------

def _dispatching(events):
    return [e for e in events if e.get("name") == "tick"
            and e["args"].get("packed_width")]


class _SpyAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: keeps name + metadata."""

    log: list = []

    def __init__(self, name, **meta):
        type(self).log.append((name, meta))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_seq_numbers_dispatching_ticks_and_reaches_both_annotations(
        tiny, monkeypatch):
    cfg, params = tiny
    _SpyAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _SpyAnnotation)
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    for n in (5, 9, 7):
        engine.submit(np.arange(1, n + 1), 5)
    engine.run_until_complete()
    engine.step()  # nothing left: a tick that dispatches nothing
    ticks = [e for e in tracer.events() if e.get("name") == "tick"]
    seqs = [e["args"]["seq"] for e in ticks if "seq" in e["args"]]
    assert seqs == list(range(1, len(seqs) + 1)) and len(seqs) >= 5
    # a tick carries the number exactly when it dispatched
    for e in ticks:
        assert ("seq" in e["args"]) == bool(e["args"]["packed_width"])
    assert any("seq" not in e["args"] for e in ticks)
    assert engine.n_mixed_dispatches == len(seqs)
    # the same number is the metadata of the tick's two annotations, whose
    # NAMES stay what the harness counts ticks by
    for name in ("serve.mixed_dispatch", "serve.host_sync"):
        got = [meta["seq"] for n, meta in _SpyAnnotation.log
               if n == name and meta]
        assert got == seqs, name
    # ... and no other annotation carries metadata
    assert all(not meta for n, meta in _SpyAnnotation.log
               if n not in ("serve.mixed_dispatch", "serve.host_sync"))
    idle_syncs = [meta for n, meta in _SpyAnnotation.log
                  if n == "serve.host_sync" and not meta]
    assert len(idle_syncs) == len(ticks) - len(seqs)


def test_the_fetch_is_cut_with_a_recorder_and_stays_one_fetch(tiny):
    cfg, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    for n in (5, 9):
        engine.submit(np.arange(1, n + 1), 6)
    engine.run_until_complete()
    ticks = _dispatching(tracer.events())
    assert ticks
    for e in ticks:
        a = e["args"]
        assert 0.0 <= a["device_wait_us"] <= a["host_sync_us"]
        assert a["host_fetches"] == 1
        assert a["device_done_at_sync"] in (0, 1)
    idle = [e for e in tracer.events() if e.get("name") == "tick"
            and not e["args"].get("packed_width")]
    assert all("device_wait_us" not in e["args"]
               and "device_done_at_sync" not in e["args"] for e in idle)


def test_without_a_recorder_the_tick_neither_waits_nor_annotates(
        tiny, monkeypatch):
    cfg, params = tiny
    engine = _engine(cfg, params)
    assert engine.tracer is None
    prompts = [np.arange(1, n + 1) for n in (5, 9)]
    for p in prompts:
        engine.submit(p, 4)
    engine.run_until_complete()  # compile everything once
    calls = Counter()
    real_wait, real_asarray = jax.block_until_ready, np.asarray
    monkeypatch.setattr(
        jax, "block_until_ready",
        lambda x: calls.update(wait=1) or real_wait(x))
    monkeypatch.setattr(
        jax.profiler, "TraceAnnotation",
        lambda *a, **k: calls.update(annotation=1) or _SpyAnnotation(*a, **k))
    import llm_np_cp_tpu.serve.engine as engine_mod

    def asarray(x, *a, **k):  # the tick's one fetch: a device array in
        if isinstance(x, jax.Array):
            calls.update(fetch=1)
        return real_asarray(x, *a, **k)

    monkeypatch.setattr(engine_mod.np, "asarray", asarray)
    counter = CompileCounter()
    fetches0, dispatches0 = engine.n_host_fetches, engine.n_mixed_dispatches
    with counter.watch():
        for p in prompts:
            engine.submit(p, 4)
        engine.run_until_complete()
    monkeypatch.undo()
    dispatched = engine.n_mixed_dispatches - dispatches0
    assert dispatched >= 4
    assert calls["wait"] == 0 and calls["annotation"] == 0
    # the fetch is the one np.asarray(out) a dispatching tick it was
    assert calls["fetch"] == dispatched == engine.n_host_fetches - fetches0
    assert counter.count == 0, counter.events
    assert_tracing_hooks_guarded()


def test_host_bound_ticks_counter_counts_the_ones(tiny):
    import time

    cfg, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    # a slow consumer: its token's publish (``deliver``, behind the next
    # dispatch) outlasts the tiny step, so the device is done when the
    # host comes to wait - the host sets those ticks
    engine.submit(np.arange(1, 8), 6,
                  callback=lambda req, tok, text: time.sleep(0.05))
    engine.submit(np.arange(1, 6), 6)
    engine.run_until_complete()
    flags = [e["args"]["device_done_at_sync"]
             for e in _dispatching(tracer.events())]
    assert set(flags) <= {0, 1} and sum(flags) >= 1
    assert engine.metrics.snapshot()["host_bound_ticks"] == sum(flags)
    text = engine.metrics.prometheus()
    assert f"llm_serve_host_bound_ticks_total {sum(flags)}" in text
    assert "# TYPE llm_serve_host_bound_ticks_total counter" in text
    # the question costs 12 us a tick on the chip, so it is the recorder's:
    # an engine without one does not ask, and counts nothing
    plain = _engine(cfg, params)
    plain.submit(np.arange(1, 8), 4,
                 callback=lambda req, tok, text: time.sleep(0.05))
    plain.run_until_complete()
    assert plain.metrics.snapshot()["host_bound_ticks"] == 0
    assert "llm_serve_host_bound_ticks_total 0" in plain.metrics.prometheus()


def test_a_collection_inside_a_traced_tick_is_a_gc_slice_with_within(tiny):
    import gc

    from llm_np_cp_tpu.serve import tracing

    cfg, params = tiny
    tracer = TraceRecorder()
    tracer.watch_gc()
    assert tracing._on_gc in gc.callbacks
    engine = _engine(cfg, params, tracer=tracer)
    # the callback runs in ``deliver``, behind the next tick's dispatch
    engine.submit(np.arange(1, 8), 4,
                  callback=lambda req, tok, text: gc.collect())
    engine.run_until_complete()
    dump = tracer.to_dict()["traceEvents"]
    forced = [e for e in dump if e.get("cat") == "gc"
              and e["args"]["generation"] == 2]
    assert len(forced) >= 3
    for e in forced:
        assert e["ph"] == "X" and e["dur"] >= 0.0
        assert e["args"]["collected"] >= 0
        assert e["tid"] == next(t["tid"] for t in dump if t["name"] == "tick")
    within = Counter(e["args"]["within"] for e in forced)
    assert within["deliver"] >= 2, within
    assert set(within) <= set(MIXED_TICK_PHASES) | {None}
    # a second recorder shares the one hook; the last to leave removes it
    other = TraceRecorder()
    other.watch_gc()
    assert gc.callbacks.count(tracing._on_gc) == 1
    tracer.unwatch_gc()
    assert tracing._on_gc in gc.callbacks
    n = len(tracer)
    gc.collect()
    assert len(tracer) == n and len(other) == 1
    other.unwatch_gc()
    assert tracing._on_gc not in gc.callbacks
    # a recorder that is dropped without unwatching takes the hook with it
    lost = TraceRecorder()
    lost.watch_gc()
    del lost
    gc.collect()
    gc.collect()
    assert tracing._on_gc not in gc.callbacks


@pytest.mark.http
def test_metrics_show_both_threads_cpu_rising(tiny):
    import asyncio

    from llm_np_cp_tpu.serve.http.client import http_get, post_completion
    from llm_np_cp_tpu.serve.http.server import HttpServer

    cfg, params = tiny
    engine = _engine(cfg, params)  # no recorder: the scrape alone reads them
    names = ("tick_thread_cpu_seconds_total", "loop_thread_cpu_seconds_total")

    def counters(body):
        text = body.decode()
        for name in names:
            assert f"# TYPE llm_serve_{name} counter" in text
        return [float(re.search(rf"^llm_serve_{name} (\S+)$", text, re.M)
                      .group(1)) for name in names]

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        host, port = srv.host, srv.port
        loop = asyncio.get_running_loop()
        scrapes = []
        for _ in range(2):
            st, _obj = await loop.run_in_executor(
                None, post_completion, host, port,
                {"prompt": [4, 2, 9], "max_tokens": 8})
            assert st == 200
            st, body = await loop.run_in_executor(
                None, http_get, host, port, "/metrics")
            assert st == 200
            scrapes.append(counters(body))
        srv.begin_drain()
        await srv.serve_until_shutdown()
        return scrapes

    first, second = asyncio.run(asyncio.wait_for(main(), timeout=120))
    assert all(v > 0.0 for v in first)
    assert all(b > a for a, b in zip(first, second)), (first, second)


def test_summarize_prints_the_host_line(tiny):
    import gc

    from tools.summarize_trace import host_account

    cfg, params = tiny
    tracer = TraceRecorder()
    tracer.watch_gc()
    try:
        engine = _engine(cfg, params, tracer=tracer)
        engine.submit(np.arange(1, 8), 5,
                      callback=lambda req, tok, text: gc.collect())
        engine.submit(np.arange(1, 6), 5)
        engine.run_until_complete()
    finally:
        tracer.unwatch_gc()
    events = tracer.to_dict()["traceEvents"]
    host = host_account(events)
    ticks = _dispatching(events)
    assert host["ticks"] == len(ticks)
    assert host["host_bound_share"] == pytest.approx(
        sum(e["args"]["device_done_at_sync"] for e in ticks) / len(ticks))
    assert host["fetch_us"] == pytest.approx(sum(
        e["args"]["host_sync_us"] - e["args"]["device_wait_us"]
        for e in ticks) / len(ticks))
    assert host["gc_count"] >= 3 and host["gc_by_phase_us"]["deliver"] > 0.0
    text = format_summary(events, top=0)
    line = next(ln for ln in text.splitlines() if ln.startswith("host: "))
    assert "found the device done at the fetch" in line
    assert "collector" in line and "deliver" in line
    # a dump from before the dispatches were numbered has no such line
    old = [dict(e, args={k: v for k, v in e["args"].items() if k != "seq"})
           if e.get("name") == "tick" else e for e in events]
    assert host_account(old) is None
    assert "host: " not in format_summary(old, top=0)


def test_summarize_joins_a_profile_by_seq_and_corrects_the_device_lead(
        monkeypatch):
    """The tool's own copy of the join, on a profile made by hand: three
    dispatches 12.2 ms apart, exposed 2.4 ms = wake 0.7 + serial 1.2 +
    launch 0.5, the device's line written 0.9 ms early."""
    from types import SimpleNamespace as NS

    from tools.summarize_trace import tick_timeline

    ms, lead = 1e6, 0.9e6
    host, modules, events = [], [], []

    def ev(name, start, end, **stats):
        return NS(name=name, start_ns=start, duration_ns=end - start,
                  stats=list(stats.items()))

    for i, seq in enumerate((7, 8, 9)):
        d0 = i * 12.2 * ms
        p0, p1 = d0 + 0.5 * ms, d0 + 10.3 * ms
        host += [ev("serve.mixed_dispatch", d0, d0 + 0.4 * ms, seq=seq),
                 ev("serve.host_sync", d0 + 5.2 * ms, p1 + 0.7 * ms, seq=seq),
                 ev("serve.deliver", d0 + 0.4 * ms, d0 + 5.2 * ms),
                 ev("DoEnqueueProgram", d0 + 0.4 * ms, d0 + 0.45 * ms,
                    run_id=100 + seq),
                 ev("CompleteCallbacks", p1 + 0.2 * ms, p1 + 0.25 * ms,
                    run_id=100 + seq)]
        modules.append(ev("jit_mixed_step(1)", p0 - lead, p1 - lead,
                          run_id=100 + seq))
        events.append({"name": "tick", "cat": "tick", "ph": "X",
                       "ts": i * 12200.0, "dur": 12000.0,
                       "args": {"seq": seq, "device_wait_us": 5400.0,
                                "host_sync_us": 5800.0}})
    planes = [
        NS(name="/device:TPU:0", lines=[
            NS(name="XLA Modules", events=modules),
            NS(name="XLA Ops", events=[])]),
        NS(name="/host:CPU", lines=[NS(name="python3", events=host)]),
    ]
    monkeypatch.setattr(
        jax.profiler.ProfileData, "from_file",
        staticmethod(lambda path: NS(planes=planes)))
    got = tick_timeline(events, "ignored.xplane.pb")
    assert got["ticks"] == 2
    assert got["exposed_us"] == pytest.approx(2400.0)
    assert got["serial_us"] == pytest.approx(1200.0)
    # the lead's bounds: 0.8 ms (the enqueue) to 1.1 ms (the callbacks)
    assert got["lead_us"] == pytest.approx(950.0)
    assert got["lead_error_us"] == pytest.approx(150.0)
    assert got["wake_gap_us"] == pytest.approx(650.0)
    assert got["launch_gap_us"] == pytest.approx(550.0)
    assert (got["wake_gap_us"] + got["serial_us"] + got["launch_gap_us"]
            == pytest.approx(got["exposed_us"]))
    # an idle tick between two dispatches takes the first of them out
    idle = {"name": "tick", "cat": "tick", "ph": "X", "ts": 6000.0,
            "dur": 10.0, "args": {}}
    assert tick_timeline(events + [idle], "x")["ticks"] == 1
    assert tick_timeline(
        [dict(e, args={}) for e in events], "x") is None


# ----------------------------------------------------------------------
# a request's first token, stage by stage (scheduler.TTFT_STAMPS): eight
# stamps on the engine clock, each taken once where the work happens, the
# request track's instants at the same readings, the tick by its kind
# ----------------------------------------------------------------------

class _Clock:
    """A clock that advances 1/1024 s a reading: every stamp is distinct,
    ordered as the reads were, and sums of differences are exact."""

    def __init__(self):
        self.n = 0

    def __call__(self):
        self.n += 1
        return self.n / 1024.0


def _staged_engine(cfg, params, *, chunk=8, slots=4, **kw):
    clock = _Clock()
    tracer = TraceRecorder(clock=clock)
    engine = _engine(
        cfg, params, max_slots=slots, num_blocks=96, max_seq_len=128,
        prefill_chunk=chunk, tick_token_budget=slots + 2 * chunk,
        clock=clock, tracer=tracer, **kw)
    return engine, tracer, clock


def _submit_as_http(engine, clock, prompt, max_new, **kw):
    """What the HTTP layer does around ``ServeEngine.submit``: a stamp at
    socket accept, one at the command's put into the inbox."""
    received = clock()
    return engine.submit(prompt, max_new, received_time=received,
                         enqueue_time=clock(), **kw)


def _track(events, rid):
    """{"begin": {span: ts}, "instant": {name: event}} of one request, the
    FIRST of each name (as ``benchmark/layers/tracefile.request_tracks``)."""
    out = {"begin": {}, "instant": {}}
    for ev in events:
        if ev.get("cat") != "request" or ev.get("id") != rid:
            continue
        if ev["ph"] == "b":
            out["begin"].setdefault(ev["name"], ev["ts"])
        elif ev["ph"] == "n":
            out["instant"].setdefault(ev["name"], ev)
    return out


def _tick_of(ticks, ts):
    """Index of the recorder's tick whose span holds ``ts``."""
    return next(i for i, t in enumerate(ticks)
                if t["ts"] <= ts <= t["ts"] + t["dur"])


@pytest.fixture(scope="module")
def staged_run(tiny):
    """32 requests, prompts of 3 to 40 tokens against a chunk of 8 and a
    budget of ``slots + 2 x chunk``, submitted in waves between ticks as
    the HTTP layer would (both of its stamps passed in)."""
    from llm_np_cp_tpu.serve.request_log import request_record

    cfg, params = tiny
    engine, tracer, clock = _staged_engine(cfg, params)
    rng = np.random.default_rng(53)
    reqs, records = [], {}
    lens = rng.integers(3, 41, size=32)

    def log(req, event):
        if event in ("stop", "length", "aborted"):
            records[req.req_id] = request_record(
                req, reason=event, clock=clock)

    for wave in range(8):
        for n in lens[4 * wave:4 * wave + 4]:
            reqs.append(_submit_as_http(
                engine, clock, rng.integers(1, cfg.vocab_size, size=int(n)),
                int(rng.integers(1, 6)), on_event=log))
        for _ in range(3):
            engine.step()
    engine.run_until_complete()
    assert all(r.finish_reason == "length" for r in reqs)
    return engine, tracer, reqs, records


def test_stages_are_consecutive_and_the_instants_sit_at_the_stamps(staged_run):
    from llm_np_cp_tpu.serve.scheduler import (
        TTFT_STAGES,
        TTFT_STAMPS,
        ttft_stages,
    )

    engine, tracer, reqs, _ = staged_run
    events = tracer.events()
    assert len(reqs) == 32
    for req in reqs:
        stamps = [getattr(req, name) for name in TTFT_STAMPS]
        assert None not in stamps, (req.req_id, stamps)
        assert stamps == sorted(stamps)
        stages = ttft_stages(req)
        assert tuple(stages) == TTFT_STAGES
        assert all(v >= 0.0 for v in stages.values())
        # exactly: the clock's readings are multiples of 2**-10
        assert sum(stages.values()) == req.first_emit_time - req.received_time
        assert stages["slot_wait"] == req.admit_time - req.submit_time
        tr = _track(events, req.req_id)
        at = tracer.us_at
        assert tr["begin"]["queued"] > at(req.enqueue_time)
        assert tr["instant"]["lane"]["ts"] == at(req.lane_time)
        assert tr["instant"]["last_chunk"]["ts"] == at(req.last_chunk_time)
        assert tr["instant"]["first_token"]["ts"] == at(req.first_token_time)
        assert at(req.admit_time) < tr["begin"]["prefill"] <= at(req.lane_time)
        if req.max_new_tokens > 1:
            assert tr["begin"]["decode"] == at(req.first_emit_time)
        else:  # finished on its first token: no decode span was opened
            assert "decode" not in tr["begin"]
        counts = tr["instant"]["last_chunk"]["args"]
        assert counts["prefill_ticks"] == req.prefill_ticks >= 1
        assert counts["lane_ticks"] == req.lane_ticks
        assert counts["starved_ticks"] == req.starved_ticks
        assert req.lane_ticks <= req.prefill_ticks
    # the dispatch a request's last chunk rode and the one that sampled its
    # first token are ONE dispatch, the recorder's tick of that ``seq``
    ticks = {e["args"]["seq"]: e for e in _dispatching(events)}
    for req in reqs:
        tr = _track(events, req.req_id)
        seq = tr["instant"]["last_chunk"]["args"]["seq"]
        assert tr["instant"]["first_token"]["args"]["seq"] == seq
        t = ticks[seq]
        assert (t["ts"] <= tr["instant"]["last_chunk"]["ts"]
                <= tr["instant"]["first_token"]["ts"] <= t["ts"] + t["dur"])
    # prompts longer than the lane met each other in it
    assert any(r.lane_ticks > 1 for r in reqs)
    assert any(r.lane_time > r.admit_time + 4 / 1024 for r in reqs)


def test_two_long_prompts_share_the_lane_oldest_first(tiny):
    """Budget ``slots + 2 x chunk``: each of two mid-prefill rows takes
    its chunk and the leftover (``slots``) goes to the older, so the older
    holds the lane from its first planned tick and the younger waits, a
    chunk a tick, until the older's prompt runs out."""
    cfg, params = tiny
    chunk, slots = 8, 4
    engine, tracer, clock = _staged_engine(cfg, params, chunk=chunk,
                                           slots=slots)
    old = engine.submit(np.arange(1, 62), 2)    # 61 tokens
    young = engine.submit(np.arange(2, 63), 2)  # 61 tokens
    engine.step()
    assert old.admit_time is not None and young.admit_time is not None
    engine.run_until_complete()
    # a prompt of ONE chunk, alone: lane and last chunk in its only tick
    short = engine.submit(np.arange(1, chunk + 1), 2)
    engine.run_until_complete()
    events = tracer.events()
    ticks = [e for e in events if e.get("name") == "tick"]
    tr_old, tr_young = _track(events, old.req_id), _track(events, young.req_id)

    first_planned = _tick_of(ticks, tracer.us_at(old.admit_time))
    assert _tick_of(ticks, tr_old["instant"]["lane"]["ts"]) == first_planned
    assert tr_old["instant"]["lane"]["args"] == {
        "rows_ahead": 0, "fair_tokens": 0}
    old_last = _tick_of(ticks, tr_old["instant"]["last_chunk"]["ts"])
    young_lane = _tick_of(ticks, tr_young["instant"]["lane"]["ts"])
    assert young_lane >= old_last > first_planned
    lane = tr_young["instant"]["lane"]["args"]
    assert lane["rows_ahead"] == 1
    # planned a chunk a tick, every tick from its admission to its lane
    assert lane["fair_tokens"] == chunk * (young_lane - first_planned) > 0
    assert young.starved_ticks == 0 == old.starved_ticks
    # the older took chunk + slots a tick, five lane ticks, and its last
    # token in a sixth, which is no more than its fair share: there the
    # leftover passed to the younger
    assert (old.lane_ticks, old.prefill_ticks) == (5, 6)
    assert young_lane == old_last
    assert young.prefill_ticks > young.lane_ticks >= 1
    # the tick says its kind: leftover went to ONE row while both prefilled
    both = ticks[first_planned]["args"]
    assert (both["lane_rows"], both["lane_tokens"]) == (1, slots)
    assert both["prefill_rows"] == 2

    assert short.lane_time == short.last_chunk_time
    assert short.prefill_ticks == 1 and short.lane_ticks == 0
    from llm_np_cp_tpu.serve.scheduler import ttft_stages

    assert ttft_stages(short)["prefill"] == 0.0
    tr = _track(events, short.req_id)
    assert tr["instant"]["lane"]["ts"] == tr["instant"]["last_chunk"]["ts"]
    # its tick carried a fair share alone: neither kind of the two
    own = ticks[_tick_of(ticks, tr["instant"]["lane"]["ts"])]["args"]
    assert own["lane_rows"] == 0 and own["prefill_tokens"] == chunk


def test_a_row_the_budget_runs_out_before_is_starved(tiny):
    """Three long prompts under a budget of two chunks: the fair share
    reaches the two oldest, the third is mid-prefill and granted nothing."""
    cfg, params = tiny
    clock = _Clock()
    engine = _engine(cfg, params, max_slots=4, num_blocks=96, max_seq_len=128,
                     prefill_chunk=8, tick_token_budget=16, clock=clock)
    reqs = [engine.submit(np.arange(1, 34), 2) for _ in range(3)]
    engine.run_until_complete()
    assert [r.starved_ticks for r in reqs][:2] == [0, 0]
    assert reqs[2].starved_ticks >= 4
    snap = engine.metrics.snapshot()
    assert snap["prefill_starved_rows"] == sum(r.starved_ticks for r in reqs)
    assert (f"llm_serve_prefill_starved_rows_total "
            f"{snap['prefill_starved_rows']}") in engine.metrics.prometheus()
    # a budget of two chunks leaves no leftover: no tick was a lane tick
    # until a prompt's tail freed part of a chunk
    assert snap["lane_ticks"] <= 2


def test_stage_family_counters_and_request_log_agree_with_the_trace(staged_run):
    from llm_np_cp_tpu.serve.scheduler import TTFT_STAGES

    engine, tracer, reqs, records = staged_run
    events = tracer.events()
    snap = engine.metrics.snapshot()
    ticks = [e for e in events if e.get("name") == "tick"]
    assert snap["lane_ticks"] == sum(
        1 for t in ticks if t["args"]["lane_rows"] > 0) > 0
    assert snap["lane_ticks"] <= snap["ticks"]
    assert sum(t["args"]["lane_tokens"] for t in ticks) > 0
    assert all((t["args"]["lane_tokens"] > 0) == (t["args"]["lane_rows"] > 0)
               for t in ticks)
    # the stages, re-derived from the request track alone (microseconds)
    derived = {stage: [] for stage in TTFT_STAGES}
    for req in reqs:
        tr = _track(events, req.req_id)
        edges = [tracer.us_at(req.received_time),
                 tracer.us_at(req.enqueue_time),
                 tr["begin"]["queued"], tr["begin"]["prefill"],
                 tr["instant"]["lane"]["ts"],
                 tr["instant"]["last_chunk"]["ts"],
                 tr["instant"]["first_token"]["ts"],
                 tracer.us_at(req.first_emit_time)]
        rec = records[req.req_id]
        assert tuple(rec["ttft_stages"]) == TTFT_STAGES
        for stage, a, b in zip(TTFT_STAGES, edges, edges[1:]):
            derived[stage].append((b - a) / 1e6)
            # ``queued`` / ``prefill`` begin a clock read after their stamp
            assert rec["ttft_stages"][stage] == pytest.approx(
                (b - a) / 1e6, abs=2.1 / 1024)
        for name in ("prefill_ticks", "lane_ticks", "starved_ticks"):
            assert rec[name] == tr["instant"]["last_chunk"]["args"][name]
        assert rec["phases"]["queue_wait_s"] == pytest.approx(
            rec["ttft_stages"]["slot_wait"])
    prom = engine.metrics.prometheus()
    for line in prom.splitlines():
        assert line.startswith("# ") or PROM_LINE.fullmatch(line), line
    assert prom.count("# TYPE llm_serve_ttft_stage_seconds_quantile gauge") == 1
    for stage in TTFT_STAGES:
        got = float(re.search(
            rf'^llm_serve_ttft_stage_seconds_quantile'
            rf'{{stage="{stage}",quantile="0.5"}} (\S+)$', prom, re.M).group(1))
        assert got == pytest.approx(snap[f"ttft_stage_{stage}_s_p50"])
        assert got == pytest.approx(np.percentile(derived[stage], 50),
                                    abs=2.1 / 1024)
    assert f"llm_serve_lane_ticks_total {snap['lane_ticks']}" in prom
    # ... and how wide a prefill tile was: 8 lanes here (no wide tile)
    assert 1 < snap["attn_prefill_tile_tokens"] <= 8
    assert ("llm_serve_attn_prefill_tiles_packed_total "
            f"{snap['attn_prefill_tiles_packed']}") in prom
    assert "llm_serve_attn_prefill_tile_tokens " in prom
    # the old names keep their meanings beside the family
    assert snap["queue_wait_s_p50"] == pytest.approx(
        snap["ttft_stage_slot_wait_s_p50"])
    assert "llm_serve_prefill_s_quantile" in prom


def test_preemption_and_recovery_replay_keep_the_first_stamps(tiny):
    from llm_np_cp_tpu.serve.scheduler import (
        TTFT_STAMPS,
        first_stamps,
        ttft_stages,
    )

    cfg, params = tiny
    clock = _Clock()
    tracer = TraceRecorder(clock=clock)
    # a pool tight enough to preempt decoding rows
    engine = _engine(cfg, params, num_blocks=6, clock=clock, tracer=tracer)
    rng = np.random.default_rng(5)
    reqs = [_submit_as_http(engine, clock,
                            rng.integers(1, cfg.vocab_size, size=5), 12)
            for _ in range(6)]
    seen: dict[int, dict] = {}
    for _ in range(400):
        if not engine.step():
            break
        for r in reqs:
            if r.first_emit_time is not None and r.req_id not in seen:
                seen[r.req_id] = first_stamps(r)
    hit = [r for r in reqs if r.n_preemptions]
    assert hit, "pool was not tight enough to exercise eviction"
    for r in hit:
        assert r.finish_reason == "length"
        if r.req_id in seen:  # preempted after its first token went out
            assert first_stamps(r) == seen[r.req_id]
    assert any(r.req_id in seen for r in hit)
    # its track holds ONE of each instant, the first life's
    events = tracer.events()
    for r in hit:
        for name in ("lane", "last_chunk", "first_token"):
            assert sum(1 for e in events if e.get("id") == r.req_id
                       and e.get("name") == name) == 1

    # a mid-flight rebuild: the replay carries the first stamps over
    live = _submit_as_http(engine, clock, np.arange(1, 8), 8)
    for _ in range(3):
        engine.step()
    assert live.first_token_time is not None and live.generated
    before = first_stamps(live)
    rebuilt = engine.clone_fresh()
    again = rebuilt.recover(live.prompt, 8, request_id=live.req_id,
                            seed=live.seed, generated=list(live.generated),
                            stamps=before)
    rebuilt.run_until_complete()
    assert again.finish_reason == "length"
    kept = first_stamps(again)
    assert {k: kept[k] for k in before} == before
    assert set(TTFT_STAMPS) <= set(kept)
    stages = ttft_stages(again)
    assert all(v >= 0.0 for v in stages.values())
    assert sum(stages.values()) == again.first_emit_time - again.received_time
    # without them the replay starts anew (as a journal replay in another
    # process must: its clock is another)
    fresh = engine.clone_fresh().recover(
        live.prompt, 8, request_id=live.req_id, seed=live.seed,
        generated=list(live.generated))
    assert fresh.received_time is None and fresh.lane_time is None
    assert fresh.submit_time > live.submit_time


def test_the_stamps_add_no_compile_and_the_hooks_stay_guarded(tiny):
    cfg, params = tiny
    engine, tracer, clock = _staged_engine(cfg, params)
    engine.tracer = None
    prompts = [np.arange(1, n + 1) for n in (5, 30, 12)]
    for p in prompts:
        _submit_as_http(engine, clock, p, 3)
    engine.run_until_complete()  # compile everything once
    counter = CompileCounter()
    for rec in (None, tracer, None):
        engine.tracer = rec
        with counter.watch():
            for p in prompts:
                _submit_as_http(engine, clock, p, 3)
            engine.run_until_complete()
        assert counter.count == 0, counter.events
    assert any(e.get("name") == "lane" for e in tracer.events())
    assert_tracing_hooks_guarded()
    from tools.lint.rules.guarded_hook import scan_hook_guard_files

    assert not scan_hook_guard_files((
        "llm_np_cp_tpu/serve/engine.py",
        "llm_np_cp_tpu/serve/http/server.py"), hooks=("tracer",))


@pytest.mark.http
def test_over_http_the_track_runs_from_accept_to_the_first_write(tiny):
    """The two stamps of the loop thread (socket accept, the inbox) reach
    the request, and with a recorder the eighth stage closes the track:
    the instants and span begins from ``http`` to ``first_write`` are
    consecutive, so the eight stages sum to first write - accept."""
    import asyncio

    from llm_np_cp_tpu.serve.http.client import astream_completion
    from llm_np_cp_tpu.serve.http.server import HttpServer
    from llm_np_cp_tpu.serve.scheduler import TTFT_STAGES

    cfg, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        results = await asyncio.gather(*(
            astream_completion(
                srv.host, srv.port,
                {"prompt": [4, 2, 9, 7][: 2 + i % 3], "max_tokens": 4,
                 "stream": True}, timeout=60)
            for i in range(6)))
        srv.begin_drain()
        await srv.serve_until_shutdown()
        return results

    results = asyncio.run(asyncio.wait_for(main(), timeout=120))
    assert all(r["status"] == 200 for r in results)
    events = tracer.events()
    rids = {e["id"] for e in events if e.get("name") == "http"}
    assert len(rids) == 6
    for rid in rids:
        tr = _track(events, rid)
        edges = [tr["begin"]["http"], tr["instant"]["enqueued"]["ts"],
                 tr["begin"]["queued"], tr["begin"]["prefill"],
                 tr["instant"]["lane"]["ts"],
                 tr["instant"]["last_chunk"]["ts"],
                 tr["instant"]["first_token"]["ts"], tr["begin"]["decode"],
                 tr["instant"]["first_write"]["ts"]]
        assert edges == sorted(edges), (rid, edges)
        assert edges[0] < edges[1] < edges[2]
        assert tr["instant"]["first_write"]["args"]["lag_us"] >= 0.0
    snap = engine.metrics.snapshot()
    for stage in TTFT_STAGES:
        assert snap[f"ttft_stage_{stage}_s_p50"] >= 0.0, stage
    assert snap["ttft_stage_parse_s_p50"] > 0.0
    assert snap["ttft_stage_inbox_wait_s_p50"] > 0.0


def test_summarize_prints_the_first_token_by_stage(staged_run):
    from tools.summarize_trace import (
        TTFT_STAGES,
        format_ttft_stages,
        ttft_stage_table,
    )

    from llm_np_cp_tpu.serve import scheduler

    assert TTFT_STAGES == scheduler.TTFT_STAGES + ("write_lag",)
    engine, tracer, reqs, _ = staged_run
    events = tracer.to_dict()["traceEvents"]
    table = ttft_stage_table(events)
    # the direct-mode run has no ``http`` span: its track starts at queued
    assert list(table["stages"]) == [
        "slot_wait", "lane_wait", "prefill", "final_tick", "publish_lag"]
    multi = [r for r in reqs if r.max_new_tokens > 1]
    assert table["stages"]["final_tick"]["n"] == len(reqs)
    assert table["stages"]["publish_lag"]["n"] == len(multi)
    lane_wait = sorted(1e6 * (r.lane_time - r.admit_time) for r in reqs)
    assert table["stages"]["lane_wait"]["p50_us"] == pytest.approx(
        lane_wait[round(0.5 * (len(reqs) - 1))], abs=2e6 / 1024)
    assert table["counts"]["prefill_ticks"] == sorted(
        r.prefill_ticks for r in reqs)[round(0.5 * (len(reqs) - 1))]
    ticks = _dispatching(events)
    assert sum(rec["n"] for rec in table["ticks"].values()) == len(ticks)
    assert table["ticks"]["prefill"]["n"] == sum(
        1 for t in ticks if t["args"]["lane_rows"] > 0)
    assert table["ticks"]["decode"]["n"] == sum(
        1 for t in ticks if not t["args"]["prefill_tokens"])
    text = format_summary(events, top=0)
    assert format_ttft_stages(table) in text
    assert "tick wall by kind: decode " in text
    # a dump from before the track carried the instants prints no table
    old = [e for e in events if e.get("name") not in (
        "lane", "last_chunk", "first_token", "enqueued")]
    assert ttft_stage_table(old) is None
    assert "first token by stage" not in format_summary(old, top=0)
