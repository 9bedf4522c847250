"""Device roofline telemetry (serve/telemetry.py) + OTLP span export
(serve/otel.py).

The contracts being pinned: the analytic byte model's constants come
from the params tree (tied lm_head re-reads the embedding, int8 pools
pay their scale pages), per-request cost attribution CONSERVES — the
attributed KV/weight bytes and device time sum to the metrics ledgers
across the mixed tick, the phase-split path, speculative verify lanes,
prefix-shared prompts, and int8 pools — and the canonical request log
carries the same numbers; roofline gauges/histograms ride the metrics
snapshot and the Prometheus scrape (absent until a dispatch was
graded), tick trace args feed tools/summarize_trace's roofline section,
the sentinel baselines the roofline deficit like any phase, the fleet
aggregate recomputes utilization from SUMS, OTLP export round-trips the
trace plane to a real (stub) collector and degrades to drop-and-count
when the collector is dead, and none of it adds a jit recompile.
"""

import json
import threading
from http.server import BaseHTTPRequestHandler, HTTPServer

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import (
    OtlpExporter,
    RequestLog,
    ServeEngine,
    ServeMetrics,
    TelemetryModel,
    TickSentinel,
    TraceRecorder,
    read_request_log,
)
from llm_np_cp_tpu.serve.replica import ReplicaSet
from llm_np_cp_tpu.serve.telemetry import (
    HBM_GBPS_DEFAULT,
    _per_slot_bytes,
)
from llm_np_cp_tpu.serve.trace import poisson_trace
from llm_np_cp_tpu.serve.tracing import gen_trace_id
from tools.compile_counter import CompileCounter
from tools.summarize_trace import format_summary, roofline


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


def _run(engine, prompts, max_tokens=5):
    for i, p in enumerate(prompts):
        engine.submit(p, max_tokens, seed=i)
    engine.run_until_complete()


def _tiled_prompts(rng, vocab, lens, pattern=4):
    """Repetitive prompts (the prompt-lookup draft's win case)."""
    out = []
    for n in lens:
        base = rng.integers(1, vocab, size=pattern, dtype=np.int64)
        out.append(np.resize(base.astype(np.int32), n))
    return out


def _assert_conserves(engine):
    """Per-request attributed bytes/time sum to the metrics ledgers —
    the cost-attribution invariant the per-tenant billing basis rests
    on.  Returns the snapshot for further checks."""
    snap = engine.metrics.snapshot()
    reqs = engine.scheduler.finished
    assert snap["roofline_ticks"] > 0, "no dispatch was graded"
    for total_key, field in (
        ("kv_read_bytes_total", "kv_bytes_read"),
        ("kv_write_bytes_total", "kv_bytes_written"),
        ("weight_bytes_total", "weight_bytes_amortized"),
        ("device_time_s_total", "device_time_s"),
    ):
        attributed = sum(getattr(r, field) for r in reqs)
        assert attributed == pytest.approx(snap[total_key], rel=1e-6), (
            f"{total_key}: attributed {attributed} != ledger "
            f"{snap[total_key]}"
        )
    assert all(r.device_time_s > 0 for r in reqs), "a request went unbilled"
    return snap


# ---------------------------------------------------------------------------
# TelemetryModel constants
# ---------------------------------------------------------------------------

def test_model_constants_from_params_tree(tiny):
    cfg, params = tiny
    model = TelemetryModel(cfg, params)
    embed_b = int(params["embed_tokens"].nbytes)
    total_b = int(sum(
        leaf.nbytes for leaf in jax.tree_util.tree_leaves(params)
    ))
    # the embedding is gathered (one row per token), not streamed...
    assert model.stream_bytes == total_b - embed_b
    assert model.embed_row_bytes == embed_b // cfg.vocab_size
    # ...but the tied lm_head re-reads the full matrix for logits
    assert cfg.tie_word_embeddings
    assert model.lm_head_bytes == embed_b
    assert model.hbm_gbps == HBM_GBPS_DEFAULT
    # weight traffic: stack+lm_head per dispatch, embed rows per token
    one = model.weight_bytes(1)
    assert model.weight_bytes(5, n_dispatches=2) == (
        2 * (one - model.embed_row_bytes) + 5 * model.embed_row_bytes
    )


def test_int8_pool_pays_scale_pages(tiny):
    cfg, _ = tiny
    f32 = _per_slot_bytes(cfg, 4)
    i8 = _per_slot_bytes(cfg, 1)
    assert f32 == cfg.num_key_value_heads * cfg.head_dim * 4 * 2
    # quantized K+V plus the per-slot f32 scales for both
    assert i8 == (cfg.num_key_value_heads * cfg.head_dim * 2
                  + cfg.num_key_value_heads * 4 * 2)


def test_model_rejects_nonpositive_rooflines(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="hbm_gbps"):
        TelemetryModel(cfg, params, hbm_gbps=0.0)
    with pytest.raises(ValueError, match="peak_tflops"):
        TelemetryModel(cfg, params, peak_tflops=-1.0)


def test_model_accepts_quantized_params_tree(tiny):
    """quantize_params turns leaves (incl. embed_tokens) into
    {"q", "scale"} subtrees — the model must sum their leaves, not
    crash on the embed special-case."""
    from llm_np_cp_tpu.quant import quantize_params

    cfg, params = tiny
    qm = TelemetryModel(cfg, quantize_params(params))
    fm = TelemetryModel(cfg, params)
    assert 0 < qm.stream_bytes < fm.stream_bytes  # int8 streams less
    assert 0 < qm.embed_row_bytes < fm.embed_row_bytes


# ---------------------------------------------------------------------------
# Cost conservation — the attribution invariant, across every tick shape
# ---------------------------------------------------------------------------

def test_mixed_tick_cost_conservation(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params,
                     telemetry=TelemetryModel(cfg, params))
    rng = np.random.default_rng(7)
    prompts = [rng.integers(1, cfg.vocab_size, size=n)
               for n in (5, 21, 9, 14, 30, 3)]
    _run(engine, prompts, max_tokens=6)
    snap = _assert_conserves(engine)
    # the graded gauges ride the snapshot once a dispatch ran
    assert snap["roofline_gbps_mean"] > 0
    assert 0 < snap["roofline_util_last"] <= snap["hbm_gbps"]
    assert snap["mfu_mean"] > 0
    assert snap["hbm_gbps"] == HBM_GBPS_DEFAULT


def test_abort_from_first_token_callback_conserves(tiny, tmp_path):
    """An abort fired from the FIRST token's callback (the supported
    abort-from-callback pattern; the callback runs a tick after the
    token was accepted) writes the request-log line during the abort —
    the attribution of every tick the request rode has landed by then,
    so the line carries a real cost block and the ledgers still
    conserve."""
    cfg, params = tiny
    path = str(tmp_path / "requests.jsonl")
    rl = RequestLog(path)
    engine = _engine(cfg, params,
                     telemetry=TelemetryModel(cfg, params),
                     request_log=rl)

    def kill_first(req, tok, delta):
        engine.abort(req.req_id)

    rng = np.random.default_rng(21)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (14, 9)]
    r0 = engine.submit(prompts[0], 6, seed=0, callback=kill_first)
    engine.submit(prompts[1], 5, seed=1)
    engine.run_until_complete()
    rl.close()
    assert r0.finish_reason == "aborted"
    # aborted requests leave the scheduler entirely (not in .finished):
    # conserve over ALL terminals — the abort's bill is real spend
    snap = engine.metrics.snapshot()
    terminals = engine.scheduler.finished + [r0]
    for total_key, field in (
        ("kv_read_bytes_total", "kv_bytes_read"),
        ("kv_write_bytes_total", "kv_bytes_written"),
        ("weight_bytes_total", "weight_bytes_amortized"),
        ("device_time_s_total", "device_time_s"),
    ):
        attributed = sum(getattr(r, field) for r in terminals)
        assert attributed == pytest.approx(snap[total_key],
                                           rel=1e-6), total_key
    assert r0.device_time_s > 0 and r0.kv_bytes_written > 0
    by_rid = {ln["rid"]: ln for ln in read_request_log(path)}
    cost = by_rid[r0.req_id]["cost"]
    assert cost["device_time_s"] > 0 and cost["kv_bytes_written"] > 0
    assert by_rid[r0.req_id]["reason"] == "aborted"
    assert snap["aborted"] == 1


def test_spec_verify_lanes_conservation(tiny):
    """Speculative verify lanes are billed as packed (the HBM sweep
    really covered them, accepted or not) and attribution still sums
    to the tick totals."""
    cfg, params = tiny
    engine = _engine(cfg, params, spec_k=3,
                     telemetry=TelemetryModel(cfg, params))
    rng = np.random.default_rng(9)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (12, 19, 8))
    for i, p in enumerate(prompts):
        engine.submit(p, 8, seed=i, speculative=True)
    engine.run_until_complete()
    snap = _assert_conserves(engine)
    assert snap["spec_drafted_tokens"] > 0, "no verify round ran"


def test_prefix_shared_blocks_conservation(tiny):
    """Prefix-shared prompts: the sharer's attention READS the shared
    blocks (billed to it) but never re-writes them — conservation
    holds and the sharers' write bill is visibly smaller."""
    cfg, params = tiny
    engine = _engine(cfg, params, num_blocks=64,
                     enable_prefix_cache=True,
                     telemetry=TelemetryModel(cfg, params))
    rng = np.random.default_rng(10)
    shared = rng.integers(1, cfg.vocab_size, size=24).astype(np.int32)
    for i in range(4):
        engine.submit(shared, 5, seed=i)
    engine.run_until_complete()
    snap = _assert_conserves(engine)
    assert snap["prefix_blocks_hit"] > 0, "nothing was shared"
    by_id = {r.req_id: r for r in engine.scheduler.finished}
    first, later = by_id[0], by_id[3]
    assert later.kv_bytes_written < first.kv_bytes_written


def test_int8_pool_conservation(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params,
                     cache_dtype=jnp.int8,
                     telemetry=TelemetryModel(cfg, params))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (6, 15)]
    _run(engine, prompts, max_tokens=4)
    _assert_conserves(engine)


# ---------------------------------------------------------------------------
# Zero overhead off / zero recompiles on
# ---------------------------------------------------------------------------

def test_off_by_default_and_attach_adds_zero_recompiles(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    assert engine.telemetry is None  # the default IS off
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (5, 13)]
    _run(engine, prompts, max_tokens=4)
    snap = engine.metrics.snapshot()
    assert "roofline_ticks" not in snap  # no fabricated zeros
    assert all(r.device_time_s == 0.0 and r.kv_bytes_read == 0.0
               for r in engine.scheduler.finished)

    # attach EVERYTHING host-side at once — telemetry, tracer, OTLP
    # sink (dead collector on purpose: failures must stay counters) —
    # and the warmed step compiles nothing new
    engine.telemetry = TelemetryModel(cfg, params)
    engine.tracer = TraceRecorder(ring=50_000)
    exporter = OtlpExporter("http://127.0.0.1:9/v1/traces",
                            timeout_s=0.2).attach(engine.tracer)
    try:
        counter = CompileCounter()
        with counter.watch():
            _run(engine, prompts, max_tokens=4)
        assert counter.count == 0, (
            f"telemetry+otel ticks compiled: {counter.events}"
        )
        assert engine.metrics.snapshot()["roofline_ticks"] > 0
    finally:
        exporter.close()
        engine.tracer = None
        engine.telemetry = None


# ---------------------------------------------------------------------------
# Trace args → summarize_trace roofline section (recorded fixture)
# ---------------------------------------------------------------------------

def test_tick_args_and_summarize_roofline_fixture(tiny, tmp_path):
    cfg, params = tiny
    events = []
    engine = _engine(cfg, params, telemetry=TelemetryModel(cfg, params),
                     tracer=TraceRecorder())
    rng = np.random.default_rng(13)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (7, 16, 11)]
    _run(engine, prompts, max_tokens=4)
    path = tmp_path / "trace.json"
    engine.tracer.dump(str(path))
    events += json.loads(path.read_text())["traceEvents"]

    ticks = [e for e in events
             if e.get("ph") == "X" and e.get("cat") == "tick"
             and "roofline_util" in (e.get("args") or {})]
    assert ticks, "no tick carried roofline args"
    for ev in ticks:
        a = ev["args"]
        # >= 0, not > 0: the args are rounded (6 places for util), and
        # a toy model's bytes over a tick that paid a CPU compile
        # (seconds) round to exactly 0.0 — the aggregate below is where
        # "the bill is positive" is pinned
        assert a["roofline_gbps"] >= 0 and a["roofline_util"] >= 0
        assert a["kv_read_bytes"] >= 0 and a["weight_bytes"] > 0
        assert a["device_time_s"] > 0

    roof = roofline(events)
    assert set(roof) == {"mixed"}
    for kind, r in roof.items():
        assert r["ticks"] > 0
        assert r["gbps_p50"] <= r["gbps_p99"]
        assert 0 < r["util_mean"] <= 1.0
        assert r["device_s_total"] > 0
    out = format_summary(events)
    assert "== roofline ==" in out
    assert "mixed" in out
    # telemetry-off traces don't grow a roofline section
    assert roofline([{"ph": "X", "cat": "tick", "args": {}}]) is None


# ---------------------------------------------------------------------------
# Sentinel: the roofline deficit pages like a phase
# ---------------------------------------------------------------------------

def test_sentinel_baselines_roofline_deficit(tiny):
    cfg, params = tiny
    sentinel = TickSentinel(warmup_ticks=4, min_us=1.0)
    engine = _engine(cfg, params,
                     telemetry=TelemetryModel(cfg, params),
                     tracer=TraceRecorder(), sentinel=sentinel)
    rng = np.random.default_rng(14)
    _run(engine, [rng.integers(1, cfg.vocab_size, size=9)], max_tokens=6)
    assert "roofline_deficit" in sentinel._stats

    # and a persistent utilization collapse (deficit step-change) is
    # flagged BY NAME once past warmup
    fresh = TickSentinel(warmup_ticks=2, threshold=3.0, min_us=1.0)
    base = (("host_sync", 0.0, 50.0), ("roofline_deficit", 0.0, 100.0))
    for _ in range(8):
        assert fresh.observe(base) == []
    bad = (("host_sync", 0.0, 50.0), ("roofline_deficit", 0.0, 50_000.0))
    outliers = fresh.observe(bad)
    assert outliers and outliers[0]["phase"] == "roofline_deficit"


# ---------------------------------------------------------------------------
# Request log: the cost basis rides the wide event
# ---------------------------------------------------------------------------

def test_request_log_cost_fields_conserve(tiny, tmp_path):
    cfg, params = tiny
    path = str(tmp_path / "requests.jsonl")
    rl = RequestLog(path)
    engine = _engine(cfg, params,
                     telemetry=TelemetryModel(cfg, params),
                     request_log=rl)
    rng = np.random.default_rng(15)
    prompts = [rng.integers(1, cfg.vocab_size, size=n)
               for n in (6, 19, 12)]
    _run(engine, prompts, max_tokens=5)
    snap = _assert_conserves(engine)
    rl.close()
    lines = read_request_log(path)
    assert len(lines) == snap["finished"]
    for key, total_key in (
        ("kv_bytes_read", "kv_read_bytes_total"),
        ("kv_bytes_written", "kv_write_bytes_total"),
        ("weight_bytes_amortized", "weight_bytes_total"),
        ("device_time_s", "device_time_s_total"),
    ):
        logged = sum(ln["cost"][key] for ln in lines)
        # fields are rounded on write (0.1 byte / ns), hence the abs slack
        assert logged == pytest.approx(snap[total_key], rel=1e-6,
                                       abs=len(lines)), key


def test_request_log_omits_cost_without_telemetry(tiny, tmp_path):
    cfg, params = tiny
    path = str(tmp_path / "requests.jsonl")
    rl = RequestLog(path)
    engine = _engine(cfg, params, request_log=rl)
    rng = np.random.default_rng(16)
    _run(engine, [rng.integers(1, cfg.vocab_size, size=8)], max_tokens=3)
    rl.close()
    (line,) = read_request_log(path)
    assert "cost" not in line  # absent, not zero-filled


# ---------------------------------------------------------------------------
# Metrics plane
# ---------------------------------------------------------------------------

def _tel_record(*, util=0.5, gbps=400.0):
    return {
        "kind": "mixed",
        "tokens": 4,
        "device_time_s": 0.01,
        "kv_read_bytes": 1000.0,
        "kv_write_bytes": 100.0,
        "weight_bytes": 5000.0,
        "achieved_gbps": gbps,
        "roofline_util": util,
        "mfu": 0.1,
        "deficit_us": 0.0,
        "hbm_gbps": 800.0,
    }


def test_metrics_ledgers_gauges_and_prometheus():
    m = ServeMetrics()
    assert "roofline_ticks" not in m.snapshot()
    assert "roofline" not in m.prometheus()
    m.on_telemetry(_tel_record(util=0.004))
    m.on_telemetry(_tel_record(util=0.3, gbps=300.0))
    s = m.snapshot()
    assert s["roofline_ticks"] == 2
    assert s["kv_read_bytes_total"] == 2000.0
    assert s["device_time_s_total"] == pytest.approx(0.02)
    assert s["roofline_gbps_last"] == 300.0
    assert s["roofline_util_mean"] == pytest.approx((0.004 + 0.3) / 2)
    text = m.prometheus()
    assert 'llm_serve_device_bytes_total{kind="kv_read"} 2000' in text
    assert "llm_serve_roofline_util " in text
    assert "llm_serve_hbm_gbps_target 800" in text
    assert "llm_serve_mfu " in text
    # the utilization histogram: one sample in the lowest buckets, one
    # mid-range, cumulative to +Inf
    assert 'llm_serve_roofline_util_hist_bucket{le="0.005"} 1' in text
    assert 'llm_serve_roofline_util_hist_bucket{le="+Inf"} 2' in text
    assert "llm_serve_roofline_util_hist_count 2" in text


def test_fleet_aggregate_recomputes_utilization_from_sums(tiny):
    cfg, params = tiny
    model = TelemetryModel(cfg, params)
    fleet = ReplicaSet([
        _engine(cfg, params, telemetry=model)
        for _ in range(2)
    ])
    rng = np.random.default_rng(17)
    trace = poisson_trace(
        rng, 8, rate_rps=50.0, prompt_len_range=(4, 20),
        max_new_tokens=4, vocab_size=cfg.vocab_size,
    )
    snap = fleet.replay_trace(trace)
    per = [e.metrics.snapshot() for e in fleet.engines]
    assert snap["roofline_ticks"] == sum(s["roofline_ticks"] for s in per)
    total_bytes = sum(
        s["kv_read_bytes_total"] + s["kv_write_bytes_total"]
        + s["weight_bytes_total"] for s in per
    )
    dev = sum(s["device_time_s_total"] for s in per)
    assert snap["roofline_gbps"] == pytest.approx(total_bytes / dev / 1e9)
    assert snap["roofline_util"] == pytest.approx(
        snap["roofline_gbps"] / HBM_GBPS_DEFAULT
    )


# ---------------------------------------------------------------------------
# OTLP export
# ---------------------------------------------------------------------------

class _StubCollector:
    """A real HTTP collector on an ephemeral loopback port: records
    every OTLP payload POSTed at it."""

    def __init__(self, fail=False):
        self.payloads: list[dict] = []
        self.fail = fail
        stub = self

        class Handler(BaseHTTPRequestHandler):
            def do_POST(self):
                body = self.rfile.read(
                    int(self.headers.get("Content-Length", 0))
                )
                if stub.fail:
                    self.send_response(500)
                else:
                    stub.payloads.append(json.loads(body))
                    self.send_response(200)
                self.end_headers()

            def log_message(self, *a):
                pass

        self.server = HTTPServer(("127.0.0.1", 0), Handler)
        self.endpoint = (
            f"http://127.0.0.1:{self.server.server_address[1]}/v1/traces"
        )
        self._thread = threading.Thread(
            target=self.server.serve_forever, daemon=True
        )
        self._thread.start()

    def spans(self):
        out = []
        for p in self.payloads:
            for rs in p["resourceSpans"]:
                for ss in rs["scopeSpans"]:
                    out.extend(ss["spans"])
        return out

    def close(self):
        self.server.shutdown()
        self.server.server_close()


@pytest.mark.http
def test_otlp_round_trip_from_live_engine(tiny):
    cfg, params = tiny
    collector = _StubCollector()
    engine = _engine(cfg, params,
                     telemetry=TelemetryModel(cfg, params),
                     tracer=TraceRecorder())
    exporter = OtlpExporter(collector.endpoint,
                            service_name="test-serve").attach(engine.tracer)
    try:
        tid = gen_trace_id()
        rng = np.random.default_rng(18)
        engine.submit(rng.integers(1, cfg.vocab_size, size=9), 4,
                      trace_id=tid)
        engine.run_until_complete()
        assert exporter.flush(10.0), "flush barrier timed out"
        st = exporter.stats()
        assert st["spans"] > 0 and st["batches"] > 0
        assert st["dropped"] == 0 and st["export_errors"] == 0
        spans = collector.spans()
        assert len(spans) == st["spans"]
        names = {s["name"] for s in spans}
        assert "tick" in names  # the tick slices made the trip
        # the request's W3C trace id survives into the collector — the
        # whole point of shipping to where the fleet's traces live
        assert tid in {s["traceId"] for s in spans}
        for s in spans:
            assert int(s["endTimeUnixNano"]) >= int(s["startTimeUnixNano"])
        # resource attrs carry the service identity
        res = collector.payloads[0]["resourceSpans"][0]["resource"]
        assert {"key": "service.name",
                "value": {"stringValue": "test-serve"}} in res["attributes"]
    finally:
        exporter.close()
        collector.close()


@pytest.mark.http
def test_otlp_conversion_pairs_instants_and_metadata(tiny):
    collector = _StubCollector()
    exporter = OtlpExporter(collector.endpoint, wall_epoch=1000.0)
    try:
        tid = gen_trace_id()
        exporter.offer({"ph": "b", "id": 7, "name": "decode", "ts": 10.0,
                        "cat": "request", "args": {"trace": tid}})
        exporter.offer({"ph": "e", "id": 7, "name": "decode", "ts": 40.0,
                        "cat": "request"})
        exporter.offer({"ph": "i", "name": "finish", "ts": 41.0,
                        "cat": "request", "args": {"reason": "stop"}})
        exporter.offer({"ph": "M", "name": "process_name", "args": {}})
        # an async begin with no end: must survive close as zero-length
        exporter.offer({"ph": "b", "id": 8, "name": "queued", "ts": 50.0,
                        "cat": "request"})
        assert exporter.flush(10.0)
        exporter.close()
        spans = {s["name"]: s for s in collector.spans()}
        assert set(spans) == {"decode", "finish", "queued"}  # M skipped
        d = spans["decode"]
        assert d["traceId"] == tid
        assert (int(d["endTimeUnixNano"]) - int(d["startTimeUnixNano"])
                == 30_000)  # 30 µs
        attrs = {a["key"]: a["value"] for a in spans["finish"]["attributes"]}
        assert attrs["llm.instant"] == {"boolValue": True}
        assert attrs["llm.reason"] == {"stringValue": "stop"}
        tail = spans["queued"]
        assert tail["startTimeUnixNano"] == tail["endTimeUnixNano"]
    finally:
        collector.close()


@pytest.mark.http
def test_otlp_collector_failure_drops_and_counts(tiny):
    """Faults-site discipline: a dead or erroring collector costs
    dropped batches and a counter, never an exception or a stall."""
    collector = _StubCollector(fail=True)
    exporter = OtlpExporter(collector.endpoint, timeout_s=1.0)
    try:
        for i in range(5):
            exporter.offer({"ph": "i", "name": f"ev{i}", "ts": float(i),
                            "cat": "tick"})
        assert exporter.flush(10.0)
        st = exporter.stats()
        assert st["dropped"] == 5 and st["export_errors"] >= 1
        assert st["spans"] == 0
        assert collector.payloads == []  # 500s recorded nothing
    finally:
        exporter.close()
        collector.close()
    with pytest.raises(ValueError, match="endpoint"):
        OtlpExporter("")
    with pytest.raises(ValueError, match="batch_max"):
        OtlpExporter("http://x/v1/traces", batch_max=0)
    with pytest.raises(ValueError, match="pending_max"):
        OtlpExporter("http://x/v1/traces", pending_max=0)


def test_otlp_pending_cap_bounds_hung_collector():
    """A BLACKHOLED collector (every POST eats the full timeout) stalls
    the writer while the engine keeps producing — the pending queue
    must cap out and drop-and-count, never grow without bound."""
    exporter = OtlpExporter("http://127.0.0.1:9/v1/traces",
                            pending_max=8, flush_interval_s=0.05,
                            timeout_s=0.2)
    entered, release = threading.Event(), threading.Event()

    def hung_export(spans):
        entered.set()
        release.wait(10.0)

    exporter._export = hung_export  # simulate the hang at the POST
    try:
        exporter.offer({"ph": "i", "name": "first", "ts": 0.0})
        assert entered.wait(10.0), "writer never picked up the batch"
        for i in range(50):  # writer is stuck mid-"POST"
            exporter.offer({"ph": "i", "name": f"ev{i}", "ts": float(i)})
        with exporter._lock:
            assert len(exporter._pending) <= 8
        assert exporter.stats()["dropped"] >= 42
    finally:
        release.set()
        exporter.close()


# ---------------------------------------------------------------------------
# slo_gate --min-bandwidth-util
# ---------------------------------------------------------------------------

def test_slo_gate_min_bandwidth_util(tmp_path):
    from tools.slo_gate import main as gate

    bench = tmp_path / "bench.json"
    bench.write_text(json.dumps({"detail": {"serve_mixed_poisson": {
        "config": "serve_mixed_poisson",
        "roofline_util_mean": 0.42, "roofline_gbps_mean": 344.0,
    }}}))
    ok = [str(bench), "--config", "serve_mixed_poisson"]
    assert gate([*ok, "--min-bandwidth-util", "0.4"]) == 0
    assert gate([*ok, "--min-bandwidth-util", "0.6"]) == 1
    # no top-level mirror: the BEST leg gates (split legs are slower
    # by design and must not fail an honest capture)
    legs = tmp_path / "legs.json"
    legs.write_text(json.dumps({
        "config": "x",
        "legs": {"unified": {"roofline_util_mean": 0.5},
                 "split": {"roofline_util_mean": 0.2}},
    }))
    assert gate([str(legs), "--config", "x",
                 "--min-bandwidth-util", "0.45"]) == 0
    assert gate([str(legs), "--config", "x",
                 "--min-bandwidth-util", "0.55"]) == 1
    # roofline fields absent entirely: the gate fails loudly (1), it
    # does not silently pass a telemetry-less capture
    bare = tmp_path / "bare.json"
    bare.write_text(json.dumps({"config": "x", "tok_s": 10.0}))
    assert gate([str(bare), "--config", "x",
                 "--min-bandwidth-util", "0.1"]) == 1
