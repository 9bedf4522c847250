"""Multi-tenant observability (serve/tenants.py + the tenant thread
through protocol → engine → journal → fleet).

The contracts being pinned: one normalizer vets every tenant id (an
injection attempt dies at the protocol boundary as a 400, never reaches
a Prometheus label or a log line), per-tenant cost attribution CONSERVES
against the global metrics ledgers and the canonical request log,
tenancy-on is observationally free (byte-identical streams, zero new
step compiles), fairness strictly raises the worst tenant's attainment
on identical arrivals, the in-flight cap 429s with the throttle counter
and trace instant, tenant identity survives kill -9 (journal replay,
compaction included), and the fleet aggregates per-tenant accounting
across replicas (ReplicaSet.snapshot, /debug/tenants, tenant-labeled
scrape with bounded cardinality).
"""

import asyncio
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import (
    RequestJournal,
    RequestLog,
    ServeEngine,
    SLOPolicy,
    TelemetryModel,
    TraceRecorder,
    read_request_log,
    scan_journal,
)
from llm_np_cp_tpu.serve.http.protocol import (
    HTTPError,
    parse_completion_request,
)
from llm_np_cp_tpu.serve.replica import ReplicaSet
from llm_np_cp_tpu.serve.scheduler import TenantThrottled
from llm_np_cp_tpu.serve.tenants import (
    TENANT_MAX_LEN,
    TenantLedger,
    aggregate_tenants,
    normalize_tenant,
)
from llm_np_cp_tpu.serve.trace import poisson_trace
from tools.compile_counter import CompileCounter


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


# ---------------------------------------------------------------------------
# normalize_tenant: the ONE validator (satellite: injection tests)
# ---------------------------------------------------------------------------

def test_normalize_tenant_accepts_and_defaults():
    assert normalize_tenant(None) == "default"
    assert normalize_tenant("") == "default"
    for ok in ("acme", "team-7", "a.b_c-D", "x" * TENANT_MAX_LEN, "0"):
        assert normalize_tenant(ok) == ok


@pytest.mark.parametrize("hostile", [
    "evil\ntenant",                       # newline → log-line injection
    'x" } bad{',                          # quote/brace → label escape
    'a"}/*',                              # Prometheus labelset breakout
    "a\\nb",                              # literal backslash
    "tab\tid",
    "space id",
    "naïve",                              # non-ASCII
    "x" * (TENANT_MAX_LEN + 1),           # over the length cap
    123,                                  # non-string
    ["a"],
])
def test_normalize_tenant_rejects_injection(hostile):
    with pytest.raises(ValueError):
        normalize_tenant(hostile)


def test_protocol_maps_tenant_to_payload_and_400():
    def parse(body, header=None):
        return parse_completion_request(
            json.dumps(body).encode(), model_id="m",
            header_tenant=header,
        )

    base = {"model": "m", "prompt": [1, 2, 3]}
    assert parse(base).tenant == "default"
    assert parse(base, header="acme").tenant == "acme"
    # the body field is the request of record: it overrides the header
    assert parse(dict(base, tenant="beta"), header="acme").tenant == "beta"
    assert parse(dict(base, tenant=""), header="acme").tenant == "default"
    # hostile ids die here with a 400, never reaching a label/log line
    for bad in ('evil\ntenant', 'x"}b', "x" * (TENANT_MAX_LEN + 1), 7):
        with pytest.raises(HTTPError) as ei:
            parse(dict(base, tenant=bad))
        assert ei.value.status == 400
    with pytest.raises(HTTPError) as ei:
        parse(base, header="bad header")
    assert ei.value.status == 400


# ---------------------------------------------------------------------------
# TenantLedger units: counters, cost shares, cardinality bound
# ---------------------------------------------------------------------------

class _FakeReq:
    def __init__(self, tenant, tokens=3, reason="stop", *, kv_r=0.0,
                 kv_w=0.0, wb=0.0, dev=0.0):
        self.tenant = tenant
        self.generated = list(range(tokens))
        self.finish_reason = reason
        self.kv_bytes_read = kv_r
        self.kv_bytes_written = kv_w
        self.weight_bytes_amortized = wb
        self.device_time_s = dev
        self.prefill_done = 0
        # SLOPolicy.verdict reads the Request timestamps
        self.submit_time = None
        self.admit_time = None
        self.first_token_time = None
        self.finish_time = None
        self.max_new_tokens = tokens


def test_ledger_counters_shares_and_validation():
    with pytest.raises(ValueError):
        TenantLedger(max_inflight=0)
    with pytest.raises(ValueError):
        TenantLedger(max_series=0)
    led = TenantLedger()
    led.on_terminal(_FakeReq("a", tokens=4, kv_r=300.0, wb=100.0))
    led.on_terminal(_FakeReq("a", tokens=2, reason="length", kv_r=100.0))
    led.on_terminal(_FakeReq("b", tokens=1, kv_w=500.0))
    led.on_throttle("b")
    snap = led.snapshot()
    assert snap["n_tenants"] == 2
    a, b = snap["tenants"]["a"], snap["tenants"]["b"]
    assert a["requests"] == 2 and a["tokens"] == 6
    assert a["finish_reasons"] == {"stop": 1, "length": 1}
    assert b["throttled"] == 1
    # byte-based shares when bytes were metered: a=500, b=500
    assert a["cost_share"] == pytest.approx(0.5)
    assert b["cost_share"] == pytest.approx(0.5)
    # token fallback when nothing was metered
    led2 = TenantLedger()
    led2.on_terminal(_FakeReq("x", tokens=3))
    led2.on_terminal(_FakeReq("y", tokens=1))
    shares = led2.snapshot()["tenants"]
    assert shares["x"]["cost_share"] == pytest.approx(0.75)
    # cost_shares folds LIVE work in (the fairness sort key)
    live = [_FakeReq("z", tokens=2)]
    live[0].prefill_done = 5
    cs = led2.cost_shares(live)
    assert cs["z"] == pytest.approx(7.0)
    assert cs["x"] == pytest.approx(3.0)


def test_prometheus_topk_and_other_rollup_conserve():
    led = TenantLedger(max_series=2)
    for i, (tenant, kv) in enumerate(
        [("big", 4000.0), ("mid", 300.0), ("small", 20.0), ("tiny", 1.0)]
    ):
        led.on_terminal(_FakeReq(tenant, tokens=i + 1, kv_r=kv))
    text = led.prometheus(const_labels={"replica": "0"})
    assert 'llm_serve_tenant_requests_total{tenant="big",replica="0"} 1' \
        in text
    assert 'tenant="mid"' in text
    # past top-K rolls into ONE "other" labelset, never dropped
    assert 'tenant="small"' not in text
    assert 'tenant="tiny"' not in text
    assert 'tenant="other"' in text
    req_lines = [
        ln for ln in text.splitlines()
        if ln.startswith("llm_serve_tenant_requests_total{")
    ]
    assert len(req_lines) == 3
    assert sum(float(ln.rsplit(" ", 1)[1]) for ln in req_lines) == 4.0
    byte_lines = [
        ln for ln in text.splitlines()
        if ln.startswith("llm_serve_tenant_device_bytes_total{")
    ]
    assert sum(float(ln.rsplit(" ", 1)[1]) for ln in byte_lines) == \
        pytest.approx(4321.0)
    # /debug/tenants always shows everyone — only the scrape is bounded
    assert led.snapshot()["n_tenants"] == 4


# ---------------------------------------------------------------------------
# Cost conservation: per-tenant sums == global ledgers == request log
# ---------------------------------------------------------------------------

def test_per_tenant_cost_conservation(tiny, tmp_path):
    """The acceptance pin: with telemetry attributing device cost and a
    request log recording it, the TenantLedger's per-tenant sums equal
    the global ServeMetrics ledgers exactly and the request-log lines
    within rounding tolerance — aborts included."""
    cfg, params = tiny
    log_path = str(tmp_path / "reqs.jsonl")
    rlog = RequestLog(log_path)
    led = TenantLedger()
    engine = _engine(cfg, params,
                     telemetry=TelemetryModel(cfg, params),
                     request_log=rlog, tenants=led,
                     enable_prefix_cache=True)
    rng = np.random.default_rng(11)
    plan = [("acme", 5), ("acme", 21), ("beta", 9), ("default", 14),
            ("beta", 30), ("acme", 3)]
    reqs = []
    for i, (tenant, n) in enumerate(plan):
        prompt = rng.integers(1, cfg.vocab_size, size=n)
        reqs.append(engine.submit(prompt, 6, seed=i, tenant=tenant))
    # an abort accrues partial cost on its tenant's bill too
    for _ in range(2):
        engine.step()
    engine.abort(reqs[4].req_id)
    engine.run_until_complete()
    assert rlog.flush(5.0)
    rlog.close()

    snap = engine.metrics.snapshot()
    tsnap = led.snapshot()["tenants"]
    assert set(tsnap) == {"acme", "beta", "default"}
    assert sum(e["requests"] for e in tsnap.values()) == 6
    assert tsnap["beta"]["finish_reasons"].get("aborted") == 1
    # tenant sums == global ledgers, exactly (same float stream)
    for total_key, field in (
        ("kv_read_bytes_total", "kv_bytes_read"),
        ("kv_write_bytes_total", "kv_bytes_written"),
        ("weight_bytes_total", "weight_bytes_amortized"),
        ("device_time_s_total", "device_time_s"),
    ):
        by_tenant = sum(e[field] for e in tsnap.values())
        assert by_tenant == pytest.approx(snap[total_key], rel=1e-6), \
            f"{total_key}: {by_tenant} != {snap[total_key]}"
    assert sum(e["tokens"] for e in tsnap.values()) == \
        snap["total_generated_tokens"]
    # ...and == the canonical request log, within its rounding (bytes
    # to 0.1, seconds to 1e-9, per line)
    records = read_request_log(log_path)
    assert len(records) == 6
    by_log: dict[str, dict[str, float]] = {}
    for rec in records:
        ent = by_log.setdefault(rec.get("tenant", "default"),
                                {"kv_bytes_read": 0.0,
                                 "kv_bytes_written": 0.0,
                                 "weight_bytes_amortized": 0.0,
                                 "device_time_s": 0.0})
        for k in ent:
            ent[k] += rec.get("cost", {}).get(k, 0.0)
    for tenant, ent in by_log.items():
        for k, tol in (("kv_bytes_read", 1.0), ("kv_bytes_written", 1.0),
                       ("weight_bytes_amortized", 1.0),
                       ("device_time_s", 1e-6)):
            assert abs(ent[k] - tsnap[tenant][k]) <= tol * len(records), \
                (tenant, k)
    # all requests billed, shares a probability distribution
    assert all(e["device_time_s"] > 0 for e in tsnap.values())
    assert sum(e["cost_share"] for e in tsnap.values()) == \
        pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Tenancy-on is observationally free: parity + zero new compiles
# ---------------------------------------------------------------------------

def test_token_parity_and_zero_compiles_with_tenancy_on(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(2)
    trace = poisson_trace(rng, 10, rate_rps=50.0, prompt_len_range=(3, 18),
                          max_new_tokens=5, vocab_size=cfg.vocab_size)
    plain = _engine(cfg, params)
    plain.replay_trace(trace)
    # submission order, not raw req_id: the tenancy leg's warmup dummy
    # shifts ids by one
    want = [list(r.generated)
            for r in sorted(plain.scheduler.finished,
                            key=lambda r: r.req_id)]

    led = TenantLedger(fairness=True,
                       policy=SLOPolicy(ttft_s=60.0, tpot_s=60.0))
    engine = _engine(cfg, params, tenants=led)
    engine.warmup([int(t["prompt"].size) for t in trace],
                  max_new_tokens=5)
    tagged = [dict(item, tenant=("a" if i % 2 else "b"))
              for i, item in enumerate(trace)]
    counter = CompileCounter()
    with counter.watch():
        engine.replay_trace(tagged)
    assert counter.count == 0, "tenancy added a step compile"
    got = [list(r.generated)
           for r in sorted(engine.scheduler.finished,
                           key=lambda r: r.req_id)]
    assert got == want, "tenancy changed the token streams"
    assert led.snapshot()["n_tenants"] == 2


# ---------------------------------------------------------------------------
# Fairness bites: worst tenant's attainment strictly rises
# ---------------------------------------------------------------------------

def _fairness_leg(cfg, params, *, fairness, policy=None):
    """One leg on a fully virtual clock (1s per tick, all submits at
    t=0): a whale tenant's three long prompts are admitted ahead of one
    short mouse request, so the prefill fill order is the whole game."""
    state = {"t": 0.0}
    led = TenantLedger(fairness=fairness, policy=policy,
                       clock=lambda: state["t"])
    engine = _engine(cfg, params, max_slots=4,
                     num_blocks=64, tick_token_budget=16,
                     tenants=led, clock=lambda: state["t"])
    rng = np.random.default_rng(5)
    whale = [rng.integers(1, cfg.vocab_size, size=24) for _ in range(3)]
    mouse = rng.integers(1, cfg.vocab_size, size=8)
    reqs = [engine.submit(p, 3, seed=i, tenant="whale")
            for i, p in enumerate(whale)]
    reqs.append(engine.submit(mouse, 3, seed=9, tenant="mouse"))
    while True:
        state["t"] += 1.0
        if not engine.step():
            break
    ttft = {r.req_id: r.first_token_time - r.submit_time for r in reqs}
    streams = {r.req_id: list(r.generated) for r in reqs}
    return ttft, streams, reqs[-1].req_id, led


def test_fairness_strictly_raises_worst_tenant_attainment(tiny):
    cfg, params = tiny
    ttft_off, streams_off, mouse, _ = _fairness_leg(
        cfg, params, fairness=False)
    ttft_on, streams_on, mouse_on, _ = _fairness_leg(
        cfg, params, fairness=True)
    assert mouse == mouse_on
    # identical arrivals → identical tokens; only the schedule moved
    assert streams_on == streams_off
    # the starved tenant's first token lands STRICTLY earlier
    assert ttft_on[mouse] < ttft_off[mouse], (ttft_on, ttft_off)

    # attainment legs: a TTFT bar between the two measured outcomes
    # turns the schedule delta into an SLO verdict delta
    bar = (ttft_on[mouse] + ttft_off[mouse]) / 2.0
    policy = SLOPolicy(ttft_s=bar, tpot_s=1e9)

    def worst(led):
        snap = led.snapshot()["tenants"]
        return min(e["slo"]["slo_attainment"] for e in snap.values())

    _, _, _, led_off = _fairness_leg(cfg, params, fairness=False,
                                     policy=policy)
    _, _, _, led_on = _fairness_leg(cfg, params, fairness=True,
                                    policy=policy)
    assert worst(led_off) == 0.0  # the mouse misses every verdict
    assert worst(led_on) > worst(led_off)
    mouse_ent = led_on.snapshot()["tenants"]["mouse"]
    assert mouse_ent["slo"]["slo_attainment"] == 1.0


# ---------------------------------------------------------------------------
# The in-flight cap: TenantThrottled + counter + trace instant
# ---------------------------------------------------------------------------

def test_tenant_cap_throttles_counts_and_traces(tiny):
    cfg, params = tiny
    tracer = TraceRecorder()
    led = TenantLedger(max_inflight=1)
    engine = _engine(cfg, params, tenants=led, tracer=tracer)
    engine.submit([1, 2, 3], 4, tenant="capped")
    with pytest.raises(TenantThrottled) as ei:
        engine.submit([4, 5, 6], 4, tenant="capped")
    assert "capped" in str(ei.value) and "in-flight cap" in str(ei.value)
    # an uncapped peer is unaffected
    engine.submit([7, 8, 9], 4, tenant="other")
    engine.run_until_complete()
    snap = led.snapshot()["tenants"]
    assert snap["capped"]["throttled"] == 1
    assert snap["capped"]["requests"] == 1
    assert engine.metrics.snapshot()["rejected"] == 1
    instants = [ev for ev in tracer.events()
                if ev.get("name") == "tenant-throttled"]
    assert len(instants) == 1
    assert instants[0]["args"] == {
        "tenant": "capped", "inflight": 1, "cap": 1}
    # throttle counter rides the tenant-labeled scrape
    assert 'llm_serve_tenant_throttled_total{tenant="capped"} 1' in \
        led.prometheus()
    # a recovery replay is exempt: the cap must never orphan a stream
    # the engine already accepted
    led2 = TenantLedger(max_inflight=1)
    engine2 = _engine(cfg, params, tenants=led2)
    engine2.recover([1, 2, 3], 4, request_id=0, tenant="capped")
    engine2.recover([4, 5, 6], 4, request_id=1, tenant="capped")
    engine2.run_until_complete()
    assert led2.snapshot()["tenants"]["capped"]["requests"] == 2
    assert led2.snapshot()["tenants"]["capped"]["throttled"] == 0


# ---------------------------------------------------------------------------
# Tenancy survives kill -9: journal replay + compaction round-trip
# ---------------------------------------------------------------------------

def test_journal_and_compaction_preserve_tenant(tiny, tmp_path):
    cfg, params = tiny
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    led = TenantLedger()
    engine = _engine(cfg, params, journal=j, tenants=led)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (6, 11)]
    engine.submit(prompts[0], 6, seed=0, tenant="acme")
    engine.submit(prompts[1], 6, seed=1)  # default stays unwritten
    for _ in range(3):
        engine.step()
    assert j.flush(5.0)
    j.close()  # kill -9: unterminated state on disk

    state, _, _ = scan_journal(path)
    assert state[0]["tenant"] == "acme"
    assert state[1]["tenant"] == "default"
    raw = open(path, "rb").read()
    assert raw.count(b'"tenant"') == 1, "default tenant got written"

    # compaction rewrites live admissions — the tenant must ride along
    j2 = RequestJournal(path, compact_bytes=256)
    for _ in range(40):  # watermark churn forces compactions
        j2.end_tick([])
        j2.terminal(999, "stop")
    assert j2.flush(5.0)
    assert j2.stats()["compactions"] >= 1
    replayed = {rec["rid"]: rec for rec in j2.replay()}
    assert replayed[0]["tenant"] == "acme"
    assert replayed[1]["tenant"] == "default"

    # the replayed stream bills the tenant that submitted it
    led2 = TenantLedger()
    engine2 = _engine(cfg, params, journal=j2, tenants=led2)
    for rec in j2.replay():
        engine2.recover(
            rec["prompt"], rec["max_tokens"], request_id=rec["rid"],
            seed=rec["seed"], generated=rec["tokens"],
            tenant=rec["tenant"],
        )
    engine2.run_until_complete()
    snap = led2.snapshot()["tenants"]
    assert snap["acme"]["requests"] == 1
    assert snap["default"]["requests"] == 1
    assert snap["acme"]["tokens"] == 6
    assert j2.flush(5.0)
    state, _, _ = scan_journal(path)
    assert state == {}
    j2.close()


# ---------------------------------------------------------------------------
# Fleet: per-tenant accounting aggregates across replicas
# ---------------------------------------------------------------------------

def test_fleet_aggregates_tenants_across_replicas(tiny):
    cfg, params = tiny
    policy = SLOPolicy(ttft_s=60.0, tpot_s=60.0)
    engines = [
        _engine(cfg, params, tenants=TenantLedger(policy=policy))
        for _ in range(2)
    ]
    fleet = ReplicaSet(engines)
    rng = np.random.default_rng(7)
    for i in range(8):
        prompt = rng.integers(1, cfg.vocab_size, size=int(
            rng.integers(3, 14)))
        fleet.submit(prompt, 4, seed=i,
                     tenant=("acme" if i % 2 else "beta"))
    fleet.run_until_complete()
    # both replicas served work, each billing its own ledger
    per_replica = [e.tenants.snapshot()["tenants"] for e in engines]
    assert all(any(e["requests"] for e in snap.values())
               for snap in per_replica)
    snap = fleet.snapshot()
    assert snap["n_tenants"] == 2
    agg = snap["tenants"]
    assert agg["acme"]["requests"] + agg["beta"]["requests"] == 8
    assert agg["acme"]["requests"] == sum(
        s.get("acme", {}).get("requests", 0) for s in per_replica)
    assert agg["acme"]["tokens"] + agg["beta"]["tokens"] == \
        snap["total_generated_tokens"]
    # SLO recomputed from summed verdicts, not averaged ratios
    assert agg["acme"]["slo"]["slo_ok"] == sum(
        s["acme"]["slo"]["slo_ok"] for s in per_replica if "acme" in s)
    assert agg["acme"]["slo"]["slo_attainment"] == 1.0
    assert agg["acme"]["cost_share"] + agg["beta"]["cost_share"] == \
        pytest.approx(1.0)
    # aggregate_tenants tolerates ledger-less replicas and empty fleets
    mixed = aggregate_tenants([e.tenants for e in engines] + [None])
    assert mixed["n_tenants"] == 2
    assert aggregate_tenants([None]) == {}
    assert aggregate_tenants([]) == {}


# ---------------------------------------------------------------------------
# HTTP e2e: header → 400/429/metrics/debug endpoint
# ---------------------------------------------------------------------------

async def _post(host, port, payload, headers=None):
    body = json.dumps(payload).encode()
    extra = "".join(f"{k}: {v}\r\n" for k, v in (headers or {}).items())
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(
        b"POST /v1/completions HTTP/1.1\r\n"
        + f"Host: {host}\r\nContent-Length: {len(body)}\r\n".encode()
        + extra.encode()
        + b"Content-Type: application/json\r\nConnection: close\r\n\r\n"
        + body
    )
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    hdrs = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.decode().partition(":")
        hdrs[k.strip().lower()] = v.strip()
    body = await reader.read()
    writer.close()
    return status, hdrs, body


def test_http_tenant_header_429_metrics_and_debug(tiny):
    from llm_np_cp_tpu.serve.http.client import http_get
    from llm_np_cp_tpu.serve.http.server import HttpServer

    cfg, params = tiny
    led = TenantLedger(max_inflight=1, max_series=20)
    engine = _engine(cfg, params, tenants=led)

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        host, port = srv.host, srv.port
        # a hostile header dies as a 400 before touching the engine
        st, _, body = await _post(
            host, port, {"prompt": [1, 2, 3], "max_tokens": 2},
            headers={"X-Tenant-Id": 'x"}evil'})
        assert st == 400 and b"disallowed characters" in body
        # X-Tenant-Id names the tenant on an accepted request
        st, _, _ = await _post(
            host, port, {"prompt": [5] * 6, "max_tokens": 3},
            headers={"X-Tenant-Id": "acme"})
        assert st == 200
        # the cap bounces the tenant's SECOND stream: hold one open
        st_a, _, reader_a, writer_a = None, None, None, None
        reader_a, writer_a = await asyncio.open_connection(host, port)
        hold = json.dumps({"prompt": [6] * 6, "max_tokens": 40,
                           "stream": True}).encode()
        writer_a.write(
            b"POST /v1/completions HTTP/1.1\r\n"
            + f"Host: {host}\r\nContent-Length: {len(hold)}\r\n".encode()
            + b"X-Tenant-Id: acme\r\n"
            + b"Content-Type: application/json\r\n\r\n" + hold)
        await writer_a.drain()
        assert int((await reader_a.readline()).split()[1]) == 200
        while True:  # wait for the stream's first SSE frame
            line = await reader_a.readline()
            if line.startswith(b"data: "):
                break
        st, hdrs, body = await _post(
            host, port, {"prompt": [7] * 6, "max_tokens": 2},
            headers={"X-Tenant-Id": "acme"})
        assert st == 429
        assert "retry-after" in hdrs
        assert b"rate_limit_error" in body
        assert b"in-flight cap" in body  # names the cap, not the queue
        # an uncapped peer tenant sails through
        st, _, _ = await _post(
            host, port, {"prompt": [8] * 6, "max_tokens": 2},
            headers={"X-Tenant-Id": "beta"})
        assert st == 200
        writer_a.close()
        deadline = asyncio.get_event_loop().time() + 20
        while (engine.scheduler.running or
               engine.scheduler.queue_depth) and \
                asyncio.get_event_loop().time() < deadline:
            await asyncio.sleep(0.02)
        # tenant-labeled series ride the one scrape
        st, prom = await asyncio.to_thread(http_get, host, port, "/metrics")
        assert st == 200
        text = prom.decode()
        assert 'llm_serve_tenant_requests_total{tenant="acme"' in text
        assert 'llm_serve_tenant_requests_total{tenant="beta"' in text
        assert 'llm_serve_tenant_throttled_total{tenant="acme"' in text
        # /debug/tenants: the full JSON breakdown
        st, body = await asyncio.to_thread(
            http_get, host, port, "/debug/tenants")
        assert st == 200
        dbg = json.loads(body)
        assert dbg["n_tenants"] >= 2
        assert dbg["tenants"]["acme"]["throttled"] == 1
        assert dbg["tenants"]["beta"]["requests"] == 1
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


def test_http_debug_tenants_404_when_off(tiny):
    from llm_np_cp_tpu.serve.http.client import http_get
    from llm_np_cp_tpu.serve.http.server import HttpServer

    cfg, params = tiny
    engine = _engine(cfg, params)  # no ledger

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        st, body = await asyncio.to_thread(
            http_get, srv.host, srv.port, "/debug/tenants")
        assert st == 404
        assert b"--tenants" in body
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=60))
