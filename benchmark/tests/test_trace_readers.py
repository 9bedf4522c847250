"""The readers PR 24 added, on two ticks recorded on the chip
(fixtures/v5e_tick_account.json): the device plane of a profile window cut
to two executions of the step, the recorder's dump for the same two ticks,
three request tracks, the set-up spans and the op map.

The readers are loaded the way the harness loads them; the fixture's dump
lies where a traced run keeps it, ``out/<workload>-<seed>/host_trace.json``
under a temporary ``tracefile.OUT``."""

import json
import sys
from pathlib import Path

import devtrace
import pytest
import run as harness

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "layers"))
import tracefile  # noqa: E402  (the way the readers import it)

BENCH = Path(__file__).resolve().parents[1]
FIXTURE = Path(__file__).parent / "fixtures" / "v5e_tick_account.json"


def load(path: Path = FIXTURE) -> dict:
    with open(path) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def bench_copy(tmp_path_factory):
    """(fixture, run record, reader loader) with the fixture's dump where
    a traced run leaves it."""
    fx = load()
    out = tmp_path_factory.mktemp("readers") / f"{fx['workload']}-{fx['seed']}"
    out.mkdir()
    (out / "host_trace.json").write_text(json.dumps(fx["dump"]))
    w0, w1 = fx["window"]
    run = dict(workload=fx["workload"], seed=fx["seed"], replicas=1,
               client={"window": [w0, w1]},
               host_trace=harness.load_host_trace(out / "host_trace.json", w0, w1),
               device_trace=devtrace.reduce(fx["trace"]))

    def read(name: str, record: dict | None = None):
        return harness.load_reader(BENCH / "layers" / name)(record or run)

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tracefile, "OUT", out.parent)
        tracefile._dumps.clear()
        yield fx, run, read
    tracefile._dumps.clear()


def phase_ms(fx: dict, name: str) -> float:
    durs = [ev["dur"] for ev in fx["dump"]["traceEvents"]
            if ev.get("cat") == "phase" and ev["name"] == name]
    assert len(durs) == 2
    return sum(durs) / 2 / 1e3


@pytest.mark.parametrize("metric,phase", [
    ("tick.pack_ms", "pack"), ("tick.h2d_ms", "h2d"),
    ("tick.dispatch_ms", "mixed_dispatch"), ("tick.deliver_ms", "deliver")])
def test_phase_readers_take_the_mean_over_dispatching_ticks(bench_copy, metric, phase):
    fx, run, read = bench_copy
    assert len(run["host_trace"]["ticks"]) == 2
    assert read(metric) == pytest.approx(phase_ms(fx, phase), rel=1e-9)
    assert read(metric) > 0.0


def test_cut_phases_add_up_to_the_tick_on_the_chip(bench_copy):
    fx, run, read = bench_copy
    names = ("admission", "draft", "grow", "plan", "pack", "h2d",
             "mixed_dispatch", "host_sync", "deliver", "account")
    assert sum(phase_ms(fx, n) for n in names) == pytest.approx(
        read("tick.wall_ms"), rel=2e-3)
    # the three that were one phase before the cut
    ticks = [ev for ev in fx["dump"]["traceEvents"] if ev["name"] == "tick"]
    h2d = [ev for ev in fx["dump"]["traceEvents"] if ev["name"] == "h2d"]
    assert all(ev["args"]["count"] == 16 and ev["args"]["bytes"] > 0 for ev in h2d)
    assert all(t["args"]["packed_width"] in (512, 768) for t in ticks)


def test_host_wait_and_context_come_from_the_tick_args(bench_copy):
    fx, run, read = bench_copy
    ticks = [ev for ev in fx["dump"]["traceEvents"] if ev["name"] == "tick"]
    want = sum(t["dur"] - t["args"]["host_sync_us"] - t["args"]["thread_cpu_us"]
               for t in ticks) / 2 / 1e3
    assert read("tick.host_wait_ms") == pytest.approx(want, rel=1e-9)
    assert read("tick.host_wait_ms") < read("tick.wall_ms")
    ctx = sum(t["args"]["context_tokens"] for t in ticks) / 2
    assert read("sched.context_tokens_per_tick") == pytest.approx(ctx)
    # 64 rows at a few hundred tokens each
    assert 64 * 64 < ctx < 64 * 900 and all(t["args"]["active_slots"] >= 60 for t in ticks)


def test_device_scope_shares_against_a_hand_sum(bench_copy):
    fx, run, read = bench_copy
    dt = run["device_trace"]
    assert dt["ticks"] == 2 and dt["busy_s"] > 0.02
    pool_s = sum(s for name, s in dt["ops_s"].items() if name.split(" ")[1] in (
        "bf16[28,1026,64,2,128]", "bf16[1026,64,2,128]"))
    pool = read("pool.move_share")
    # the pool-shaped operations by their shape, plus the scatter of kv_write
    assert pool >= 100.0 * pool_s / dt["busy_s"] - 1e-9
    assert pool <= 100.0 * pool_s / dt["busy_s"] + 3.0
    assert 20.0 < pool < 50.0
    mlp, proj, attributed = (read("mlp.time_share"), read("proj.time_share"),
                             read("step.attributed_share"))
    attn = harness.load_reader(BENCH / "layers" / "attn.time_share")(run)
    epi = harness.load_reader(BENCH / "layers" / "epilogue.time_share")(run)
    assert 10.0 < mlp < 30.0 and 3.0 < proj < 20.0
    assert attributed >= 90.0
    # scopes and pool do not overlap: with the kernels they stay under it
    assert pool + mlp + proj + attn + epi <= attributed + 1e-6
    assert attributed <= 100.0 + 1e-9


def test_an_ambiguous_name_is_nobodys(bench_copy):
    fx, run, read = bench_copy
    table = tracefile.op_table(run)
    before = read("step.attributed_share"), read("mlp.time_share")
    # the program found one mlp matmul meaning two things in two buckets
    ran = {name.rsplit(" ", 1)[0]: s for name, s in run["device_trace"]["ops_s"].items()}
    key = max((k for k, v in table.items() if v and v[0] == "mlp" and k in ran),
              key=ran.get)
    dump = json.loads(json.dumps(fx["dump"]))
    dump["otherData"]["op_map"][key] = None
    path = tracefile.OUT / f"{fx['workload']}-{fx['seed']}" / "host_trace.json"
    original = path.read_text()
    try:
        path.write_text(json.dumps(dump))
        tracefile._dumps.clear()
        assert tracefile.op_table(run)[key] is None
        assert read("step.attributed_share") < before[0]
        assert read("mlp.time_share") < before[1]
    finally:
        path.write_text(original)
        tracefile._dumps.clear()


def test_http_readers_follow_the_request_track(bench_copy):
    fx, run, read = bench_copy
    req = [ev for ev in fx["dump"]["traceEvents"] if ev.get("cat") == "request"]
    rids = sorted({ev["id"] for ev in req})
    assert len(rids) == 3

    def ts(rid, name, ph):
        return next(ev for ev in req if ev["id"] == rid and ev["name"] == name
                    and ev["ph"] == ph)

    accept = sorted(ts(r, "queued", "b")["ts"] - ts(r, "http", "b")["ts"] for r in rids)
    assert read("http.accept_to_queue_p50_ms") == pytest.approx(accept[1] / 1e3)
    first = sorted(ts(r, "first_write", "n")["ts"] - ts(r, "decode", "b")["ts"]
                   for r in rids)
    assert read("http.first_write_lag_p50_ms") == pytest.approx(first[1] / 1e3)
    means = sorted(ts(r, "stream_end", "n")["args"]["lag_mean_us"] for r in rids)
    got = read("http.write_lag_p95_ms")
    assert means[1] / 1e3 <= got <= means[2] / 1e3
    for r in rids:
        end = ts(r, "stream_end", "n")["args"]
        assert end["frames"] >= 384 and end["lag_mean_us"] <= end["lag_max_us"]
    # a window that holds no accept: nothing to read
    empty = dict(run, client={"window": [0.0, 1.0]})
    assert read("http.accept_to_queue_p50_ms", empty) is None


def test_setup_readers_read_the_spans(bench_copy):
    fx, run, read = bench_copy
    spans = {}
    for ev in fx["dump"]["traceEvents"]:
        if ev.get("cat") == "setup":
            spans.setdefault(ev["name"], []).append(ev)
    assert read("setup.engine_build_s") == pytest.approx(
        spans["engine_build"][0]["dur"] / 1e6)
    assert read("setup.warmup_s") == pytest.approx(spans["warmup"][0]["dur"] / 1e6)
    widths = [ev["args"]["width"] for ev in spans["warmup.bucket"]]
    assert widths == sorted(widths) and widths[-1] == 768
    assert sum(ev["dur"] for ev in spans["warmup.bucket"]) <= spans["warmup"][0]["dur"]
    assert {"load_place", "pool_alloc", "op_map", "listen"} <= set(spans)


NEW = ("tick.pack_ms", "tick.h2d_ms", "tick.dispatch_ms", "tick.deliver_ms",
       "tick.host_wait_ms", "sched.context_tokens_per_tick", "pool.move_share",
       "mlp.time_share", "proj.time_share", "step.attributed_share",
       "http.accept_to_queue_p50_ms", "http.first_write_lag_p50_ms",
       "http.write_lag_p95_ms", "setup.engine_build_s", "setup.warmup_s")


@pytest.mark.parametrize("metric", NEW)
def test_a_program_without_the_spans_reads_nothing(bench_copy, metric, tmp_path):
    """The parent of PR 24: uncut phases, no new tick args, no op map, no
    socket instants, no set-up spans - every new reader returns None and
    the line leaves the metric out; none raises."""
    fx, run, read = bench_copy
    old = load(Path(__file__).parent / "fixtures" / "v5e_two_ticks.json")
    ticks = [dict(start=s, dur_s=d, args=dict(prefill_tokens=0, decode_tokens=64,
                                              host_sync_us=30000.0, active_slots=64))
             for s, d in ((0.0, 0.05), (0.05, 0.05))]
    phases = [dict(name=n, start=s, dur_s=d) for s, d, n in old["phases"]]
    parent = dict(run, workload="no-such-run", host_trace=dict(ticks=ticks, phases=phases),
                  device_trace=devtrace.reduce(old["trace"]))
    assert read(metric, parent) is None
    assert read(metric, dict(parent, host_trace=None, device_trace=None)) is None


def test_every_new_metric_has_an_entry_a_reader_and_a_layer_of_perf_md():
    with open(BENCH.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    perf = (BENCH.parent / "PERF.md").read_text()
    for name in NEW:
        assert (BENCH / "layers" / f"{name}.py").exists(), name
        assert entries[name]["moves"] in e2e
        assert f"| {entries[name]['layer']} |" in perf, entries[name]["layer"]
        assert f"`{name}`" in perf, name
    # appended, nothing that was there moved: the old entries come first
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == list(NEW)
