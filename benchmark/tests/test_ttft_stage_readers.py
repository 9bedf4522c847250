"""A request's first token stage by stage, and the tick by its two kinds
(PR 53): ``layers/ttftstages.py`` and the thirteen readers that stand on it.

One recorded fixture, ``fixtures/v5e_ttft_stages.json``: the ticks around the
3 s profiler capture of a traced run of ``trinity-large-5l-ep8.longdoc-closed``
on the chip, the capture's annotations and device programs, the whole request
tracks of the requests that arrived in the 12 s before and during it, and the
run's two scrapes of the lane's counters.  Every reader is held against
arithmetic done here on the fixture's own events; a parent-shaped run (tracks
without the instants, ticks without ``lane_rows``, a scrape without the
counter) reads nothing; the rehearsal runs with the new entries.
"""

import json
import sys
from pathlib import Path

import pytest
import run as harness
import stats
import tiny_root
from test_rehearsal import run as rehearse

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "layers"))
import ticktimeline  # noqa: E402  (the way the readers import it)
import tracefile  # noqa: E402
import ttftstages  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
FIXTURE = Path(__file__).parent / "fixtures" / "v5e_ttft_stages.json"
# metric -> the two edges of the request track it lies between, percentile
STAGES = {
    "ttft.inbox_wait_p50_ms": ("enqueued", "queued", 50),
    "ttft.lane_wait_p50_ms": ("prefill", "lane", 50),
    "ttft.lane_wait_p95_ms": ("prefill", "lane", 95),
    "ttft.prefill_p50_ms": ("lane", "last_chunk", 50),
    "ttft.final_tick_p50_ms": ("last_chunk", "first_token", 50),
    "ttft.publish_lag_p50_ms": ("first_token", "decode", 50),
    "ttft.server_p50_ms": ("http", "first_write", 50),
}
KINDS = {"tick.prefill_wall_ms": "prefill", "tick.decode_wall_ms": "decode",
         "step.prefill_device_ms": "prefill", "step.decode_device_ms": "decode"}
NEW = (*STAGES, "ttft.prefill_ticks_p50", "sched.lane_busy_share", *KINDS)
EDGES = ("http", "enqueued", "queued", "prefill", "lane", "last_chunk",
         "first_token", "decode", "first_write")
BEGINS = ("http", "queued", "prefill", "decode")


def read(name: str, run: dict):
    return harness.load_reader(BENCH / "layers" / name)(run)


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        fx = json.load(f)
    profile = {k: ({int(a): b for a, b in v} if k != "modules" else v)
               for k, v in fx["profile"].items()}
    return fx, profile


def scrape(counters: dict) -> dict:
    return {"/metrics": {"text": "".join(
        f"llm_serve_{name} {value}\n" for name, value in counters.items()
        if value is not None)}}


@pytest.fixture()
def traced(recorded, tmp_path, monkeypatch):
    """A run record over the recorded ticks and tracks, the profile served
    from memory and the dump where a traced run keeps its own."""
    fx, profile = recorded
    epoch = fx["wall_epoch"]
    events = list(fx["events"])
    for t in fx["ticks"]:
        events.append(dict(name="tick", cat="tick", ph="X", tid=11,
                           ts=(t["start"] - epoch) * 1e6, dur=t["dur_s"] * 1e6,
                           args=t["args"]))
    out = tmp_path / f"{fx['workload']}-{fx['seed']}"
    out.mkdir()
    (out / "host_trace.json").write_text(json.dumps(
        dict(traceEvents=events, otherData=dict(wall_epoch=epoch))))
    monkeypatch.setattr(tracefile, "OUT", tmp_path)
    monkeypatch.setattr(ticktimeline, "profile_of", lambda run: profile)
    tracefile._dumps.clear()
    yield dict(workload=fx["workload"], seed=fx["seed"], replicas=1,
               client=dict(window=fx["window"], scrapes=dict(
                   start=scrape(fx["counters"]["start"]),
                   end=scrape(fx["counters"]["end"]))),
               host_trace=dict(ticks=fx["ticks"], phases=[]), device_trace=None)
    tracefile._dumps.clear()


def tracks(fx: dict) -> dict:
    """rid -> {edge: ts_us}, the FIRST of each name, of the requests whose
    ``http`` span began in the fixture's window: this file's own reading."""
    first: dict = {}
    for ev in fx["events"]:
        want = "b" if ev["name"] in BEGINS else "n"
        if ev["name"] in EDGES and ev["ph"] == want:
            first.setdefault(ev["id"], {}).setdefault(ev["name"], ev["ts"])
    w0, w1 = fx["window"]
    return {rid: tr for rid, tr in first.items() if "http" in tr
            and w0 <= fx["wall_epoch"] + tr["http"] / 1e6 < w1}


def test_the_recorded_tracks_are_whole_and_consecutive(recorded):
    fx, _ = recorded
    got = tracks(fx)
    assert len(got) >= 20
    whole = [tr for tr in got.values() if all(e in tr for e in EDGES)]
    assert len(whole) >= 0.8 * len(got)  # the rest were still on their way
    for tr in whole:
        ts = [tr[e] for e in EDGES]
        assert ts == sorted(ts), tr
        # the eight stages sum to the server's own TTFT, each request
        assert sum(b - a for a, b in zip(ts, ts[1:])) == pytest.approx(
            tr["first_write"] - tr["http"])
    # the long prompts of this cell wait for the lane and hold it for ticks
    assert max(tr["lane"] - tr["prefill"] for tr in whole) > 50e3
    assert stats.percentile([tr["last_chunk"] - tr["lane"] for tr in whole], 50) > 50e3


@pytest.mark.parametrize("name", STAGES)
def test_a_stage_reader_is_the_percentile_of_its_two_edges(traced, recorded, name):
    fx, _ = recorded
    a, b, q = STAGES[name]
    vals = [tr[b] - tr[a] for tr in tracks(fx).values() if a in tr and b in tr]
    assert len(vals) >= 20
    assert read(name, traced) == pytest.approx(stats.percentile(vals, q) / 1e3)
    assert read(name, traced) >= 0.0


def test_the_stage_medians_on_the_chip_are_of_the_size_the_ticks_say(traced):
    got = {name: read(name, traced) for name in NEW}
    wall = got["tick.prefill_wall_ms"]
    # one tick from its plan on: no longer than a prompt tick, most of one
    assert 0.5 * got["tick.decode_wall_ms"] < got["ttft.final_tick_p50_ms"] < 1.5 * wall
    # the next tick's admission ... dispatch: a few ms, never a whole tick
    assert 0.2 < got["ttft.publish_lag_p50_ms"] < wall
    # the wait for the running tick to end: under one prompt tick
    assert 0.0 < got["ttft.inbox_wait_p50_ms"] < wall
    assert got["ttft.lane_wait_p50_ms"] <= got["ttft.lane_wait_p95_ms"]
    # the lane's ticks: the prompt's stage is about its ticks x a prompt tick
    ticks = got["ttft.prefill_ticks_p50"]
    assert ticks >= 2
    assert got["ttft.prefill_p50_ms"] < ticks * 1.5 * wall
    assert got["ttft.server_p50_ms"] > (got["ttft.prefill_p50_ms"]
                                        + got["ttft.final_tick_p50_ms"])


def test_prefill_ticks_is_the_median_of_the_last_chunks_count(traced, recorded):
    fx, _ = recorded
    ids = set(tracks(fx))
    seen, vals = set(), []
    for ev in fx["events"]:
        if ev["name"] == "last_chunk" and ev["id"] in ids and ev["id"] not in seen:
            seen.add(ev["id"])
            vals.append(ev["args"]["prefill_ticks"])
            assert ev["args"]["lane_ticks"] <= ev["args"]["prefill_ticks"]
            assert ev["args"]["seq"] > 0
    assert read("ttft.prefill_ticks_p50", traced) == pytest.approx(
        stats.percentile(vals, 50))


def by_kind(ticks: list[dict]) -> dict:
    out: dict = {"prefill": [], "decode": [], "fair": []}
    for t in ticks:
        a = t["args"]
        if a.get("prefill_tokens", 0) + a.get("decode_tokens", 0) == 0:
            continue
        out["prefill" if a["lane_rows"] > 0 else
            "fair" if a["prefill_tokens"] else "decode"].append(t)
    return out


def test_the_tick_by_its_two_kinds(traced, recorded):
    fx, profile = recorded
    kinds = by_kind(fx["ticks"])
    assert len(kinds["prefill"]) >= 20 and len(kinds["decode"]) >= 20
    for name in ("tick.prefill_wall_ms", "tick.decode_wall_ms"):
        ts = kinds[KINDS[name]]
        assert read(name, traced) == pytest.approx(
            1e3 * sum(t["dur_s"] for t in ts) / len(ts))
    rows = ticktimeline.join(profile, fx["ticks"])
    for name in ("step.prefill_device_ms", "step.decode_device_ms"):
        mine = [r["program"][1] - r["program"][0] for r in rows
                if r["tick"] in kinds[KINDS[name]]]
        assert len(mine) >= 20
        assert read(name, traced) == pytest.approx(sum(mine) / len(mine) / 1e6)
    # a tick with a prompt aboard is another program, two to four times as long
    assert (read("step.prefill_device_ms", traced)
            > 1.5 * read("step.decode_device_ms", traced))
    assert read("step.decode_device_ms", traced) < read("tick.decode_wall_ms", traced)
    assert read("step.prefill_device_ms", traced) < read("tick.prefill_wall_ms", traced)
    # ... and a lane tick says what it handed out
    for t in kinds["prefill"]:
        assert 0 < t["args"]["lane_tokens"] <= t["args"]["prefill_tokens"]
    assert all(t["args"]["lane_tokens"] == 0 for t in kinds["decode"] + kinds["fair"])


@pytest.mark.parametrize("name", KINDS)
def test_fewer_than_twenty_ticks_of_a_kind_read_nothing(traced, recorded, name):
    fx, _ = recorded
    mine = by_kind(fx["ticks"])[KINDS[name]]
    few = {id(t) for t in mine[19:]}  # all but 19 of this kind go
    ticks = [t for t in fx["ticks"] if id(t) not in few]
    assert read(name, dict(traced, host_trace=dict(ticks=ticks, phases=[]))) is None
    assert ttftstages.MIN_TICKS == 20


def test_lane_busy_share_is_the_ratio_of_the_counters_deltas(traced, recorded):
    fx, _ = recorded
    c0, c1 = fx["counters"]["start"], fx["counters"]["end"]
    want = 100.0 * (c1["lane_ticks_total"] - c0["lane_ticks_total"]) / (
        c1["ticks_total"] - c0["ticks_total"])
    assert read("sched.lane_busy_share", traced) == pytest.approx(want)
    assert 0.0 < want < 100.0


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_gives_nothing_on_a_parent_shaped_run(traced, recorded, name):
    fx, _ = recorded
    data = tracefile.dump(traced)
    gone = ("enqueued", "lane", "last_chunk", "first_token")
    data["traceEvents"] = [e for e in data["traceEvents"] if e["name"] not in gone]
    strip = ("lane_rows", "lane_tokens")
    ticks = [dict(t, args={k: v for k, v in t["args"].items() if k not in strip})
             for t in fx["ticks"]]
    old = scrape({"ticks_total": 1000})
    parent = dict(traced, host_trace=dict(ticks=ticks, phases=[]),
                  client=dict(traced["client"], scrapes=dict(start=old, end=old)))
    # (``ttft.server_p50_ms`` too, whose two edges the parent has: it is
    # read where the stamps between them are)
    assert read(name, parent) is None
    if name != "sched.lane_busy_share":
        assert read(name, dict(parent, host_trace=None)) is None or name in STAGES


def test_every_new_metric_has_an_entry_a_reader_and_a_row_of_perf_md():
    with open(BENCH.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    cells = [w["name"] for w in bench["workloads"]]
    perf = (BENCH.parent / "PERF.md").read_text()
    for name in NEW:
        assert ((BENCH / "layers" / f"{name}.py").exists()
                or (BENCH / "layers" / f"{name}.json").exists()), name
        entry = entries[name]
        assert set(entry) <= {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
        assert entry["moves"] in e2e and entry["better"] == "lower"
        assert f"| {entry['layer']} |" in perf, entry["layer"]
        assert f"`{name}`" in perf, name
        if name in KINDS:
            # a kind the capture may hold fewer than 20 ticks of: listed
            # only where the builder's traced runs found it
            assert set(entry["workloads"]) <= set(cells) and entry["workloads"]
            assert entry["moves"] == ("out_tok_s" if KINDS[name] == "prefill"
                                      else "tpot_p50_s")
        else:
            assert "workloads" not in entry  # every cell reports them
            assert entry["moves"] == "ttft_p50_s"
    # appended, nothing that was there moved: these are the last entries
    names = [m["name"] for m in bench["per_layer"]]
    assert names[-len(NEW):] == [
        "ttft.inbox_wait_p50_ms", "ttft.lane_wait_p50_ms", "ttft.lane_wait_p95_ms",
        "ttft.prefill_p50_ms", "ttft.prefill_ticks_p50", "ttft.final_tick_p50_ms",
        "ttft.publish_lag_p50_ms", "ttft.server_p50_ms", "sched.lane_busy_share",
        "tick.prefill_wall_ms", "tick.decode_wall_ms", "step.prefill_device_ms",
        "step.decode_device_ms"]
    assert "ttftstages" not in entries  # the helper is no metric


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    root, workload = tiny_root.make(tmp_path_factory.mktemp("pr53"))
    # the kind readers list their cells: let them run in the tiny one too
    path = root / "BENCHMARK.json"
    bench = json.loads(path.read_text())
    for m in bench["per_layer"]:
        if m["name"] in KINDS:
            m["workloads"].append(workload)
    path.write_text(json.dumps(bench, indent=1))
    _, result = rehearse(root, workload, "--trace", "1")
    return result["rehearsal_metrics"]


@pytest.mark.parametrize("name", [n for n in NEW if not n.startswith("step.")])
def test_the_rehearsal_reads_the_programs_own_stages(rehearsal, name):
    if name == "tick.prefill_wall_ms" and name not in rehearsal:
        pytest.skip("the 3 s window held fewer than 20 lane ticks")
    assert name in rehearsal, sorted(rehearsal)
    assert rehearsal[name]["value"] >= 0.0


@pytest.mark.parametrize("name", ["step.prefill_device_ms", "step.decode_device_ms"])
def test_the_device_side_is_left_out_off_the_chip(rehearsal, name):
    # a CPU profile holds the annotations with their seq and no device line
    assert name not in rehearsal
