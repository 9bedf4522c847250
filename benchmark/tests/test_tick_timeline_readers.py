"""The tick on one clock (PR 36): ``layers/ticktimeline.py`` and the nine
readers that stand on it.

Two fixtures: a timeline made by hand, two joined ticks of known gaps on a
device line that leads the host lines by a known amount; and four ticks
recorded on the chip (fixtures/v5e_tick_timeline.json), whose programs
"start" before the call that launches them.  A parent-shaped run (ticks
without ``seq``, a scrape without the counters) reads nothing, and the
rehearsal runs with the new entries.
"""

import json
import sys
from pathlib import Path

import pytest
import run as harness
import tiny_root
from test_rehearsal import run as rehearse

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "layers"))
import ticktimeline  # noqa: E402  (the way the readers import it)
import tracefile  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
FIXTURE = Path(__file__).parent / "fixtures" / "v5e_tick_timeline.json"
PROFILE_SIDE = ("tick.exposed_ms", "tick.wake_gap_ms", "tick.serial_ms",
                "tick.launch_gap_ms")
RECORDER_SIDE = ("tick.fetch_ms", "tick.host_bound_share", "tick.cpu_ms",
                 "host.gc_ms_per_tick")
NEW = PROFILE_SIDE + RECORDER_SIDE + ("http.loop_cpu_ms",)
MS = 1e6  # ns


def read(name: str, run: dict):
    return harness.load_reader(BENCH / "layers" / name)(run)


def tick(seq: int | None, start: float, **args) -> dict:
    base = dict(prefill_tokens=0, decode_tokens=64 if seq else 0,
                host_sync_us=6000.0, thread_cpu_us=4000.0)
    if seq is not None:
        base.update(seq=seq, device_wait_us=5400.0, device_done_at_sync=0)
    return dict(start=start, dur_s=0.012, args={**base, **args})


def by_hand(lead_ms: float = 0.0, runtime: bool = True) -> tuple[dict, list[dict]]:
    """Three dispatches 12 ms apart on the host's clock; each program starts
    0.5 ms after its dispatch annotation starts (0.1 ms after the runtime
    enqueues it), runs 9.8 ms, the runtime sees it done 0.2 ms later, the
    host sees it ready 0.3 ms after its end and has fetched it 0.7 ms after
    its end; the next dispatch starts 1.2 ms after that: exposed 2.4 = wake
    0.7 + serial 1.2 + launch 0.5.  The device line is written ``lead_ms``
    EARLY, as a v5e profile has it."""
    profile = dict(dispatch={}, host_sync={}, modules=[], enqueue={}, complete={})
    ticks = []
    for i, seq in enumerate((7, 8, 9)):
        d0 = i * 12.2 * MS
        p0, p1 = d0 + 0.5 * MS, d0 + 10.3 * MS
        profile["dispatch"][seq] = [d0, d0 + 0.4 * MS]
        profile["host_sync"][seq] = [d0 + 5.2 * MS, p1 + 0.7 * MS]
        profile["modules"].append([p0 - lead_ms * MS, p1 - lead_ms * MS, 100 + seq])
        if runtime:
            profile["enqueue"][100 + seq] = d0 + 0.4 * MS
            profile["complete"][100 + seq] = p1 + 0.2 * MS
        # ready 0.3 ms after the program's end: 5.4 ms into host_sync
        ticks.append(tick(seq, 1000.0 + i * 0.0122))
    return profile, ticks


@pytest.mark.parametrize("lead_ms", [0.0, 0.9, 2.1])
def test_two_ticks_of_known_gaps(lead_ms):
    profile, ticks = by_hand(lead_ms)
    rows = ticktimeline.join(profile, ticks)
    assert [r["seq"] for r in rows] == [7, 8]
    for r in rows:
        # the device's line alone, the host's lines alone: no clock crossed
        assert r["exposed"] == pytest.approx(2.4 * MS)
        assert r["serial"] == pytest.approx(1.2 * MS)
        # as read, the two gaps are off by the lead, in opposite directions
        assert r["wake_gap_raw"] == pytest.approx((0.7 + lead_ms) * MS)
        assert r["launch_gap_raw"] == pytest.approx((0.5 - lead_ms) * MS)
        # the lead lies between what causality allows: not less than the
        # enqueue says (0.1 ms before the start), not more than the
        # completion callbacks say (0.2 ms after the end)
        lo, hi = r["lead_bounds"]
        assert lo == pytest.approx((lead_ms - 0.1) * MS)
        assert hi == pytest.approx((lead_ms + 0.2) * MS)
        assert r["lead"] == pytest.approx((lead_ms + 0.05) * MS)
        # corrected by the middle of the bounds: right to half their distance
        assert r["wake_gap"] == pytest.approx(0.65 * MS)
        assert r["launch_gap"] == pytest.approx(0.55 * MS)
        assert abs(r["wake_gap"] - 0.7 * MS) <= (hi - lo) / 2 + 1e-6
        assert r["wake_gap"] + r["serial"] + r["launch_gap"] == pytest.approx(
            r["exposed"], abs=1e-3)


def test_without_the_runtimes_events_the_annotations_bound_the_lead():
    profile, ticks = by_hand(0.9, runtime=False)
    rows = ticktimeline.join(profile, ticks)
    lo, hi = rows[0]["lead_bounds"]
    # no start before the dispatch annotation's (0.5 ms), no end after the
    # host saw it ready (host_sync + device_wait_us: 0.3 ms after the end)
    assert lo == pytest.approx((0.9 - 0.5) * MS)
    assert hi == pytest.approx((0.9 + 0.3) * MS)
    for r in rows:
        assert r["wake_gap"] + r["serial"] + r["launch_gap"] == pytest.approx(
            r["exposed"], abs=1e-3)
    # a tick without device_wait_us: the annotation's end bounds it
    for t in ticks:
        del t["args"]["device_wait_us"]
    assert ticktimeline.join(profile, ticks)[0]["lead_bounds"][1] == pytest.approx(
        (0.9 + 0.7) * MS)


def drop(profile: dict, key: str, n: int) -> dict:
    return {**profile, key: {k: v for k, v in profile[key].items() if k != n}}


def test_a_tick_the_profile_or_the_recorder_missed_is_dropped_not_guessed():
    profile, ticks = by_hand(0.9)
    seqs = lambda p, t: [r["seq"] for r in ticktimeline.join(p, t)]  # noqa: E731
    assert seqs(profile, ticks) == [7, 8]
    # the profile did not see the successor's dispatch, or this tick's fetch
    assert seqs(drop(profile, "dispatch", 9), ticks) == [7]
    assert seqs(drop(profile, "host_sync", 8), ticks) == [7]
    # ... or a program (the capture began after it started)
    assert seqs({**profile, "modules": profile["modules"][1:]}, ticks) == [8]
    # the recorder's ring lost the tick
    assert seqs(profile, [t for t in ticks if t["args"]["seq"] != 8]) == []
    # a tick that dispatched nothing between two dispatches: the tick thread
    # slept on its queue, that is not the host's work
    idle = tick(None, 1000.0 + 0.0122 * 1.5)
    assert seqs(profile, ticks + [idle]) == [7]
    # dispatch numbers with a hole (a restart, another engine's ticks)
    assert seqs(profile, [ticks[0], ticks[2]]) == []
    assert ticktimeline.join(dict(profile, dispatch={}), ticks) == []


@pytest.fixture(scope="module")
def recorded():
    with open(FIXTURE) as f:
        fx = json.load(f)
    profile = {k: ({int(a): b for a, b in v} if k != "modules" else v)
               for k, v in fx["profile"].items()}
    return fx, profile


def test_on_the_chip_the_device_line_leads_and_the_parts_still_sum(recorded):
    fx, profile = recorded
    rows = ticktimeline.join(profile, fx["ticks"])
    assert len(rows) == 3 and len(fx["ticks"]) == 4
    for r in rows:
        # as read, the program starts before the call that launches it
        assert r["launch_gap_raw"] < 0.0 < r["launch_gap"]
        assert 0.0 < r["wake_gap"] < r["wake_gap_raw"]
        assert r["wake_gap"] + r["serial"] + r["launch_gap"] == pytest.approx(
            r["exposed"], abs=1e-3)  # to a millionth of the issue's 0.01 ms
        assert r["wake_gap_raw"] + r["serial"] + r["launch_gap_raw"] == pytest.approx(
            r["exposed"], abs=1e-3)
        assert 2.0 * MS < r["exposed"] < 4.0 * MS
        assert 1.0 * MS < r["serial"] < 1.6 * MS
    lo, hi = rows[0]["lead_bounds"]
    assert 0.6 * MS < lo < hi < 1.2 * MS
    # the runtime's events bound the lead tighter than the annotations do
    bare = ticktimeline.join({**profile, "enqueue": {}, "complete": {}}, fx["ticks"])
    lo0, hi0 = bare[0]["lead_bounds"]
    assert lo0 < lo and hi < hi0 + 1e-6 and hi - lo < 0.6 * (hi0 - lo0)


@pytest.fixture()
def traced(recorded, tmp_path, monkeypatch):
    """A run record over the recorded ticks, the profile served from memory
    and a dump with collector slices where a traced run keeps its own."""
    fx, profile = recorded
    w0 = fx["ticks"][0]["start"] - 1.0
    epoch = w0 - 5.0
    us = lambda t: (t - epoch) * 1e6  # noqa: E731
    events = []
    for t in fx["ticks"]:
        events.append(dict(name="tick", cat="tick", ph="X", tid=11, ts=us(t["start"]),
                           dur=t["dur_s"] * 1e6, args=t["args"]))
    events.append(dict(name="first_write", cat="request", ph="n", tid=22, id=1,
                       ts=us(fx["ticks"][0]["start"])))
    for tid, ts, dur in ((11, us(fx["ticks"][1]["start"]) + 50.0, 300.0),   # tick thread
                         (22, us(fx["ticks"][2]["start"]) + 10.0, 100.0),   # loop thread
                         (33, us(fx["ticks"][2]["start"]) + 20.0, 9000.0),  # another thread
                         (11, us(w0) - 1e6, 5000.0)):                       # before the window
        events.append(dict(name="gc", cat="gc", ph="X", tid=tid, ts=ts, dur=dur,
                           args=dict(generation=0, collected=0, within="deliver")))
    out = tmp_path / f"{fx['workload']}-{fx['seed']}"
    out.mkdir()
    (out / "host_trace.json").write_text(json.dumps(
        dict(traceEvents=events, otherData=dict(wall_epoch=epoch))))
    monkeypatch.setattr(tracefile, "OUT", tmp_path)
    monkeypatch.setattr(ticktimeline, "profile_of", lambda run: profile)
    tracefile._dumps.clear()
    scrape = lambda loop, ticks: {"/metrics": {"text": (  # noqa: E731
        f"llm_serve_loop_thread_cpu_seconds_total {loop}\n"
        f"llm_serve_ticks_total {ticks}\n")}}
    yield dict(workload=fx["workload"], seed=fx["seed"], replicas=1,
               client=dict(window=[w0, w0 + 51.0],
                           scrapes=dict(start=scrape(100.0, 1000), end=scrape(100.5, 1050))),
               host_trace=dict(ticks=fx["ticks"], phases=[]), device_trace=None)
    tracefile._dumps.clear()


def test_the_readers_on_the_recorded_ticks(traced, recorded):
    fx, profile = recorded
    rows = ticktimeline.join(profile, fx["ticks"])
    mean = lambda key: sum(r[key] for r in rows) / len(rows) / MS  # noqa: E731
    got = {name: read(name, traced) for name in NEW}
    assert got["tick.exposed_ms"] == pytest.approx(mean("exposed"))
    assert got["tick.serial_ms"] == pytest.approx(mean("serial"))
    assert got["tick.wake_gap_ms"] == pytest.approx(mean("wake_gap"))
    assert got["tick.launch_gap_ms"] == pytest.approx(mean("launch_gap"))
    # the acceptance criterion: the three sum to the first to 0.01 ms
    assert (got["tick.wake_gap_ms"] + got["tick.serial_ms"]
            + got["tick.launch_gap_ms"]) == pytest.approx(got["tick.exposed_ms"], abs=0.01)
    args = [t["args"] for t in fx["ticks"]]
    assert got["tick.fetch_ms"] == pytest.approx(
        sum(a["host_sync_us"] - a["device_wait_us"] for a in args) / 4 / 1e3)
    assert 0.3 < got["tick.fetch_ms"] < 1.0
    assert got["tick.host_bound_share"] == pytest.approx(
        100.0 * sum(a["device_done_at_sync"] for a in args) / 4)
    assert got["tick.cpu_ms"] == pytest.approx(
        sum(a["thread_cpu_us"] for a in args) / 4 / 1e3)
    # the tick thread's and the loop thread's slices of the window, over 4 ticks
    assert got["host.gc_ms_per_tick"] == pytest.approx((300.0 + 100.0) / 1e3 / 4)
    # 0.5 s of the loop's CPU over 50 ticks between the two scrapes
    assert got["http.loop_cpu_ms"] == pytest.approx(10.0)


def test_a_window_without_a_collection_reads_zero_not_nothing(traced):
    data = tracefile.dump(traced)
    data["traceEvents"] = [e for e in data["traceEvents"] if e.get("cat") != "gc"]
    assert read("host.gc_ms_per_tick", traced) == 0.0


@pytest.mark.parametrize("name", NEW)
def test_every_new_reader_gives_nothing_on_a_parent_shaped_run(traced, name):
    strip = ("seq", "device_wait_us", "device_done_at_sync")
    parent_ticks = [dict(t, args={k: v for k, v in t["args"].items() if k not in strip})
                    for t in traced["host_trace"]["ticks"]]
    scrape = {"/metrics": {"text": "llm_serve_ticks_total 1000\n"}}
    parent = dict(traced, host_trace=dict(ticks=parent_ticks, phases=[]),
                  client=dict(traced["client"], scrapes=dict(start=scrape, end=scrape)))
    assert read(name, parent) is None
    assert read(name, dict(parent, host_trace=None)) is None
    # the change's own run, untraced: no recorder, no profile
    assert read(name, dict(traced, host_trace=None)) is None or name == "http.loop_cpu_ms"


def test_the_seq_join_lays_the_recorder_on_the_profile():
    profile, ticks = by_hand(0.9)
    epoch, events = 1000.0, []
    for t in ticks:
        ts = (t["start"] - epoch) * 1e6
        events.append(dict(name="tick", cat="tick", ph="X", ts=ts, dur=12000.0,
                           args=t["args"]))
        events.append(dict(name="mixed_dispatch", cat="phase", ph="X", ts=ts + 900.0,
                           dur=400.0))
    # an idle tick's empty dispatch phase joins nothing
    events.append(dict(name="tick", cat="tick", ph="X", ts=5e7, dur=10.0, args={}))
    events.append(dict(name="mixed_dispatch", cat="phase", ph="X", ts=5e7, dur=0.0))
    offs = ticktimeline.seq_offsets_ns(profile, dict(traceEvents=events))
    # dispatch k starts at k x 12.2 ms in the profile, the recorder's phase
    # at k x 12.2 ms + 0.9 ms on its own clock: the same offset every tick
    assert len(offs) == 3
    assert offs == pytest.approx([-900e3] * 3, abs=1.0)


def test_every_new_metric_has_an_entry_a_reader_and_a_row_of_perf_md():
    with open(BENCH.parent / "BENCHMARK.json") as f:
        bench = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    e2e = {m["name"] for m in bench["end_to_end"]}
    perf = (BENCH.parent / "PERF.md").read_text()
    for name in NEW:
        assert ((BENCH / "layers" / f"{name}.py").exists()
                or (BENCH / "layers" / f"{name}.json").exists()), name
        assert entries[name]["moves"] in e2e
        assert f"| {entries[name]['layer']} |" in perf, entries[name]["layer"]
        assert f"`{name}`" in perf, name
        assert "workloads" not in entries[name]  # every cell reports them
    # appended, nothing that was there moved: these are the last entries
    names = [m["name"] for m in bench["per_layer"]]
    assert sorted(names[-len(NEW):]) == sorted(NEW)
    assert entries["http.loop_cpu_ms"]["moves"] == "tpot_p50_s"


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    root, workload = tiny_root.make(tmp_path_factory.mktemp("pr36"))
    _, result = rehearse(root, workload, "--trace", "1")
    return result["rehearsal_metrics"]


@pytest.mark.parametrize("name", RECORDER_SIDE + ("http.loop_cpu_ms",))
def test_the_rehearsal_reads_the_recorders_side(rehearsal, name):
    assert name in rehearsal, sorted(rehearsal)
    assert rehearsal[name]["value"] >= 0.0
    if name == "tick.host_bound_share":
        assert rehearsal[name]["value"] <= 100.0


@pytest.mark.parametrize("name", PROFILE_SIDE)
def test_the_profiles_side_is_left_out_off_the_chip(rehearsal, name):
    # a CPU profile holds the annotations with their seq and no device line
    assert name not in rehearsal
