"""The tick on one clock (PR 36): what the readers of the tick's timeline share.

Not a metric's reader (no metric has this name); like ``tracefile.py`` it
lies beside the readers, which put their directory on the path and
``import ticktimeline``.

The program numbers its mixed dispatches.  The number is tick arg ``seq`` of
the recorder's tick and metadata of the ``serve.mixed_dispatch`` and
``serve.host_sync`` annotations of the same tick, so three records of one
tick join without a fitted clock:

- the profile's host annotations (``serve.mixed_dispatch``: the jitted call;
  ``serve.host_sync``: the wait for the program and the fetch),
- the profile's device line ``XLA Modules`` (one event per program execution),
- the recorder's tick, for its args.

``rows`` yields one row per dispatching tick k of the profile window whose
successor k + 1 the profile saw too, with what the device waited for between
the two programs::

    program k ends  ->  host_sync k ends  ->  dispatch k+1 starts  ->  program k+1 starts
         wake gap            serial host             launch gap

The three parts sum to the exposed host by construction.  A tick whose
neighbour the profile did not see, or that is followed by a tick that
dispatched nothing (the tick thread slept on its queue), is dropped, not
guessed; with several device planes the first is read.

**One profile, two clocks.**  The exposed host lies on the device's line
alone and the serial host on the host's lines alone; the wake gap and the
launch gap each cross from one to the other, and on a v5e the profile's
device line LEADS its host lines by an amount that differs from one capture
to the next (0.4 to 2.3 ms over PR 36's runs: programs "start" before the
call that launches them).  ``device_lead`` bounds the lead by causality - a
program cannot start before the runtime enqueues it (``DoEnqueueProgram``,
joined to the device's execution by ``run_id``; failing that, before its
``serve.mixed_dispatch`` annotation starts) nor end after the runtime has
run its completion callbacks (``CompleteCallbacks``; failing that, after the
host saw the result ready) - and the two gaps are corrected by the middle of
the bounds; half their distance is the error of the split (PERF.md §6).

Every function returns None / nothing on a program without ``seq`` (the
parent of PR 36): the metric is then left out of the line.

    python benchmark/layers/ticktimeline.py <workload> <seed> [rows.json]

prints, for a traced run kept under ``benchmark/out``, the parts with their
quantiles, the device line's lead with its bounds, the recorder-to-profile
offset the ``seq`` join gives with its spread (that clock's error), the
offset ``devtrace.align`` fits on the same run, and the idle time by host
phase under each of the two.
"""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside
sys.path.insert(0, str(Path(__file__).parents[1]))  # devtrace.py, stats.py
import devtrace  # noqa: E402
import stats  # noqa: E402
import tracefile  # noqa: E402

DISPATCH = devtrace.TICK_ANNOTATION
HOST_SYNC = "serve.host_sync"
# the TPU runtime's own host events around one execution (stat ``run_id``,
# which the device's ``XLA Modules`` event of that execution carries too):
# the launch thread hands the program to the hardware; the completion thread
# has seen it done
ENQUEUE = "DoEnqueueProgram"
COMPLETE = "CompleteCallbacks"
PARTS = ("wake_gap", "serial", "launch_gap")
_profiles: dict[str, dict | None] = {}


# ----------------------------------------------------------------------
# the profile, as plain data
# ----------------------------------------------------------------------

def read_profile(path: str) -> dict:
    """{"dispatch": {seq: [start_ns, end_ns]}, "host_sync": {seq: [..]},
    "modules": [[start_ns, end_ns, run_id or None], ...] by start,
    "enqueue": {run_id: start_ns}, "complete": {run_id: start_ns}} of one
    ``.xplane.pb``: the two annotations that carry ``seq``, the first
    device plane's program executions, and the runtime's own host events
    around an execution where the profile has them."""
    from jax.profiler import ProfileData

    out: dict = {"dispatch": {}, "host_sync": {}, "modules": [],
                 "enqueue": {}, "complete": {}}
    keep = {DISPATCH: (out["dispatch"], "seq"), HOST_SYNC: (out["host_sync"], "seq"),
            ENQUEUE: (out["enqueue"], "run_id"), COMPLETE: (out["complete"], "run_id")}
    have_device = False
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            if have_device:
                continue
            for line in plane.lines:
                if line.name == devtrace.MODULES_LINE:
                    out["modules"] = sorted(
                        [float(ev.start_ns), float(ev.start_ns + ev.duration_ns),
                         next((v for k, v in ev.stats if k == "run_id"), None)]
                        for ev in line.events)
                    have_device = bool(out["modules"])
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name not in keep:
                    continue
                into, key = keep[ev.name]
                n = next((v for k, v in ev.stats if k == key), None)
                if n is None:
                    continue
                span = [float(ev.start_ns), float(ev.start_ns + ev.duration_ns)]
                into[int(n)] = span if key == "seq" else span[0]
    return out


def profile_of(run: dict) -> dict | None:
    """The traced run's own profile, or None (no profile, or no ``seq``)."""
    found = devtrace.find_xplane(
        str(tracefile.OUT / f"{run['workload']}-{run['seed']}" / "profile"))
    if found is None:
        return None
    if found not in _profiles:
        try:
            got = read_profile(found)
        except (OSError, ValueError, ImportError):
            got = None
        _profiles[found] = got if got and got["dispatch"] else None
    return _profiles[found]


# ----------------------------------------------------------------------
# the join
# ----------------------------------------------------------------------

def _programs(profile: dict) -> dict[int, list[float]]:
    """seq -> [start_ns, end_ns] of the device program(s) its dispatch
    launched: an execution belongs to the dispatch in whose interval
    [dispatch start, next dispatch start) its MIDPOINT lies (a program ends
    before the next dispatch starts: the host fetched its result first)."""
    from bisect import bisect_right

    seqs = sorted(profile["dispatch"])
    starts = [profile["dispatch"][s][0] for s in seqs]
    out: dict[int, list[float]] = {}
    for m0, m1, *_ in profile["modules"]:
        i = bisect_right(starts, (m0 + m1) / 2) - 1
        if i < 0:
            continue
        span = out.setdefault(seqs[i], [m0, m1])
        span[0], span[1] = min(span[0], m0), max(span[1], m1)
    return out


def join(profile: dict, ticks: list[dict]) -> list[dict]:
    """One row per dispatching tick k that the profile and the recorder
    both saw whole, with its successor: times in ns as the profile has them
    (``dispatch``, ``program`` [start, end], ``host_sync`` [start, end], and
    ``next_dispatch`` / ``next_program`` starts), the recorder's tick as
    ``tick``, and in ns ``exposed``, ``serial`` and the two gaps that cross
    the profile's clocks: as read (``wake_gap_raw``, ``launch_gap_raw``) and
    corrected by the device line's lead (``wake_gap``, ``launch_gap``;
    ``lead``, and its bounds ``lead_bounds``, are the same in every row)."""
    ticks = sorted(ticks, key=lambda t: t["start"])
    at = {t["args"]["seq"]: i for i, t in enumerate(ticks) if "seq" in t["args"]}
    programs = _programs(profile)
    rows = []
    for seq in sorted(profile["dispatch"]):
        nxt, i = seq + 1, at.get(seq)
        if (i is None or i + 1 >= len(ticks)
                or ticks[i + 1]["args"].get("seq") != nxt
                or seq not in profile["host_sync"]
                or nxt not in profile["dispatch"]
                or seq not in programs or nxt not in programs):
            continue
        sync_end = profile["host_sync"][seq][1]
        next_dispatch = profile["dispatch"][nxt][0]
        rows.append(dict(
            seq=seq, dispatch=profile["dispatch"][seq][0],
            program=programs[seq], host_sync=profile["host_sync"][seq],
            next_dispatch=next_dispatch, next_program=programs[nxt][0],
            tick=ticks[i], exposed=programs[nxt][0] - programs[seq][1],
            serial=next_dispatch - sync_end,
            wake_gap_raw=sync_end - programs[seq][1],
            launch_gap_raw=programs[nxt][0] - next_dispatch))
    if rows:
        lo, hi = device_lead(profile, rows)
        lead = (lo + hi) / 2
        for row in rows:
            row.update(lead=lead, lead_bounds=[lo, hi],
                       wake_gap=row["wake_gap_raw"] - lead,
                       launch_gap=row["launch_gap_raw"] + lead)
    return rows


def device_lead(profile: dict, rows: list[dict]) -> tuple[float, float]:
    """Bounds (ns) on how far the profile's device line leads its host
    lines, from causality over the window.  At least: no program starts
    before the runtime enqueues it, nor before the annotation around its
    launch starts.  At most: no program ends after the runtime ran its
    completion callbacks, nor after the host saw the result ready
    (``serve.host_sync`` start + tick arg ``device_wait_us``; the
    annotation's end where the tick has no such arg)."""
    lo = [r["next_dispatch"] - r["next_program"] for r in rows]
    lo += [r["dispatch"] - r["program"][0] for r in rows]
    hi = [r["host_sync"][0] + r["tick"]["args"]["device_wait_us"] * 1e3
          - r["program"][1] if "device_wait_us" in r["tick"]["args"]
          else r["host_sync"][1] - r["program"][1] for r in rows]
    for m0, m1, *rest in profile["modules"]:
        run_id = rest[0] if rest else None
        if run_id in profile.get("enqueue", {}):
            lo.append(profile["enqueue"][run_id] - m0)
        if run_id in profile.get("complete", {}):
            hi.append(profile["complete"][run_id] - m1)
    return max(lo), min(hi)


def rows(run: dict) -> list[dict]:
    ht, profile = run.get("host_trace"), None
    if ht and any("seq" in t["args"] for t in ht["ticks"]):
        profile = profile_of(run)
    return join(profile, ht["ticks"]) if profile else []


def part_mean_ms(run: dict, part: str) -> float | None:
    """Mean of one part (or ``exposed``) over the rows, in ms."""
    got = rows(run)
    return sum(r[part] for r in got) / len(got) / 1e6 if got else None


# ----------------------------------------------------------------------
# the recorder's side alone
# ----------------------------------------------------------------------

def numbered_ticks(run: dict) -> list[dict]:
    """The window's dispatching ticks of a program that numbers them."""
    return [t for t in tracefile.dispatching_ticks(run) if "seq" in t["args"]]


def tick_mean(run: dict, value) -> float | None:
    vals = [v for v in map(value, numbered_ticks(run)) if v is not None]
    return sum(vals) / len(vals) if vals else None


def gc_ms_per_tick(run: dict) -> float | None:
    """Time of the window's ``cat: "gc"`` slices on the tick thread and on
    the loop thread (the one that stamps ``first_write``), over the window's
    dispatching ticks."""
    ticks, data = numbered_ticks(run), tracefile.dump(run)
    if not ticks or not data:
        return None
    epoch = data["otherData"]["wall_epoch"]
    w0, w1 = run["client"]["window"]
    threads, spent = set(), 0.0
    for ev in data["traceEvents"]:
        if ev.get("name") in ("tick", "first_write"):
            threads.add(ev["tid"])
    for ev in data["traceEvents"]:
        if (ev.get("cat") == "gc" and ev["tid"] in threads
                and w0 <= epoch + ev["ts"] / 1e6 < w1):
            spent += ev["dur"]
    return spent / 1e3 / len(ticks)


# ----------------------------------------------------------------------
# the two clocks (a diagnostic: python ticktimeline.py <workload> <seed>)
# ----------------------------------------------------------------------

def seq_offsets_ns(profile: dict, dump: dict) -> list[float]:
    """(annotation start on the profile's clock) - (the recorder's
    ``mixed_dispatch`` phase start on the recorder's clock, ns since its
    epoch), one per tick both saw: their median (less the epoch) lays the
    wall clock on the profile, their spread is that clock's error."""
    out, seq = [], None
    for ev in dump["traceEvents"]:
        if ev.get("ph") != "X":
            continue
        if ev["name"] == "tick":  # a tick's phases follow it in the dump
            seq = ev.get("args", {}).get("seq")
        elif (ev.get("cat") == "phase" and ev["name"] == "mixed_dispatch"
              and seq in profile["dispatch"]):
            out.append(profile["dispatch"][seq][0] - ev["ts"] * 1e3)
    return out


def _quantiles(vals: list[float], scale: float = 1.0) -> dict:
    return {f"p{q}": stats.percentile(vals, q) / scale
            for q in (0, 5, 25, 50, 75, 95, 100)} if vals else {}


def main(argv: list[str]) -> int:
    import json

    import run as harness

    workload, seed = argv[0], int(argv[1])
    save = argv[2] if len(argv) > 2 else None  # the joined rows, as JSON
    out_dir = tracefile.OUT / f"{workload}-{seed}"
    with open(out_dir / "client.json") as f:
        client = json.load(f)
    w0, w1 = client["window"]
    host_trace = harness.load_host_trace(out_dir / "host_trace.json", w0, w1)
    run = dict(workload=workload, seed=seed, client=client, host_trace=host_trace)
    profile, dump = profile_of(run), tracefile.dump(run)
    if not profile or not dump:
        print(json.dumps({"error": "no profile with seq, or no dump"}))
        return 1
    got = rows(run)
    report: dict = {"workload": workload, "seed": seed, "rows": len(got),
                    "dispatches_in_profile": len(profile["dispatch"]),
                    "programs_in_profile": len(profile["modules"])}
    lead = got[0]["lead_bounds"] if got else [0.0, 0.0]
    report["device_lead_ms"] = dict(
        low=lead[0] / 1e6, high=lead[1] / 1e6, used=sum(lead) / 2e6,
        half_width=(lead[1] - lead[0]) / 2e6,
        runtime_events=[len(profile["enqueue"]), len(profile["complete"])])
    for part in PARTS + ("exposed", "wake_gap_raw", "launch_gap_raw"):
        report[part + "_ms"] = dict(
            mean=sum(r[part] for r in got) / max(len(got), 1) / 1e6,
            **_quantiles([r[part] for r in got], 1e6))
    # where the program's end lies against the moment the host learnt of it
    # (host_sync's start + device_wait_us): negative = the device's clock
    # runs ahead of the host's inside the profile
    ready = [r["host_sync"][0] + r["tick"]["args"]["device_wait_us"] * 1e3
             - r["program"][1] for r in got]
    report["ready_after_program_end_raw_ms"] = _quantiles(ready, 1e6)
    report["fetch_after_ready_ms"] = _quantiles(
        [r["host_sync"][1] - (r["program"][1] + d) for r, d in zip(got, ready)], 1e6)
    report["host_bound_rows"] = sum(
        r["tick"]["args"]["device_done_at_sync"] for r in got)
    offs = seq_offsets_ns(profile, dump)
    q = _quantiles(offs)
    seq_shift = q["p50"] - dump["otherData"]["wall_epoch"] * 1e9
    report["seq_offset"] = dict(
        ticks=len(offs), median_ns=seq_shift, iqr_us=(q["p75"] - q["p25"]) / 1e3,
        p5_to_p95_us=(q["p95"] - q["p5"]) / 1e3,
        range_us=(q["p100"] - q["p0"]) / 1e3)
    # the harness's fit, on the same run: run.load_device_trace's steps
    trace = devtrace.read_xplane(devtrace.find_xplane(str(out_dir / "profile")))
    reduced = devtrace.reduce(trace)
    ann = sorted(s + d for p in trace["planes"] if not p["name"].startswith("/device:")
                 for ln in p["lines"] for n, s, d in ln["events"] if n == DISPATCH)
    p0, p1 = [(x - seq_shift) / 1e9 for x in reduced["window_ns"]]
    every = harness.load_host_trace(out_dir / "host_trace.json", 0.0, 1e12)
    disp = sorted(p["start"] + p["dur_s"] for p in every["phases"]
                  if p["name"] == "mixed_dispatch" and p0 - 1.0 <= p["start"] <= p1 + 1.0)
    fit = devtrace.align(ann, [d * 1e9 for d in disp])
    report["fitted_offset"] = dict(
        ns=fit, minus_seq_us=None if fit is None else (fit - seq_shift) / 1e3)
    for name, shift in (("seq", seq_shift), ("fitted", fit)):
        if shift is None:
            continue
        named = [(p["start"] * 1e9 + shift, (p["start"] + p["dur_s"]) * 1e9 + shift,
                  p["name"]) for p in every["phases"]]
        report[f"idle_gaps_{name}"] = devtrace.name_gaps(reduced["gaps_ns"], named)
    report["idle_share"] = reduced["idle_share"]
    if save:
        with open(save, "w") as f:
            json.dump(dict(profile={k: (sorted(v.items()) if isinstance(v, dict) else v)
                                    for k, v in profile.items()},
                           rows=got), f)
    print(json.dumps(report, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
