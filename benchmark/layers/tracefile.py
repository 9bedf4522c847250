"""What the readers of the program's own account of itself share (PR 24).

Not a metric's reader (no metric has this name).  The harness loads a
reader by its path under the data root, which in a rehearsal is a temporary
copy, so the tick-phase, tick-argument, device-scope, request-track and
set-up readers put their own directory on the path and ``import tracefile``:
the copy beside them, whose ``OUT`` is where that run kept its dump.

``run["host_trace"]`` holds the recorder's ticks and phases in the window.
What it does not hold - request-track events, set-up spans, the device-side
op map - is read here from the trace dump the traced run keeps,
``<benchmark>/out/<workload>-<seed>/host_trace.json``.

Every function returns None where the program has no such span, argument
or map (the parent of PR 24 has none of them): the metric is then left out
of the line, as the harness does for any reader that finds nothing.
"""

from __future__ import annotations

import json
from bisect import bisect_right
from pathlib import Path

import stats

OUT = Path(__file__).resolve().parents[1] / "out"  # where run.py keeps a run
_dumps: dict[str, dict | None] = {}


def dump(run: dict) -> dict | None:
    """The whole trace dump of this run, or None."""
    path = OUT / f"{run['workload']}-{run['seed']}" / "host_trace.json"
    key = str(path)
    if key not in _dumps:
        try:
            with open(path) as f:
                _dumps[key] = json.load(f)
        except (OSError, ValueError):
            _dumps[key] = None
    return _dumps[key]


# ----------------------------------------------------------------------
# the host side of the tick
# ----------------------------------------------------------------------

def dispatching_ticks(run: dict) -> list[dict]:
    ht = run.get("host_trace")
    if not ht:
        return []
    return [t for t in ht["ticks"]
            if t["args"].get("prefill_tokens", 0) + t["args"].get("decode_tokens", 0)]


def phase_mean_ms(run: dict, name: str, needs: str) -> float | None:
    """Mean length of phase ``name`` over the dispatching ticks of the
    window.  ``needs`` is a phase only the cut tick has: without it the
    name still means the uncut phase, and there is nothing to read."""
    ticks = dispatching_ticks(run)
    phases = run["host_trace"]["phases"] if ticks else []
    if not any(p["name"] == needs for p in phases):
        return None
    starts = sorted(t["start"] for t in ticks)
    ends = {t["start"]: t["start"] + t["dur_s"] for t in ticks}
    durs = []
    for p in phases:
        if p["name"] != name:
            continue
        i = bisect_right(starts, p["start"]) - 1
        if i >= 0 and p["start"] <= ends[starts[i]]:
            durs.append(p["dur_s"])
    return 1e3 * sum(durs) / len(durs) if durs else None


def tick_arg_mean(run: dict, arg: str) -> float | None:
    vals = [t["args"][arg] for t in dispatching_ticks(run) if arg in t["args"]]
    return sum(vals) / len(vals) if vals else None


def host_wait_ms(run: dict) -> float | None:
    """Mean of (tick - host_sync - the tick thread's own CPU time)."""
    vals = [t["dur_s"] * 1e3 - (t["args"].get("host_sync_us", 0.0)
                                + t["args"]["thread_cpu_us"]) / 1e3
            for t in dispatching_ticks(run) if "thread_cpu_us" in t["args"]]
    return sum(vals) / len(vals) if vals else None


# ----------------------------------------------------------------------
# the device side of the tick
# ----------------------------------------------------------------------

def op_table(run: dict) -> dict | None:
    """The dump's op map: {"%name shape": [scope, pool kind], or None
    where two buckets of the step disagree}.  The program writes the key
    as the profile's own short name without its opcode
    (``devtrace.short_name``), so an operation is looked up as it is."""
    return (dump(run) or {}).get("otherData", {}).get("op_map") or None


def scope_share(run: dict, pick) -> float | None:
    """Own time of the profile's operations for which ``pick(scope, pool
    kind)`` holds, in % of device busy time.  An operation the map does
    not know, or knows two ways, is nobody's."""
    dt, table = run.get("device_trace"), op_table(run)
    if not dt or not dt.get("busy_s") or table is None:
        return None
    hit = 0.0
    for name, seconds in dt["ops_s"].items():
        known = table.get(name.rsplit(" ", 1)[0])
        if known is not None and pick(*known):
            hit += seconds
    return 100.0 * hit / dt["busy_s"]


# ----------------------------------------------------------------------
# the request track and the set-up spans
# ----------------------------------------------------------------------

def request_tracks(run: dict) -> list[dict] | None:
    """One dict per request whose ``http`` span began in the window:
    {"begin": {span: ts_us}, "instant": {name: event}}."""
    data = dump(run)
    if not data:
        return None
    epoch = data.get("otherData", {}).get("wall_epoch")
    if epoch is None:
        return None
    tracks: dict = {}
    for ev in data["traceEvents"]:
        if ev.get("cat") != "request" or ev.get("ph") not in ("b", "n"):
            continue
        tr = tracks.setdefault(ev["id"], {"begin": {}, "instant": {}})
        kind = "begin" if ev["ph"] == "b" else "instant"
        tr[kind].setdefault(ev["name"], ev["ts"] if kind == "begin" else ev)
    w0, w1 = run["client"]["window"]
    return [tr for tr in tracks.values() if "http" in tr["begin"]
            and w0 <= epoch + tr["begin"]["http"] / 1e6 < w1]


def track_percentile_ms(run: dict, value, q: float) -> float | None:
    """``q``-th percentile over the window's requests of ``value(track)``
    (microseconds, or None to leave a request out)."""
    tracks = request_tracks(run)
    vals = [v for v in map(value, tracks or []) if v is not None]
    got = stats.percentile(vals, q)
    return None if got is None else got / 1e3


def setup_span_s(run: dict, name: str) -> float | None:
    """Summed length of the ``cat: "setup"`` spans called ``name`` (one
    per engine: replicas build and warm up one after another)."""
    data = dump(run)
    durs = [ev["dur"] for ev in (data or {}).get("traceEvents", [])
            if ev.get("cat") == "setup" and ev.get("ph") == "X"
            and ev["name"] == name]
    return sum(durs) / 1e6 if durs else None
