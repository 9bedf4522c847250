"""From the profiler's ``.xplane.pb`` to device metrics.

Two steps, so the arithmetic is testable on a small recorded fixture
without a chip: ``read_xplane`` turns the file into plain lists (device
planes whole, host planes only the program's ``serve.*`` annotations,
with operation names cut to result, shape and opcode), ``reduce`` turns
those lists into busy time, idle share, time per
operation, ticks and idle gaps.

A TPU device plane (``/device:TPU:n``) carries a line of XLA operations
(``XLA Ops``).  Operations nest (a ``while`` spans its body), so busy
time is the UNION of the line's intervals, and an operation's own time
is its interval minus what its children cover.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
TICK_ANNOTATION = "serve.mixed_dispatch"


def find_xplane(profile_dir: str) -> str | None:
    found = sorted(glob.glob(os.path.join(
        profile_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def read_xplane(path: str) -> dict:
    """{"planes": [{"name", "lines": [{"name", "events": [[name, start_ns,
    dur_ns], ...]}]}]} - device planes whole, host planes cut to the
    ``serve.*`` annotations."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            events = [[short_name(ev.name) if device else ev.name,
                       float(ev.start_ns), float(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name.startswith("serve.")]
            if events:
                lines.append(dict(name=line.name, events=events))
        if lines:
            planes.append(dict(name=plane.name, lines=lines))
    return dict(planes=planes)


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Sorted, merged (start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def short_name(name: str) -> str:
    """An XLA operation's trace name is its whole HLO line; keep the
    result name, the output's type and shape, and the opcode:
    ``%copy.89 bf16[28,1026,64,2,128] copy``."""
    m = re.match(r"(%[\w.\-]+) = (.*?)\s([\w\-]+)\(", re.sub(r"\{[^{}]*\}", "", name))
    if not m:
        return name[:120]
    out = m.group(2)
    return f"{m.group(1)} {out if len(out) <= 60 else out[:57] + '...'} {m.group(3)}"


def self_times(events: list[list]) -> dict[str, float]:
    """Own time per operation name: each event's interval minus what its
    directly nested children cover (events of one line nest properly)."""
    out: dict[str, float] = {}
    stack: list[list] = []  # [name, end, self]
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out[done[0]] = out.get(done[0], 0.0) + done[2]
        if stack:
            stack[-1][2] -= min(dur, stack[-1][1] - start)
        stack.append([name, start + dur, dur])
    for done in stack:
        out[done[0]] = out.get(done[0], 0.0) + done[2]
    return out


def device_planes(trace: dict) -> list[dict]:
    return [p for p in trace["planes"] if p["name"].startswith("/device:")
            and any(ln["name"] == OPS_LINE for ln in p["lines"])]


def line_events(plane: dict, name: str) -> list[list]:
    for ln in plane["lines"]:
        if ln["name"] == name:
            return ln["events"]
    return []


def reduce(trace: dict, window_ns: tuple[float, float] | None = None) -> dict | None:
    """Busy seconds, idle share, own time per operation, ticks and the
    idle gaps of the traced window.  ``window_ns`` bounds the window on
    the trace's clock; by default it runs from the first device event's
    start to the last one's end.  Returns None when no operation ran on
    a device (nothing to read)."""
    planes = device_planes(trace)
    if not planes:
        return None
    per_device = []
    for p in planes:
        ops = line_events(p, OPS_LINE)
        spans = [(s, s + d) for _, s, d in ops]
        per_device.append(dict(name=p["name"], ops=ops, busy=union(spans),
                               modules=len(line_events(p, MODULES_LINE))))
    if window_ns is None:
        window_ns = (min(d["busy"][0][0] for d in per_device),
                     max(d["busy"][-1][1] for d in per_device))
    w0, w1 = window_ns
    window_s = (w1 - w0) / 1e9
    devices = []
    for d in per_device:
        busy = [(max(s, w0), min(e, w1)) for s, e in d["busy"] if e > w0 and s < w1]
        busy_s = sum(e - s for s, e in busy) / 1e9
        edges = [w0] + [x for se in busy for x in se] + [w1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        devices.append(dict(name=d["name"], busy_s=busy_s, gaps=gaps,
                            ops=self_times(d["ops"]), modules=d["modules"]))
    ticks = sum(1 for p in trace["planes"] if not p["name"].startswith("/device:")
                for ln in p["lines"] for name, s, _ in ln["events"]
                if name == TICK_ANNOTATION and w0 <= s < w1)
    ops_total: dict[str, float] = {}
    for d in devices:
        for name, ns in d["ops"].items():
            ops_total[name] = ops_total.get(name, 0.0) + ns / 1e9 / len(devices)
    busy_mean = sum(d["busy_s"] for d in devices) / len(devices)
    return dict(
        window_s=window_s, window_ns=[w0, w1], busy_s=busy_mean,
        idle_share=1.0 - busy_mean / window_s if window_s > 0 else None,
        ticks=ticks, n_devices=len(devices),
        ops_s=dict(sorted(ops_total.items(), key=lambda kv: -kv[1])),
        gaps_ns=sorted((g for d in devices[:1] for g in d["gaps"]),
                       key=lambda g: g[0] - g[1]),
        per_device=[dict(name=d["name"], busy_s=d["busy_s"], modules=d["modules"])
                    for d in devices])


def align(trace_starts: list[float], wall_starts: list[float],
          probe: int = 16) -> float | None:
    """The offset (ns) that lays the wall clock on the trace's clock.

    Both lists hold the start of the SAME events (the program's tick
    annotation in the profile, the recorder's dispatch phase on the wall
    clock), but the profile saw only some of them.  The k-th trace event
    is matched to the (k + j)-th wall event for the j whose gaps between
    consecutive starts agree best; returns None when there is too little
    to match."""
    a, b = sorted(trace_starts), sorted(wall_starts)
    n = min(probe, len(a) - 1)
    if n < 2 or len(b) < n + 1:
        return None
    best, best_cost = None, None
    for j in range(len(b) - n):
        cost = sum(abs((a[i + 1] - a[i]) - (b[j + i + 1] - b[j + i]))
                   for i in range(n))
        if best_cost is None or cost < best_cost:
            best, best_cost = j, cost
    return sum(a[i] - b[best + i] for i in range(n + 1)) / (n + 1)


SHORT_GAP_NS = 20_000.0


def name_gaps(gaps_ns: list, phases: list[tuple[float, float, str]],
              top: int = 10) -> list[list]:
    """Idle time by what the host was doing: each gap is shared out over
    the host phases (start_ns, end_ns, name on the trace's clock; phases
    do not overlap) it overlaps, and what no phase covers is "outside
    any tick".  Gaps under 20 us are the seams between operations inside
    a step and get a name of their own.  -> [[name, seconds], ...], the
    longest first."""
    from bisect import bisect_right

    phases = sorted(phases)
    starts = [p[0] for p in phases]
    totals: dict[str, float] = {}

    def add(name: str, ns: float) -> None:
        if ns > 0:
            totals[name] = totals.get(name, 0.0) + ns / 1e9

    for g0, g1 in gaps_ns:
        if g1 - g0 < SHORT_GAP_NS:
            add("between operations (gaps under 20 us)", g1 - g0)
            continue
        covered = 0.0
        i = max(bisect_right(starts, g0) - 1, 0)
        while i < len(phases) and phases[i][0] < g1:
            c = min(g1, phases[i][1]) - max(g0, phases[i][0])
            if c > 0:
                add(phases[i][2], c)
                covered += c
            i += 1
        add("outside any tick", g1 - g0 - covered)
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])][:top]


def share_by_name(reduced: dict, needles: list[str]) -> float | None:
    """Own time of the operations whose name contains any needle, as a
    share of device busy time."""
    if not reduced or not reduced["busy_s"]:
        return None
    hit = sum(s for name, s in reduced["ops_s"].items()
              if any(n in name for n in needles))
    return hit / reduced["busy_s"]


def summary(path: str) -> list[dict]:
    """Planes, lines, event counts and the commonest names of a profile."""
    from collections import Counter

    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        lines = []
        for line in plane.lines:
            names = Counter(ev.name for ev in line.events)
            if names:
                lines.append(dict(name=line.name, events=sum(names.values()),
                                  top=names.most_common(12)))
        out.append(dict(name=plane.name, lines=lines))
    return out


if __name__ == "__main__":  # python benchmark/devtrace.py <file.xplane.pb>
    import json
    import sys

    print(json.dumps(summary(sys.argv[1]), indent=1))
