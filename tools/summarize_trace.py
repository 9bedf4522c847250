"""Summarize a serve trace-event JSON dump without leaving the terminal.

The Perfetto UI is the right tool for staring at one slow tick; this is
the right tool for the first question — *where did the time go overall?*
Reads the Chrome trace-event JSON written by ``--trace-out`` (or scraped
from ``GET /debug/trace``) and prints:

- **per-phase totals** — count / total / mean / max for every tick-phase
  slice (``MIXED_TICK_PHASES`` below), plus the phase-coverage ratio
  (phase time / tick time — the tracer's own sanity invariant);
- **top-K slowest ticks** — timestamp, duration, and the tick's args
  (active slots, queue depth, admissions), the starting point for any
  p99 hunt;
- **roofline** (when the trace was recorded with ``--roofline``) —
  per-tick achieved GB/s and roofline-utilization percentiles from the
  telemetry tick args;
- **kv_tier** (when the trace was recorded with ``--kv-tier host``) —
  spilled/restored bytes and restore-latency percentiles from the
  host-tier tick args;
- **tick account** (unified tick) — the cut host phases in tick order
  (pack / h2d / mixed_dispatch / deliver / host_sync / accept /
  account) with the transfers' count and bytes, the share of ticks
  whose ``deliver`` handed the previous tick's tokens out behind the
  dispatch, the tick thread's own CPU time and what is left over
  (neither CPU nor the device wait), the live context per dispatch,
  and the ticks by packed width (tile lanes inside attention) and by
  program (``packed x dense`` width) with the share of dense lanes
  that held a token; then the host's side of the tick on one line: the
  share of ticks at whose fetch the device had already finished (the
  host set them), the fetch after the wait (copy + the way back into
  the interpreter), the collector's time a tick by the phase that held
  it, and with ``--profile`` the exposed host a tick (device program
  end → next program start, on the profile's clock, joined to the
  ticks by the dispatch's ``seq``) cut into wake gap, serial host and
  launch gap;
- **set-up** — the ``cat: "setup"`` spans (load + place, engine build
  and its probes, every warm-up program with its seconds and whether it
  compiled, the op map, listen) and the
  backend compiles the recorder saw, by where they fell (one under
  traffic is named with its tick phase);
- **device scopes** (``--profile FILE.xplane.pb``) — device time by the
  step's named scopes, from the op map the dump carries
  (``otherData.op_map``: operation → scope, pool-shaped or not);
- **per-request lifecycle table** — queued / prefill / decode (and, when
  the HTTP layer traced it, the accept→response bracket, the first
  frame's emit-to-write lag, the stream's mean and largest lag and its
  number of frames) per request,
  with eviction/recovery counts and the finish reason;
- **first token by stage** — a request's way from socket accept to its
  first written frame cut where the work happens (the request track's
  ``http`` begin, ``enqueued``, ``queued``, ``prefill``, ``lane``,
  ``last_chunk``, ``first_token``, ``decode`` begin, ``first_write``):
  each stage's p50 / p95 over the dump's requests, the medians of the
  ticks a prompt took (with a grant, with leftover of the prompt lane,
  with nothing), and the tick's wall time by its kind — a tick that
  handed a prompt leftover of the lane, a decode-only tick, a tick of
  fair-share chunks alone;
- **tenants** (when ``--request-log PATH`` points at the canonical
  request log for the same run) — per-tenant request / token / cost
  breakdown joined from the wide-event lines: requests by finish
  reason, prompt+new tokens, device-cost totals and each tenant's share
  of the fleet's device cost.

- **merge mode** (``--merge`` / multiple files) — stitch PER-REPLICA or
  per-process trace files into ONE request-ordered timeline.  Each
  recorder stamps a wall-clock anchor (``otherData.wall_epoch``) next
  to its perf_counter epoch, so files from different processes (a
  server killed and restarted, or N replica recorders) rebase onto one
  axis; each file becomes its own pid namespace (Perfetto shows it as a
  process track) and every request's events — connected across files by
  the W3C trace id their span args carry — print as one ordered
  lifecycle: ``queued@f0 → prefill@f0 → drain-to-peer → recovery-replay
  @f1 → finish``.  ``--merge OUT.json`` also writes the stitched trace
  for the Perfetto UI.

Usage::

    python tools/summarize_trace.py TRACE.json [--top K]
    python tools/summarize_trace.py TRACE.json --profile RUN.xplane.pb
    python tools/summarize_trace.py TRACE.json --request-log REQS.jsonl
    python tools/summarize_trace.py A.json B.json [--merge OUT.json]
"""

from __future__ import annotations

import argparse
import json
import os
from collections import defaultdict
from typing import Any

# The request-lifecycle table columns: serve.tracing.REQUEST_PHASES plus
# the HTTP layer's accept→response bracket span.  Kept as a local copy
# so this tool stays stdlib-only (no jax import just to print a table);
# pinned equal to the recorder's vocabulary by tests/test_serve_tracing.
LIFECYCLE_COLUMNS = ("queued", "prefill", "decode", "http")
# serve.tracing.MIXED_TICK_PHASES, the same way (the tick-account line
# prints them in tick order)
MIXED_TICK_PHASES = (
    "admission", "draft", "grow", "plan", "pack", "h2d", "mixed_dispatch",
    "deliver", "host_sync", "accept", "account",
)
# The stages of a request's way to its first token, each between two
# neighbours of these edges of its track (serve.scheduler.TTFT_STAGES
# plus ``write_lag``, which only a recorder sees): a span's begin
# (``ph: b``) or an instant (``ph: n``), the FIRST of its name.
TTFT_EDGES = (
    ("http", "b"), ("enqueued", "n"), ("queued", "b"), ("prefill", "b"),
    ("lane", "n"), ("last_chunk", "n"), ("first_token", "n"),
    ("decode", "b"), ("first_write", "n"),
)
TTFT_STAGES = (
    "parse", "inbox_wait", "slot_wait", "lane_wait", "prefill",
    "final_tick", "publish_lag", "write_lag",
)
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_trace(path: str) -> list[dict]:
    """Accepts the ``{"traceEvents": [...]}`` wrapper or a bare event
    list (both are valid Chrome trace JSON)."""
    return load_trace_file(path)[0]


def load_trace_file(path: str) -> tuple[list[dict], float]:
    """→ ``(events, wall anchor)``; anchor 0.0 for pre-anchor dumps
    (mergeable only with themselves)."""
    with open(path) as f:
        data = json.load(f)
    events = data.get("traceEvents") if isinstance(data, dict) else data
    if not isinstance(events, list):
        raise ValueError(f"{path}: not a trace-event JSON file")
    anchor = 0.0
    if isinstance(data, dict):
        anchor = float(
            (data.get("otherData") or {}).get("wall_epoch", 0.0)
        )
    return events, anchor


def merge_traces(paths: list[str]) -> dict:
    """Stitch N trace files onto one time axis: every file's events are
    shifted by its wall anchor (relative to the earliest file) and moved
    into a per-file pid namespace, so per-replica / pre-and-post-restart
    recorders land as separate process tracks on one timeline."""
    files = [(p,) + load_trace_file(p) for p in paths]
    base = min((anchor for _, _, anchor in files if anchor), default=0.0)
    merged: list[dict] = []
    for idx, (path, events, anchor) in enumerate(files):
        shift_us = (anchor - base) * 1e6 if anchor else 0.0
        merged.append({
            "name": "process_name", "ph": "M", "pid": idx, "tid": 0,
            "args": {"name": os.path.basename(path)},
        })
        for ev in events:
            ev = dict(ev)
            ev["pid"] = idx
            if "ts" in ev:
                ev["ts"] = ev["ts"] + shift_us
            merged.append(ev)
    return {
        "traceEvents": merged,
        "displayTimeUnit": "ms",
        "otherData": {
            "merged_from": [os.path.basename(p) for p in paths],
            "wall_epoch": base,
        },
    }


def request_timelines(events: list[dict]) -> dict[str, list[dict]]:
    """trace id → its request/router events in time order (begin spans
    and instants only — one entry per lifecycle step).  The connectivity
    check for a merged trace: a request that crossed replicas/restarts
    has ONE timeline here, spanning multiple pids."""
    out: dict[str, list[dict]] = defaultdict(list)
    for ev in events:
        if ev.get("cat") not in ("request", "router"):
            continue
        if ev.get("ph") not in ("b", "n", "i"):
            continue
        tid = (ev.get("args") or {}).get("trace")
        if tid is None:
            continue
        out[tid].append(ev)
    for evs in out.values():
        evs.sort(key=lambda e: e.get("ts", 0.0))
    return dict(out)


def format_merged(events: list[dict]) -> str:
    """The request-ordered merged timeline, one line per request."""
    timelines = request_timelines(events)
    lines = [f"== merged timeline: {len(timelines)} traced requests =="]
    for tid, evs in sorted(
        timelines.items(), key=lambda kv: kv[1][0].get("ts", 0.0)
    ):
        steps = []
        rid = None
        for ev in evs:
            rid = ev.get("id", (ev.get("args") or {}).get("rid", rid))
            name = ev["name"]
            args = ev.get("args") or {}
            if name == "finish":
                name = f"finish({args.get('reason', '?')})"
            elif name == "drain-to-peer":
                name = (f"drain-to-peer({args.get('from_replica', '?')}"
                        f"→{args.get('to_replica', '?')})")
            steps.append(f"{name}@f{ev.get('pid', 0)}")
        n_files = len({ev.get("pid", 0) for ev in evs})
        lines.append(
            f"  {tid[:12]} rid={rid} files={n_files}: "
            + " → ".join(steps)
        )
    return "\n".join(lines)


def phase_totals(events: list[dict]) -> dict[str, dict[str, float]]:
    """name → {count, total_us, mean_us, max_us} over the synchronous
    slices (tick phases + prefill chunks)."""
    out: dict[str, dict[str, float]] = {}
    for ev in events:
        if ev.get("ph") != "X" or ev.get("cat") not in ("phase", "prefill"):
            continue
        rec = out.setdefault(ev["name"],
                             {"count": 0, "total_us": 0.0, "max_us": 0.0})
        rec["count"] += 1
        rec["total_us"] += ev.get("dur", 0.0)
        rec["max_us"] = max(rec["max_us"], ev.get("dur", 0.0))
    for rec in out.values():
        rec["mean_us"] = rec["total_us"] / rec["count"] if rec["count"] else 0.0
    return out


def tick_stats(events: list[dict]) -> dict[str, float]:
    """Tick count/total plus phase coverage (sum of phase durations over
    sum of tick durations — the contiguous-timestamps invariant)."""
    tick_us = sum(e.get("dur", 0.0) for e in events
                  if e.get("ph") == "X" and e.get("cat") == "tick")
    phase_us = sum(e.get("dur", 0.0) for e in events
                   if e.get("ph") == "X" and e.get("cat") == "phase")
    n = sum(1 for e in events
            if e.get("ph") == "X" and e.get("cat") == "tick")
    return {
        "ticks": n,
        "tick_total_us": tick_us,
        "phase_total_us": phase_us,
        "phase_coverage": phase_us / tick_us if tick_us else 0.0,
    }


def mixed_utilization(events: list[dict]) -> dict[str, float] | None:
    """Unified-tick (mixed_step) budget utilization from the per-tick
    ``prefill_tokens``/``decode_tokens`` args: how the engine's token
    budget was actually split between catching up prefills and keeping
    the decode batch fed.  Spec-enabled engines additionally stamp
    ``spec_draft_tokens``/``spec_accept_tokens`` per tick — the
    draft/verify/accept-length split lands here too (verify lanes =
    drafted tokens riding the one dispatch; accept rate = how many paid
    off; emitted decode tokens = decode_tokens + spec_accept_tokens).
    None when no tick carries the args."""
    pairs = [
        (e.get("args") or {}, float(e.get("dur", 0.0)))
        for e in events
        if e.get("ph") == "X" and e.get("cat") == "tick"
    ]
    pairs = [(a, d) for a, d in pairs if "prefill_tokens" in a]
    if not pairs:
        return None
    ticks = [a for a, _ in pairs]
    durs = [d for _, d in pairs]
    pre = sum(a["prefill_tokens"] for a in ticks)
    dec = sum(a["decode_tokens"] for a in ticks)
    total = pre + dec
    out = {
        "ticks": len(ticks),
        "prefill_tokens": pre,
        "decode_tokens": dec,
        "tokens_per_tick_mean": total / len(ticks),
        "prefill_frac": pre / total if total else 0.0,
    }
    spec_ticks = [a for a in ticks if "spec_draft_tokens" in a]
    if spec_ticks:
        drafted = sum(a["spec_draft_tokens"] for a in spec_ticks)
        accepted = sum(a["spec_accept_tokens"] for a in spec_ticks)
        out["spec_draft_tokens"] = drafted
        out["spec_accept_tokens"] = accepted
        out["spec_accept_rate"] = accepted / drafted if drafted else 0.0
        # decode rows with at least one draft lane = verify rounds are
        # not in the args; accept length per TICK is the honest
        # per-sweep view here (the exact per-round histogram lives on
        # /metrics)
        out["spec_accept_per_tick"] = accepted / len(spec_ticks)
    # host_sync column (the tick-tail fusion before/after instrument):
    # per-tick host_sync wall + its share of the tick, readable from a
    # trace alone — plus the one-fetch contract's transfer count
    hs_pairs = [
        (a["host_sync_us"], d) for a, d in zip(ticks, durs)
        if "host_sync_us" in a
    ]
    if hs_pairs:
        hs = [h for h, _ in hs_pairs]
        tick_total = sum(d for _, d in hs_pairs)
        out["host_sync_us_mean"] = sum(hs) / len(hs)
        out["host_sync_us_p99"] = _pct(hs, 99.0)
        out["host_sync_share"] = (
            sum(hs) / tick_total if tick_total else 0.0
        )
        fetches = [a["host_fetches"] for a in ticks if "host_fetches" in a]
        if fetches:
            out["host_fetches_max"] = max(fetches)
    return out


def _pct(vals: list[float], q: float) -> float:
    """Nearest-rank percentile over a non-empty list (stdlib-only — no
    numpy import just to print a table)."""
    vals = sorted(vals)
    idx = min(int(round(q / 100.0 * (len(vals) - 1))), len(vals) - 1)
    return vals[idx]


def roofline(events: list[dict]) -> dict[str, dict[str, float]] | None:
    """Roofline telemetry from the per-tick ``roofline_gbps``/
    ``roofline_util`` args (serve/telemetry.py stamps them when
    ``--roofline`` is on): achieved-GB/s and utilization percentiles
    under the one tick kind there is, ``mixed``.  None when no tick
    carries the args (telemetry was off)."""
    ticks = [
        ev["args"] for ev in events
        if ev.get("ph") == "X" and ev.get("cat") == "tick"
        and "roofline_util" in (ev.get("args") or {})
    ]
    if not ticks:
        return None
    gbps = [a["roofline_gbps"] for a in ticks]
    util = [a["roofline_util"] for a in ticks]
    return {"mixed": {
        "ticks": len(ticks),
        "gbps_p50": _pct(gbps, 50),
        "gbps_p90": _pct(gbps, 90),
        "gbps_p99": _pct(gbps, 99),
        "util_p50": _pct(util, 50),
        "util_p99": _pct(util, 99),
        "util_mean": sum(util) / len(util),
        "device_s_total": sum(a.get("device_time_s", 0.0) for a in ticks),
    }}


def kv_tier(events: list[dict]) -> dict[str, float] | None:
    """Host-tier flow from the per-tick ``tier_spill_bytes`` /
    ``tier_restore_bytes`` / ``tier_restore_us`` args (the engine
    stamps them when ``--kv-tier host`` is on): total spilled/restored
    bytes, how many ticks moved blocks either way, and restore-latency
    percentiles over the ticks that restored.  None when no tick
    carries the args (the tier was off)."""
    ticks = [
        (ev.get("args") or {}) for ev in events
        if ev.get("ph") == "X" and ev.get("cat") == "tick"
        and "tier_spill_bytes" in (ev.get("args") or {})
    ]
    if not ticks:
        return None
    spill = [a["tier_spill_bytes"] for a in ticks]
    restore = [a["tier_restore_bytes"] for a in ticks]
    lat = [a["tier_restore_us"] for a in ticks if a["tier_restore_bytes"]]
    out = {
        "ticks": len(ticks),
        "spill_bytes": float(sum(spill)),
        "restore_bytes": float(sum(restore)),
        "spill_ticks": sum(1 for b in spill if b),
        "restore_ticks": sum(1 for b in restore if b),
    }
    if lat:
        out["restore_us_p50"] = _pct(lat, 50)
        out["restore_us_p99"] = _pct(lat, 99)
        out["restore_us_mean"] = sum(lat) / len(lat)
    return out


def load_request_log(path: str) -> list[dict]:
    """Parse a request-log JSONL file (serve/request_log.py), skipping
    blank and torn lines.  Local copy so this tool stays stdlib-only —
    pinned equivalent to ``serve.request_log.read_request_log`` by the
    shared on-disk format (one JSON object per line)."""
    out: list[dict] = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                out.append(json.loads(line))
            except ValueError:
                continue  # torn tail
    return out


def tenant_table(records: list[dict]) -> dict[str, dict[str, Any]]:
    """tenant → request/token/cost totals from request-log lines.  The
    request log writes ``tenant`` only when non-default, so absent maps
    to ``"default"`` — the same convention the journal uses."""
    out: dict[str, dict[str, Any]] = {}
    for rec in records:
        t = rec.get("tenant", "default")
        ent = out.setdefault(t, {
            "requests": 0, "prompt_tokens": 0, "new_tokens": 0,
            "reasons": defaultdict(int),
            "kv_bytes_read": 0.0, "kv_bytes_written": 0.0,
            "weight_bytes_amortized": 0.0, "device_time_s": 0.0,
        })
        ent["requests"] += 1
        ent["prompt_tokens"] += int(rec.get("prompt_tokens", 0))
        ent["new_tokens"] += int(rec.get("new_tokens", 0))
        ent["reasons"][rec.get("reason", "?")] += 1
        cost = rec.get("cost") or {}
        for k in ("kv_bytes_read", "kv_bytes_written",
                  "weight_bytes_amortized", "device_time_s"):
            ent[k] += float(cost.get(k, 0.0))
    total_cost = sum(
        e["kv_bytes_read"] + e["kv_bytes_written"]
        + e["weight_bytes_amortized"] for e in out.values()
    )
    for ent in out.values():
        mine = (ent["kv_bytes_read"] + ent["kv_bytes_written"]
                + ent["weight_bytes_amortized"])
        ent["cost_share"] = mine / total_cost if total_cost else 0.0
        ent["reasons"] = dict(ent["reasons"])
    return out


def format_tenants(records: list[dict]) -> str:
    """The per-tenant breakdown table, worst-billed tenant first."""
    table = tenant_table(records)
    lines = [f"== tenants: {len(table)} from {len(records)} "
             f"request-log lines =="]
    lines.append(
        f"{'tenant':<16} {'reqs':>5} {'prompt':>7} {'new':>6} "
        f"{'dev_MiB':>8} {'dev_ms':>7} {'share':>6} reasons"
    )
    by_cost = sorted(
        table.items(), key=lambda kv: (-kv[1]["cost_share"], kv[0])
    )
    for tenant, ent in by_cost:
        dev_bytes = (ent["kv_bytes_read"] + ent["kv_bytes_written"]
                     + ent["weight_bytes_amortized"])
        reasons = ",".join(
            f"{r}={n}" for r, n in sorted(ent["reasons"].items())
        )
        lines.append(
            f"{tenant:<16} {ent['requests']:>5} "
            f"{ent['prompt_tokens']:>7} {ent['new_tokens']:>6} "
            f"{dev_bytes / 2**20:>8.2f} "
            f"{ent['device_time_s'] * 1e3:>7.2f} "
            f"{ent['cost_share']:>6.1%} {reasons}"
        )
    return "\n".join(lines)


def tick_account(events: list[dict]) -> dict[str, Any] | None:
    """Where the host's share of a unified tick goes, over DISPATCHING
    ticks: mean of every cut phase (tick order), the ``h2d`` slice's
    transfer count and bytes, and from the tick args the rows
    ``_pack_mixed`` filled by whole-array writes, the tick thread's
    own CPU time, the leftover (tick - host_sync - CPU: neither
    computing nor waiting for the device), the live context, the
    packed widths (tile lanes inside attention) the dispatches used
    and — where the ticks say it — their programs, ``packed x dense``
    width, with the share of dense lanes that held a token and the
    share of the tile-aligned axis' lanes that did.  None for a trace
    without the cut phases (older dumps)."""
    ticks = [e for e in events if e.get("ph") == "X"
             and e.get("cat") == "tick" and "packed_width" in
             (e.get("args") or {}) and e["args"]["packed_width"]]
    if not ticks:
        return None
    spans = sorted((e["ts"], e["ts"] + e["dur"]) for e in ticks)
    out: dict[str, Any] = {"ticks": len(ticks)}
    sums: dict[str, float] = defaultdict(float)
    puts = put_bytes = 0
    i = 0
    for ev in sorted((e for e in events if e.get("ph") == "X"
                      and e.get("cat") == "phase"), key=lambda e: e["ts"]):
        while i < len(spans) and spans[i][1] < ev["ts"]:
            i += 1
        if i == len(spans) or ev["ts"] < spans[i][0]:
            continue  # a phase of a tick that dispatched nothing
        sums[ev["name"]] += ev["dur"]
        if ev["name"] == "h2d":
            puts += (ev.get("args") or {}).get("count", 0)
            put_bytes += (ev.get("args") or {}).get("bytes", 0)
    n = len(ticks)
    for name in MIXED_TICK_PHASES:
        out[f"{name}_us"] = sums.get(name, 0.0) / n
    out["h2d_count"] = puts / n
    out["h2d_bytes"] = put_bytes / n
    out["tick_us"] = sum(e["dur"] for e in ticks) / n
    out["context_tokens"] = sum(
        e["args"].get("context_tokens", 0) for e in ticks) / n
    out["pack_array_rows"] = sum(
        e["args"].get("pack_array_rows", 0) for e in ticks) / n
    out["rows"] = sum(e["args"].get("active_slots", 0) for e in ticks) / n
    widths: dict[int, int] = defaultdict(int)
    for e in ticks:
        widths[e["args"]["packed_width"]] += 1
    out["packed_widths"] = dict(sorted(widths.items()))
    dense = [e["args"] for e in ticks if e["args"].get("dense_width")]
    if dense:
        programs: dict[tuple[int, int], int] = defaultdict(int)
        for a in dense:
            programs[a["packed_width"], a["dense_width"]] += 1
        out["programs"] = {
            f"{t}x{d}": n for (t, d), n in sorted(programs.items())}
        tokens = sum(
            a.get("prefill_tokens", 0) + a.get("decode_tokens", 0)
            + a.get("spec_draft_tokens", 0) for a in dense)
        out["dense_occupancy"] = tokens / sum(a["dense_width"] for a in dense)
        # ... and of the tile-aligned axis inside attention: a decode row
        # is one token in a tile of eight lanes
        out["tile_lane_occupancy"] = tokens / sum(
            a["packed_width"] for a in dense)
    paged = [e["args"] for e in ticks if e["args"].get("attn_grid_steps")]
    if paged:
        # one layer's attention call: the pages its tiles stream, the kv
        # grid steps it takes, and how many of the steps' page slots
        # (steps x P) hold a live page
        out["attn_pages"] = sum(a["attn_pages"] for a in paged) / len(paged)
        out["attn_grid_steps"] = sum(
            a["attn_grid_steps"] for a in paged) / len(paged)
        out["attn_slot_share"] = sum(a["attn_pages"] for a in paged) / sum(
            a["attn_grid_steps"] * a["attn_pages_per_step"] for a in paged)
    tiled = [a for a in paged if a.get("attn_live_tiles")]
    if tiled:
        # the live query tiles of a dispatch, and those that hold ONE
        # token: the kernel attends such a tile's one token alone
        for key in ("attn_live_tiles", "attn_decode_tiles"):
            out[key] = sum(a[key] for a in tiled) / len(tiled)
        out["attn_decode_tile_share"] = (
            out["attn_decode_tiles"] / out["attn_live_tiles"])
        # the others hold a prefill chunk's tokens: each streams its
        # row's visible pages again
        out["attn_prefill_tile_share"] = 1.0 - out["attn_decode_tile_share"]
    classes = [e["args"] for e in ticks if "attn_pages_window" in e["args"]]
    if classes:
        # a pool with a window class: what one layer of each kind streams,
        # the window blocks the rows hold and those a tick's pack recycled
        for key in ("attn_pages_global", "attn_pages_window",
                    "window_blocks_live", "window_blocks_recycled"):
            out[key] = sum(a[key] for a in classes) / len(classes)
    moe = [e["args"] for e in ticks if "experts_touched" in e["args"]]
    if moe:
        # dropless expert layers: what the step counted, back with the
        # tick's one fetch (experts that got a token, summed over the
        # expert layers; the worst layer's most loaded expert and mean)
        for key in ("experts_touched", "expert_load_max",
                    "expert_load_mean", "state_slots_live"):
            out[key] = sum(a.get(key, 0) for a in moe) / len(moe)
        routed = sum(a.get("pairs_routed", 0) for a in moe)
        if routed:
            # the (token, expert) pairs this chip holds of those the
            # routers made, and the share of the ticks in which the grouped
            # matmul's calls moved the held pairs' rows themselves (else
            # XLA moved the rows of all the pairs routed)
            out["pairs_held_share"] = sum(
                a["pairs_held"] for a in moe) / routed
            out["expert_rows_kernel_share"] = sum(
                a.get("expert_rows_impl") == "kernel" for a in moe) / len(moe)
    for kind in ("ssm", "kda", "retention"):
        # state-space mixers (``ssm_*``) / delta-rule linear-attention
        # layers (``kda_*``, scopes ``kda_proj`` / ``kda_scan``) /
        # power-retention layers (``retention_*``, scopes ``retention_proj``
        # / ``retention_scan``): rows whose recurrent state the dispatch read
        # and wrote, live tokens through the recurrence
        rec = [e["args"] for e in ticks if f"{kind}_state_rows" in e["args"]]
        if not rec:
            continue
        for key in (f"{kind}_state_rows", f"{kind}_scan_tokens",
                    "state_slots_live"):
            out[key] = sum(a.get(key, 0) for a in rec) / len(rec)
        # ... and the share of those ticks in which the Pallas kernel moved
        # those rows alone (else the compiler's passes moved every row;
        # dumps older than the argument count as that)
        out[f"{kind}_kernel_share"] = sum(
            a.get(f"{kind}_state_impl") == "pallas" for a in rec) / len(rec)
    dsa = [e["args"] for e in ticks if e["args"].get("dsa_visible")]
    if dsa:
        # a sparse-attention indexer (``dsa_*``, scopes ``dsa_proj`` /
        # ``dsa_score`` / ``dsa_select`` / ``dsa_attn``), by one layer: the
        # positions a dispatch's tokens may see, those they attend, the
        # tokens that attend all they see, the index-key pages scored
        for key in ("dsa_visible", "dsa_selected", "dsa_dense_tokens",
                    "dsa_index_pages"):
            out[key] = sum(a.get(key, 0) for a in dsa) / len(dsa)
        out["dsa_selected_share"] = sum(
            a["dsa_selected"] for a in dsa) / sum(a["dsa_visible"] for a in dsa)
    pub = [e["args"] for e in ticks if e["args"].get("publish_rows")]
    if pub:
        # ``deliver`` hands the PREVIOUS tick's tokens out: behind this
        # tick's dispatch (overlapped) wherever there is one to hide
        # behind — every dispatching tick counted here has one
        out["publish_ticks"] = len(pub)
        out["publish_overlapped_share"] = sum(
            a.get("publish_overlapped", 0) for a in pub) / len(pub)
        out["publish_rows"] = sum(a["publish_rows"] for a in pub) / len(pub)
    cpu = [e["args"]["thread_cpu_us"] for e in ticks
           if "thread_cpu_us" in e["args"]]
    if cpu:
        out["thread_cpu_us"] = sum(cpu) / len(cpu)
        out["host_wait_us"] = sum(
            e["dur"] - e["args"].get("host_sync_us", 0.0)
            - e["args"]["thread_cpu_us"]
            for e in ticks if "thread_cpu_us" in e["args"]) / len(cpu)
    return out


def host_account(events: list[dict]) -> dict[str, Any] | None:
    """What a tick's numbered dispatch says of the host (tick args ``seq``,
    ``device_wait_us``, ``device_done_at_sync``) and the collector's
    slices: the share of dispatching ticks at whose fetch the device had
    already finished, the mean wait for the program and the mean fetch
    after it (``host_sync_us`` - ``device_wait_us``), and ``cat: "gc"``
    time a dispatching tick from the first such tick on, whole and by
    the phase that held it.  None for a dump whose ticks carry no
    ``seq``."""
    numbered = [e for e in events if e.get("ph") == "X"
                and e.get("cat") == "tick" and "seq" in (e.get("args") or {})]
    if not numbered:
        return None
    ticks, n = [e["args"] for e in numbered], len(numbered)
    first = min(e["ts"] for e in numbered)  # set-up's collections are not a tick's
    runs = [e for e in events if e.get("cat") == "gc" and e["ts"] >= first]
    gc_by: dict[str, float] = defaultdict(float)
    for ev in runs:
        gc_by[(ev.get("args") or {}).get("within") or "between spans"] += (
            ev["dur"])
    return {
        "ticks": n,
        "host_bound_share": sum(a["device_done_at_sync"] for a in ticks) / n,
        "device_wait_us": sum(a["device_wait_us"] for a in ticks) / n,
        "fetch_us": sum(a["host_sync_us"] - a["device_wait_us"]
                        for a in ticks) / n,
        "gc_count": len(runs),
        "gc_us": sum(gc_by.values()) / n,
        "gc_by_phase_us": {k: v / n for k, v in sorted(
            gc_by.items(), key=lambda kv: -kv[1])},
    }


def tick_timeline(events: list[dict], profile: str) -> dict[str, Any] | None:
    """The exposed host a tick and its three parts: the dispatch's ``seq``
    joins the profile's ``serve.mixed_dispatch`` / ``serve.host_sync``
    annotations and the device's program executions (``XLA Modules``,
    first device plane) to the recorder's ticks.  A tick k counts when
    the profile saw it and its successor whole and the recorder's next
    tick IS that successor (no idle tick between).  The exposed host is
    read on the device's line and the serial host on the host's; the
    wake gap and the launch gap cross the two, and the device's line
    leads the host's by an amount that differs from capture to capture,
    so both are corrected by the middle of the lead's causal bounds (no
    program starts before the runtime's ``DoEnqueueProgram`` of its
    ``run_id`` nor before its dispatch annotation; none ends after the
    runtime's ``CompleteCallbacks`` nor after ``host_sync`` +
    ``device_wait_us``).  The benchmark's ``layers/ticktimeline.py`` does
    the same join; this is the tool's own copy (needs jax to read the
    profile, like the device scopes)."""
    from bisect import bisect_right

    from jax.profiler import ProfileData

    by_seq: dict[str, dict[int, tuple[float, float]]] = {
        "serve.mixed_dispatch": {}, "serve.host_sync": {}}
    by_run: dict[str, dict[int, float]] = {
        "DoEnqueueProgram": {}, "CompleteCallbacks": {}}
    modules: list[tuple[float, float, Any]] = []
    for plane in ProfileData.from_file(profile).planes:
        device = plane.name.startswith("/device:")
        if device and modules:
            continue
        for line in plane.lines:
            if device:
                if line.name == "XLA Modules":
                    modules = sorted(
                        (float(e.start_ns), float(e.start_ns + e.duration_ns),
                         dict(e.stats).get("run_id")) for e in line.events)
                continue
            for e in line.events:
                if e.name in by_seq or e.name in by_run:
                    stats = dict(e.stats)
                    if e.name in by_seq and "seq" in stats:
                        by_seq[e.name][int(stats["seq"])] = (
                            float(e.start_ns),
                            float(e.start_ns + e.duration_ns))
                    elif "run_id" in stats:
                        by_run[e.name][stats["run_id"]] = float(e.start_ns)
    dispatch, sync = by_seq["serve.mixed_dispatch"], by_seq["serve.host_sync"]
    seqs = sorted(dispatch)
    starts = [dispatch[q][0] for q in seqs]
    program: dict[int, list[float]] = {}
    lead_lo: list[float] = []
    lead_hi: list[float] = []
    for m0, m1, run_id in modules:  # a program belongs where its midpoint lies
        i = bisect_right(starts, (m0 + m1) / 2) - 1
        if i >= 0:
            span = program.setdefault(seqs[i], [m0, m1])
            span[0], span[1] = min(span[0], m0), max(span[1], m1)
        if run_id in by_run["DoEnqueueProgram"]:
            lead_lo.append(by_run["DoEnqueueProgram"][run_id] - m0)
        if run_id in by_run["CompleteCallbacks"]:
            lead_hi.append(by_run["CompleteCallbacks"][run_id] - m1)
    ticks = sorted((e for e in events if e.get("ph") == "X"
                    and e.get("cat") == "tick"), key=lambda e: e["ts"])
    args = {e["args"]["seq"]: e["args"] for e in ticks
            if "seq" in (e.get("args") or {})}
    follows = {(a.get("args") or {}).get("seq"): (b.get("args") or {}).get("seq")
               for a, b in zip(ticks, ticks[1:])}
    parts = {"wake_gap": 0.0, "serial": 0.0, "launch_gap": 0.0}
    n = 0
    for q in seqs:
        if (follows.get(q) != q + 1 or q not in sync or q + 1 not in dispatch
                or q not in program or q + 1 not in program):
            continue
        n += 1
        parts["wake_gap"] += sync[q][1] - program[q][1]
        parts["serial"] += dispatch[q + 1][0] - sync[q][1]
        parts["launch_gap"] += program[q + 1][0] - dispatch[q + 1][0]
        lead_lo.append(dispatch[q + 1][0] - program[q + 1][0])
        lead_hi.append(sync[q][0] + args[q].get("device_wait_us", 0.0) * 1e3
                       - program[q][1] if "device_wait_us" in args[q]
                       else sync[q][1] - program[q][1])
    if not n:
        return None
    lead = (max(lead_lo) + min(lead_hi)) / 2
    out: dict[str, Any] = {k + "_us": v / n / 1e3 for k, v in parts.items()}
    out["exposed_us"] = sum(out.values())
    out["wake_gap_us"] -= lead / 1e3
    out["launch_gap_us"] += lead / 1e3
    out["lead_us"] = lead / 1e3
    out["lead_error_us"] = (min(lead_hi) - max(lead_lo)) / 2e3
    out["ticks"] = n
    return out


def setup_spans(events: list[dict]) -> list[dict]:
    """The ``cat: "setup"`` spans in start order, each with the number
    of backend compiles that fell in it (its children's included) and
    how many of those the persistent cache did not serve."""
    spans = sorted((e for e in events if e.get("ph") == "X"
                    and e.get("cat") == "setup"), key=lambda e: e["ts"])
    compiles = [e for e in events if e.get("cat") == "compile"]
    out = []
    for ev in spans:
        inside = [c for c in compiles if c["tid"] == ev["tid"]
                  and ev["ts"] <= c["ts"] + c["dur"] / 2 <= ev["ts"] + ev["dur"]]
        out.append({
            "name": ev["name"], "dur_us": ev["dur"],
            "args": ev.get("args") or {}, "compiles": len(inside),
            "compile_misses": sum(
                1 for c in inside
                if not (c.get("args") or {}).get("cache_hit")),
        })
    return out


def stray_compiles(events: list[dict]) -> dict[str, int]:
    """Backend compiles that fell OUTSIDE set-up, counted by where the
    recorder says they fell (``args.within``: a tick phase, or "between
    spans"): a recompile under traffic, which warm-up exists to prevent."""
    setup = {e["name"] for e in events if e.get("cat") == "setup"}
    out: dict[str, int] = defaultdict(int)
    for ev in events:
        if ev.get("cat") == "compile":
            within = (ev.get("args") or {}).get("within")
            if within not in setup:
                out[within or "between spans"] += 1
    return dict(out)


def _load_by_path(name: str, rel: str):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        name, os.path.join(REPO, rel))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_scopes(op_map: dict, profile: str) -> dict[str, Any] | None:
    """Device time of a ``jax.profiler`` window by the step's named
    scopes: every operation's own time goes to the scope the dump's op
    map gives it under the profile's own name for it (``pool``: the
    result is the KV pool or one layer's slab of it, whatever the scope;
    ``-``: the map has no scope for it; ``?``: not in the map, or two
    buckets of the step disagree).
    Reads the profile with the benchmark's own reader (needs jax)."""
    devtrace = _load_by_path("devtrace", "benchmark/devtrace.py")
    reduced = devtrace.reduce(devtrace.read_xplane(profile))
    if not reduced or not reduced["busy_s"]:
        return None
    by: dict[str, float] = defaultdict(float)
    for name, seconds in reduced["ops_s"].items():
        known = op_map.get(name.rsplit(" ", 1)[0])
        if known is None:
            by["?"] += seconds
        else:
            scope, kind = known
            by["pool" if kind and scope != "attn" else scope or "-"] += seconds
    return {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"],
            "ticks": reduced["ticks"], "scopes": dict(by)}


def format_device_scopes(dev: dict[str, Any]) -> str:
    lines = [
        "== device scopes ==",
        f"{dev['busy_s'] * 1e3:.1f} ms busy of {dev['window_s'] * 1e3:.1f} ms"
        f" ({dev['ticks']} ticks)",
        f"{'scope':<10} {'ms':>10} {'share':>7}",
    ]
    for scope, seconds in sorted(dev["scopes"].items(),
                                 key=lambda kv: -kv[1]):
        lines.append(f"{scope:<10} {seconds * 1e3:>10.2f} "
                     f"{seconds / dev['busy_s']:>7.1%}")
    return "\n".join(lines)


def ttft_stage_table(events: list[dict]) -> dict[str, Any] | None:
    """The way to the first token over the dump's requests: per stage the
    count, p50 and p95 (us), the medians of the three tick counts the
    ``last_chunk`` instant carries, and per kind of tick — ``prefill``
    (tick arg ``lane_rows`` > 0: leftover of the prompt lane handed out),
    ``decode`` (no prompt token aboard), ``fair`` (fair-share chunks
    alone) — the dispatching ticks and their mean wall (us).  None for a
    dump from before the track carried the ``lane`` instant."""
    first: dict[Any, dict] = defaultdict(dict)
    counts: dict[str, list[int]] = defaultdict(list)
    for ev in events:
        if ev.get("cat") != "request":
            continue
        key = (ev["name"], ev["ph"])
        if key in TTFT_EDGES and key not in first[ev.get("id")]:
            first[ev.get("id")][key] = ev["ts"]
            if ev["name"] == "last_chunk":
                for k, v in (ev.get("args") or {}).items():
                    if k != "seq":
                        counts[k].append(v)
    if not any(("lane", "n") in tr for tr in first.values()):
        return None
    stages = {}
    for stage, a, b in zip(TTFT_STAGES, TTFT_EDGES, TTFT_EDGES[1:]):
        vals = [tr[b] - tr[a] for tr in first.values()
                if a in tr and b in tr]
        if vals:
            stages[stage] = {"n": len(vals), "p50_us": _pct(vals, 50),
                             "p95_us": _pct(vals, 95)}
    kinds: dict[str, list[float]] = defaultdict(list)
    for ev in events:
        args = ev.get("args") or {}
        if ev.get("name") != "tick" or "lane_rows" not in args \
                or "seq" not in args:
            continue
        kinds["prefill" if args["lane_rows"] > 0 else
              "fair" if args.get("prefill_tokens") else "decode"
              ].append(ev["dur"])
    return {
        "stages": stages,
        "counts": {k: _pct(v, 50) for k, v in counts.items()},
        "ticks": {k: {"n": len(v), "mean_us": sum(v) / len(v)}
                  for k, v in kinds.items()},
    }


def format_ttft_stages(table: dict[str, Any]) -> str:
    lines = ["== first token by stage ==",
             f"{'stage':<12} {'requests':>8} {'p50_ms':>9} {'p95_ms':>9}"]
    for stage, rec in table["stages"].items():
        lines.append(f"{stage:<12} {rec['n']:>8} {rec['p50_us'] / 1e3:>9.3f} "
                     f"{rec['p95_us'] / 1e3:>9.3f}")
    if table["counts"]:
        lines.append("ticks a prompt took (median): " + ", ".join(
            f"{k} {v}" for k, v in table["counts"].items()))
    if table["ticks"]:
        lines.append("tick wall by kind: " + "; ".join(
            f"{kind} {rec['mean_us'] / 1e3:.2f} ms x {rec['n']}"
            for kind, rec in sorted(table["ticks"].items())))
    return "\n".join(lines)


def slowest_ticks(events: list[dict], k: int) -> list[dict]:
    ticks = [e for e in events
             if e.get("ph") == "X" and e.get("cat") == "tick"]
    return sorted(ticks, key=lambda e: e.get("dur", 0.0), reverse=True)[:k]


def request_table(events: list[dict]) -> dict[Any, dict]:
    """rid → per-phase durations (µs, summed across requeues), eviction/
    recovery counts, and the finish reason, from the async request
    events."""
    table: dict[Any, dict] = defaultdict(lambda: {
        "phases_us": defaultdict(float), "evictions": 0, "recoveries": 0,
        "finish": None, "first_write_lag_us": None, "write_lag_us": None,
        "write_lag_max_us": None, "frames": None,
    })
    open_spans: dict[tuple, float] = {}
    for ev in events:
        if ev.get("cat") != "request":
            continue
        rid, name, ph = ev.get("id"), ev["name"], ev["ph"]
        if ph == "b":
            open_spans[(rid, name)] = ev["ts"]
        elif ph == "e":
            t0 = open_spans.pop((rid, name), None)
            if t0 is not None:
                table[rid]["phases_us"][name] += ev["ts"] - t0
        elif ph == "n":
            if name == "finish":
                table[rid]["finish"] = (ev.get("args") or {}).get("reason")
            elif name == "evicted-requeued":
                table[rid]["evictions"] += 1
            elif name == "recovery-replay":
                table[rid]["recoveries"] += 1
            elif name == "first_write":
                # the first frame's emit (tick thread) → write (loop)
                table[rid]["first_write_lag_us"] = (
                    ev.get("args") or {}).get("lag_us")
            elif name == "stream_end":
                end = ev.get("args") or {}
                table[rid]["write_lag_us"] = end.get("lag_mean_us")
                table[rid]["write_lag_max_us"] = end.get("lag_max_us")
                table[rid]["frames"] = end.get("frames")
    return dict(table)


def format_summary(events: list[dict], top: int = 5,
                   profile: str | None = None) -> str:
    lines: list[str] = []
    totals = phase_totals(events)
    stats = tick_stats(events)
    lines.append("== tick phases ==")
    lines.append(f"{'phase':<16} {'count':>7} {'total_ms':>10} "
                 f"{'mean_us':>9} {'max_us':>9}")
    for name, rec in sorted(totals.items(),
                            key=lambda kv: -kv[1]["total_us"]):
        lines.append(
            f"{name:<16} {rec['count']:>7} {rec['total_us'] / 1e3:>10.2f} "
            f"{rec['mean_us']:>9.1f} {rec['max_us']:>9.1f}"
        )
    lines.append(
        f"{stats['ticks']} ticks, {stats['tick_total_us'] / 1e3:.2f} ms "
        f"total, phase coverage {stats['phase_coverage']:.1%}"
    )
    util = mixed_utilization(events)
    if util is not None:
        lines.append(
            f"== mixed_step utilization ==\n"
            f"{util['prefill_tokens']} prefill + {util['decode_tokens']} "
            f"decode tokens over {util['ticks']} ticks "
            f"({util['tokens_per_tick_mean']:.1f} tok/tick, "
            f"{util['prefill_frac']:.1%} prefill)"
        )
        if "spec_draft_tokens" in util:
            lines.append(
                f"speculative: {util['spec_draft_tokens']} drafted / "
                f"{util['spec_accept_tokens']} accepted verify tokens "
                f"({util['spec_accept_rate']:.1%} accept rate, "
                f"+{util['spec_accept_per_tick']:.2f} free tok/tick)"
            )
        if "host_sync_us_mean" in util:
            lines.append(
                f"host_sync: mean {util['host_sync_us_mean']:.1f}us  "
                f"p99 {util['host_sync_us_p99']:.1f}us  "
                f"({util['host_sync_share']:.1%} of tick"
                + (f", <= {util['host_fetches_max']} fetch/tick"
                   if "host_fetches_max" in util else "")
                + ")"
            )
    acct = tick_account(events)
    # one slot's state, all layers (the engine_build span says what the
    # slots carry besides K/V, and how many slots there are)
    build = next((e.get("args", {}) for e in events
                  if e.get("name") == "engine_build"), {})
    state_row_bytes = (build.get("state_bytes", 0)
                       / max(build.get("state_slots", 0), 1))
    if acct is not None:
        lines.append(
            f"== tick account ({acct['ticks']:.0f} dispatching ticks, "
            f"mean us) ==\n"
            + "  ".join(f"{name} {acct[name + '_us']:.0f}"
                        for name in MIXED_TICK_PHASES)
            + f"\ntick {acct['tick_us']:.0f}us; h2d "
            f"{acct['h2d_count']:.0f} transfers, "
            f"{acct['h2d_bytes']:.0f} bytes"
            + (f"; experts touched {acct['experts_touched']:.1f} a tick, "
               f"load max {acct['expert_load_max']:.1f} / mean "
               f"{acct['expert_load_mean']:.2f} tokens an expert, "
               f"{acct['state_slots_live']:.1f} conv-state slots live"
               if "experts_touched" in acct else "")
            + (f"; {acct['pairs_held_share']:.1%} of the pairs routed are "
               f"held, their rows moved in the grouped matmul's calls in "
               f"{acct['expert_rows_kernel_share']:.1%} of those ticks"
               if "pairs_held_share" in acct else "")
            + "".join(
                f"; {what} {acct[kind + '_scan_tokens']:.1f} tokens a "
                f"tick, {acct[kind + '_state_rows']:.1f} rows' {state} "
                f"read and written"
                + (f" ({acct[kind + '_state_rows'] * state_row_bytes / 2**20:.0f}"
                   " MiB a tick each way)" if state_row_bytes else "")
                + f", {acct['state_slots_live']:.1f} state slots live, the "
                f"state-update kernel in {acct[kind + '_kernel_share']:.1%} "
                "of those ticks"
                for kind, what, state in (
                    ("ssm", "state-space scan", "recurrent state"),
                    ("kda", "delta-rule recurrence", "matrix state"),
                    ("retention", "power-retention recurrence",
                     "symmetric-power state"))
                if kind + "_state_rows" in acct)
            + (f"; sparse-attention indexer: a layer scores "
               f"{acct['dsa_index_pages']:.0f} index-key pages a dispatch, "
               f"its tokens attend {acct['dsa_selected']:.0f} of the "
               f"{acct['dsa_visible']:.0f} positions they see "
               f"({acct['dsa_selected_share']:.1%}), "
               f"{acct['dsa_dense_tokens']:.1f} tokens attend all they see"
               if "dsa_visible" in acct else "")
            + "; pack wrote "
            f"{acct['pack_array_rows']:.1f} of {acct['rows']:.1f} rows as "
            f"arrays; context "
            f"{acct['context_tokens']:.0f} tokens/dispatch"
            + (f"; attention streams {acct['attn_pages']:.0f} pages a layer "
               f"in {acct['attn_grid_steps']:.0f} kv grid steps, "
               f"{acct['attn_slot_share']:.0%} of their page slots live"
               if "attn_pages" in acct else "")
            + (f"; {acct['attn_decode_tiles']:.1f} of "
               f"{acct['attn_live_tiles']:.1f} live query tiles a dispatch "
               f"({acct['attn_decode_tile_share']:.0%}) hold one token"
               if "attn_live_tiles" in acct else "")
            + (f"; {acct['tile_lane_occupancy']:.0%} of the tiled axis' "
               "lanes held a token" if "tile_lane_occupancy" in acct else "")
            + (f"; two page classes: a global layer streams "
               f"{acct['attn_pages_global']:.0f} pages, a window layer "
               f"{acct['attn_pages_window']:.0f}; "
               f"{acct['window_blocks_live']:.0f} window blocks live, "
               f"{acct['window_blocks_recycled']:.2f} recycled a tick"
               + (f"; {acct['attn_prefill_tile_share']:.0%} of the live "
                  "query tiles hold a prefill chunk's tokens (each streams "
                  "its row's pages again)"
                  if "attn_prefill_tile_share" in acct else "")
               if "attn_pages_window" in acct else "")
            + "; packed width "
            + " ".join(f"{w}x{n}" for w, n in acct["packed_widths"].items())
            + ("; programs (packed x dense width: ticks) "
               + " ".join(f"{p}:{n}" for p, n in acct["programs"].items())
               + f", {acct['dense_occupancy']:.0%} of dense lanes held a "
               "token" if "programs" in acct else "")
            + (f"; tick thread CPU {acct['thread_cpu_us']:.0f}us, "
               f"neither CPU nor device wait {acct['host_wait_us']:.0f}us"
               if "thread_cpu_us" in acct else "")
            + (f"\npublish: {acct['publish_overlapped_share']:.1%} of "
               f"{acct['publish_ticks']} dispatching ticks handed the "
               f"previous tick's {acct['publish_rows']:.1f} items out "
               "behind the dispatch (deliver off the device's critical "
               "path)" if "publish_ticks" in acct else "")
        )
        host = host_account(events)
        line = tick_timeline(events, profile) if host and profile else None
        if host is not None:
            lines.append(
                "host: "
                + (f"exposed {line['exposed_us']:.0f}us a tick = wake gap "
                   f"{line['wake_gap_us']:.0f} + serial "
                   f"{line['serial_us']:.0f} + launch gap "
                   f"{line['launch_gap_us']:.0f} (device program end to "
                   f"next program start, {line['ticks']} ticks of the "
                   "profile, joined by seq; the two gaps corrected by the "
                   f"device line's lead {line['lead_us']:.0f} +- "
                   f"{line['lead_error_us']:.0f}us); " if line else "")
                + f"{host['host_bound_share']:.1%} of {host['ticks']} "
                "dispatching ticks found the device done at the fetch "
                f"(the host set them); wait {host['device_wait_us']:.0f}us,"
                f" fetch after it {host['fetch_us']:.0f}us; collector "
                f"{host['gc_us']:.1f}us a tick in {host['gc_count']} runs"
                + (" (" + ", ".join(
                    f"{k} {v:.1f}" for k, v in
                    list(host["gc_by_phase_us"].items())[:4]) + ")"
                   if host["gc_by_phase_us"] else "")
        )
    setup = setup_spans(events)
    if setup:
        lines.append("== set-up ==")
        for sp in setup:
            extra = " ".join(f"{k}={v}" for k, v in sp["args"].items())
            lines.append(
                f"  {sp['name']:<22} {sp['dur_us'] / 1e3:>10.1f} ms  "
                f"compiles {sp['compiles']} ({sp['compile_misses']} not "
                f"from the cache)" + (f"  {extra}" if extra else ""))
        warm = [sp for sp in setup if sp["name"] == "warmup.bucket"]
        if warm:
            lines.append(
                f"  warm-up: {len(warm)} programs, "
                f"{sum(sp['dur_us'] for sp in warm) / 1e6:.2f} s (packed"
                " x dense width, s; * = compiled): " + " ".join(
                    f"{sp['args'].get('width')}x"
                    f"{sp['args'].get('dense', sp['args'].get('width'))}"
                    f"{'*' if sp['args'].get('compiled') else ''} "
                    f"{sp['dur_us'] / 1e6:.2f}" for sp in warm))
        build = next((sp["args"] for sp in setup
                      if sp["name"] == "engine_build"), {})
        if "pool_carried" in build:
            # how the tick's layer loop holds the K/V pool (PR 38)
            lines.append(
                f"  pool: pages {build.get('pool_page_shape')}, "
                + ("carried flat over (layer, block) and written in place"
                   if build["pool_carried"] else
                   "moved by layer slabs (not row-major on this device)"))
        if "weights_reput" in build:
            # the layouts the step reads its weights in (PR 54): what the
            # build moved, and what a program still re-lays out a tick
            left = next((sp["args"].get("weight_relayout_bytes")
                         for sp in setup if sp["name"] == "op_map"), None)
            lines.append(
                f"  weights: {build['weights_reput']} leaves "
                f"({build['weights_reput_bytes'] / 2**20:.1f} MiB) put into "
                "the layout the step reads them in; re-laid-out a tick, "
                "all programs: " + ("not read" if left is None
                                    else f"{left / 2**20:.1f} MiB"))
        stray = stray_compiles(events)
        lines.append("  compiles outside set-up: " + (", ".join(
            f"{n} in {where}" for where, n in sorted(stray.items()))
            or "none"))
    roof = roofline(events)
    if roof is not None:
        lines.append("== roofline ==")
        for kind in sorted(roof):
            r = roof[kind]
            lines.append(
                f"{kind:<6} {r['ticks']:.0f} ticks: "
                f"GB/s p50 {r['gbps_p50']:.3f}  p90 {r['gbps_p90']:.3f}"
                f"  p99 {r['gbps_p99']:.3f}; util p50 "
                f"{r['util_p50']:.2%}  p99 {r['util_p99']:.2%}  "
                f"mean {r['util_mean']:.2%}; device "
                f"{r['device_s_total'] * 1e3:.2f} ms"
            )
    tier = kv_tier(events)
    if tier is not None:
        lines.append(
            f"== kv_tier ==\n"
            f"spill {tier['spill_bytes'] / 2**20:.2f} MiB over "
            f"{tier['spill_ticks']} ticks; restore "
            f"{tier['restore_bytes'] / 2**20:.2f} MiB over "
            f"{tier['restore_ticks']} ticks"
            + (
                f"; restore latency p50 {tier['restore_us_p50']:.0f}us "
                f"p99 {tier['restore_us_p99']:.0f}us"
                if "restore_us_p50" in tier else ""
            )
        )
    staged = ttft_stage_table(events)
    if staged is not None:
        lines.append(format_ttft_stages(staged))
    lines.append(f"== top {top} slowest ticks ==")
    for ev in slowest_ticks(events, top):
        args = ev.get("args") or {}
        lines.append(
            f"  ts={ev['ts'] / 1e3:.2f}ms dur={ev.get('dur', 0.0):.0f}us "
            f"active={args.get('active_slots', '-')} "
            f"queue={args.get('queue_depth', '-')} "
            f"admitted={args.get('admitted', '-')}"
        )
    table = request_table(events)
    lines.append("== requests ==")
    lines.append(
        f"{'rid':>5} "
        + " ".join(f"{c + '_ms':>10}" for c in LIFECYCLE_COLUMNS)
        + f" {'fw_lag_ms':>9} {'wr_lag_ms':>9} {'wr_max_ms':>9} {'frames':>6}"
        + f" {'evict':>5} {'recov':>5} finish"
    )
    for rid in sorted(table, key=str):
        rec = table[rid]
        p = rec["phases_us"]

        def ms(name: str) -> str:
            return f"{p[name] / 1e3:.2f}" if name in p else "-"

        lines.append(
            f"{rid!s:>5} "
            + " ".join(f"{ms(c):>10}" for c in LIFECYCLE_COLUMNS)
            + "".join(
                f" {rec[k] / 1e3:>9.2f}" if rec[k] is not None
                else f" {'-':>9}"
                for k in ("first_write_lag_us", "write_lag_us",
                          "write_lag_max_us"))
            + f" {'-' if rec['frames'] is None else rec['frames']:>6}"
            + f" {rec['evictions']:>5} {rec['recoveries']:>5} "
            f"{rec['finish'] or '-'}"
        )
    return "\n".join(lines)


def main(argv: list[str] | None = None) -> str:
    p = argparse.ArgumentParser(
        description="Per-phase totals, slowest ticks, and per-request "
        "lifecycle tables from a serve --trace-out dump; multiple "
        "files (or --merge) stitch per-replica/per-process traces into "
        "one request-ordered timeline",
    )
    p.add_argument("trace", nargs="+",
                   help="trace-event JSON file(s) "
                   "(--trace-out / GET /debug/trace)")
    p.add_argument("--top", type=int, default=5,
                   help="how many slowest ticks to list")
    p.add_argument("--merge", default=None, metavar="OUT",
                   help="write the merged/rebased trace JSON to OUT "
                   "(implied merge mode; open at ui.perfetto.dev)")
    p.add_argument("--profile", default=None, metavar="XPLANE",
                   help="a jax.profiler .xplane.pb of the same run: adds "
                   "device time by the step's named scopes, from the op "
                   "map in the dump, and the exposed host a tick in its "
                   "three parts (needs jax to read the profile)")
    p.add_argument("--request-log", default=None, metavar="PATH",
                   help="canonical request log (--request-log JSONL) "
                   "for the same run: adds the per-tenant request/"
                   "token/cost breakdown section")
    args = p.parse_args(argv)
    if args.merge is not None or len(args.trace) > 1:
        merged = merge_traces(args.trace)
        out = format_merged(merged["traceEvents"])
        if args.merge:
            with open(args.merge, "w") as f:
                json.dump(merged, f)
            out += (f"\nwrote {len(merged['traceEvents'])} merged "
                    f"events to {args.merge}")
    else:
        out = format_summary(load_trace(args.trace[0]), top=args.top,
                             profile=args.profile)
    if args.profile is not None:
        with open(args.trace[0]) as f:
            data = json.load(f)
        op_map = (data.get("otherData", {}) if isinstance(data, dict)
                  else {}).get("op_map")
        dev = device_scopes(op_map, args.profile) if op_map else None
        out += "\n" + (format_device_scopes(dev) if dev else
                       "== device scopes ==\nnothing to read: the dump "
                       "has no op map or the profile no device operation")
    if args.request_log is not None:
        out += "\n" + format_tenants(load_request_log(args.request_log))
    print(out)
    return out


if __name__ == "__main__":
    main()
