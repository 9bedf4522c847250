"""What the ``dsa.*`` and ``glm-5.*`` readers share (PR 58).  Not a metric's
reader (no metric has this name): like ``tracefile.py`` it lies beside the
readers, which put their own directory on the path and import it.

Every function returns None for a configuration without a sparse-attention
indexer, and where the program has no such scope, counter or argument (the
parent of PR 58 cannot run the configuration at all): the metric is then left
out of the line."""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py, ticktimeline.py
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_glm_dsa.py, devtrace.py
import costs_glm_dsa  # noqa: E402
import devtrace  # noqa: E402
import ticktimeline  # noqa: E402
import tracefile  # noqa: E402
import ttftstages  # noqa: E402

SCORE, SELECT, ATTN = "dsa_score", "dsa_select", "dsa_attn"
_events: dict[str, list | None] = {}


def has_indexer(run: dict) -> bool:
    return bool(run["config"].get("index_topk"))


def scope_share(run: dict, scopes: tuple[str, ...]) -> float | None:
    """Own time of the operations the op map puts under ``scopes``, in % of
    device busy time."""
    if not has_indexer(run):
        return None
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] in scopes for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda s, kind: s in scopes)


def device_ops(run: dict) -> list | None:
    """The first device plane's operations ``[name, start_ns, dur_ns]`` of
    the traced run's own profile, or None."""
    found = devtrace.find_xplane(
        str(tracefile.OUT / f"{run['workload']}-{run['seed']}" / "profile"))
    if found is None:
        return None
    if found not in _events:
        try:
            planes = devtrace.device_planes(devtrace.read_xplane(found))
            _events[found] = (devtrace.line_events(planes[0], devtrace.OPS_LINE)
                              if planes else None)
        except (OSError, ValueError, ImportError):
            _events[found] = None
    return _events[found]


def decode_ticks(run: dict) -> list[dict]:
    """The capture's ticks with no prompt token aboard, joined to their
    device programs by ``seq`` (``step.decode_device_ms``'s ticks); none
    for fewer than ``ttftstages.MIN_TICKS``."""
    rows = [r for r in ticktimeline.rows(run)
            if ttftstages.kind(r["tick"]["args"]) == "decode"
            and r["tick"]["args"].get("dsa_visible")]
    return rows if len(rows) >= ttftstages.MIN_TICKS else []


def decode_scope_seconds(run: dict, rows: list[dict], scope: str) -> float:
    """Device seconds, summed over ``rows``' programs, of the operations the
    op map puts under ``scope`` (own time is not needed: a kernel's call has
    no child operation)."""
    from bisect import bisect_right

    table, ops = tracefile.op_table(run), device_ops(run)
    if not table or not ops:
        return 0.0
    spans = sorted((r["program"][0], r["program"][1]) for r in rows)
    starts = [s for s, _ in spans]
    total = 0.0
    for name, start, dur in ops:
        known = table.get(name.rsplit(" ", 1)[0])  # (read_xplane shortened it)
        if not known or known[0] != scope:
            continue
        i = bisect_right(starts, start) - 1
        if i >= 0 and start < spans[i][1]:
            total += dur / 1e9
    return total


def decode_roofline(run: dict, scope: str, arg: str, bytes_of) -> float | None:
    """A kernel's share of its byte bound over the capture's decode-only
    ticks: the least time the chip could take to read what the mathematics
    needs (``bytes_of(config, tick arg ``arg`` summed over those ticks)`` a
    layer x the layers) over the peak HBM rate / the device time those
    ticks spend under ``scope``, in %."""
    dt = run.get("device_trace")
    if not has_indexer(run) or not dt or run["peaks"] is None:
        return None
    rows = decode_ticks(run)
    if not rows:
        return None
    seconds = decode_scope_seconds(run, rows, scope)
    if not seconds:
        return None
    serve = run["config"].get("serve", {})
    positions = sum(r["tick"]["args"][arg] for r in rows)
    nbytes = run["config"]["num_hidden_layers"] * bytes_of(
        run["config"], positions, serve.get("cache_dtype", "bf16"))
    return 100.0 * nbytes / (run["peaks"]["hbm_gbps"] * 1e9) / seconds


def step_roofline(run: dict) -> float | None:
    """The least time the chip could take for the mean tick of the profiler
    window (``costs_glm_dsa.tick_cost``: weights outside the routed experts
    once, the held experts the tick touched, an index key a position seen and
    a latent row a position attended, the tick's rows and keys written, the
    scores, absorbed attention over the selection, the head a row) / the
    device time the tick took, in %."""
    dt, ht = run.get("device_trace"), run.get("host_trace")
    if (not has_indexer(run) or not dt or not dt.get("ticks") or not ht
            or run["peaks"] is None):
        return None
    p0, p1 = dt["wall"]
    ticks = [t for t in ht["ticks"] if p0 <= t["start"] < p1
             and "dsa_visible" in t["args"]]
    if not ticks:
        return None
    mean = lambda key: sum(t["args"].get(key, 0) for t in ticks) / len(ticks)  # noqa: E731
    serve = run["config"].get("serve", {})
    cost = costs_glm_dsa.tick_cost(
        run["config"], tokens=mean("prefill_tokens") + mean("decode_tokens"),
        rows=max(mean("active_slots"), 1.0), visible=mean("dsa_visible"),
        selected=mean("dsa_selected"),
        experts_touched=mean("experts_touched"), pairs_held=mean("pairs_held"),
        dtype=serve.get("dtype", "bf16"),
        cache_dtype=serve.get("cache_dtype", "bf16"))
    least_s, _bound = costs_glm_dsa.least_seconds(cost, run["peaks"])
    return 100.0 * least_s / (dt["busy_s"] / dt["ticks"])
