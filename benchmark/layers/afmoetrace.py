"""What the ``afmoe.*`` readers share (PR 50).  Not a metric's reader (no
metric has this name): like ``tracefile.py`` it lies beside the readers,
which put their own directory on the path and import it.

Every function returns None for another architecture, and where the program
has no such scope, span or argument (the parent of PR 50 cannot run the
configuration at all): the metric is then left out of the line."""

from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_afmoe.py
import costs_afmoe  # noqa: E402
import tracefile  # noqa: E402

KERNEL = "ragged_paged_attention"
SAMPLES = 20


def is_afmoe(run: dict) -> bool:
    return run["config"].get("model_type") == "afmoe"


def scope_share(run: dict, scope: str) -> float | None:
    """Own time of the operations the op map puts under ``scope``, in % of
    device busy time."""
    if not is_afmoe(run):
        return None
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == scope for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda s, kind: s == scope)


def _traced(run: dict):
    dt, ht = run.get("device_trace"), run.get("host_trace")
    table = tracefile.op_table(run)
    if (not is_afmoe(run) or not dt or not dt.get("ticks") or not ht
            or not table or run["peaks"] is None):
        return None
    return dt, ht, table


def scope_seconds(dt: dict, table: dict, scope: str, needle: str = "") -> float:
    """Device seconds of the profile's operations under ``scope`` whose
    name holds ``needle``."""
    return sum(seconds for name, seconds in dt["ops_s"].items()
               if needle in name
               and (table.get(name.rsplit(" ", 1)[0]) or [""])[0] == scope)


def profiled_arg_mean(dt: dict, ht: dict, arg: str) -> float | None:
    """Mean of a tick argument over the ticks inside the profiler window
    that carry it."""
    p0, p1 = dt["wall"]
    vals = [t["args"][arg] for t in ht["ticks"]
            if p0 <= t["start"] < p1 and arg in t["args"]]
    return sum(vals) / len(vals) if vals else None


def kernel_roofline(run: dict, kind: str) -> float | None:
    """The ragged kernel's share of its byte bound over the pages of
    ``kind``'s class, the kernel ALONE: the least time the chip could take
    to read what the kind's calls are asked to stream (the tick argument
    ``attn_pages_<kind>`` - per layer of the kind, the pages in every query
    tile's visible range: a tile re-reads its row's pages - x the block
    size x the bytes a token holds in the kind's layers) over the peak HBM
    rate / the device time a tick spends in operations named
    ``ragged_paged_attention`` under the ``attn_<kind>`` scope, in %."""
    got = _traced(run)
    if got is None:
        return None
    dt, ht, table = got
    pages = profiled_arg_mean(dt, ht, "attn_pages_" + kind)
    kernel_s = scope_seconds(dt, table, "attn_" + kind, KERNEL)
    if not pages or not kernel_s:
        return None
    serve = run["config"].get("serve", {})
    nbytes = costs_afmoe.attention_bytes(
        run["config"], pages if kind == "global" else 0.0,
        pages if kind == "window" else 0.0,
        serve.get("block_size", 64), serve.get("cache_dtype", "bf16"))
    least_s = nbytes / (run["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (kernel_s / dt["ticks"])


def experts_roofline(run: dict) -> float | None:
    """The least time the chip could take to read the experts a tick
    TOUCHES (``experts_touched``, summed over the expert layers, x the
    bytes of one expert) over the peak HBM rate / the device time a tick
    spends under the ``moe_experts`` scope, in %."""
    got = _traced(run)
    if got is None:
        return None
    dt, ht, table = got
    touched = profiled_arg_mean(dt, ht, "experts_touched")
    scope_s = scope_seconds(dt, table, "moe_experts")
    if not touched or not scope_s:
        return None
    nbytes = costs_afmoe.touched_expert_bytes(
        run["config"], touched,
        run["config"].get("serve", {}).get("dtype", "bf16"))
    least_s = nbytes / (run["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (scope_s / dt["ticks"])


def step_roofline(run: dict) -> float | None:
    """The least time the chip could take for the mean tick of the
    profiler window (``costs_afmoe.tick_cost``: weights outside the routed
    experts once, the held experts the tick touched, each class's pages
    read ONCE, the tick's tokens written, attention over what each kind
    sees, the head a row) / the device time the tick took, in %."""
    got = _traced(run)
    if got is None:
        return None
    dt, ht, _ = got
    rec = run["client"]
    p0, p1 = dt["wall"]
    ticks = [t for t in ht["ticks"] if p0 <= t["start"] < p1
             and "pairs_held" in t["args"]]
    if not ticks:
        return None
    mean = lambda key: sum(t["args"].get(key, 0) for t in ticks) / len(ticks)  # noqa: E731
    context = 0.0
    for i in range(SAMPLES):
        at = p0 + (i + 0.5) * (p1 - p0) / SAMPLES
        context += sum(r["prompt_len"] + sum(1 for x in r["times"] if x <= at)
                       for r in rec["requests"]
                       if r["sent"] is not None and r["sent"] <= at < r.get("end", 0))
    serve = run["config"].get("serve", {})
    cost = costs_afmoe.tick_cost(
        run["config"], tokens=mean("prefill_tokens") + mean("decode_tokens"),
        rows=max(mean("active_slots"), 1.0), context_tokens=context / SAMPLES,
        experts_touched=mean("experts_touched"), pairs_held=mean("pairs_held"),
        dtype=serve.get("dtype", "bf16"),
        cache_dtype=serve.get("cache_dtype", "bf16"))
    least_s, _bound = costs_afmoe.least_seconds(cost, run["peaks"])
    return 100.0 * least_s / (dt["busy_s"] / dt["ticks"])


def tick_ratio(run: dict, num: str, den: str) -> float | None:
    """Sum over the window's dispatching ticks of argument ``num`` over
    that of ``den``."""
    if not is_afmoe(run):
        return None
    ticks = [t["args"] for t in tracefile.dispatching_ticks(run)
             if num in t["args"] and den in t["args"]]
    total = sum(a[den] for a in ticks)
    return sum(a[num] for a in ticks) / total if total else None
