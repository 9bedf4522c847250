"""Arithmetic over the load generator's record.  Stdlib only.

The yardstick: later PRs may change the program, not these definitions.
A request is *measured* when its due time (open loop: the seeded due
time; closed loop: the moment its client became free to send) falls in
the window ``[w0, w1)`` and it is not a ramp request cut short for the
stationary start.  A measured request is *good* when it came back 200,
finished ``length`` and delivered exactly the tokens asked for; any
other measured request is *failed* and has no latency at all.
"""

from __future__ import annotations

import math
import re


def percentile(values: list[float], p: float) -> float | None:
    """Linear interpolation between closest ranks (numpy's default)."""
    if not values:
        return None
    xs = sorted(values)
    k = (len(xs) - 1) * p / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def measured(record: dict) -> list[dict]:
    w0, w1 = record["window"]
    return [r for r in record["requests"]
            if w0 <= r["due"] < w1 and not r.get("ramp_cut")]


def good(r: dict) -> bool:
    return (r["status"] == 200 and not r["error"] and r["finish"] == "length"
            and len(r["tokens"]) == r["max_tokens"])


def ttft_s(r: dict) -> float | None:
    """Due time -> first token, on the client's clock."""
    return r["times"][0] - r["due"] if good(r) and r["times"] else None


def tpot_s(r: dict) -> float | None:
    """(last token - first token) / (tokens - 1); None for one token."""
    if not good(r) or len(r["times"]) < 2:
        return None
    return (r["times"][-1] - r["times"][0]) / (len(r["times"]) - 1)


def late_s(r: dict) -> float | None:
    return None if r["sent"] is None else max(0.0, r["sent"] - r["due"])


def tokens_in_window(record: dict) -> int:
    """Output tokens that ARRIVED in the window, whichever request they
    belong to (ramp requests still running included)."""
    w0, w1 = record["window"]
    return sum(1 for r in record["requests"] for t in r["times"] if w0 <= t < w1)


def window_seconds(record: dict) -> float:
    return record["window"][1] - record["window"][0]


def series(record: dict, fn) -> list[float]:
    return [v for v in (fn(r) for r in measured(record)) if v is not None]


def in_flight(record: dict, at: float) -> int:
    """Requests due but not ended at time ``at`` (the backlog the knee
    sweep watches)."""
    return sum(1 for r in record["requests"]
               if r["due"] <= at and r.get("end", math.inf) > at)


# ----- /metrics text -> numbers --------------------------------------

def scrape_values(text: str, name: str, labels: dict | None = None) -> list[float]:
    """Every sample of one series: one per label set (replica, ...) that
    carries all of ``labels``."""
    out = []
    for m in re.finditer(rf"^llm_serve_{name}(?:\{{([^}}]*)\}})? (\S+)$", text, re.M):
        have = dict(re.findall(r'(\w+)="([^"]*)"', m.group(1) or ""))
        if all(have.get(k) == str(v) for k, v in (labels or {}).items()):
            out.append(float(m.group(2)))
    return out


def scrape_sum(text: str, name: str, labels: dict | None = None) -> float | None:
    """Sum of a gauge/counter over its label sets (one per replica)."""
    vals = scrape_values(text, name, labels)
    return sum(vals) if vals else None


def scrape_mean(text: str, name: str, labels: dict | None = None) -> float | None:
    vals = scrape_values(text, name, labels)
    return sum(vals) / len(vals) if vals else None


def scrape_delta(record: dict, name: str) -> float | None:
    """Counter at the window's end minus at its start."""
    edges = [scrape_sum(record["scrapes"].get(k, {}).get("/metrics", {})
                        .get("text", ""), name) for k in ("start", "end")]
    return None if None in edges else edges[1] - edges[0]
