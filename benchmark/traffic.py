"""The one general traffic generator: a traffic file + a seed -> a request table.

Stdlib only (the load generator imports it and must never import JAX).

A traffic file (``benchmark/traffic/<name>.json``) is data:

    loop            "closed" (each client sends its next request when the
                    last one ended) or "open" (seeded Poisson due times)
    ramp_s          seconds of the same traffic sent before the window
    prompt_tokens   a distribution (below)
    output_tokens   a distribution
    stream_share    share of requests sent with "stream": true
    sharing         {"kind": "none"} | {"kind": "prefix", "groups": G,
                    "prefix_tokens": dist} | {"kind": "sessions",
                    "turns": dist, "think_s": dist}
    bursts          null | {"period_s": P, "burst_s": B, "factor": F}
    block           requests per stratified block (default 64)
    order_seed      optional whole number: the ORDER of lengths and gaps
                    inside a block comes from it, not from the run's seed

Distributions: {"dist": "constant", "value"}, {"dist": "uniform", "min",
"max"}, {"dist": "lognormal", "median", "sigma", "min", "max"},
{"dist": "choice", "values", "weights"}.

Steadiness rule (the builder's contract): every seed gets the SAME set
of sizes and arrival gaps, in another order.  So nothing is drawn at
random from a distribution: each block of ``block`` requests takes the
distribution's ``block`` mid-quantiles, and the seed only shuffles the
order inside the block (and makes the token ids).  Any ``block``
consecutive requests therefore carry the same multiset of lengths and
gaps whatever the seed.

Where order itself changes the work - an open loop queues differently
when two long prompts arrive together - a mix sets ``order_seed``: every
block then has the one order that number gives, so the traffic is one
periodic sequence, and the run's seed only chooses where in the period
the run starts (and makes the token ids).  Measured on the chip (PR 23,
chat-open): two runs of one seed agreed to 0.05 % in tokens/s and 1-7 %
in median TTFT, runs of different seeds differed by 5 % and 25 %.
"""

from __future__ import annotations

import json
import math
import random
from statistics import NormalDist

DEFAULT_BLOCK = 64


def load_traffic(path: str) -> dict:
    with open(path) as f:
        t = json.load(f)
    if t.get("loop") not in ("open", "closed"):
        raise ValueError(f"{path}: 'loop' must be 'open' or 'closed'")
    for key in ("prompt_tokens", "output_tokens"):
        if key not in t:
            raise ValueError(f"{path}: missing {key!r}")
    return t


def quantile(dist: dict, u: float) -> float:
    """Inverse CDF of a distribution family at ``u`` in (0, 1)."""
    kind = dist["dist"]
    if kind == "constant":
        return float(dist["value"])
    if kind == "uniform":
        return dist["min"] + u * (dist["max"] - dist["min"])
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
        return min(max(x, dist["min"]), dist["max"])
    if kind == "exponential":
        return -dist["mean"] * math.log1p(-u)
    if kind == "choice":
        weights = dist.get("weights") or [1.0] * len(dist["values"])
        acc, total = 0.0, float(sum(weights))
        for v, w in zip(dist["values"], weights):
            acc += w / total
            if u <= acc:
                return float(v)
        return float(dist["values"][-1])
    raise ValueError(f"unknown distribution family {kind!r}")


def dist_bounds(dist: dict) -> tuple[int, int]:
    """(least, greatest) whole value the family can produce."""
    if dist["dist"] == "constant":
        return int(dist["value"]), int(dist["value"])
    if dist["dist"] == "choice":
        return int(min(dist["values"])), int(max(dist["values"]))
    return int(dist["min"]), int(dist["max"])


def stratified(dist: dict, n: int, rng: random.Random, block: int,
               whole: bool = True) -> list:
    """``n`` values: per block of ``block`` the family's mid-quantiles,
    shuffled by ``rng``."""
    out: list = []
    while len(out) < n:
        vals = [quantile(dist, (i + 0.5) / block) for i in range(block)]
        if whole:
            vals = [int(round(v)) for v in vals]
        rng.shuffle(vals)
        out.extend(vals)
    return out[:n]


def _burst_warp(bursts: dict, rate: float):
    """Map 'work time' (unit-rate-scaled) to wall time under a periodic
    on/off intensity with the same mean rate: ``factor`` x the mean for
    ``burst_s`` of every ``period_s``, and whatever is left in between."""
    period, on, factor = bursts["period_s"], bursts["burst_s"], bursts["factor"]
    hi = rate * factor
    lo = (rate * period - hi * on) / (period - on)
    if lo <= 0:
        raise ValueError("bursts: factor * burst_s must stay below period_s")
    per_period = rate * period  # expected arrivals a period

    def warp(work: float) -> float:
        k, rest = divmod(work * rate, per_period)
        if rest <= hi * on:
            return k * period + rest / hi
        return k * period + on + (rest - hi * on) / lo

    return warp


def due_times_from_gaps(gaps: list[float], rate_rps: float,
                        bursts: dict | None = None) -> list[float]:
    warp = _burst_warp(bursts, rate_rps) if bursts else (lambda t: t)
    out, t = [], 0.0
    for g in gaps:
        t += g
        out.append(warp(t))
    return out


def due_times(rate_rps: float, n: int, rng: random.Random, block: int,
              bursts: dict | None = None) -> list[float]:
    """Open-loop due times from 0: exponential gaps (mean 1/rate) taken
    as stratified quantiles, so every block of ``block`` gaps spans the
    same time whatever the seed; optional burst time-warp."""
    gaps = stratified({"dist": "exponential", "mean": 1.0 / rate_rps}, n, rng,
                      block, whole=False)
    return due_times_from_gaps(gaps, rate_rps, bursts)


def token_ids(rng: random.Random, n: int, vocab: int) -> list[int]:
    return rng.choices(range(1, vocab), k=n)


def _block(traffic: dict, seed: int, b: int, vocab: int) -> list[dict]:
    """Block ``b`` of the table: ``block`` requests that depend on
    (traffic, seed, b, vocab) alone, so a longer table only ADDS blocks."""
    block = int(traffic.get("block", DEFAULT_BLOCK))
    ids_rng = random.Random(f"{seed}:block:{b}")
    order = traffic.get("order_seed")
    rng = ids_rng if order is None else random.Random(f"{order}:order")
    prompts = stratified(traffic["prompt_tokens"], block, rng, block)
    outputs = stratified(traffic["output_tokens"], block, rng, block)
    share = float(traffic.get("stream_share", 1.0))
    streams = stratified({"dist": "choice", "values": [1, 0],
                          "weights": [share, 1.0 - share]}, block, rng, block)
    sharing = traffic.get("sharing") or {"kind": "none"}
    kind = sharing.get("kind", "none")
    p_max = dist_bounds(traffic["prompt_tokens"])[1]
    base = b * block
    reqs = [dict(idx=base + i, max_tokens=max(1, outputs[i]),
                 stream=bool(streams[i]), session=base + i, turn=0, think_s=0.0)
            for i in range(block)]
    if kind == "none":
        for r, p in zip(reqs, prompts):
            r["prompt"] = token_ids(ids_rng, max(1, p), vocab)
    elif kind == "prefix":
        # the shared prefixes belong to the seed, not to a block
        groups = int(sharing["groups"])
        grng = random.Random(f"{seed}:prefixes")
        plens = stratified(sharing["prefix_tokens"], groups, grng, groups)
        prefixes = [token_ids(grng, k, vocab) for k in plens]
        order = stratified({"dist": "uniform", "min": -0.5,
                            "max": groups - 0.5}, block, rng, block)
        for r, p, g in zip(reqs, prompts, order):
            head = prefixes[g][:max(p - 1, 0)]
            r["prompt"] = head + token_ids(ids_rng, max(1, p - len(head)), vocab)
            r["group"] = g
    elif kind == "sessions":
        # a session's turns are consecutive requests of one block
        turns = stratified(sharing["turns"], block, rng, block)
        thinks = stratified(sharing["think_s"], block, rng, block, whole=False)
        i = s = 0
        while i < block:
            length = 0
            for turn in range(max(1, turns[s])):
                if i >= block:
                    break
                new = min(max(1, prompts[i]), p_max)
                if turn and length + new > p_max:
                    break  # the history has outgrown the longest prompt
                reqs[i].update(session=base + s, turn=turn, think_s=thinks[i],
                               prompt=token_ids(ids_rng, new, vocab))
                length += new + reqs[i]["max_tokens"]
                i += 1
            s += 1
    else:
        raise ValueError(f"unknown sharing kind {kind!r}")
    history: dict[int, int] = {}
    for r in reqs:
        before = history.get(r["session"], 0) if r["turn"] else 0
        r["prompt_len"] = before + len(r["prompt"])
        history[r["session"]] = r["prompt_len"] + r["max_tokens"]
    return reqs


def start_offset(traffic: dict, seed: int) -> int:
    """Where in the period a run starts: 0 unless the mix fixes its order."""
    if traffic.get("order_seed") is None:
        return 0
    block = int(traffic.get("block", DEFAULT_BLOCK))
    return random.Random(f"{seed}:offset").randrange(block)


def request_table(traffic: dict, seed: int, n: int, vocab: int) -> list[dict]:
    """The first ``n`` requests, a pure function of (traffic, seed,
    vocab); a longer table has the shorter one as its prefix.  Each is
    ``{"idx", "prompt", "prompt_len", "max_tokens", "stream", "session",
    "turn", "think_s"}``.  A request with ``turn > 0`` is sent
    ``think_s`` after its session's previous turn ended, and its
    ``prompt`` holds only the NEW tokens of the turn: the load generator
    puts the history (earlier prompt + the server's own answer) in
    front.  ``prompt_len`` is the length as sent, history included -
    exact, because every answer runs to ``max_tokens``."""
    block = int(traffic.get("block", DEFAULT_BLOCK))
    skip = start_offset(traffic, seed)
    out: list[dict] = []
    b = 0
    while len(out) < skip + n + block:
        out.extend(_block(traffic, seed, b, vocab))
        b += 1
    out = out[skip:]
    while out and out[0]["turn"]:  # a session cut by the start: drop its rest
        out.pop(0)
    out = out[:n]
    for i, r in enumerate(out):
        r["idx"] = i
    return out


def arrival_times(traffic: dict, seed: int, rate_rps: float, n: int) -> list[float]:
    """Open-loop due times from 0 for ``n`` arrivals: the seed's own
    shuffle of the stratified gaps, or - where the mix fixes its order -
    the one periodic sequence of gaps, entered where the table is."""
    block = int(traffic.get("block", DEFAULT_BLOCK))
    order = traffic.get("order_seed")
    if order is None:
        return due_times(rate_rps, n, random.Random(f"{seed}:gaps"), block,
                         traffic.get("bursts"))
    skip = start_offset(traffic, seed)
    gaps: list[float] = []
    while len(gaps) < skip + n:  # every block of gaps in the SAME order
        gaps.extend(stratified({"dist": "exponential", "mean": 1.0 / rate_rps},
                               block, random.Random(f"{order}:gaps"), block,
                               whole=False))
    return due_times_from_gaps(gaps[skip:skip + n], rate_rps, traffic.get("bursts"))


def limits(traffic: dict) -> tuple[int, int]:
    """(longest prompt, most output tokens) the mix can ask for: the
    server's ``--prompt-len`` / ``--max-tokens``."""
    return (dist_bounds(traffic["prompt_tokens"])[1],
            dist_bounds(traffic["output_tokens"])[1])


def phase_fractions(n: int, rng: random.Random) -> list[float]:
    """Closed-loop stationary start: client ``i``'s first (ramp) request
    is cut to this fraction of its drawn answer, so the clients begin
    spread over a request's life instead of in lockstep."""
    fr = [(i + 0.5) / n for i in range(n)]
    rng.shuffle(fr)
    return fr
