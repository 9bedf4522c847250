"""Least time the chip could take for the mean tick of the profiler
window (costs.py: weights once per tick, K/V of the live context, the
tokens' matmuls) / the device time the tick took.  Tokens and rows per
tick come from the recorder's tick arguments, the live context from the
client's own record of prompt lengths and token arrivals."""
import costs

SAMPLES = 20


def read(run: dict) -> float | None:
    dt, ht, rec = run["device_trace"], run["host_trace"], run["client"]
    if not dt or not dt["ticks"] or not ht or run["peaks"] is None:
        return None
    p0, p1 = dt["wall"]
    ticks = [t for t in ht["ticks"] if p0 <= t["start"] < p1
             and t["args"].get("prefill_tokens", 0) + t["args"].get("decode_tokens", 0)]
    if not ticks:
        return None
    tokens = sum(t["args"]["prefill_tokens"] + t["args"]["decode_tokens"]
                 for t in ticks) / len(ticks)
    rows = sum(t["args"].get("active_slots", 0) for t in ticks) / len(ticks)
    context = 0.0
    for i in range(SAMPLES):
        at = p0 + (i + 0.5) * (p1 - p0) / SAMPLES
        context += sum(r["prompt_len"] + sum(1 for x in r["times"] if x <= at)
                       for r in rec["requests"]
                       if r["sent"] is not None and r["sent"] <= at < r.get("end", 0))
    context /= SAMPLES * run["replicas"]  # live context of ONE replica's tick
    serve = run["config"].get("serve", {})
    cost = costs.tick_cost(run["config"], tokens=tokens, rows=max(rows, 1.0),
                           context_tokens=context, dtype=serve.get("dtype", "bf16"),
                           cache_dtype=serve.get("cache_dtype", "bf16"), tp=run["tp"])
    least_s, _bound = costs.least_seconds(cost, run["peaks"])
    device_s = dt["busy_s"] / (dt["ticks"] / run["replicas"])
    return 100.0 * least_s / device_s
