"""``step_roofline`` for a ``mimo_v2`` stack: the least time the chip could
take for the mean tick of the profiler window, with the tick's bytes and
operations from ``costs_mimo_v2.py`` (weights outside the routed experts
once, the held experts the tick touched, the global layers' pages of the live
context and the window layers' pages of the rows' windows read once, the
tick's tokens written in both, attention over what each kind sees, the head a
row) / the device time the tick took, in %.  ``step_roofline`` itself prices
one kind of layer and K as wide as V, and is not reported in such a cell."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_mimo_v2.py
import costs_mimo_v2  # noqa: E402,F401
import tracefile  # noqa: E402

SAMPLES = 20


def read(run: dict) -> float | None:
    dt, ht, rec = run.get("device_trace"), run.get("host_trace"), run["client"]
    if (run["config"].get("model_type") != "mimo_v2" or not dt
            or not dt.get("ticks") or not ht or run["peaks"] is None):
        return None
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
    cost = costs_mimo_v2.tick_cost(
        run["config"], tokens=mean("prefill_tokens") + mean("decode_tokens"),
        rows=max(mean("active_slots"), 1.0), context_tokens=context / SAMPLES,
        experts_touched=mean("experts_touched"), pairs_held=mean("pairs_held"),
        dtype=serve.get("dtype", "bf16"),
        cache_dtype=serve.get("cache_dtype", "bf16"))
    least_s, _bound = costs_mimo_v2.least_seconds(cost, run["peaks"])
    return 100.0 * least_s / (dt["busy_s"] / dt["ticks"])
