"""``step_roofline`` for a ``ling_hybrid`` stack: the least time the chip
could take for the mean tick of the profiler window, with the tick's bytes and
operations from ``costs_ling_v3.py`` (weights outside the routed experts
once, the held experts the tick touched, the matrix state and the convolution
history of the rows the tick touched read and written, the latent layer's
rows of the live context read once, the head a row) / the device time the tick
took, in %.  ``step_roofline`` itself prices one kind of layer and no state,
and is not reported in such a cell."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_ling_v3.py
import costs_ling_v3  # noqa: E402
import tracefile  # noqa: E402

SAMPLES = 20


def read(run: dict) -> float | None:
    dt, ht, rec = run.get("device_trace"), run.get("host_trace"), run["client"]
    if (run["config"].get("model_type") != "ling_hybrid" or not dt
            or not dt.get("ticks") or not ht or run["peaks"] is None):
        return None
    p0, p1 = dt["wall"]
    ticks = [t for t in ht["ticks"] if p0 <= t["start"] < p1
             and "kda_state_rows" in t["args"]]
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
    cost = costs_ling_v3.tick_cost(
        run["config"], tokens=mean("prefill_tokens") + mean("decode_tokens"),
        rows=max(mean("active_slots"), 1.0), context_tokens=context / SAMPLES,
        experts_touched=mean("experts_touched"), pairs_held=mean("pairs_held"),
        state_rows=mean("kda_state_rows"), dtype=serve.get("dtype", "bf16"),
        cache_dtype=serve.get("cache_dtype", "bf16"))
    least_s, _bound = costs_ling_v3.least_seconds(cost, run["peaks"])
    return 100.0 * least_s / (dt["busy_s"] / dt["ticks"])
