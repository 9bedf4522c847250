"""``step_roofline`` for a ``falcon_h1`` stack: the least time the chip could
take for the mean tick of the profiler window, with the tick's bytes and
operations from ``costs_falcon_h1.py`` (every layer's weights and the head
once, K/V of the live context, the recurrent state and the convolution
history of the rows the tick touched, read and written) / the device time
the tick took, in %.  ``step_roofline`` itself reads ``costs.py``'s
one-kind-of-layer formula, which knows no mixer and no state."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_falcon_h1.py
import costs_falcon_h1  # noqa: E402

SAMPLES = 20


def read(run: dict) -> float | None:
    dt, ht, rec = run.get("device_trace"), run.get("host_trace"), run["client"]
    if (run["config"].get("model_type") != "falcon_h1" or not dt or not dt.get("ticks")
            or not ht or run["peaks"] is None):
        return None
    p0, p1 = dt["wall"]
    ticks = [t for t in ht["ticks"] if p0 <= t["start"] < p1
             and "ssm_state_rows" in t["args"]]
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
    cost = costs_falcon_h1.tick_cost(
        run["config"], tokens=mean("prefill_tokens") + mean("decode_tokens"),
        rows=max(mean("active_slots"), 1.0), context_tokens=context / SAMPLES,
        state_rows=mean("ssm_state_rows"), dtype=serve.get("dtype", "bf16"),
        cache_dtype=serve.get("cache_dtype", "bf16"))
    least_s, _bound = costs_falcon_h1.least_seconds(cost, run["peaks"])
    return 100.0 * least_s / (dt["busy_s"] / dt["ticks"])
