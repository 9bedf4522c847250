"""``step_roofline`` for a ``brumby`` stack: the least time the chip could take
for the mean tick of the profiler window, with the tick's bytes and operations
from ``costs_brumby.py`` (every layer's weights and the head once, the
power-retention state of the rows the tick touched read and written — nothing
reads the context) / the device time the tick took, in %.  ``step_roofline``
itself reads ``costs.py``'s one-kind-of-layer formula, which prices a K/V cache
this stack does not have and no state, and is not reported in such a cell."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_brumby.py


def read(run: dict) -> float | None:
    dt, ht = run.get("device_trace"), run.get("host_trace")
    if (run["config"].get("model_type") != "brumby" or not dt or not dt.get("ticks")
            or not ht or run["peaks"] is None):
        return None
    p0, p1 = dt["wall"]
    ticks = [t for t in ht["ticks"] if p0 <= t["start"] < p1
             and "retention_state_rows" in t["args"]]
    if not ticks:
        return None
    import costs_brumby

    mean = lambda key: sum(t["args"].get(key, 0) for t in ticks) / len(ticks)  # noqa: E731
    cost = costs_brumby.tick_cost(
        run["config"], tokens=mean("prefill_tokens") + mean("decode_tokens"),
        rows=max(mean("active_slots"), 1.0),
        state_rows=mean("retention_state_rows"),
        dtype=run["config"].get("serve", {}).get("dtype", "bf16"))
    least_s, _bound = costs_brumby.least_seconds(cost, run["peaks"])
    return 100.0 * least_s / (dt["busy_s"] / dt["ticks"])
