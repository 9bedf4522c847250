"""The expert matmuls' share of their roofline at this stack's shape (128
held experts of 3 x 2,560 x 768 = 11.8 MB each): the least time the chip
could take to read the experts a tick TOUCHES (the tick argument
``experts_touched``, summed over the expert layers, x the bytes of one expert
from ``costs_ling_v3.py``, over the peak HBM rate) / the device time a tick
spends under the ``moe_experts`` scope, in %.  Memory-bound by construction:
an expert here sees about one token a tick."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_ling_v3.py
import costs_ling_v3  # noqa: E402
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    dt, ht, table = run.get("device_trace"), run.get("host_trace"), tracefile.op_table(run)
    if (run["config"].get("model_type") != "ling_hybrid" or not dt
            or not dt.get("ticks") or not ht or not table or run["peaks"] is None):
        return None
    p0, p1 = dt["wall"]
    touched = [t["args"]["experts_touched"] for t in ht["ticks"]
               if p0 <= t["start"] < p1 and "experts_touched" in t["args"]]
    scope_s = sum(seconds for name, seconds in dt["ops_s"].items()
                  if (table.get(name.rsplit(" ", 1)[0]) or [""])[0] == "moe_experts")
    if not touched or not scope_s:
        return None
    nbytes = costs_ling_v3.touched_expert_bytes(
        run["config"], sum(touched) / len(touched),
        run["config"].get("serve", {}).get("dtype", "bf16"))
    least_s = nbytes / (run["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (scope_s / dt["ticks"])
