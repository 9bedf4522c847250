"""The power-retention state's update against ITS byte bound: the least time
the chip could take to read and write the state rows the mean profiled tick
touched (``retention_state_rows`` rows x every layer x 2 x one row's float32
state as the MATHEMATICS needs it, 34,080,768 B at the published widths, from
``costs_brumby.py`` — the program holds 6 % more: whole registers — at the
chip's memory bandwidth) / the device time a tick spends under the
``retention_scan`` scope, in %: the new state-update kernel's share of its
roofline, with whatever else the scope holds (a prefill row's chunk passes,
the gathers) in the denominator.  A program without the scope or the tick
argument reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_brumby.py
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    dt, ht, config = run.get("device_trace"), run.get("host_trace"), run["config"]
    if (config.get("model_type") != "brumby" or not dt or not dt.get("ticks")
            or not ht or run["peaks"] is None):
        return None
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == "retention_scan" for v in table.values()):
        return None
    share = tracefile.scope_share(run, lambda scope, kind: scope == "retention_scan")
    p0, p1 = dt["wall"]
    rows = [t["args"]["retention_state_rows"] for t in ht["ticks"]
            if p0 <= t["start"] < p1 and "retention_state_rows" in t["args"]]
    if not share or not rows:
        return None
    import costs_brumby

    least_s = (costs_brumby.state_update_bytes(config, sum(rows) / len(rows))
               / (run["peaks"]["hbm_gbps"] * 1e9))
    return 100.0 * least_s / (share / 100.0 * dt["busy_s"] / dt["ticks"])
