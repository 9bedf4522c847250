"""Mean of (tick span - its host_sync phase): the part of a tick in which
the host is not waiting for the device (admission, plan, pack, dispatch,
deliver)."""


def read(run: dict) -> float | None:
    ht = run["host_trace"]
    if not ht or not ht["ticks"]:
        return None
    own = [t["dur_s"] - t["args"].get("host_sync_us", 0.0) / 1e6 for t in ht["ticks"]]
    return 1e3 * sum(own) / len(own)
