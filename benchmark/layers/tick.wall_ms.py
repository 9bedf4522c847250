"""Mean wall time of a tick (the recorder's ``tick`` span) in the window."""


def read(run: dict) -> float | None:
    ht = run["host_trace"]
    if not ht or not ht["ticks"]:
        return None
    return 1e3 * sum(t["dur_s"] for t in ht["ticks"]) / len(ht["ticks"])
