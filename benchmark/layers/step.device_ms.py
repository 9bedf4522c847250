"""Device time of one tick: the union of device-operation intervals in
the profiler window / the ticks in it (counted by the program's
``serve.mixed_dispatch`` annotations; every replica ticks on its own
devices, so ticks per device = ticks / replicas)."""


def read(run: dict) -> float | None:
    dt = run["device_trace"]
    if not dt or not dt["ticks"]:
        return None
    return 1e3 * dt["busy_s"] / (dt["ticks"] / run["replicas"])
