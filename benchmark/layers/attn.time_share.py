"""The ragged paged attention kernel's own device time as a share of
device busy time in the profiler window."""
import devtrace

NEEDLES = ["ragged"]


def read(run: dict) -> float | None:
    v = devtrace.share_by_name(run["device_trace"], NEEDLES)
    return None if v is None else 100.0 * v
