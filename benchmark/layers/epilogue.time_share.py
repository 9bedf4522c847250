"""The sampling tail's own device time (the fused norm -> lm head ->
sample kernel; under TP the XLA tail is not separable by name and the
reader returns nothing) as a share of device busy time."""
import devtrace

NEEDLES = ["epilogue"]


def read(run: dict) -> float | None:
    v = devtrace.share_by_name(run["device_trace"], NEEDLES)
    return None if not v else 100.0 * v
