"""The 95th percentile of ``ttft.lane_wait_p50_ms``'s stage (admission ->
instant ``lane``): the lane is a single server whose job is a whole prompt,
so under arrivals its queue is the TTFT tail."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ttftstages.py lies beside the readers
import ttftstages  # noqa: E402


def read(run: dict) -> float | None:
    return ttftstages.stage_percentile_ms(run, "prefill", "lane", 95)
