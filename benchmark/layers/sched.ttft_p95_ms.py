"""The client's TTFT tail (95th percentile, due -> first token) in the
traced run.  A per-layer metric with no bound until a cell holds the 200
requests a p95 wants (PERF.md section 2)."""
import stats


def read(run: dict) -> float | None:
    v = stats.percentile(stats.series(run["client"], stats.ttft_s), 95)
    return None if v is None else v * 1e3
