"""Median time from due (open) or send (closed) to the first token."""
import stats


def read(run: dict) -> float | None:
    return stats.percentile(stats.series(run["client"], stats.ttft_s), 50)
