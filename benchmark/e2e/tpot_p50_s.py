"""Median over requests of (last - first token) / (tokens - 1)."""
import stats


def read(run: dict) -> float | None:
    return stats.percentile(stats.series(run["client"], stats.tpot_s), 50)
