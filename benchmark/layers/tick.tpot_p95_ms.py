"""The client's TPOT tail (95th percentile over requests of (last - first
token) / (tokens - 1)) in the traced run; no bound, as for the TTFT tail."""
import stats


def read(run: dict) -> float | None:
    v = stats.percentile(stats.series(run["client"], stats.tpot_s), 95)
    return None if v is None else v * 1e3
