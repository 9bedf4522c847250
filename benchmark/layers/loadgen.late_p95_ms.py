"""How late the load generator ran: 95th percentile of (sent - due) over
the measured requests.  A starved generator is not a fast server."""
import stats


def read(run: dict) -> float | None:
    v = stats.percentile(stats.series(run["client"], stats.late_s), 95)
    return None if v is None else v * 1e3
