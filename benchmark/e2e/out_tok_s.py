"""Output tokens that arrived in the window / its seconds, whole cell."""
import stats


def read(run: dict) -> float | None:
    rec = run["client"]
    return stats.tokens_in_window(rec) / stats.window_seconds(rec)
