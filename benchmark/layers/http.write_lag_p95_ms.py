"""95th percentile over the window's requests of the mean emit-to-write lag
of a request's token frames (``stream_end``'s ``lag_mean_us``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.track_percentile_ms(
        run, lambda tr: tr["instant"]["stream_end"]["args"]["lag_mean_us"]
        if "stream_end" in tr["instant"] else None, 95)
