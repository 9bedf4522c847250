"""Median over the window's requests of the first token's emit on the tick
thread (``decode`` begins) -> its SSE frame is written (``first_write``)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.track_percentile_ms(
        run, lambda tr: tr["instant"]["first_write"]["ts"] - tr["begin"]["decode"]
        if "first_write" in tr["instant"] and "decode" in tr["begin"] else None, 50)
