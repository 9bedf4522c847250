"""Median over the window's requests of the ticks that granted the row prompt
tokens on its way to its first token (arg ``prefill_ticks`` of the request
track's ``last_chunk`` instant): prompt / lane where one row has the lane,
more where it took fair-share chunks behind an older prompt."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    # (the helper divides microseconds into milliseconds)
    return tracefile.track_percentile_ms(
        run, lambda tr: 1e3 * tr["instant"]["last_chunk"]["args"]["prefill_ticks"]
        if "last_chunk" in tr["instant"] else None, 50)
