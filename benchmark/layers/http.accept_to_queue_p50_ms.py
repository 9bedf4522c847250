"""Median over the window's requests of socket accept (the ``http`` span's
begin) -> the engine has the request (``queued`` begins): reading and
parsing the request, and the hop to the tick thread."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.track_percentile_ms(
        run, lambda tr: tr["begin"]["queued"] - tr["begin"]["http"]
        if "queued" in tr["begin"] else None, 50)
