"""The ``warmup`` set-up spans (the dummy request and one dead batch per
packed-width bucket), summed over engines."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.setup_span_s(run, "warmup")
