"""The ``engine_build`` set-up spans (``ServeEngine.__init__``: probes, pool
allocation, placing the weights on a mesh), summed over engines."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.setup_span_s(run, "engine_build")
