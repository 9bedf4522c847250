"""The ragged kernel's share of ITS byte bound over the global class's pages
of an ``afmoe`` stack (1 global layer, every page of a row's context a query
tile): ``afmoetrace.kernel_roofline``.  A program without the scope or the
argument reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    return afmoetrace.kernel_roofline(run, "global")
