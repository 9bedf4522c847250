"""The ragged kernel's share of ITS byte bound over the window class's pages
of an ``afmoe`` stack (4 window layers, 64-67 pages a query tile at 4,096 B
a token and layer): ``afmoetrace.kernel_roofline``.  What the kernel is asked
to stream - every query tile its row's visible pages again - not what an
ideal one would read once.  A program without the scope or the argument
reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    return afmoetrace.kernel_roofline(run, "window")
