"""Mean of the recorder's ``mixed_dispatch`` phase over the dispatching
ticks of the window, where the tick is cut (a ``pack`` phase exists): the
jitted call alone.  Before the cut the name covered packing and transfers
too, and there is nothing to read."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.phase_mean_ms(run, "mixed_dispatch", needs="pack")
