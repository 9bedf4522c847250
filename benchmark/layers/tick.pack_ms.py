"""Mean of the recorder's ``pack`` phase (``_pack_mixed``: the numpy
operands of the dispatch) over the dispatching ticks of the window."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.phase_mean_ms(run, "pack", needs="pack")
