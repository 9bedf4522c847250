"""Mean of the recorder's ``deliver`` phase over the dispatching ticks of
the window, where the tick is cut (an ``account`` phase exists): the emit /
accept walks and their callbacks, without the journal and the metrics."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.phase_mean_ms(run, "deliver", needs="account")
