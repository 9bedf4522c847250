"""Mean of the recorder's ``accept`` phase over the dispatching ticks of the
window: what stays on the device's critical path after the fetch - the
tokens into their requests, finish decided, slots and blocks released for
the next plan.  The callbacks, the metrics and the journal (``deliver``) run
behind the next dispatch.  A program without the phase gives nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.phase_mean_ms(run, "accept", needs="account")
