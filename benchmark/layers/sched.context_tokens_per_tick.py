"""Mean of the tick argument ``context_tokens`` over dispatching ticks: the
live context the rows of a dispatch attend, summed over rows, as the engine
counts it (``step_roofline`` rebuilds the same from the client's record)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.tick_arg_mean(run, "context_tokens")
