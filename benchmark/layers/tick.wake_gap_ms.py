"""The host learns late that the step is done: the end of tick k's device
program to the end of its ``serve.host_sync`` annotation (the wake-up, the
result's copy and the way back into the interpreter), as the profile has the two
lines, mean over the joined ticks of the profile window, less the lead of the
profile's device line over its host lines (``ticktimeline.device_lead``: the
middle of its causal bounds).  In a tick the host set
(``tick.host_bound_share``) it holds the rest of ``deliver``."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ticktimeline.py lies beside the readers
import ticktimeline  # noqa: E402


def read(run: dict) -> float | None:
    return ticktimeline.part_mean_ms(run, "wake_gap")
