"""What the device waits for between two steps: the end of tick k's device
program to the start of tick k + 1's, both on the profile's clock, mean over
the ticks of the profile window that it and the recorder saw whole with
their successor (joined by the dispatch's ``seq``, ``ticktimeline.py``) -
what PERF.md wrote by hand as ``tick.wall_ms`` - ``step.device_ms``.  A
program without ``seq`` gives nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ticktimeline.py lies beside the readers
import ticktimeline  # noqa: E402


def read(run: dict) -> float | None:
    return ticktimeline.part_mean_ms(run, "exposed")
