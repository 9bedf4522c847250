"""The launch: the start of tick k + 1's ``serve.mixed_dispatch``
annotation to the start of its device program as the profile has the two
lines, mean over the joined ticks of the profile window, plus the lead of the
profile's device line over its host lines (``ticktimeline.device_lead``).
With ``tick.wake_gap_ms`` and ``tick.serial_ms`` it sums to
``tick.exposed_ms`` by construction."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ticktimeline.py lies beside the readers
import ticktimeline  # noqa: E402


def read(run: dict) -> float | None:
    return ticktimeline.part_mean_ms(run, "launch_gap")
