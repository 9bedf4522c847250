"""Share (%) of the window's dispatching ticks at whose fetch the device
had already finished (tick arg ``device_done_at_sync``): the host, not the
step, set their length."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ticktimeline.py lies beside the readers
import ticktimeline  # noqa: E402


def read(run: dict) -> float | None:
    return ticktimeline.tick_mean(
        run, lambda t: 100.0 * t["args"]["device_done_at_sync"]
        if "device_done_at_sync" in t["args"] else None)
