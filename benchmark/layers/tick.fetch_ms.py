"""The fetch after the wait: mean over the window's dispatching ticks of
tick args ``host_sync_us`` - ``device_wait_us`` - the result's copy and the
return into the interpreter, measured where they happen (with a recorder the
engine waits for the program, stamps, then fetches)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ticktimeline.py lies beside the readers
import ticktimeline  # noqa: E402


def read(run: dict) -> float | None:
    return ticktimeline.tick_mean(
        run, lambda t: (t["args"]["host_sync_us"] - t["args"]["device_wait_us"]) / 1e3
        if "device_wait_us" in t["args"] else None)
