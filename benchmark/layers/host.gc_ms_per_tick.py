"""The garbage collector a tick: time of the window's ``cat: "gc"`` slices
on the tick thread and on the loop thread over the window's dispatching
ticks - a stall of the whole interpreter no phase shows.  0 where the
collector never ran; nothing on a program that does not watch it."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ticktimeline.py lies beside the readers
import ticktimeline  # noqa: E402


def read(run: dict) -> float | None:
    return ticktimeline.gc_ms_per_tick(run)
