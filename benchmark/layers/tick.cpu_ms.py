"""The tick thread's own CPU a tick outside the fetch: mean tick arg
``thread_cpu_us`` over the window's dispatching ticks (of a program that
numbers its dispatches: the readers of PR 36 read one program)."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ticktimeline.py lies beside the readers
import ticktimeline  # noqa: E402


def read(run: dict) -> float | None:
    return ticktimeline.tick_mean(
        run, lambda t: t["args"]["thread_cpu_us"] / 1e3
        if "thread_cpu_us" in t["args"] else None)
