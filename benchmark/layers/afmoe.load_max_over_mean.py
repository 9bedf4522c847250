"""Skew of an ``afmoe`` stack's expert layers over the 32 experts HELD: mean
over dispatching ticks of the tick arguments ``expert_load_max /
expert_load_mean`` (tokens the most loaded held expert of the most loaded
layer got, over that layer's mean).  1 is a perfectly even tick.  Another
architecture, or a program without the arguments, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    if not afmoetrace.is_afmoe(run):
        return None
    vals = [t["args"]["expert_load_max"] / t["args"]["expert_load_mean"]
            for t in afmoetrace.tracefile.dispatching_ticks(run)
            if t["args"].get("expert_load_mean")]
    return sum(vals) / len(vals) if vals else None
