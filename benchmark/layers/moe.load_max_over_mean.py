"""Skew of the dropless expert layers: mean over dispatching ticks of the
tick arguments ``expert_load_max / expert_load_mean`` (tokens the most loaded
expert of the most loaded layer got, over that layer's mean).  1 is a
perfectly even tick; a grouped matmul pays for the most loaded expert."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    vals = [t["args"]["expert_load_max"] / t["args"]["expert_load_mean"]
            for t in tracefile.dispatching_ticks(run)
            if t["args"].get("expert_load_mean")]
    return sum(vals) / len(vals) if vals else None
