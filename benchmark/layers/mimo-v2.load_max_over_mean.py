"""Skew of a ``mimo_v2`` stack's expert layers over the 16 experts HELD:
mean over dispatching ticks of the tick arguments ``expert_load_max /
expert_load_mean`` (tokens the most loaded held expert of the most loaded
layer got, over that layer's mean).  1 is a perfectly even tick; the grouped
matmul pays whole row tiles for the most loaded expert and a whole 50.3 MB
expert for one that got a single token: ``moe.load_max_over_mean``'s
reading, in this stack's cell.  Another architecture, or a program without
the arguments, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_mimo_v2.py
import costs_mimo_v2  # noqa: E402,F401
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "mimo_v2":
        return None
    vals = [t["args"]["expert_load_max"] / t["args"]["expert_load_mean"]
            for t in tracefile.dispatching_ticks(run)
            if t["args"].get("expert_load_mean")]
    return sum(vals) / len(vals) if vals else None
