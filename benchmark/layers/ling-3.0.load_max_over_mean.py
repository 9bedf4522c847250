"""Skew of a ``ling_hybrid`` stack's expert layers over the 128 experts
HELD: mean over dispatching ticks of the tick arguments ``expert_load_max /
expert_load_mean`` (tokens the most loaded held expert of the most loaded
layer got, over that layer's mean).  1 is a perfectly even tick; the grouped
matmul pays a whole 11.8 MB expert for one that got a single token:
``moe.load_max_over_mean``'s reading, in this stack's cell.  Another
architecture, or a program without the arguments, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "ling_hybrid":
        return None
    vals = [t["args"]["expert_load_max"] / t["args"]["expert_load_mean"]
            for t in tracefile.dispatching_ticks(run)
            if t["args"].get("expert_load_mean")]
    return sum(vals) / len(vals) if vals else None
