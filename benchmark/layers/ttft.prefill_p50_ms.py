"""Median over the window's requests of instant ``lane`` -> instant
``last_chunk`` (the plan of the tick that carries the row's last prompt
token): the ticks a prompt holds the lane before its final one; 0 for a
prompt that takes the lane and finishes in one tick."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ttftstages.py lies beside the readers
import ttftstages  # noqa: E402


def read(run: dict) -> float | None:
    return ttftstages.stage_percentile_ms(run, "lane", "last_chunk", 50)
