"""Median over the window's requests of admission (``prefill`` begins) -> the
plan of the first tick that hands the row more than its fair share of the
prompt lane, or completes its prompt (instant ``lane``): what a prompt waits
behind older prompts, a chunk a tick; 0 to a tick's own plan where nothing is
ahead.  A program without the instant gives nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ttftstages.py lies beside the readers
import ttftstages  # noqa: E402


def read(run: dict) -> float | None:
    return ttftstages.stage_percentile_ms(run, "prefill", "lane", 50)
