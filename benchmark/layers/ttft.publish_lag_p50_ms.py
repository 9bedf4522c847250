"""Median over the window's requests of instant ``first_token`` (the accept, at
tick N's fetch) -> ``decode`` begins (the emit, behind tick N + 1's dispatch):
the next tick's admission ... dispatch, which a first token waits out since
PR 35."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ttftstages.py lies beside the readers
import ttftstages  # noqa: E402


def read(run: dict) -> float | None:
    return ttftstages.stage_percentile_ms(run, "first_token", "decode", 50)
