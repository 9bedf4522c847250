"""Median over the window's requests of instant ``last_chunk`` -> instant
``first_token`` (the accept of the sampled token): one tick from its plan on
- pack, transfer, dispatch, the device program, the fetch, the accept."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ttftstages.py lies beside the readers
import ttftstages  # noqa: E402


def read(run: dict) -> float | None:
    return ttftstages.stage_percentile_ms(run, "last_chunk", "first_token", 50)
