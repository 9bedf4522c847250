"""Mean ``tick`` span over the window's dispatching ticks with no prompt token
aboard (tick arg ``lane_rows`` == 0 and ``prefill_tokens`` == 0); nothing for
fewer than 20 such ticks, or a program without the arg."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ttftstages.py lies beside the readers
import ttftstages  # noqa: E402


def read(run: dict) -> float | None:
    return ttftstages.tick_wall_ms(run, "decode")
