"""Mean duration of the device program over the capture's ticks with no
prompt token aboard (tick arg ``lane_rows`` == 0 and ``prefill_tokens`` == 0;
joined by ``seq``): the steady decode program alone, whatever the capture's
mix.  Nothing for fewer than 20 such ticks."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ttftstages.py lies beside the readers
import ttftstages  # noqa: E402


def read(run: dict) -> float | None:
    return ttftstages.program_ms(run, "decode")
