"""Mean duration of the device program over the capture's ticks that handed a
prompt leftover of the lane (tick arg ``lane_rows`` > 0; the profile's
``XLA Modules`` execution joined to the recorder's tick by ``seq``): a device
time that does not depend on the mix of ticks the 3 s capture met.  Nothing
for fewer than 20 such ticks."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ttftstages.py lies beside the readers
import ttftstages  # noqa: E402


def read(run: dict) -> float | None:
    return ttftstages.program_ms(run, "prefill")
