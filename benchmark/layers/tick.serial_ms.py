"""Nothing in flight: the end of tick k's ``serve.host_sync`` annotation
to the start of tick k + 1's ``serve.mixed_dispatch`` (``accept`` ...
``h2d``), on the profile's clock, mean over the joined ticks of the profile
window."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # ticktimeline.py lies beside the readers
import ticktimeline  # noqa: E402


def read(run: dict) -> float | None:
    return ticktimeline.part_mean_ms(run, "serial")
