"""``step_roofline`` for an ``afmoe`` stack: ``afmoetrace.step_roofline``, the
least time the chip could take for the mean tick of the profiler window
(bytes and operations from ``costs_afmoe.py``) / the device time the tick
took, in %.  ``step_roofline`` itself prices one kind of layer and every
expert's weights, and is not reported in such a cell."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    return afmoetrace.step_roofline(run)
