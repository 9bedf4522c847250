"""The expert matmuls' share of their roofline at this stack's shape (32
held experts of 3 x 3,072 x 3,072 = 56.6 MB each):
``afmoetrace.experts_roofline``.  Memory-bound by construction: an expert
here sees about ten tokens a tick."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    return afmoetrace.experts_roofline(run)
