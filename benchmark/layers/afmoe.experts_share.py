"""Own time of the operations the op map puts under the ``moe_experts`` scope
of an ``afmoe`` stack (the grouped matmuls over the 32 experts HELD, and the
post-norm of the sum with its residual add: what ``afmoe.experts_roofline``
divides by), in % of device busy time.  Another architecture, or a program
without the scope, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    return afmoetrace.scope_share(run, "moe_experts")
