"""Own time of the operations the op map puts under the ``moe_shared`` scope
of an ``afmoe`` stack (the shared expert's SwiGLU of 3,072 on every token and
its sum with the routed part), in % of device busy time.  Another
architecture, or a program without the scope, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    return afmoetrace.scope_share(run, "moe_shared")
