"""Own time of the operations the op map puts under the ``moe_route`` scope of
an ``afmoe`` stack (the pre-norm, the gate over all 256 experts of the layer,
sigmoid, selection bias, top 4, normalise, the pair sort and the groups'
layout in whole row tiles), in % of device busy time.  Another
architecture, or a program without the scope, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    return afmoetrace.scope_share(run, "moe_route")
