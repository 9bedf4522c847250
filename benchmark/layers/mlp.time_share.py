"""Own time of the operations the op map puts under the ``mlp`` scope
(norm, gate / up / down, residual), in % of device busy time."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.scope_share(run, lambda scope, kind: scope == "mlp" and not kind)
