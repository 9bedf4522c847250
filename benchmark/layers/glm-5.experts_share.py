"""Own time of the operations the op map puts under the ``moe_route`` and
``moe_experts`` scopes of a ``glm_moe_dsa`` stack (the router over every expert
of the layer, the sort, the grouped matmuls over the experts HELD and their
weighted combine), in % of device busy time.  Another architecture, or a
program without the scopes, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # dsatrace.py lies beside the readers
import dsatrace  # noqa: E402


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "glm_moe_dsa":
        return None
    return dsatrace.scope_share(run, ("moe_route", "moe_experts"))
