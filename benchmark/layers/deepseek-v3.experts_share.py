"""Own time of the operations the op map puts under the ``moe_route`` and
``moe_experts`` scopes of a ``deepseek_v3`` stack (the router over every
expert of the layer, the sort, the grouped matmuls over the experts HELD and
their weighted combine), in % of device busy time.  Another architecture, or
a program without the scopes, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402

SCOPES = ("moe_route", "moe_experts")


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "deepseek_v3":
        return None
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] in SCOPES for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda scope, kind: scope in SCOPES)
