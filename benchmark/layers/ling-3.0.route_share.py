"""Own time of the operations the op map puts under the ``moe_route`` scope of
a ``ling_hybrid`` stack (norm, the gate over all 512 experts of the layer,
sigmoid, selection bias, the group limit, top 8, normalise, the pair sort and
the groups' layout in whole row tiles), in % of device busy time:
``moe.route_share``'s reading, in this stack's cell.  Another architecture,
or a program without the scope, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "ling_hybrid":
        return None
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == "moe_route" for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda scope, kind: scope == "moe_route")
