"""Own time of the operations the op map puts under the ``moe_experts`` scope
of a ``ling_hybrid`` stack (the row gather, the grouped matmuls over the 128
experts HELD, the un-sort and the weighted combine: what
``ling-3.0.experts_roofline`` divides by), in % of device busy time.  Another
architecture, or a program without the scope, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "ling_hybrid":
        return None
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == "moe_experts" for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda scope, kind: scope == "moe_experts")
