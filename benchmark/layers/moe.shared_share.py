"""Own time of the operations the op map puts under the ``moe_shared`` scope
(the shared experts of a ``deepseek_v3`` expert layer: one SwiGLU every token
takes beside its routed experts), in % of device busy time.  A program
without the scope reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == "moe_shared" for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda scope, kind: scope == "moe_shared")
