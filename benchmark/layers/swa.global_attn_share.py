"""Own time of the operations the op map puts under the ``attn_global`` scope
(a global layer's attention in a stack whose window layers hold pages of
their own: the ragged kernel over the growing class's pages, and the gathers
around it), in % of device busy time.  A program without the scope reads
nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_mimo_v2.py
import costs_mimo_v2  # noqa: E402,F401
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == "attn_global" for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda scope, kind: scope == "attn_global")
