"""Own time of the operations the op map puts under the ``ssm_proj`` scope
(everything of the state-space mixer around its recurrence: in_proj, the multipliers, the convolution and its history read and written, the gated norm, out_proj),
in % of device busy time.  A program without the scope (every stack
without state-space layers, and the parent of PR 34) reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == "ssm_proj" for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda scope, kind: scope == "ssm_proj")
