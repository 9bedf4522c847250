"""Own time of the operations the op map puts under the ``conv`` scope
(the gated short convolutions (norm, in_proj, gates, filter, out_proj, the per-slot state read and written)), in % of device busy time.  A program without the scope (every
homogeneous stack, and the parent of PR 32) reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == "conv" for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda scope, kind: scope == "conv")
