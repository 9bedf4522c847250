"""Own time of the operations the op map puts under the ``retention_scan``
scope (everything that touches a power-retention layer's state: the
state-update kernel or its twin over the rows a tick touches, the chunk
passes of a prefill row, the gathers around them), in % of device busy time.
Another architecture, or a program without the scope (the parent of PR 56),
reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "brumby":
        return None
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == "retention_scan" for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda scope, kind: scope == "retention_scan")
