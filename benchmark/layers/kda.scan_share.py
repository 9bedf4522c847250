"""Own time of the operations the op map puts under the ``kda_scan`` scope
(everything that touches a delta-rule layer's matrix state: the state-update
kernel or its twin over the rows a tick touches, the chunk passes of a
prefill row, the gathers around them), in % of device busy time.  Another
architecture, or a program without the scope (the parent of PR 47), reads
nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "ling_hybrid":
        return None
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == "kda_scan" for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda scope, kind: scope == "kda_scan")
