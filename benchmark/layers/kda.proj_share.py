"""Own time of the operations the op map puts under the ``kda_proj`` scope
(a delta-rule layer around its recurrence: input norm, the q / k / v / decay
projections, the convolution and its history, L2 norms, gates, the output
norm and out_proj), in % of device busy time.  Another architecture, or a
program without the scope (the parent of PR 47), reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    if run["config"].get("model_type") != "ling_hybrid":
        return None
    table = tracefile.op_table(run)
    if not table or not any(v and v[0] == "kda_proj" for v in table.values()):
        return None
    return tracefile.scope_share(run, lambda scope, kind: scope == "kda_proj")
