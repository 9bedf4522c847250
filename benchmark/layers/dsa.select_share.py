"""Own time of the operations the op map puts under the ``dsa_select`` scope
(the exact top-``index_topk`` of every token's index scores, as a mask), in %
of device busy time.  A configuration without an indexer, or a program without
the scope, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # dsatrace.py lies beside the readers
import dsatrace  # noqa: E402


def read(run: dict) -> float | None:
    return dsatrace.scope_share(run, (dsatrace.SELECT,))
