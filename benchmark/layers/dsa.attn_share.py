"""Own time of the operations the op map puts under the ``dsa_attn`` scope
(latent attention over each token's selection: the page walk under a per-token
mask), in % of device busy time.  A configuration without an indexer, or a
program without the scope, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # dsatrace.py lies beside the readers
import dsatrace  # noqa: E402


def read(run: dict) -> float | None:
    return dsatrace.scope_share(run, (dsatrace.ATTN,))
