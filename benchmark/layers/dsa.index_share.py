"""Own time of the operations the op map puts under the ``dsa_proj`` and
``dsa_score`` scopes (a sparse-attention indexer's three projections with their
norm and RoPE, and its scores over the cached index keys), in % of device busy
time.  A configuration without an indexer, or a program without the scopes,
reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # dsatrace.py lies beside the readers
import dsatrace  # noqa: E402


def read(run: dict) -> float | None:
    return dsatrace.scope_share(run, ("dsa_proj", dsatrace.SCORE))
