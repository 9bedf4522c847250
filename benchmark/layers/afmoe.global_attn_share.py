"""Own time of the operations the op map puts under the ``attn_global`` scope of
an ``afmoe`` stack (the one position-free global layer's attention: the ragged
kernel over the growing class's pages, and the gathers around it), in % of
device busy time.  Another architecture, or a program without the scope,
reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    return afmoetrace.scope_share(run, "attn_global")
