"""Own time of the operations the op map puts under the ``attn_window`` scope of
an ``afmoe`` stack (a window layer's attention: the ragged kernel over the
window class's rings behind a table that starts past position 0, and the
gathers that spread the queries over its tiles and bring them back), in % of
device busy time.  Another architecture, or a program without the scope,
reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    return afmoetrace.scope_share(run, "attn_window")
