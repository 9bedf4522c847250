"""Own time of the operations the op map puts under the ``attn_gate`` scope
(the output gate of every attention layer: a 3,072 x 6,144 projection, its
sigmoid and the product with the attention's result before ``o_proj``), in %
of device busy time.  Another architecture, or a program without the scope,
reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    return afmoetrace.scope_share(run, "attn_gate")
