"""Device busy time the op map accounts for: operations it gives a scope or
marks pool-shaped, in %.  A name two buckets of the step use for different
things counts as not attributed."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.scope_share(run, lambda scope, kind: bool(scope or kind))
