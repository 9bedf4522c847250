"""Own time of the operations under the ``qkv`` scope (norm, q / k / v
projections, RoPE) and the ``o_proj`` scope, in % of device busy time."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.scope_share(
        run, lambda scope, kind: scope in ("qkv", "o_proj") and not kind)
