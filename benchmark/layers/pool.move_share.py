"""Own time of the operations that move the KV pool, in % of device busy
time: those the program's op map marks pool-shaped (the result is the pool
or one layer's slab of it) or puts under ``kv_write``, outside ``attn``."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
import tracefile  # noqa: E402


def read(run: dict) -> float | None:
    return tracefile.scope_share(
        run, lambda scope, kind: scope != "attn" and (kind or scope == "kv_write"))
