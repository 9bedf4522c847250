"""Bytes of K/V pages in use a token of live context of an ``afmoe`` stack, off
``/metrics`` at the window's end: (``kv_global_blocks_in_use`` x
``kv_global_block_bytes`` + ``kv_window_blocks_in_use`` x
``kv_window_block_bytes``) / ``context_tokens_live``.  20,480 if a window
layer kept every token it was given; towards the global class's 4,096 plus a
slot's ring (67 blocks of 64 x 16,384 B) over its context where window blocks
are recycled.  A program without the gauges reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402

import stats  # noqa: E402

GAUGES = ("kv_global_blocks_in_use", "kv_global_block_bytes",
          "kv_window_blocks_in_use", "kv_window_block_bytes",
          "context_tokens_live")


def read(run: dict) -> float | None:
    if not afmoetrace.is_afmoe(run):
        return None
    text = run["client"]["scrapes"].get("end", {}).get("/metrics", {}).get("text", "")
    g = [stats.scrape_sum(text, name) for name in GAUGES]
    if None in g or not g[4]:
        return None
    return (g[0] * g[1] + g[2] * g[3]) / g[4]
