"""Bytes of K/V pages in use a token of live context, off ``/metrics`` at the
window's end: (``kv_global_blocks_in_use`` x ``kv_global_block_bytes`` +
``kv_window_blocks_in_use`` x ``kv_window_block_bytes``) /
``context_tokens_live``.  30,720 if a window layer kept every token it was
given; towards the global class's 5,120 plus a slot's ring over its context
where window blocks are recycled.  A program without the gauges (one page
class) reads nothing."""
import stats

GAUGES = ("kv_global_blocks_in_use", "kv_global_block_bytes",
          "kv_window_blocks_in_use", "kv_window_block_bytes",
          "context_tokens_live")


def read(run: dict) -> float | None:
    text = run["client"]["scrapes"].get("end", {}).get("/metrics", {}).get("text", "")
    g = [stats.scrape_sum(text, name) for name in GAUGES]
    if None in g or not g[4]:
        return None
    return (g[0] * g[1] + g[2] * g[3]) / g[4]
