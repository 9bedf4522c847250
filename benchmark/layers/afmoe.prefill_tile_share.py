"""Share of the live query tiles that hold a prefill chunk's tokens (the tick
arguments ``attn_prefill_tiles / attn_live_tiles``, summed over the window's
dispatching ticks), in %: each such tile streams its row's visible pages
again, 16 tiles a chunk of 128 - what PERF.md section 7 item (b) asks about.
Another architecture, or a program without the argument, reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # afmoetrace.py lies beside the readers
import afmoetrace  # noqa: E402


def read(run: dict) -> float | None:
    share = afmoetrace.tick_ratio(run, "attn_prefill_tiles", "attn_live_tiles")
    return None if share is None else 100.0 * share
