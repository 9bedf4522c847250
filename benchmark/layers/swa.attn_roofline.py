"""The ragged kernel's share of ITS byte bound over both page classes of a
``mimo_v2`` stack: the least time the chip could take to read the pages a
tick's attention calls are asked to stream (the tick arguments
``attn_pages_global`` / ``attn_pages_window`` - per layer of the kind, the
pages in every query tile's visible range - x the block size x the bytes a
token holds in the kind's layers, from ``costs_mimo_v2.py``) over the peak
HBM rate / the device time a tick spends under the ``attn_global`` and
``attn_window`` scopes (the kernel and the gathers that lay its queries out),
in %.  What the kernel must read, not what it did: a page fetched twice is
priced once.  A program without the scopes or the arguments reads nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_mimo_v2.py
import costs_mimo_v2  # noqa: E402,F401
import tracefile  # noqa: E402

SCOPES = ("attn_global", "attn_window")


def read(run: dict) -> float | None:
    dt, ht, table = run.get("device_trace"), run.get("host_trace"), tracefile.op_table(run)
    if (run["config"].get("model_type") != "mimo_v2" or not dt or not dt.get("ticks")
            or not ht or not table or run["peaks"] is None):
        return None
    p0, p1 = dt["wall"]
    ticks = [t["args"] for t in ht["ticks"]
             if p0 <= t["start"] < p1 and "attn_pages_window" in t["args"]]
    scope_s = sum(seconds for name, seconds in dt["ops_s"].items()
                  if (table.get(name.rsplit(" ", 1)[0]) or [""])[0] in SCOPES)
    if not ticks or not scope_s:
        return None
    serve = run["config"].get("serve", {})
    nbytes = costs_mimo_v2.attention_bytes(
        run["config"],
        sum(t["attn_pages_global"] for t in ticks) / len(ticks),
        sum(t["attn_pages_window"] for t in ticks) / len(ticks),
        serve.get("block_size", 64), serve.get("cache_dtype", "bf16"))
    least_s = nbytes / (run["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (scope_s / dt["ticks"])
