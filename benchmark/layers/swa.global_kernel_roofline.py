"""The ragged kernel's share of ITS byte bound over the global class's pages
of a ``mimo_v2`` stack, the kernel ALONE: the least time the chip could take
to read the pages the global layers' calls are asked to stream (the tick
argument ``attn_pages_global`` - per layer of the kind, the pages in every
query tile's visible range - x the block size x the bytes a token holds in
the kind's layers, from ``costs_mimo_v2.py``) over the peak HBM rate / the
device time a tick spends in operations named ``ragged_paged_attention``
that the op map puts under the ``attn_global`` scope, in %.
``swa.attn_roofline`` divides both classes' bytes by everything under the
two scopes, the gathers around the kernel included.  What the kernel must
read, not what it did.  A program without the scope or the argument reads
nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parent))  # tracefile.py lies beside the readers
sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_mimo_v2.py
import costs_mimo_v2  # noqa: E402,F401
import tracefile  # noqa: E402

KIND = "global"
NEEDLE = "ragged_paged_attention"


def read(run: dict) -> float | None:
    dt, ht, table = run.get("device_trace"), run.get("host_trace"), tracefile.op_table(run)
    if (run["config"].get("model_type") != "mimo_v2" or not dt or not dt.get("ticks")
            or not ht or not table or run["peaks"] is None):
        return None
    p0, p1 = dt["wall"]
    pages = [t["args"]["attn_pages_" + KIND] for t in ht["ticks"]
             if p0 <= t["start"] < p1 and "attn_pages_" + KIND in t["args"]]
    kernel_s = sum(seconds for name, seconds in dt["ops_s"].items()
                   if NEEDLE in name
                   and (table.get(name.rsplit(" ", 1)[0]) or [""])[0] == "attn_" + KIND)
    if not pages or not kernel_s:
        return None
    serve = run["config"].get("serve", {})
    mean = sum(pages) / len(pages)
    nbytes = costs_mimo_v2.attention_bytes(
        run["config"], mean if KIND == "global" else 0.0,
        mean if KIND == "window" else 0.0,
        serve.get("block_size", 64), serve.get("cache_dtype", "bf16"))
    least_s = nbytes / (run["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (kernel_s / dt["ticks"])
