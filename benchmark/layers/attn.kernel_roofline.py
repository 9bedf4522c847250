"""The ragged attention kernel's share of ITS roofline: the least time the
chip could take to read the K/V pages a tick's attention calls are asked to
stream (the tick argument ``attn_pages`` — per layer, the pages in every
query tile's visible range — x the bytes of one page over all layers, from
``costs.py`` and the cell's block size, over the peak HBM rate) / the device
time a tick spends in operations named ``ragged_paged_attention``, in %.
Memory-bound by construction: the arithmetic of a decode row is tiny.  The
FULL name: the expert layers' ``ragged-dot`` calls carry the short one."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parents[1]))  # costs.py
import costs  # noqa: E402

NEEDLE = "ragged_paged_attention"


def read(run: dict) -> float | None:
    dt, ht = run.get("device_trace"), run.get("host_trace")
    if not dt or not dt.get("ticks") or not ht or run["peaks"] is None:
        return None
    p0, p1 = dt["wall"]
    pages = [t["args"]["attn_pages"] for t in ht["ticks"]
             if p0 <= t["start"] < p1 and t["args"].get("attn_pages")]
    kernel_s = sum(s for name, s in dt["ops_s"].items() if NEEDLE in name)
    if not pages or not kernel_s:
        return None
    serve = run["config"].get("serve", {})
    page_bytes = serve.get("block_size", 64) * costs.kv_bytes_per_token(
        run["config"], serve.get("cache_dtype", "bf16")) / run["tp"]
    least_s = sum(pages) / len(pages) * page_bytes / (run["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (kernel_s / (dt["ticks"] / run["replicas"]))
