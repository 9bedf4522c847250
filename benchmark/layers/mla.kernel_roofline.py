"""The latent attention kernel's share of ITS byte bound: the least time the
chip could take to read the latent pages a tick's attention calls are asked
to stream (the tick argument ``attn_pages`` - per layer, the pages in every
query tile's visible range - x the cell's block size x the latent bytes a
token over all layers, from ``costs_deepseek_v3.py``: 1,152 B a token and
layer, what the algorithm needs and not the 1,280 the pool stores) over the
peak HBM rate / the device time a tick spends in operations named
``ragged_latent_attention``, in %.  A program without the kernel reads
nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).parents[1]))  # costs_deepseek_v3.py
import costs_deepseek_v3  # noqa: E402

NEEDLE = "ragged_latent_attention"


def read(run: dict) -> float | None:
    dt, ht = run.get("device_trace"), run.get("host_trace")
    if (run["config"].get("model_type") != "deepseek_v3" or not dt
            or not dt.get("ticks") or not ht or run["peaks"] is None):
        return None
    p0, p1 = dt["wall"]
    pages = [t["args"]["attn_pages"] for t in ht["ticks"]
             if p0 <= t["start"] < p1 and t["args"].get("attn_pages")]
    kernel_s = sum(s for name, s in dt["ops_s"].items() if NEEDLE in name)
    if not pages or not kernel_s:
        return None
    serve = run["config"].get("serve", {})
    page_bytes = serve.get("block_size", 64) * costs_deepseek_v3.latent_bytes_per_token(
        run["config"], serve.get("cache_dtype", "bf16"))
    least_s = sum(pages) / len(pages) * page_bytes / (run["peaks"]["hbm_gbps"] * 1e9)
    return 100.0 * least_s / (kernel_s / dt["ticks"])
