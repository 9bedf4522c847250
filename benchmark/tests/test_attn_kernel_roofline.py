"""``attn.kernel_roofline`` (PR 33) against hand arithmetic, and its
entry in BENCHMARK.json."""

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]


def _read():
    spec = importlib.util.spec_from_file_location(
        "reader_attn_kernel_roofline", BENCH / "layers" / "attn.kernel_roofline.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _run(**args):
    config = json.loads((BENCH / "configs" / "qwen2.5-1.5b.json").read_text())
    ticks = [dict(start=100.0 + 0.01 * i, dur_s=0.01, args=dict(
        prefill_tokens=0, decode_tokens=64, active_slots=64, **args))
        for i in range(20)]
    # a tick outside the profiler's window does not count
    ticks.append(dict(start=99.0, dur_s=0.01, args=dict(
        prefill_tokens=0, decode_tokens=64, active_slots=64, attn_pages=10**6)))
    return dict(
        config=config, replicas=1, tp=1,
        peaks={"hbm_gbps": 819, "bf16_tflops": 197},
        host_trace=dict(ticks=ticks, phases=[]),
        device_trace=dict(
            busy_s=0.2, window_s=0.2, ticks=20, wall=[100.0, 100.2],
            ops_s={"%_ragged_paged_attention.11_bf16_512_2_6_128 = bf16[512,2,6,128]": 0.10,
                   "%_ragged_paged_attention.12_bf16_768_2_6_128 = bf16[768,2,6,128]": 0.02,
                   # an expert layer's grouped matmul carries the short needle
                   "%ragged-dot-none.3 = bf16[64,1792]": 0.05,
                   "%fusion.9 = bf16[64,8960]": 0.03}))


def test_by_hand():
    # 424 pages a layer x 64 tokens x 28,672 B a token (K and V, 28 layers)
    # = 778 MB a tick = 0.95 ms at 819 GB/s, over 0.12 s / 20 ticks = 6 ms
    want = 100.0 * (424 * 64 * 28_672 / 819e9) / 0.006
    assert _read()(_run(attn_pages=424)) == pytest.approx(want)
    assert 15.0 < want < 16.0


def test_nothing_to_read_on_a_program_without_the_counter():
    read = _read()
    assert read(_run()) is None  # the parent of PR 33: no such tick arg
    no_kernel = _run(attn_pages=424)
    no_kernel["device_trace"]["ops_s"] = {"%ragged-dot-none.3 = bf16[64,1792]": 0.05}
    assert read(no_kernel) is None
    assert read(dict(_run(attn_pages=424), device_trace=None)) is None


def test_entry():
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry, = (m for m in bench["per_layer"] if m["name"] == "attn.kernel_roofline")
    share, = (m for m in bench["per_layer"] if m["name"] == "attn.time_share")
    assert entry["layer"] == share["layer"] and entry["moves"] == "out_tok_s"
    assert entry["unit"] == "%" and entry["source"] == "device_trace"
    cells = {w["name"]: w["config"] for w in bench["workloads"]}
    assert entry["workloads"] == [
        n for n in cells if cells[n].startswith("qwen2.5")]
