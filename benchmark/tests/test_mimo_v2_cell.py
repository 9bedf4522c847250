"""The ``mimo_v2`` cell's data and readers on the CPU: the cell's files load
the way the harness loads them, the eleven readers PR 43 added read a made-up
trace that is checked by hand (and nothing from a run without one, which is
what the parent of PR 43 gives them), and the sizes the cell's ``sizing``
states follow from ``costs_mimo_v2.py``.  The served path at a tiny size is
tests/test_mimo_v2.py."""

import importlib.util
import json
import sys
from pathlib import Path

import costs_mimo_v2 as costs
import pytest

BENCH = Path(__file__).resolve().parents[1]
CELL = "mimo-v2.5-7l-ep16.longctx-closed"
NEW = ("swa.window_attn_share", "swa.global_attn_share", "swa.attn_roofline",
       "mimo-v2.experts_share", "mimo-v2.experts_roofline",
       "mimo-v2.step_roofline", "swa.pool_bytes_per_context_token",
       "mimo-v2.route_share", "mimo-v2.load_max_over_mean",
       "swa.global_kernel_roofline", "swa.window_kernel_roofline")


def config():
    return json.loads((BENCH / "configs" / "mimo-v2.5-7l-ep16.json").read_text())


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_").replace("-", "_"),
        BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_cells_files_load_as_the_harness_loads_them():
    import run as harness
    import traffic

    spec = harness.load_spec(BENCH.parent, CELL)
    assert spec["config_name"] == "mimo-v2.5-7l-ep16"
    assert spec["cell"]["chips"] == 1 and spec["params"]["slots"] == 64
    assert spec["params"]["clients"] == 64 and not spec["params"]["num_blocks"]
    assert spec["params"]["serve_flags"] == [
        "--arch", "mimo_v2", "--max-queue", "512", "--tick-token-budget", "576"]
    assert traffic.limits(spec["traffic"]) == (4096, 768)
    tr = spec["traffic"]
    assert (tr["loop"], tr["ramp_s"], tr["order_seed"], tr["block"]) == (
        "closed", 90, 0, 64)
    assert tr["prompt_tokens"] == {"dist": "uniform", "min": 2048, "max": 4096}
    assert tr["output_tokens"] == {"dist": "uniform", "min": 384, "max": 768}
    # every new metric is reported in this cell alone, and moves out_tok_s
    assert set(NEW) <= set(spec["per_layer"])
    bench = spec["bench"]
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
            assert (BENCH / "layers" / f"{m['name']}.py").exists()
    assert "step_roofline" not in spec["per_layer"]
    assert "attn.kernel_roofline" not in spec["per_layer"]
    argv = harness.serve_argv(spec, "port", None)
    assert argv[argv.index("--prompt-len") + 1] == "4096"
    assert "--num-blocks" not in argv  # the CLI's rule


def test_the_sizes_in_the_cells_sizing_follow_from_the_costs():
    c = config()
    sizing = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["sizing"]
    mib = lambda n: f"{round(n / 2**20):,} MiB"  # noqa: E731
    weights = costs.weight_bytes(c)
    per_seq = -(-(4096 + 768 + 127) // 64)
    blocks = 64 * per_seq + 2
    glob = blocks * 64 * costs.kv_bytes_per_token(c, "global")
    ring = -(-(c["sliding_window"] - 1 + 128) // 64) + 1
    wind = (64 * ring + 1) * 64 * costs.kv_bytes_per_token(c, "window")
    assert (per_seq, blocks, ring) == (78, 4994, 5)
    for text in (f"{costs.param_count(c):,} bf16 parameters", mib(weights),
                 f"64 x {per_seq} + 2 = {blocks:,} blocks", mib(glob),
                 f"{64 * ring + 1} blocks", mib(wind), mib(weights + glob + wind),
                 mib(blocks * 64 * 30720)):
        assert text in sizing, text
    assert c["sizes"]["parameters"] == costs.param_count(c) == 4523620160
    assert c["sizes"]["kv_bytes_per_token_global_bf16"] == 5120
    assert c["sizes"]["kv_bytes_per_token_window_bf16"] == 25600


@pytest.fixture()
def made_up(tmp_path, monkeypatch):
    """What the chip's profile and the recorder's dump would hold: the
    kernel and one operation beside it under each attention scope, one
    operation under each other scope, 20 ticks of 10 ms busy."""
    sys.path.insert(0, str(BENCH / "layers"))
    import tracefile

    table = {"%ragged_paged_attention.3 bf16[8]": ["attn_window", ""],
             "%gather_w.1 bf16[8]": ["attn_window", ""],
             "%ragged_paged_attention bf16[4]": ["attn_global", ""],
             "%gather_g.1 bf16[8]": ["attn_global", ""],
             "%route.1 f32[8]": ["moe_route", ""],
             "%grouped_matmul.2 bf16[8]": ["moe_experts", ""],
             "%qkv.1 bf16[8]": ["qkv", ""]}
    out = tmp_path / f"{CELL}-7"
    out.mkdir()
    (out / "host_trace.json").write_text(json.dumps(
        {"traceEvents": [], "otherData": {"op_map": table}}))
    monkeypatch.setattr(tracefile, "OUT", tmp_path)
    tracefile._dumps.clear()
    ticks = [dict(start=100.0 + 0.01 * i, dur_s=0.01, args=dict(
        prefill_tokens=512, decode_tokens=64, active_slots=64,
        attn_pages=4000, attn_pages_global=4000, attn_pages_window=300,
        experts_touched=96, pairs_held=1300, expert_load_max=30 + i % 2 * 20,
        expert_load_mean=20.0)) for i in range(20)]
    gauges = "\n".join(f"llm_serve_{k} {v}" for k, v in dict(
        kv_global_blocks_in_use=3400, kv_global_block_bytes=64 * 5120,
        kv_window_blocks_in_use=200, kv_window_block_bytes=64 * 25600,
        context_tokens_live=210000).items())
    return dict(
        workload=CELL, seed=7, config=config(), replicas=1, tp=1,
        peaks={"hbm_gbps": 819, "bf16_tflops": 197},
        client=dict(requests=[dict(prompt_len=3000, times=[], sent=99.0,
                                   end=101.0)] * 64,
                    window=[100.0, 100.2],
                    scrapes={"end": {"/metrics": {"text": gauges}}}),
        host_trace=dict(ticks=ticks, phases=[]),
        device_trace=dict(
            busy_s=0.2, window_s=0.2, ticks=20, wall=[100.0, 100.2],
            ops_s={"%ragged_paged_attention.3 bf16[8] custom-call": 0.015,
                   "%gather_w.1 bf16[8] fusion": 0.005,
                   "%ragged_paged_attention bf16[4] custom-call": 0.024,
                   "%gather_g.1 bf16[8] fusion": 0.006,
                   "%route.1 f32[8] fusion": 0.01,
                   "%grouped_matmul.2 bf16[8] custom-call": 0.06,
                   "%qkv.1 bf16[8] fusion": 0.08}))


def test_the_readers_on_a_made_up_trace_checked_by_hand(made_up):
    read = {name: reader(name) for name in NEW}
    assert read["swa.window_attn_share"](made_up) == pytest.approx(10.0)
    assert read["swa.global_attn_share"](made_up) == pytest.approx(15.0)
    # the grouped matmuls alone; the router has its own reading
    assert read["mimo-v2.experts_share"](made_up) == pytest.approx(30.0)
    assert read["mimo-v2.route_share"](made_up) == pytest.approx(5.0)
    # ten ticks at 30 / 20 and ten at 50 / 20
    assert read["mimo-v2.load_max_over_mean"](made_up) == pytest.approx(2.0)
    # the kernel alone, a class: 4,000 pages x 64 x 5,120 B over 1.2 ms,
    # 300 x 64 x 25,600 B over 0.75 ms
    want = 100 * (64 * 4000 * 5120 / 819e9) / 0.0012
    assert read["swa.global_kernel_roofline"](made_up) == pytest.approx(want)
    want = 100 * (64 * 300 * 25600 / 819e9) / 0.00075
    assert read["swa.window_kernel_roofline"](made_up) == pytest.approx(want)
    # (4,000 pages x 5,120 B + 300 x 25,600 B) x 64 tokens at 819 GB/s over
    # 0.05 s / 20 ticks = 2.5 ms under the two scopes
    want = 100 * (64 * (4000 * 5120 + 300 * 25600) / 819e9) / 0.0025
    assert read["swa.attn_roofline"](made_up) == pytest.approx(want)
    assert 0 < want < 100
    # 96 experts x 50.3 MB at 819 GB/s over 3 ms under moe_experts
    want = 100 * (96 * 50331648 / 819e9) / 0.003
    assert read["mimo-v2.experts_roofline"](made_up) == pytest.approx(want)
    cost = costs.tick_cost(made_up["config"], tokens=576, rows=64,
                           context_tokens=64 * 3000, experts_touched=96,
                           pairs_held=1300)
    least, _ = costs.least_seconds(cost, made_up["peaks"])
    assert read["mimo-v2.step_roofline"](made_up) == pytest.approx(
        100 * least / 0.01)
    assert read["swa.pool_bytes_per_context_token"](made_up) == pytest.approx(
        (3400 * 64 * 5120 + 200 * 64 * 25600) / 210000)


def test_the_readers_read_nothing_where_the_program_has_nothing(made_up):
    """A run without a trace, a map, the tick arguments or the gauges (the
    parent of PR 43, another architecture): every reader returns None and
    raises nothing."""
    bare = dict(made_up, device_trace=None, host_trace=None,
                client=dict(made_up["client"], scrapes={}))
    other = dict(made_up, config=dict(made_up["config"], model_type="qwen2"),
                 workload="none", client=dict(made_up["client"], scrapes={}))
    for name in NEW:
        assert reader(name)(bare) is None, name
        assert reader(name)(other) is None, name
    old = dict(made_up, host_trace=dict(ticks=[
        dict(t, args={k: v for k, v in t["args"].items()
                      if not k.startswith("attn_pages_")})
        for t in made_up["host_trace"]["ticks"]], phases=[]))
    assert reader("swa.attn_roofline")(old) is None
