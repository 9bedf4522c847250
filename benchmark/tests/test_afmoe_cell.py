"""The ``afmoe`` cell's data and readers on the CPU: the cell's files load the
way the harness loads them, the sizes its ``sizing`` and the configuration's
``sizes`` state follow from ``costs_afmoe.py`` (and that from hand
arithmetic, to the parameter), the thirteen readers PR 50 added read a
made-up trace that is checked by hand (and nothing from a run without one,
which is what the parent of PR 50 gives them), and ``run.py`` end to end on
the tiny configuration: a rehearsal of the cell, prompts past three windows
through both page classes.  The model and the served path at a tiny size are
tests/test_afmoe.py and tests/test_afmoe_serve.py."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import costs_afmoe as costs
import pytest

BENCH = Path(__file__).resolve().parents[1]
NAME = "trinity-large-5l-ep8"
CELL = NAME + ".longdoc-closed"
NEW = ("afmoe.window_attn_share", "afmoe.global_attn_share",
       "afmoe.window_kernel_roofline", "afmoe.global_kernel_roofline",
       "afmoe.prefill_tile_share", "afmoe.gate_share", "afmoe.experts_share",
       "afmoe.experts_roofline", "afmoe.route_share", "afmoe.shared_share",
       "afmoe.load_max_over_mean", "afmoe.pool_bytes_per_context_token",
       "afmoe.step_roofline")


def config():
    return json.loads((BENCH / "configs" / f"{NAME}.json").read_text())


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
    assert spec["config_name"] == NAME
    assert spec["cell"]["chips"] == 1 and spec["params"]["slots"] == 32
    assert spec["params"]["clients"] == 32 and not spec["params"]["num_blocks"]
    flags = spec["params"]["serve_flags"]
    assert flags[:4] == ["--arch", "afmoe", "--max-queue", "512"]
    assert flags[4] == "--tick-token-budget" and len(flags) == 6
    assert traffic.limits(spec["traffic"]) == (8192, 640)
    tr = spec["traffic"]
    assert (tr["loop"], tr["ramp_s"], tr["order_seed"], tr["block"]) == (
        "closed", 90, 0, 64)
    assert tr["prompt_tokens"] == {"dist": "uniform", "min": 4096, "max": 8192}
    assert tr["output_tokens"] == {"dist": "uniform", "min": 384, "max": 640}
    assert tr["stream_share"] == 1.0 and tr["sharing"] == {"kind": "none"}
    assert tr["bursts"] is None
    # every new metric is reported in this cell alone, and moves out_tok_s
    assert set(NEW) <= set(spec["per_layer"])
    for m in spec["bench"]["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
            assert (BENCH / "layers" / f"{m['name']}.py").exists()
    assert "step_roofline" not in spec["per_layer"]
    argv = harness.serve_argv(spec, "port", None)
    assert argv[argv.index("--prompt-len") + 1] == "8192"
    assert argv[argv.index("--max-tokens") + 1] == "640"
    assert "--num-blocks" not in argv  # the CLI's rule


def test_the_costs_follow_from_hand_arithmetic_to_the_parameter():
    c = config()
    # ISSUE 50's table, part by part
    attention = (2 * 3072 * 6144 + 2 * 3072 * 1024 + 6144 * 3072 + 2 * 128)
    assert costs.attention_params(c) == attention == 62914816
    assert costs.dense_ff_params(c) == 3 * 3072 * 12288 == 113246208
    assert costs.expert_params(c) == 3 * 3072 * 3072 == 28311552
    assert costs.held_expert_params(c) == 32 * 28311552 * 4 == 3623878656
    assert costs.shared_params(c) + costs.router_params(c) == (
        28311552 + 3072 * 256 + 256) == 29098240
    assert 2 * costs.head_params(c) + costs.norm_params(c) == (
        2 * 25024 * 3072 + 21 * 3072) == 153811968
    assert costs.param_count(c) == c["sizes"]["parameters"] == 4321903872
    assert round(costs.weight_bytes(c) / 2**20) == 8243
    assert costs.kinds(c) == {"global": 1, "window": 4}
    assert costs.counts(c) == {"dense": 1, "experts": 4}
    assert costs.kv_bytes_per_token(c, "global") == 4096
    assert costs.kv_bytes_per_token(c, "window") == 16384
    assert costs.window_blocks_per_slot(c, 128, 64) == 67
    pool = costs.pool_bytes(c, slots=32, global_blocks=32 * 140 + 2,
                            widest_slice=128, block_size=64)
    assert pool == {"global": 4482 * 64 * 4096, "window": 2145 * 64 * 16384}
    assert [round(v / 2**20) for v in pool.values()] == [1120, 2145]
    assert c["sizes"]["pool"]["global_class_bytes"] == pool["global"]
    assert c["sizes"]["pool"]["window_class_bytes"] == pool["window"]
    # a tick of 32 decode rows at 6,400 tokens of context, all 128 held
    # experts touched: every class's pages once, the weights outside the
    # routed experts and the embedding once
    cost = costs.tick_cost(c, tokens=32, rows=32, context_tokens=32 * 6400,
                           experts_touched=128, pairs_held=64)
    dense = 4321903872 - 25024 * 3072 - 3623878656
    assert costs.dense_streamed_params(c) == dense
    assert cost["bytes"] == (
        2 * dense + 128 * 2 * 28311552 + 4096 * (32 * 6400 + 32)
        + 16384 * (32 * 4096 + 32))
    matmul = 5 * (62914816 - 256) + 113246208 + 4 * (28311552 + 3072 * 256)
    assert costs.active_matmul_params(c) == matmul
    assert cost["flops"] == (
        2 * matmul * 32 + 2 * 28311552 * 64 + 2 * 25024 * 3072 * 32
        + 4 * 128 * 48 * (1 * 32 * 6400 + 4 * 32 * 4096))
    # what the kernel is asked to stream is per tile, not once
    assert costs.attention_bytes(c, 100, 67, 64) == 64 * (100 * 4096 + 67 * 16384)


def test_the_sizes_in_the_cells_sizing_follow_from_the_costs():
    c = config()
    sizing = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["sizing"]
    mib = lambda n: f"{round(n / 2**20):,} MiB"  # noqa: E731
    weights = costs.weight_bytes(c)
    per_seq = -(-(8192 + 640 + 127) // 64)
    blocks = 32 * per_seq + 2
    pool = costs.pool_bytes(c, slots=32, global_blocks=blocks,
                            widest_slice=128, block_size=64)
    assert (per_seq, blocks) == (140, 4482)
    for text in (f"{costs.param_count(c):,} bf16 parameters", mib(weights),
                 f"32 x {per_seq} + 2 = {blocks:,} blocks", mib(pool["global"]),
                 "2,145 blocks", mib(pool["window"]),
                 mib(weights + pool["global"] + pool["window"]),
                 mib(blocks * 64 * 20480)):
        assert text in sizing, text


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
             "%gate.1 bf16[8]": ["attn_gate", ""],
             "%route.1 f32[8]": ["moe_route", ""],
             "%grouped_matmul.2 bf16[8]": ["moe_experts", ""],
             "%shared.2 bf16[8]": ["moe_shared", ""],
             "%qkv.1 bf16[8]": ["qkv", ""]}
    out = tmp_path / f"{CELL}-7"
    out.mkdir()
    (out / "host_trace.json").write_text(json.dumps(
        {"traceEvents": [], "otherData": {"op_map": table}}))
    monkeypatch.setattr(tracefile, "OUT", tmp_path)
    tracefile._dumps.clear()
    ticks = [dict(start=100.0 + 0.01 * i, dur_s=0.01, args=dict(
        prefill_tokens=384, decode_tokens=32, active_slots=32,
        attn_pages=5000, attn_pages_global=5000, attn_pages_window=1500,
        attn_live_tiles=80, attn_decode_tiles=32, attn_prefill_tiles=48,
        experts_touched=120, pairs_held=800, expert_load_max=12 + i % 2 * 12,
        expert_load_mean=6.0)) for i in range(20)]
    gauges = "\n".join(f"llm_serve_{k} {v}" for k, v in dict(
        kv_global_blocks_in_use=3300, kv_global_block_bytes=64 * 4096,
        kv_window_blocks_in_use=2100, kv_window_block_bytes=64 * 16384,
        context_tokens_live=205000).items())
    return dict(
        workload=CELL, seed=7, config=config(), replicas=1, tp=1,
        peaks={"hbm_gbps": 819, "bf16_tflops": 197},
        client=dict(requests=[dict(prompt_len=6000, times=[], sent=99.0,
                                   end=101.0)] * 32,
                    window=[100.0, 100.2],
                    scrapes={"end": {"/metrics": {"text": gauges}}}),
        host_trace=dict(ticks=ticks, phases=[]),
        device_trace=dict(
            busy_s=0.2, window_s=0.2, ticks=20, wall=[100.0, 100.2],
            ops_s={"%ragged_paged_attention.3 bf16[8] custom-call": 0.05,
                   "%gather_w.1 bf16[8] fusion": 0.01,
                   "%ragged_paged_attention bf16[4] custom-call": 0.02,
                   "%gather_g.1 bf16[8] fusion": 0.004,
                   "%gate.1 bf16[8] fusion": 0.006,
                   "%route.1 f32[8] fusion": 0.01,
                   "%grouped_matmul.2 bf16[8] custom-call": 0.05,
                   "%shared.2 bf16[8] fusion": 0.008,
                   "%qkv.1 bf16[8] fusion": 0.042}))


def test_the_readers_on_a_made_up_trace_checked_by_hand(made_up):
    read = {name: reader(name) for name in NEW}
    assert read["afmoe.window_attn_share"](made_up) == pytest.approx(30.0)
    assert read["afmoe.global_attn_share"](made_up) == pytest.approx(12.0)
    assert read["afmoe.gate_share"](made_up) == pytest.approx(3.0)
    assert read["afmoe.experts_share"](made_up) == pytest.approx(25.0)
    assert read["afmoe.route_share"](made_up) == pytest.approx(5.0)
    assert read["afmoe.shared_share"](made_up) == pytest.approx(4.0)
    # 48 of 80 live tiles a tick hold a chunk's tokens
    assert read["afmoe.prefill_tile_share"](made_up) == pytest.approx(60.0)
    # ten ticks at 12 / 6 and ten at 24 / 6
    assert read["afmoe.load_max_over_mean"](made_up) == pytest.approx(3.0)
    # the kernel alone, a class: 5,000 pages x 64 x 4,096 B over 1 ms,
    # 1,500 x 64 x 16,384 B over 2.5 ms
    want = 100 * (64 * 5000 * 4096 / 819e9) / 0.001
    assert read["afmoe.global_kernel_roofline"](made_up) == pytest.approx(want)
    want = 100 * (64 * 1500 * 16384 / 819e9) / 0.0025
    assert read["afmoe.window_kernel_roofline"](made_up) == pytest.approx(want)
    assert 0 < want < 100
    # 120 experts x 56.6 MB at 819 GB/s over 2.5 ms under moe_experts
    want = 100 * (120 * 2 * 28311552 / 819e9) / 0.0025
    assert read["afmoe.experts_roofline"](made_up) == pytest.approx(want)
    cost = costs.tick_cost(made_up["config"], tokens=416, rows=32,
                           context_tokens=32 * 6000, experts_touched=120,
                           pairs_held=800)
    least, _ = costs.least_seconds(cost, made_up["peaks"])
    assert read["afmoe.step_roofline"](made_up) == pytest.approx(
        100 * least / 0.01)
    assert read["afmoe.pool_bytes_per_context_token"](made_up) == pytest.approx(
        (3300 * 64 * 4096 + 2100 * 64 * 16384) / 205000)


def test_the_readers_read_nothing_where_the_program_has_nothing(made_up):
    """A run without a trace, a map, the tick arguments or the gauges (the
    parent of PR 50, another architecture): every reader returns None and
    raises nothing."""
    bare = dict(made_up, device_trace=None, host_trace=None,
                client=dict(made_up["client"], scrapes={}))
    other = dict(made_up, config=dict(made_up["config"], model_type="mimo_v2"),
                 workload="none", client=dict(made_up["client"], scrapes={}))
    for name in NEW:
        assert reader(name)(bare) is None, name
        assert reader(name)(other) is None, name
    old = dict(made_up, host_trace=dict(ticks=[
        dict(t, args={k: v for k, v in t["args"].items()
                      if not k.startswith(("attn_pages_", "attn_prefill"))})
        for t in made_up["host_trace"]["ticks"]], phases=[]))
    for name in ("afmoe.window_kernel_roofline", "afmoe.global_kernel_roofline",
                 "afmoe.prefill_tile_share"):
        assert reader(name)(old) is None, name


# ----------------------------------------------------------------------
# the rehearsal: run.py end to end on the tiny configuration
# ----------------------------------------------------------------------

def tiny_afmoe_root(tmp: Path) -> tuple[Path, str]:
    """``tiny_root.make``'s temporary copy of the benchmark's data with a
    tiny ``afmoe`` cell ADDED beside its own: the program's toy
    configuration behind the cell's flags, prompts of three to six windows
    of 8."""
    import tiny_root
    from llm_np_cp_tpu.config import tiny_config
    from llm_np_cp_tpu.utils.synthetic import hf_config_dict

    root, _ = tiny_root.make(tmp)
    b = root / "benchmark"
    cfg = dict(hf_config_dict(tiny_config("afmoe")), source="none: a toy",
               reduced=[], serve=tiny_root.TINY_CONFIG["serve"])
    (b / "configs" / "tiny-afmoe.json").write_text(json.dumps(cfg))
    (b / "traffic" / "tiny-longdoc.json").write_text(json.dumps(dict(
        tiny_root.TINY_CLOSED,
        prompt_tokens={"dist": "uniform", "min": 24, "max": 48},
        output_tokens={"dist": "uniform", "min": 6, "max": 12})))
    cell = "tiny-afmoe.tiny-longdoc"
    (b / "cells" / f"{cell}.json").write_text(json.dumps(
        {"slots": 3, "num_blocks": 0, "clients": 3, "rate_rps": None,
         "serve_flags": ["--arch", "afmoe", "--max-queue", "64",
                         "--tick-token-budget", "32"]}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-afmoe", source="none", reduced=[],
                                 file="benchmark/configs/tiny-afmoe.json",
                                 why="test"))
    bench["workloads"].append(dict(name=cell, config="tiny-afmoe",
                                   traffic="tiny-longdoc", why="test", chips=1))
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root, cell


def test_the_cell_rehearsed_on_the_tiny_configuration(tmp_path):
    root, cell = tiny_afmoe_root(tmp_path)
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--rehearsal", "--data-root",
         str(root), "--workload", cell, "--seed", str(2**31 + 50),
         "--seconds", "3", "--trace", "1"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["rehearsal"] is True and result["metrics"] == {}
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    # the banner names both classes and the ring
    assert "global x1 + window" in proc.stdout and "(ring of " in proc.stdout
    got = result["rehearsal_metrics"]
    # the counters' readers found something to read; the device-trace
    # readers nothing (no device trace off the chip) and were left out
    for name in ("afmoe.prefill_tile_share", "afmoe.load_max_over_mean",
                 "afmoe.pool_bytes_per_context_token"):
        assert got[name]["value"] > 0, name
    assert not any(name.endswith(("_roofline", "_attn_share", "gate_share"))
                   and name.startswith("afmoe.") for name in got)
    detail = json.loads((root / "benchmark" / "out" /
                         f"{cell}-{2**31 + 50}.json").read_text())
    assert detail["reference"] and all(r["ok"] for r in detail["reference"])
    assert max(r["prompt_len"] for r in detail["reference"]) > 3 * 8
