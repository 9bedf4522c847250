"""The ``lfm2_moe`` cell on the CPU with a tiny preset: the harness path
(``--arch`` in the cell's ``serve_flags``, the plain forward's check), the
readers PR 32 added on a rehearsal trace and on a made-up device trace, the
flips diagnostic, and ``costs_lfm2_moe.py`` against hand arithmetic."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import costs_lfm2_moe as costs
import pytest
import tiny_root
from test_rehearsal import run

BENCH = Path(__file__).resolve().parents[1]
CELL = "lfm2-8b-a1b-16l.decode-closed"
NEW = ("moe.experts_share", "moe.route_share", "conv.time_share",
       "moe.experts_roofline", "lfm2.step_roofline", "moe.load_max_over_mean")

TINY_LFM2 = {
    "model_type": "lfm2_moe", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32, "num_hidden_layers": 10,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "max_position_embeddings": 512, "rope_theta": 1000000, "norm_eps": 1e-5,
    "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 2, "num_experts": 8,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1,
    "source": "none: a toy for the harness's own tests", "reduced": [],
    "serve": {"dtype": "f32", "cache_dtype": "f32", "block_size": 8,
              "mesh": "", "replicas": 1, "chips": 1},
}


def config(name="lfm2-8b-a1b-16l"):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_"), BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------------
# costs_lfm2_moe.py against the issue's table, to the parameter
# ----------------------------------------------------------------------

def test_per_kind_sizes_by_hand():
    c = config()
    h = 2048
    assert costs.conv_operator_params(c) == h * 3 * h + h * h + h * 3 == 16_783_360
    assert costs.attn_operator_params(c) == 2 * h * 2048 + 2 * h * 512 + 2 * 64 == 10_485_888
    assert costs.dense_ff_params(c) == 3 * h * 7168 == 44_040_192
    assert costs.expert_params(c) == 3 * h * 1792 == 11_010_048
    assert costs.expert_ff_params(c) == 32 * 11_010_048 + h * 32 + 32 == 352_387_104
    assert costs.head_params(c) == 65_536 * h == 134_217_728
    assert costs.counts(c) == {"conv": 12, "attn": 4, "dense": 2, "experts": 14}


def test_the_cut_and_the_published_model():
    c = config()
    assert costs.param_count(c) == 5_399_129_024 == c["sizes"]["parameters"]
    assert costs.weight_bytes(c) == 10_798_258_048 == c["sizes"]["weight_bytes_bf16"]
    # K/V of the 4 attention layers only; the conv state of the 12 others
    assert costs.kv_bytes_per_token(c) == 4 * 2 * 8 * 64 * 2 == 8_192
    assert costs.state_bytes_per_slot(c) == 12 * 2 * 2048 * 2 == 98_304
    assert c["sizes"]["kv_bytes_per_token_bf16"] == 8_192
    assert c["sizes"]["conv_state_bytes_per_slot_bf16"] == 98_304
    # the published 24 layers: 18 conv + 6 attention, 2 dense + 22 expert
    row = dict(c, num_hidden_layers=24, layer_types=c["layer_types"] + [
        "conv", "conv", "full_attention", "conv", "conv", "full_attention",
        "conv", "conv"])
    assert costs.counts(row) == {"conv": 18, "attn": 6, "dense": 2, "experts": 22}
    assert costs.param_count(row) == c["sizes"]["published"]["parameters"] == 8_339_930_560


def test_a_tick_reads_the_experts_it_touches_and_no_others():
    c = config()
    all_touched = costs.tick_cost(c, tokens=64, rows=64, context_tokens=25_000,
                                  experts_touched=14 * 32)
    none = costs.tick_cost(c, tokens=64, rows=64, context_tokens=25_000,
                           experts_touched=0)
    one_expert = 11_010_048 * 2
    assert all_touched["bytes"] - none["bytes"] == 14 * 32 * one_expert
    # with every expert touched the tick reads every weight once
    assert all_touched["bytes"] == (
        costs.weight_bytes(c) + 8_192 * (25_000 + 64) + 2 * 64 * 98_304)
    # 4 of 32 experts a token in each of 14 layers, the gate, the head per row
    per_token = (12 * 16_783_360 + 4 * 10_485_888 + 2 * 44_040_192
                 + 14 * (4 * 11_010_048 + 2048 * 32))
    attended = 25_000 * 64 / 64
    assert all_touched["flops"] == (2 * per_token * 64 + 2 * 134_217_728 * 64
                                    + 4 * 64 * 32 * 4 * attended)
    least, bound = costs.least_seconds(all_touched, {"hbm_gbps": 819, "bf16_tflops": 197})
    assert bound == "memory" and 0.0132 < least < 0.0137  # the issue's 13.2 ms


# ----------------------------------------------------------------------
# the cell's files
# ----------------------------------------------------------------------

def test_the_cell_states_the_architecture_the_server_must_load():
    cell = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    assert cell["serve_flags"] == ["--arch", "lfm2_moe"]
    assert cell["slots"] == cell["clients"] == 64 and cell["num_blocks"] == 0
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b-16l")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"]
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL], m["name"]
    assert {m["name"] for m in bench["per_layer"]} >= set(NEW)


def test_the_configuration_is_the_catalog_rows_first_16_layers():
    c = config()
    assert c["model_type"] == "lfm2_moe" and c["num_hidden_layers"] == 16
    assert c["layer_types"] == ["conv", "conv", "full_attention", "conv"] + \
        ["conv", "conv", "full_attention", "conv"] * 3
    for key, value in dict(hidden_size=2048, intermediate_size=7168,
                           moe_intermediate_size=1792, num_experts=32,
                           num_experts_per_tok=4, num_dense_layers=2,
                           num_attention_heads=32, num_key_value_heads=8,
                           vocab_size=65536, conv_L_cache=3, norm_eps=1e-5,
                           rope_theta=1000000, use_expert_bias=True,
                           norm_topk_prob=True).items():
        assert c[key] == value, key


# ----------------------------------------------------------------------
# the harness path, on a tiny preset
# ----------------------------------------------------------------------

def add_tiny_lfm2(root: Path) -> str:
    b = root / "benchmark"
    (b / "configs" / "tiny-lfm2.json").write_text(json.dumps(TINY_LFM2))
    (b / "cells" / "tiny-lfm2.tiny-mix.json").write_text(json.dumps(
        {"slots": 4, "num_blocks": 0, "clients": 4, "rate_rps": 6.0,
         "serve_flags": ["--arch", "lfm2_moe"]}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-lfm2", source="none", reduced=[],
                                 file="benchmark/configs/tiny-lfm2.json", why="test"))
    bench["workloads"].append(dict(name="tiny-lfm2.tiny-mix", config="tiny-lfm2",
                                   traffic="tiny-mix", why="test", chips=1))
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = m["workloads"] + ["tiny-lfm2.tiny-mix"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return "tiny-lfm2.tiny-mix"


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root, _ = tiny_root.make(tmp_path_factory.mktemp("lfm2"))
    workload = add_tiny_lfm2(root)
    _, result = run(root, workload, "--trace", "1")
    out = root / "benchmark" / "out"
    dump = json.loads((out / f"{workload}-{2**31 + 11}" / "host_trace.json").read_text())
    return root, workload, result, dump


def test_rehearsal_serves_the_tiny_stack_through_the_unified_tick(traced):
    root, workload, result, _ = traced
    assert result["correct"] is True and result["failed"] == 0
    detail = json.loads((root / "benchmark" / "out" /
                         f"{workload}-{2**31 + 11}.json").read_text())
    assert detail["resolution"]["tick"] == "unified"
    assert detail["resolution"]["ragged_attn"] == "pallas"
    assert detail["resolution"]["epilogue"] == "fused"
    assert detail["reference"] and all(r["ok"] for r in detail["reference"])


def test_the_counter_reader_reads_and_the_device_readers_are_left_out(traced):
    got = traced[2]["rehearsal_metrics"]
    # 8 experts, top-2: an expert's mean load is a quarter of the tick's rows
    assert 1.0 <= got["moe.load_max_over_mean"]["value"] <= 4.0
    for name in NEW[:-1]:  # no device trace off the chip
        assert name not in got


def test_the_dump_names_the_new_scopes_and_counters(traced):
    _, _, _, dump = traced
    known = [v for v in dump["otherData"]["op_map"].values() if v is not None]
    assert {scope for scope, _ in known} >= {"conv", "moe_route", "moe_experts", "mlp"}
    ticks = [e["args"] for e in dump["traceEvents"]
             if e.get("name") == "tick" and e.get("args", {}).get("decode_tokens")]
    assert ticks and all(
        {"experts_touched", "expert_load_max", "expert_load_mean",
         "state_slots_live"} <= set(a) for a in ticks)


def test_the_new_readers_on_a_made_up_device_trace(traced):
    """What the chip's profile would hold: two operations under the new
    scopes, 20 ticks of 10 ms; the readers divide what they should."""
    root, workload, _, dump = traced
    table = dump["otherData"]["op_map"]
    by_scope = {}
    for key, val in table.items():
        if val and not val[1]:
            by_scope.setdefault(val[0], key)
    ticks = [dict(start=100.0 + 0.01 * i, dur_s=0.01, args=dict(
        prefill_tokens=0, decode_tokens=4, active_slots=4, experts_touched=40,
        expert_load_max=3, expert_load_mean=1.0)) for i in range(20)]
    run_rec = dict(
        workload=workload, seed=2**31 + 11, config=TINY_LFM2, replicas=1, tp=1,
        peaks={"hbm_gbps": 819, "bf16_tflops": 197},
        client=dict(requests=[], window=[100.0, 100.2]),
        host_trace=dict(ticks=ticks, phases=[]),
        device_trace=dict(
            busy_s=0.2, window_s=0.2, ticks=20, wall=[100.0, 100.2],
            ops_s={by_scope["moe_experts"] + " fusion": 0.08,
                   by_scope["moe_route"] + " fusion": 0.02,
                   by_scope["conv"] + " fusion": 0.04}))
    # the readers look the dump up beside themselves: point them at the copy
    sys.path.insert(0, str(root / "benchmark" / "layers"))
    theirs = sys.modules.pop("tracefile", None)  # other tests hold this one
    try:
        read = {name: _reader_from(root, name) for name in NEW}
        assert read["moe.experts_share"](run_rec) == pytest.approx(40.0)
        assert read["moe.route_share"](run_rec) == pytest.approx(10.0)
        assert read["conv.time_share"](run_rec) == pytest.approx(20.0)
        assert read["moe.load_max_over_mean"](run_rec) == pytest.approx(3.0)
        # 40 experts x 3 x 64 x 32 x 4 B (f32) a tick over 4 ms a tick
        want = 100.0 * (40 * 3 * 64 * 32 * 4 / 819e9) / 0.004
        assert read["moe.experts_roofline"](run_rec) == pytest.approx(want)
        step = read["lfm2.step_roofline"](run_rec)
        assert 0.0 < step < 100.0
        # a program without the scopes or the counters: nothing to read
        bare = dict(run_rec, host_trace=dict(ticks=[dict(t, args=dict(
            prefill_tokens=0, decode_tokens=4, active_slots=4)) for t in ticks],
            phases=[]), config=dict(TINY_LFM2, model_type="qwen2"))
        assert read["lfm2.step_roofline"](bare) is None
        assert read["moe.experts_roofline"](bare) is None
        assert read["moe.load_max_over_mean"](bare) is None
    finally:
        sys.path.remove(str(root / "benchmark" / "layers"))
        sys.modules.pop("tracefile", None)
        if theirs is not None:
            sys.modules["tracefile"] = theirs


def _reader_from(root: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        "copy_" + name.replace(".", "_"), root / "benchmark" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_scope_readers_read_nothing_from_a_map_without_the_scopes():
    """The parent's op map knows none of the three scopes."""
    run_rec = dict(workload="none", seed=0, device_trace=dict(busy_s=1.0, ops_s={}))
    for name in NEW[:3]:
        assert reader(name)(run_rec) is None


def test_flips_diagnostic_runs_on_the_rehearsals_record(traced):
    root, workload, _, _ = traced
    proc = subprocess.run(
        [sys.executable, str(BENCH / "flips_lfm2_moe.py"), "--data-root", str(root),
         "--workload", workload, "--seed", str(2**31 + 11), "--samples", "2"],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = json.loads((root / "benchmark" / "out" /
                       f"{workload}-{2**31 + 11}.flips.json").read_text())
    assert len(rows) == 2
    for row in rows:  # float32 served against float32: the same experts, no gap
        assert row["flip_share"] == 0.0
        assert row["served_under_f32"]["quantiles"][-1] < 1e-3
