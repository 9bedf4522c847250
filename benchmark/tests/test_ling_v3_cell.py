"""The ``ling_hybrid`` cell's data and readers on the CPU: the cell's files
load the way the harness loads them, the configuration's file states the
catalog row's keys (every number of it but what ``reduced`` names), the nine
readers PR 47 added read a made-up trace that is checked by hand (and nothing
from a run without one, or from another configuration's record, which is what
the parent of PR 47 and every other cell give them), and the sizes the cell's
``sizing`` states follow from ``costs_ling_v3.py``.  The served path at a
tiny size is tests/test_ling_hybrid_serve.py."""

import importlib.util
import json
import sys
from pathlib import Path

import costs_ling_v3 as costs
import pytest

BENCH = Path(__file__).resolve().parents[1]
NAME = "ling-3.0-flash-7l-ep4"
CELL = NAME + ".decode-closed"
NEW = ("kda.scan_share", "kda.proj_share", "kda.state_roofline",
       "ling-3.0.experts_share", "ling-3.0.experts_roofline",
       "ling-3.0.route_share", "ling-3.0.mla_attn_share",
       "ling-3.0.load_max_over_mean", "ling-3.0.step_roofline")
CATALOG = Path("/opt/skills/guides/model-configs/architectures.jsonl")


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
    assert spec["cell"]["chips"] == 1 and spec["cell"]["traffic"] == "decode-closed"
    assert spec["params"]["slots"] == spec["params"]["clients"] == 64
    assert not spec["params"]["num_blocks"]
    assert spec["params"]["serve_flags"] == ["--arch", "ling_hybrid"]
    assert traffic.limits(spec["traffic"]) == (256, 640)
    # every new metric is reported in this cell alone, moves out_tok_s and
    # has its reader; the entries are the LAST of their lists
    assert set(NEW) <= set(spec["per_layer"])
    bench = spec["bench"]
    assert [m["name"] for m in bench["per_layer"]][-len(NEW):] == list(NEW)
    for m in bench["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert (BENCH / "layers" / f"{m['name']}.py").exists()
    assert bench["workloads"][-1]["name"] == CELL
    assert bench["configs"][-1]["name"] == NAME
    assert "step_roofline" not in spec["per_layer"]
    argv = harness.serve_argv(spec, "port", None)
    assert argv[argv.index("--slots") + 1] == "64"
    assert "--num-blocks" not in argv  # the CLI's rule


def test_the_configurations_file_states_the_catalog_rows_keys():
    c = config()
    entry = next(e for e in json.loads(
        (BENCH.parent / "BENCHMARK.json").read_text())["configs"]
        if e["name"] == NAME)
    assert entry["source"] == c["source"] and entry["reduced"] == c["reduced"]
    assert c["model_type"] == "ling_hybrid"
    assert (c["num_hidden_layers"], c["first_k_dense_replace"],
            c["num_experts"], c["router_experts"], c["first_expert"]) == (
        7, 1, 128, 512, 0)
    assert len(c["assumed"]) >= 12 and "28 chips" in c["deployment"]
    if not CATALOG.exists():
        pytest.skip("no catalog beside the guides here")
    row = next(r for r in map(json.loads, CATALOG.read_text().splitlines())
               if r.get("source_url") == c["source"])
    for key, value in row["config"].items():
        if key in c["reduced"]:
            assert c[key] != value, key
        else:
            assert c[key] == value, key
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        assert c[key] == row["config"][key][:7] and not any(c[key])


def test_the_sizes_in_the_cells_sizing_follow_from_the_costs():
    c = config()
    sizing = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())["sizing"]
    mib = lambda n: f"{round(n / 2**20):,} MiB"  # noqa: E731
    weights = costs.weight_bytes(c)
    per_seq = -(-(256 + 640 + 127) // 64)
    blocks = 64 * per_seq + 2
    pages = blocks * 64 * 1280  # a row of 576 values stored 640 wide
    state = costs.state_bytes(c, 64)
    assert (per_seq, blocks) == (16, 1026)
    for text in (f"{costs.param_count(c):,} bf16 parameters", mib(weights),
                 f"64 x {per_seq} + 2 = {blocks:,} blocks", mib(pages),
                 mib(state), mib(weights + pages + state),
                 f"{costs.kda_state_bytes_per_row(c):,} B float32",
                 f"{costs.conv_state_bytes_per_row(c):,} B bf16"):
        assert text in sizing, text
    assert c["sizes"]["parameters"] == costs.param_count(c) == 5772871616
    assert c["sizes"]["latent_bytes_per_token_bf16"] == 1152
    assert c["sizes"]["state_bytes_per_slot"] == 6 * (2097152 + 73728)
    assert costs.expert_params(c) == 3 * 2560 * 768
    p = costs.parts(c)
    assert p["experts_held"] == 6 * 128 * costs.expert_params(c)
    assert p["embedding"] == p["head"] == 157184 * 2560


@pytest.fixture()
def made_up(tmp_path, monkeypatch):
    """What the chip's profile and the recorder's dump would hold: the
    state-update kernel and one operation beside it under ``kda_scan``, one
    operation under each other scope, 20 ticks of 20 ms busy."""
    sys.path.insert(0, str(BENCH / "layers"))
    import tracefile

    table = {"%kda_state_update.12 f32[6]": ["kda_scan", ""],
             "%gather_k.1 f32[8]": ["kda_scan", ""],
             "%kda_in.1 bf16[8]": ["kda_proj", ""],
             "%ragged_latent_attention.2 bf16[4]": ["attn", ""],
             "%route.1 f32[8]": ["moe_route", ""],
             "%grouped_matmul.2 bf16[8]": ["moe_experts", ""],
             "%head.1 bf16[8]": ["tail", ""]}
    out = tmp_path / f"{CELL}-7"
    out.mkdir()
    (out / "host_trace.json").write_text(json.dumps(
        {"traceEvents": [], "otherData": {"op_map": table}}))
    monkeypatch.setattr(tracefile, "OUT", tmp_path)
    tracefile._dumps.clear()
    ticks = [dict(start=100.0 + 0.01 * i, dur_s=0.01, args=dict(
        prefill_tokens=16, decode_tokens=62, active_slots=63,
        kda_state_rows=63, kda_scan_tokens=78, kda_state_impl="pallas",
        experts_touched=480, pairs_held=790, expert_load_max=4 + i % 2 * 4,
        expert_load_mean=2.0)) for i in range(20)]
    return dict(
        workload=CELL, seed=7, config=config(), replicas=1, tp=1,
        peaks={"hbm_gbps": 819, "bf16_tflops": 197},
        client=dict(requests=[dict(prompt_len=160, times=[], sent=99.0,
                                   end=101.0)] * 63,
                    window=[100.0, 100.2], scrapes={}),
        host_trace=dict(ticks=ticks, phases=[]),
        device_trace=dict(
            busy_s=0.4, window_s=0.4, ticks=20, wall=[100.0, 100.2],
            ops_s={"%kda_state_update.12 f32[6] custom-call": 0.05,
                   "%gather_k.1 f32[8] fusion": 0.01,
                   "%kda_in.1 bf16[8] fusion": 0.02,
                   "%ragged_latent_attention.2 bf16[4] custom-call": 0.004,
                   "%route.1 f32[8] fusion": 0.016,
                   "%grouped_matmul.2 bf16[8] custom-call": 0.18,
                   "%head.1 bf16[8] fusion": 0.02}))


def test_the_readers_on_a_made_up_trace_checked_by_hand(made_up):
    read = {name: reader(name) for name in NEW}
    assert read["kda.scan_share"](made_up) == pytest.approx(15.0)
    assert read["kda.proj_share"](made_up) == pytest.approx(5.0)
    assert read["ling-3.0.experts_share"](made_up) == pytest.approx(45.0)
    assert read["ling-3.0.route_share"](made_up) == pytest.approx(4.0)
    assert read["ling-3.0.mla_attn_share"](made_up) == pytest.approx(1.0)
    # ten ticks at 4 / 2 and ten at 8 / 2
    assert read["ling-3.0.load_max_over_mean"](made_up) == pytest.approx(3.0)
    # 63 rows x 6 layers x 2 x 2 MiB at 819 GB/s over the 3 ms a tick
    # spends under kda_scan (the kernel AND what stands beside it)
    want = 100 * (63 * 6 * 2 * 2097152 / 819e9) / 0.003
    assert read["kda.state_roofline"](made_up) == pytest.approx(want)
    assert 0 < want < 100
    # 480 experts x 11.8 MB at 819 GB/s over 9 ms under moe_experts
    want = 100 * (480 * 3 * 2560 * 768 * 2 / 819e9) / 0.009
    assert read["ling-3.0.experts_roofline"](made_up) == pytest.approx(want)
    assert 0 < want < 105
    cost = costs.tick_cost(made_up["config"], tokens=78, rows=63,
                           context_tokens=63 * 160, experts_touched=480,
                           pairs_held=790, state_rows=63)
    least, bound = costs.least_seconds(cost, made_up["peaks"])
    assert bound == "memory"
    assert read["ling-3.0.step_roofline"](made_up) == pytest.approx(
        100 * least / 0.02)
    # the step's least bytes: dense weights once, touched experts, the
    # state twice, the latent context once
    c = made_up["config"]
    assert cost["bytes"] == pytest.approx(
        costs.dense_streamed_params(c) * 2 + 480 * costs.expert_params(c) * 2
        + 63 * 6 * 2 * (2097152 + 73728) + 1152 * (63 * 160 + 78))


def test_the_readers_read_nothing_where_the_program_has_nothing(made_up):
    """A run without a trace, a map or the tick arguments (the parent of PR
    47), and another configuration's record: every reader returns None and
    raises nothing."""
    bare = dict(made_up, device_trace=None, host_trace=None)
    other = dict(made_up, workload="none", config=json.loads(
        (BENCH / "configs" / "falcon-h1-34b-6l.json").read_text()))
    for name in NEW:
        assert reader(name)(bare) is None, name
        assert reader(name)(other) is None, name
    old = dict(made_up, host_trace=dict(ticks=[
        dict(t, args={k: v for k, v in t["args"].items()
                      if not k.startswith("kda_")})
        for t in made_up["host_trace"]["ticks"]], phases=[]))
    assert reader("kda.state_roofline")(old) is None
    assert reader("ling-3.0.step_roofline")(old) is None
