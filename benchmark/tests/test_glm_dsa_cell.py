"""The ``glm_moe_dsa`` cell on the CPU with a tiny preset: the harness path
(``--arch`` in the cell's ``serve_flags``, the plain forward's check), the
readers PR 58 added on a rehearsal trace and on made-up device numbers checked
by hand, the parity diagnostic with controls it must refuse, ``costs_glm_dsa.py``
against the hand arithmetic of the issue to the parameter, and the entries and
files of the cell."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import costs_glm_dsa as costs
import pytest
import tiny_root
from test_rehearsal import run

BENCH = Path(__file__).resolve().parents[1]
CONFIG = "glm-5-5l-ep16"
CELL = "glm-5-5l-ep16.sparsectx-closed"
NEW = ("dsa.index_share", "dsa.select_share", "dsa.attn_share",
       "dsa.index_roofline", "dsa.attn_roofline", "glm-5.experts_share",
       "glm-5.step_roofline")
COUNTER = "dsa.selected_share"
TICK_KINDS = ("tick.prefill_wall_ms", "tick.decode_wall_ms",
              "step.decode_device_ms", "step.prefill_device_ms")

TINY = {
    "model_type": "glm_moe_dsa", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "max_position_embeddings": 512, "rms_norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "tie_word_embeddings": False, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 24,
    "rope_interleave": True, "index_topk": 12, "index_n_heads": 2,
    "index_head_dim": 16, "indexer_rope_interleave": True,
    "n_routed_experts": 4, "router_experts": 8, "first_expert": 2,
    "n_shared_experts": 1, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "routed_scaling_factor": 2.5, "ep_size": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True,
    "source": "none: a toy for the harness's own tests", "reduced": [],
    "serve": {"dtype": "f32", "cache_dtype": "f32", "block_size": 8,
              "mesh": "", "replicas": 1, "chips": 1},
}


def config():
    return json.loads((BENCH / "configs" / f"{CONFIG}.json").read_text())


def reader(name, root=BENCH.parent):
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_").replace("-", "_"),
        root / "benchmark" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------------
# costs_glm_dsa.py against the issue's arithmetic, to the parameter
# ----------------------------------------------------------------------

def test_a_layers_sizes_by_hand():
    c = config()
    assert costs.attention_params(c) == (
        12582912 + 2048 + 33554432 + 3538944 + 512 + 14680064 + 100663296
    ) == 165022208
    assert costs.indexer_params(c) == 8388608 + 786432 + 256 + 196608 == 9371904
    assert costs.ds.dense_ff_params(c) == 3 * 6144 * 12288 == 226492416
    assert costs.ds.expert_params(c) == costs.ds.shared_params(c) == 37748736
    assert costs.ds.router_params(c) == 6144 * 256 + 256  # 256 wide whoever holds what
    assert costs.ds.head_params(c) == 19360 * 6144 == 118947840
    per = c["sizes"]["per_layer_parameters"]
    assert per["dense_layer"] == 174394112 + 12288 + 226492416 == 400898816
    assert per["expert_layer_held"] == 817708032
    assert per["expert_layer_whole"] == 817708032 + 240 * 37748736 == 9877404672


def test_the_cut_and_the_published_model():
    c = config()
    # 400.9 + 4 x 817.7 + 237.9 M (+ the final norm) = 3,909.6 M = 7,457 MiB
    assert costs.param_count(c) == 400898816 + 4 * 817708032 + 2 * 118947840 + 6144
    assert costs.param_count(c) == 3909632768 == c["sizes"]["parameters"]
    assert costs.weight_bytes(c) == 7819265536 == c["sizes"]["weight_bytes_bf16"]
    assert c["sizes"] == costs.sizes(c, {k: c["sizes"]["published"][k] for k in (
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size")}) | {"notes": c["sizes"]["notes"]}
    assert 743e9 < c["sizes"]["published"]["parameters"] < 745e9  # "744B"
    # a token: 5 x (1,152 B needed, 1,280 stored + 256 B of index key)
    assert costs.cache_bytes_per_token(c) == 5 * (1152 + 256) == 7040
    assert costs.stored_bytes_per_token(c) == 5 * (1280 + 256) == 7680
    # ... and the program's own statements of both
    sys.path.insert(0, str(BENCH.parent))
    from llm_np_cp_tpu.config import ModelConfig
    from llm_np_cp_tpu.models.transformer import param_shapes
    import jax
    import math

    cfg = ModelConfig.from_hf_dict(c)
    leaves = jax.tree.leaves(param_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple))
    assert sum(math.prod(s) for s in leaves) == 3909632768
    assert cfg.kv_bytes_per_token(2) == 7040


def test_the_two_byte_bounds_are_what_the_mathematics_needs():
    c = config()
    # a token that sees 8,000 positions: 8,000 index keys, 2,048 rows, a layer
    assert costs.index_bytes(c, 8000) == 8000 * 256
    assert costs.selected_row_bytes(c, 2048) == 2048 * 1152
    base = dict(tokens=24, rows=24, visible=0, selected=0, experts_touched=0,
                pairs_held=0)
    nothing = costs.tick_cost(c, **base)
    # everything outside the routed experts, the embedding only gathered
    assert nothing["bytes"] == 2 * (
        3909632768 - 118947840 - 4 * 16 * 37748736) + 24 * 7040
    seen = costs.tick_cost(c, **dict(base, visible=24 * 8000, selected=24 * 2048))
    assert seen["bytes"] - nothing["bytes"] == 5 * 24 * (8000 * 256 + 2048 * 1152)
    assert seen["flops"] - nothing["flops"] == 5 * 24 * (
        2 * 32 * 128 * 8000 + 2 * (576 + 512) * 64 * 2048)
    touched = costs.tick_cost(c, **dict(base, experts_touched=10, pairs_held=40))
    assert touched["bytes"] - nothing["bytes"] == 10 * 37748736 * 2
    assert touched["flops"] - nothing["flops"] == 2 * 37748736 * 40


def test_the_cell_the_traffic_and_the_entries_are_the_issues():
    cell = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    assert (cell["slots"], cell["clients"], cell["num_blocks"],
            cell["rate_rps"]) == (24, 24, 0, None)
    flags = cell["serve_flags"]
    assert flags[:4] == ["--arch", "glm_moe_dsa", "--max-queue", "512"]
    assert flags[4] == "--tick-token-budget" and int(flags[5]) in (280, 536, 792)
    mix = json.loads((BENCH / "traffic" / "sparsectx-closed.json").read_text())
    assert mix["loop"] == "closed" and mix["ramp_s"] == 90 and mix["block"] == 64
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 6144, "max": 8192}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 384, "max": 640}
    assert mix["stream_share"] == 1.0 and mix["sharing"] == {"kind": "none"}
    assert mix["bursts"] is None and mix["order_seed"] == 0
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (entry["chips"], entry["traffic"], entry["config"]) == (
        1, "sparsectx-closed", CONFIG) and len(entry["why"]) <= 200
    conf = next(c for c in bench["configs"] if c["name"] == CONFIG)
    assert conf["file"] == f"benchmark/configs/{CONFIG}.json"
    assert conf["reduced"] == config()["reduced"] and len(conf["why"]) <= 200
    assert conf["source"] == config()["source"]
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW + (COUNTER,):
        m = by_name[name]
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        assert m["unit"] == "%"
        assert any((BENCH / "layers" / f"{name}{ext}").exists()
                   for ext in (".py", ".json"))
    for name in TICK_KINDS:
        assert CELL in by_name[name]["workloads"]
    # the keys of the catalog row, every width as published
    c = config()
    assert (c["hidden_size"], c["num_attention_heads"], c["qk_nope_head_dim"],
            c["qk_rope_head_dim"], c["v_head_dim"], c["q_lora_rank"],
            c["kv_lora_rank"], c["index_n_heads"], c["index_head_dim"],
            c["index_topk"], c["moe_intermediate_size"], c["router_experts"],
            c["num_experts_per_tok"], c["intermediate_size"]) == (
        6144, 64, 192, 64, 256, 2048, 512, 32, 128, 2048, 2048, 256, 8, 12288)
    assert c["assumed"] and "16 chips" in c["deployment"]


# ----------------------------------------------------------------------
# the harness path, on a tiny preset
# ----------------------------------------------------------------------

def add_tiny(root: Path) -> str:
    b = root / "benchmark"
    (b / "configs" / "tiny-dsa.json").write_text(json.dumps(TINY))
    (b / "cells" / "tiny-dsa.tiny-mix.json").write_text(json.dumps(
        {"slots": 4, "num_blocks": 0, "clients": 4, "rate_rps": 6.0,
         "serve_flags": ["--arch", "glm_moe_dsa"]}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-dsa", source="none", reduced=[],
                                 file="benchmark/configs/tiny-dsa.json", why="test"))
    bench["workloads"].append(dict(name="tiny-dsa.tiny-mix", config="tiny-dsa",
                                   traffic="tiny-mix", why="test", chips=1))
    for m in bench["per_layer"]:
        if m["name"] in NEW + (COUNTER,):
            m["workloads"] = m["workloads"] + ["tiny-dsa.tiny-mix"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return "tiny-dsa.tiny-mix"


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root, _ = tiny_root.make(tmp_path_factory.mktemp("dsa"))
    workload = add_tiny(root)
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
    assert detail["reference"] and all(r["ok"] for r in detail["reference"])
    # prompts of 4-24 tokens with answers of 12-24: contexts cross index_topk 12
    assert max(r["prompt_len"] + r["tokens"] for r in detail["reference"]) > 24
    for name in NEW:  # no device trace off the chip
        assert name not in result["rehearsal_metrics"]
    # the program counter is read off /metrics with or without a device
    share = result["rehearsal_metrics"][COUNTER]["value"]
    assert 20.0 < share < 100.0


def test_the_dump_names_the_new_scopes_and_arguments(traced):
    _, _, _, dump = traced
    known = [v for v in dump["otherData"]["op_map"].values() if v is not None]
    assert {scope for scope, _ in known} >= {
        "dsa_proj", "dsa_score", "dsa_select", "dsa_attn", "moe_shared",
        "moe_route", "moe_experts", "qkv", "kv_write"}
    assert any(kind == "pool" for _, kind in known)
    ticks = [e["args"] for e in dump["traceEvents"]
             if e.get("name") == "tick" and e.get("args", {}).get("decode_tokens")]
    assert ticks and all(
        {"dsa_visible", "dsa_selected", "dsa_dense_tokens", "dsa_index_pages",
         "pairs_held", "experts_touched"} <= set(a) for a in ticks)
    assert all(a["dsa_selected"] <= a["dsa_visible"] for a in ticks)
    assert any(a["dsa_selected"] < a["dsa_visible"] for a in ticks)
    build = next(e for e in dump["traceEvents"] if e.get("name") == "engine_build")
    assert build["args"]["page_bytes_per_token"] == 3 * (40 + 16) * 4


def test_the_new_readers_on_made_up_device_numbers_checked_by_hand(
        traced, monkeypatch):
    """What the chip's profile would hold: one operation under each scope, 20
    decode-only ticks of 10 ms busy whose programs are known."""
    root, workload, _, dump = traced
    table = dump["otherData"]["op_map"]
    by_scope = {}
    for key, val in table.items():
        if val:
            by_scope.setdefault(val[0], key)
    args = dict(prefill_tokens=0, decode_tokens=4, active_slots=4, lane_rows=0,
                dsa_visible=80, dsa_selected=48, experts_touched=6, pairs_held=9)
    ticks = [dict(start=100.0 + 0.01 * i, dur_s=0.01, args=dict(args, seq=i))
             for i in range(20)]
    run_rec = dict(
        workload=workload, seed=2**31 + 11, config=TINY, replicas=1, tp=1,
        peaks={"hbm_gbps": 819, "bf16_tflops": 197},
        client=dict(requests=[], window=[100.0, 100.2]),
        host_trace=dict(ticks=ticks, phases=[]),
        device_trace=dict(
            busy_s=0.2, window_s=0.2, ticks=20, wall=[100.0, 100.2],
            ops_s={by_scope["dsa_proj"] + " fusion": 0.01,
                   by_scope["dsa_score"] + " custom-call": 0.03,
                   by_scope["dsa_select"] + " custom-call": 0.02,
                   by_scope["dsa_attn"] + " custom-call": 0.05,
                   by_scope["moe_route"] + " fusion": 0.02,
                   by_scope["moe_experts"] + " fusion": 0.04,
                   by_scope["qkv"] + " fusion": 0.03}))
    sys.path.insert(0, str(root / "benchmark" / "layers"))
    held = {name: sys.modules.pop(name, None)
            for name in ("tracefile", "dsatrace", "ticktimeline", "ttftstages")}
    try:
        read = {name: reader(name, root) for name in NEW}
        dsatrace = sys.modules["dsatrace"]
        assert read["dsa.index_share"](run_rec) == pytest.approx(20.0)
        assert read["dsa.select_share"](run_rec) == pytest.approx(10.0)
        assert read["dsa.attn_share"](run_rec) == pytest.approx(25.0)
        assert read["glm-5.experts_share"](run_rec) == pytest.approx(30.0)
        cost = costs.tick_cost(TINY, tokens=4, rows=4, visible=80, selected=48,
                               experts_touched=6, pairs_held=9, dtype="f32",
                               cache_dtype="f32")
        want = 100.0 * max(cost["bytes"] / 819e9, cost["flops"] / 197e12) / 0.01
        assert read["glm-5.step_roofline"](run_rec) == pytest.approx(want)
        assert 0.0 < want < 100.0
        # the two kernels over the decode-only ticks: every tick's program is
        # [i, i + 8] ms on the device's clock, 1 us under dsa_score and 2 us
        # under dsa_attn in each, and as much again outside any decode program
        rows = [dict(tick=t, program=[i * 1e7, i * 1e7 + 8e6])
                for i, t in enumerate(ticks)]
        ops = []
        for i in range(20):
            ops += [[by_scope["dsa_score"] + " custom-call", i * 1e7 + 1e6, 1e3],
                    [by_scope["dsa_attn"] + " custom-call", i * 1e7 + 2e6, 2e3],
                    [by_scope["dsa_score"] + " custom-call", i * 1e7 + 9e6, 1e3],
                    [by_scope["dsa_attn"] + " custom-call", i * 1e7 + 9e6, 2e3]]
        monkeypatch.setattr(dsatrace.ticktimeline, "rows", lambda run: rows)
        monkeypatch.setattr(dsatrace, "device_ops", lambda run: ops)
        # 80 positions seen x 16 values x 4 B x 3 layers over 1 us
        want = 100.0 * (80 * 16 * 4 * 3 / 819e9) / 1e-6
        assert read["dsa.index_roofline"](run_rec) == pytest.approx(want)
        assert 0.0 < want < 100.0
        # 48 rows attended x 40 values x 4 B x 3 layers over 2 us
        want = 100.0 * (48 * 40 * 4 * 3 / 819e9) / 2e-6
        assert read["dsa.attn_roofline"](run_rec) == pytest.approx(want)
        assert 0.0 < want < 100.0
        # fewer than 20 decode-only ticks: nothing, not a mean of three
        monkeypatch.setattr(dsatrace.ticktimeline, "rows", lambda run: rows[:19])
        assert read["dsa.index_roofline"](run_rec) is None
        monkeypatch.setattr(dsatrace.ticktimeline, "rows", lambda run: rows)
        # another architecture, or a program without the scopes or the
        # arguments (the parent of PR 58): nothing to read, nothing raised
        other = dict(run_rec, config=dict(TINY, model_type="deepseek_v3",
                                          index_topk=None))
        for name in NEW:
            assert read[name](other) is None
        bare = dict(run_rec, host_trace=dict(ticks=[dict(t, args=dict(
            prefill_tokens=0, decode_tokens=4, active_slots=4, lane_rows=0,
            seq=t["args"]["seq"])) for t in ticks], phases=[]),
            device_trace=dict(run_rec["device_trace"], ops_s={
                by_scope["qkv"] + " fusion": 0.2}))
        monkeypatch.setattr(dsatrace.ticktimeline, "rows", lambda run: [
            dict(r, tick=t) for r, t in zip(rows, bare["host_trace"]["ticks"])])
        for name in ("dsa.index_roofline", "dsa.attn_roofline",
                     "glm-5.step_roofline"):
            assert read[name](bare) is None
    finally:
        sys.path.remove(str(root / "benchmark" / "layers"))
        for name, mod in held.items():
            sys.modules.pop(name, None)
            if mod is not None:
                sys.modules[name] = mod


def test_readers_read_nothing_from_a_run_without_a_trace_or_a_map():
    run_rec = dict(workload="none", seed=0, config={}, peaks=None, client={},
                   host_trace=None, device_trace=dict(busy_s=1.0, ops_s={}))
    for name in NEW:
        assert reader(name)(run_rec) is None
        assert reader(name)(dict(run_rec, config=TINY)) is None
        assert reader(name)(dict(run_rec, config=TINY, device_trace=None)) is None


def test_the_parent_does_not_know_the_architecture():
    """What the parent of PR 58 does with the cell: ``from_hf_dict`` raises on
    the model type before anything is built (run.py then exits non-zero at
    once).  Here: the same refusal for a type this program lacks."""
    from llm_np_cp_tpu.config import ModelConfig

    with pytest.raises(ValueError, match="unknown model_type 'glm_moe_dsa2'"):
        ModelConfig.from_hf_dict(dict(TINY, model_type="glm_moe_dsa2"))


def test_parity_diagnostic_runs_on_the_tiny_cell_and_refuses_controls(traced):
    root, workload, _, _ = traced
    proc = subprocess.run(
        [sys.executable, str(BENCH / "parity_glm_dsa.py"), "--data-root",
         str(root), "--workload", workload, "--seed", str(2**31 + 11),
         "--samples", "2", "--new", "6", "--prompt", "21", "9", "--selection",
         "--control", "recent_2048", "--control", "dense"],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-2000:]
    rows = json.loads((root / "benchmark" / "out" /
                       f"{workload}-{2**31 + 11}.parity.json").read_text())
    base, recent, dense = rows
    # float32 program against float32 reference: rounding only, one selection
    assert base["control"] is None and base["finite"] and base["within_limits"]
    assert base["off"]["worst"] < 1e-4 and base["rule_correct"]
    assert base["select_overlap"]["mean"] == 1.0 == base["select_overlap"]["least"]
    assert base["select_overlap"]["tokens_x_layers"] == 2 * 5 * 3
    assert base["select_flip_share"] == 0.0 and base["off_given"]["worst"] < 1e-4
    for control in (recent, dense):
        assert not control["within_limits"]
        assert control["off"]["worst"] > 100 * base["off"]["worst"]
        assert control["select_overlap"]["mean"] < 0.9
        # (GIVEN the answer tokens' selections the reference is nearer, not
        # near: the control's PROMPT tokens selected wrongly too)
        assert control["off_given"]["worst"] < control["off"]["worst"]
