"""The ``deepseek_v3`` cell on the CPU with a tiny preset: the harness path
(``--arch`` in the cell's ``serve_flags``, the plain forward's check), the
readers PR 41 added on a rehearsal trace and on a made-up device trace that
is checked by hand, the parity diagnostic with a control it must refuse, and
``costs_deepseek_v3.py`` against the hand arithmetic of the issue, to the
parameter."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import costs_deepseek_v3 as costs
import pytest
import tiny_root
from test_rehearsal import run

BENCH = Path(__file__).resolve().parents[1]
CELL = "kanana-2-30b-a3b-24l-ep8.context-closed"
NEW = ("mla.attn_share", "mla.kernel_roofline", "moe.shared_share",
       "deepseek-v3.experts_share", "deepseek-v3.step_roofline")

TINY = {
    "model_type": "deepseek_v3", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 8,
    "max_position_embeddings": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "kv_lora_rank": 32, "q_lora_rank": None, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "v_head_dim": 16, "rope_interleave": True,
    "rope_scaling": None, "n_routed_experts": 4, "router_experts": 8,
    "first_expert": 2, "n_shared_experts": 2, "num_experts_per_tok": 2,
    "first_k_dense_replace": 1, "moe_intermediate_size": 32,
    "moe_layer_freq": 1, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True,
    "source": "none: a toy for the harness's own tests", "reduced": [],
    "serve": {"dtype": "f32", "cache_dtype": "f32", "block_size": 8,
              "mesh": "", "replicas": 1, "chips": 1},
}


def config(name="kanana-2-30b-a3b-24l-ep8"):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_").replace("-", "_"),
        BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------------
# costs_deepseek_v3.py against the issue's arithmetic, to the parameter
# ----------------------------------------------------------------------

def test_a_layers_sizes_by_hand():
    c = config()
    assert costs.attention_params(c) == (
        12582912 + 1179648 + 512 + 4194304 + 8388608) == 26345984
    assert costs.dense_ff_params(c) == 3 * 2048 * 6144
    assert costs.expert_params(c) == 4718592
    assert costs.shared_params(c) == 9437184
    assert costs.router_params(c) == 262272  # 128 wide whoever holds what
    assert costs.head_params(c) == 262668288


def test_the_cut_and_the_published_model():
    c = config()
    assert costs.param_count(c) == 3155018624 == c["sizes"]["parameters"]
    assert costs.weight_bytes(c) == 6310037248
    published = dict(c, num_hidden_layers=48, n_routed_experts=128)
    assert costs.param_count(published) == 30670815104
    assert costs.latent_bytes_per_token(c) == 27648 == 24 * 1152
    assert costs.latent_bytes_per_token(published) == 48 * 1152
    assert c["sizes"]["latent_bytes_per_token_stored_bf16"] == 24 * 1280


def test_a_tick_reads_the_experts_it_touches_and_the_context_once():
    c = config()
    base = dict(tokens=96, rows=96, context_tokens=0, experts_touched=0,
                pairs_held=0)
    nothing = costs.tick_cost(c, **base)
    # everything outside the routed experts, the embedding only gathered
    assert nothing["bytes"] == 2 * (
        3155018624 - 262668288 - 23 * 16 * 4718592) + 96 * 27648
    touched = costs.tick_cost(c, **dict(base, experts_touched=10, pairs_held=40))
    assert touched["bytes"] - nothing["bytes"] == 10 * 4718592 * 2
    assert touched["flops"] - nothing["flops"] == 2 * 4718592 * 40
    ctx = costs.tick_cost(c, **dict(base, context_tokens=1000))
    assert ctx["bytes"] - nothing["bytes"] == 1000 * 27648
    # absorbed attention: 2 x (576 + 512) x 32 heads a (token, position), layer
    assert ctx["flops"] - nothing["flops"] == 2 * 1088 * 32 * 24 * 1000 * 96 / 96


def test_the_cell_and_the_traffic_are_the_issues_letter_for_letter():
    cell = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    assert (cell["slots"], cell["clients"], cell["num_blocks"],
            cell["rate_rps"]) == (96, 96, 0, None)
    assert cell["serve_flags"] == ["--arch", "deepseek_v3", "--max-queue", "512"]
    mix = json.loads((BENCH / "traffic" / "context-closed.json").read_text())
    assert mix["loop"] == "closed" and mix["ramp_s"] >= 20 and mix["block"] == 64
    assert mix["prompt_tokens"] == {"dist": "uniform", "min": 512, "max": 1536}
    assert mix["output_tokens"] == {"dist": "uniform", "min": 384, "max": 640}
    assert mix["stream_share"] == 1.0 and mix["sharing"] == {"kind": "none"}
    # the order inside a block is fixed (the generator's steadiness option,
    # as chat-open): the sizes are the ISSUE's, only which prompts queue
    # behind which no longer changes with the seed (PERF.md section 6)
    assert mix["order_seed"] == 0
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1 and entry["traffic"] == "context-closed"
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s"
        if m["name"] == "step_roofline":  # the one changed entry
            assert m["workloads"] == [w["name"] for w in bench["workloads"][:6]]


# ----------------------------------------------------------------------
# the harness path, on a tiny preset
# ----------------------------------------------------------------------

def add_tiny(root: Path) -> str:
    b = root / "benchmark"
    (b / "configs" / "tiny-mla.json").write_text(json.dumps(TINY))
    (b / "cells" / "tiny-mla.tiny-mix.json").write_text(json.dumps(
        {"slots": 4, "num_blocks": 0, "clients": 4, "rate_rps": 6.0,
         "serve_flags": ["--arch", "deepseek_v3"]}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-mla", source="none", reduced=[],
                                 file="benchmark/configs/tiny-mla.json", why="test"))
    bench["workloads"].append(dict(name="tiny-mla.tiny-mix", config="tiny-mla",
                                   traffic="tiny-mix", why="test", chips=1))
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = m["workloads"] + ["tiny-mla.tiny-mix"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return "tiny-mla.tiny-mix"


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root, _ = tiny_root.make(tmp_path_factory.mktemp("mla"))
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
    assert detail["resolution"]["epilogue"] == "fused"
    assert detail["reference"] and all(r["ok"] for r in detail["reference"])
    for name in NEW + ("step_roofline",):  # no device trace off the chip
        assert name not in result["rehearsal_metrics"]


def test_the_dump_names_the_new_scope_and_counters(traced):
    _, _, _, dump = traced
    known = [v for v in dump["otherData"]["op_map"].values() if v is not None]
    assert {scope for scope, _ in known} >= {
        "moe_shared", "moe_route", "moe_experts", "attn", "qkv", "kv_write"}
    # the latent pool is pool-shaped to the op map (pool.move_share reads it)
    assert any(kind == "pool" for _, kind in known)
    ticks = [e["args"] for e in dump["traceEvents"]
             if e.get("name") == "tick" and e.get("args", {}).get("decode_tokens")]
    assert ticks and all(
        {"pairs_held", "experts_touched", "expert_load_max", "attn_pages",
         "attn_grid_steps", "attn_pages_per_step"} <= set(a) for a in ticks)
    # 2 expert layers x 4 held experts; a pair is held or it is not
    assert all(a["experts_touched"] <= 8 for a in ticks)
    assert all(a["pairs_held"] <= 2 * 2 * (a["prefill_tokens"] + a["decode_tokens"])
               for a in ticks)
    build = next(e for e in dump["traceEvents"] if e.get("name") == "engine_build")
    assert build["args"]["page_bytes_per_token"] == 3 * 40 * 4
    assert build["args"]["pool_bytes_per_token"] == 3 * 128 * 4
    assert build["args"]["experts_held"] == 4


def _reader_from(root: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        "copy_" + name.replace(".", "_").replace("-", "_"),
        root / "benchmark" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_new_readers_on_a_made_up_device_trace_checked_by_hand(traced):
    """What the chip's profile would hold: the kernel under its own name,
    one operation under each scope, 20 ticks of 10 ms busy."""
    root, workload, _, dump = traced
    table = dump["otherData"]["op_map"]
    by_scope = {}
    for key, val in table.items():
        if val:
            by_scope.setdefault(val[0], key)
    ticks = [dict(start=100.0 + 0.01 * i, dur_s=0.01, args=dict(
        prefill_tokens=0, decode_tokens=4, active_slots=4, attn_pages=30,
        experts_touched=6, pairs_held=9)) for i in range(20)]
    run_rec = dict(
        workload=workload, seed=2**31 + 11, config=TINY, replicas=1, tp=1,
        peaks={"hbm_gbps": 819, "bf16_tflops": 197},
        client=dict(requests=[], window=[100.0, 100.2]),
        host_trace=dict(ticks=ticks, phases=[]),
        device_trace=dict(
            busy_s=0.2, window_s=0.2, ticks=20, wall=[100.0, 100.2],
            ops_s={"%ragged_latent_attention.3 custom-call": 0.08,
                   "%ragged-dot.1 custom-call": 0.01,
                   by_scope["moe_shared"] + " fusion": 0.03,
                   by_scope["moe_route"] + " fusion": 0.02,
                   by_scope["moe_experts"] + " fusion": 0.04,
                   by_scope["qkv"] + " fusion": 0.02}))
    sys.path.insert(0, str(root / "benchmark" / "layers"))
    theirs = sys.modules.pop("tracefile", None)  # other tests hold this one
    try:
        read = {name: _reader_from(root, name) for name in NEW}
        assert read["mla.attn_share"](run_rec) == pytest.approx(40.0)
        assert read["moe.shared_share"](run_rec) == pytest.approx(15.0)
        assert read["deepseek-v3.experts_share"](run_rec) == pytest.approx(30.0)
        # 30 pages x 8 tokens x 3 layers x 40 values x 4 B at 819 GB/s, over
        # 0.08 s / 20 ticks = 4 ms in the kernel
        want = 100.0 * (30 * 8 * 3 * 40 * 4 / 819e9) / 0.004
        assert read["mla.kernel_roofline"](run_rec) == pytest.approx(want)
        cost = costs.tick_cost(TINY, tokens=4, rows=4, context_tokens=0,
                               experts_touched=6, pairs_held=9, dtype="f32",
                               cache_dtype="f32")
        want = 100.0 * max(cost["bytes"] / 819e9, cost["flops"] / 197e12) / 0.01
        assert read["deepseek-v3.step_roofline"](run_rec) == pytest.approx(want)
        assert 0.0 < want < 100.0
        # another architecture, or a program without the kernel, the counter
        # or the scopes (the parent of PR 41): nothing to read, nothing raised
        other = dict(run_rec, config=dict(TINY, model_type="qwen2"))
        for name in ("mla.kernel_roofline", "deepseek-v3.experts_share",
                     "deepseek-v3.step_roofline"):
            assert read[name](other) is None
        bare = dict(run_rec, host_trace=dict(ticks=[dict(t, args=dict(
            prefill_tokens=0, decode_tokens=4, active_slots=4)) for t in ticks],
            phases=[]), device_trace=dict(run_rec["device_trace"], ops_s={
                by_scope["qkv"] + " fusion": 0.2}))
        for name in ("mla.attn_share", "mla.kernel_roofline",
                     "deepseek-v3.step_roofline"):
            assert read[name](bare) is None
    finally:
        sys.path.remove(str(root / "benchmark" / "layers"))
        sys.modules.pop("tracefile", None)
        if theirs is not None:
            sys.modules["tracefile"] = theirs


def test_readers_read_nothing_from_a_run_without_a_trace_or_a_map():
    run_rec = dict(workload="none", seed=0, config={}, peaks=None, client={},
                   device_trace=dict(busy_s=1.0, ops_s={}))
    for name in NEW:
        assert reader(name)(run_rec) is None
    for name in NEW:
        assert reader(name)(dict(run_rec, device_trace=None)) is None


def test_the_parent_does_not_know_the_architecture():
    """What the parent of PR 41 does with the cell: ``from_hf_dict`` raises
    on the model type before anything is built (run.py then exits non-zero
    at once).  Here: the same refusal for a type this program lacks."""
    from llm_np_cp_tpu.config import ModelConfig

    with pytest.raises(ValueError, match="unknown model_type 'deepseek_v4'"):
        ModelConfig.from_hf_dict(dict(TINY, model_type="deepseek_v4"))


def _parity(root, workload, *more):
    proc = subprocess.run(
        [sys.executable, str(BENCH / "parity_deepseek_v3.py"), "--data-root",
         str(root), "--workload", workload, "--seed", str(2**31 + 11),
         "--samples", "2", "--new", "6", "--prompt", "21", "9", *more],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    rows = json.loads((root / "benchmark" / "out" /
                       f"{workload}-{2**31 + 11}.parity.json").read_text())
    return proc, rows


def test_parity_diagnostic_runs_on_the_tiny_cell_and_refuses_a_control(traced):
    root, workload, _, _ = traced
    proc, rows = _parity(root, workload, "--control", "no_shared",
                         "--control", "halfsplit_rope")
    assert proc.returncode == 0, proc.stderr[-2000:]
    base, no_shared, halfsplit = rows
    # float32 program against float32 reference: rounding only
    assert base["control"] is None and base["finite"] and base["within_limits"]
    assert base["off"]["worst"] < 1e-4 and base["rule_correct"]
    assert no_shared["off"]["worst"] > 100 * base["off"]["worst"]
    assert halfsplit["off"]["worst"] > 100 * base["off"]["worst"]
