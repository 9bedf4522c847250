"""The ``falcon_h1`` cell on the CPU with a tiny preset: the harness path
(``--arch`` in the cell's ``serve_flags``, the plain forward's check), the
readers PR 34 added on a rehearsal trace and on a made-up device trace that
is checked by hand, the parity diagnostic, and ``costs_falcon_h1.py`` against
the hand arithmetic of the issue, to the parameter."""

import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import costs_falcon_h1 as costs
import pytest
import tiny_root
from test_rehearsal import run

BENCH = Path(__file__).resolve().parents[1]
CELL = "falcon-h1-34b-6l.decode-closed"
NEW = ("ssm.scan_share", "ssm.proj_share", "ssm.state_roofline",
       "falcon-h1.step_roofline")

TINY_FALCON = {
    "model_type": "falcon_h1", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "rope_theta": 100000000000,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_conv_bias": True, "mamba_chunk_size": 8, "mamba_expand": 2,
    "embedding_multiplier": 5.5, "lm_head_multiplier": 0.125,
    "key_multiplier": 0.7, "attention_in_multiplier": 1.25,
    "attention_out_multiplier": 0.6, "ssm_in_multiplier": 1.5,
    "ssm_out_multiplier": 0.8, "mlp_multipliers": [0.9, 0.45],
    "ssm_multipliers": [0.85, 1.2, 1.4, 1.1, 0.75],
    "init_ssm_in_proj_std": 0.2,
    "source": "none: a toy for the harness's own tests", "reduced": [],
    "serve": {"dtype": "f32", "cache_dtype": "f32", "block_size": 8,
              "mesh": "", "replicas": 1, "chips": 1},
}


def config(name="falcon-h1-34b-6l"):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "r_" + name.replace(".", "_").replace("-", "_"),
        BENCH / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ----------------------------------------------------------------------
# costs_falcon_h1.py against the issue's arithmetic, to the parameter
# ----------------------------------------------------------------------

def test_a_layers_sizes_by_hand():
    c = config()
    h = 5120
    assert costs.attn_params(c) == 2 * h * 2560 + 2 * h * 512 == 31_457_280
    assert costs.mixer_parts(c) == dict(
        in_proj=h * (4096 + 4096 + 512 + 512 + 32), conv=5120 * (4 + 1),
        scalars=96, norm=4096, out_proj=4096 * h)
    assert costs.mixer_parts(c)["in_proj"] == 47_349_760
    assert costs.mixer_params(c) == 68_351_072
    assert costs.ff_params(c) == 3 * h * 21_504 == 330_301_440
    assert costs.layer_params(c) == 430_120_032
    assert costs.head_params(c) == 261_120 * h == 1_336_934_400


def test_the_cut_and_the_published_model():
    c = config()
    assert costs.param_count(c) == 5_254_594_112 == c["sizes"]["parameters"]
    assert costs.weight_bytes(c) == 10_509_188_224 == c["sizes"]["weight_bytes_bf16"]
    assert costs.kv_bytes_per_token(c) == 6 * 2 * 4 * 128 * 2 == 12_288
    assert costs.ssm_state_bytes_per_row(c) == 32 * 128 * 256 * 4 == 4_194_304
    assert costs.conv_state_bytes_per_row(c) == 3 * 5120 * 2 == 30_720
    assert costs.state_bytes_per_slot(c) == 6 * (4_194_304 + 30_720)
    assert 64 * costs.state_bytes_per_slot(c) / 2**20 == pytest.approx(1547.25)
    assert costs.param_count(dict(c, num_hidden_layers=72)) == \
        c["sizes"]["published"]["parameters"] == 33_642_516_224


def test_a_tick_reads_and_writes_the_state_of_the_rows_it_touches():
    c = config()
    tick = costs.tick_cost(c, tokens=64, rows=64, context_tokens=26_500)
    p = tick["parts"]
    assert p["layer_weights"] == 6 * 430_120_032 * 2          # 5.16 GB
    assert p["head"] == (1_336_934_400 + 5120) * 2            # 2.67 GB
    assert p["state"] == 2 * 64 * 6 * (4_194_304 + 30_720)    # 3.24 GB
    assert p["kv"] == 12_288 * (26_500 + 64)                  # 0.33 GB
    assert p["embedding_rows"] == 64 * 5120 * 2
    assert tick["bytes"] == sum(p.values())
    half = costs.tick_cost(c, tokens=64, rows=64, context_tokens=26_500,
                           state_rows=32)
    assert tick["bytes"] - half["bytes"] == 2 * 32 * 6 * (4_194_304 + 30_720)
    least, bound = costs.least_seconds(tick, {"hbm_gbps": 819, "bf16_tflops": 197})
    assert bound == "memory" and 0.0138 < least < 0.0141  # the issue's 13.9 ms
    per_token = 6 * (31_457_280 + 68_351_072 + 330_301_440)
    assert tick["flops"] == (
        2 * per_token * 64 + 2 * 1_336_934_400 * 64
        + 4 * 128 * 20 * 6 * 26_500 * 64 / 64 + 6 * 32 * 128 * 256 * 6 * 64)


# ----------------------------------------------------------------------
# the cell's files
# ----------------------------------------------------------------------

def test_the_cell_states_the_architecture_the_server_must_load():
    cell = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    assert cell["serve_flags"] == ["--arch", "falcon_h1"]
    assert cell["slots"] == cell["clients"] == 64 and cell["num_blocks"] == 0
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    entry = next(c for c in bench["configs"] if c["name"] == "falcon-h1-34b-6l")
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["file"] == "benchmark/configs/falcon-h1-34b-6l.json"
    work = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert work == dict(work, config="falcon-h1-34b-6l", traffic="decode-closed",
                        chips=1)
    for name in NEW:
        m = next(m for m in bench["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "out_tok_s", name


def test_the_configuration_is_the_catalog_rows_first_6_layers():
    c = config()
    assert c["model_type"] == "falcon_h1" and c["num_hidden_layers"] == 6
    assert c["reduced"] == ["num_hidden_layers"]
    for key, value in dict(
            hidden_size=5120, intermediate_size=21504, vocab_size=261120,
            num_attention_heads=20, num_key_value_heads=4, head_dim=128,
            mamba_d_ssm=4096, mamba_n_heads=32, mamba_d_head=128,
            mamba_d_state=256, mamba_n_groups=2, mamba_d_conv=4,
            mamba_chunk_size=128, mamba_expand=2, rope_theta=100000000000,
            rms_norm_eps=1e-5, tie_word_embeddings=False,
            lm_head_multiplier=0.0078125, ssm_in_multiplier=0.25).items():
        assert c[key] == value, key
    assert len(c["ssm_multipliers"]) == 5 and len(c["mlp_multipliers"]) == 2


# ----------------------------------------------------------------------
# the harness path, on a tiny preset
# ----------------------------------------------------------------------

def add_tiny_falcon(root: Path) -> str:
    b = root / "benchmark"
    (b / "configs" / "tiny-falcon.json").write_text(json.dumps(TINY_FALCON))
    (b / "cells" / "tiny-falcon.tiny-mix.json").write_text(json.dumps(
        {"slots": 4, "num_blocks": 0, "clients": 4, "rate_rps": 6.0,
         "serve_flags": ["--arch", "falcon_h1"]}))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append(dict(name="tiny-falcon", source="none", reduced=[],
                                 file="benchmark/configs/tiny-falcon.json", why="test"))
    bench["workloads"].append(dict(name="tiny-falcon.tiny-mix", config="tiny-falcon",
                                   traffic="tiny-mix", why="test", chips=1))
    for m in bench["per_layer"]:
        if m["name"] in NEW:
            m["workloads"] = m["workloads"] + ["tiny-falcon.tiny-mix"]
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return "tiny-falcon.tiny-mix"


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root, _ = tiny_root.make(tmp_path_factory.mktemp("falcon"))
    workload = add_tiny_falcon(root)
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
    for name in NEW:  # no device trace off the chip: left out, not raised
        assert name not in result["rehearsal_metrics"]


def test_the_dump_names_the_new_scopes_and_counters(traced):
    _, _, _, dump = traced
    known = [v for v in dump["otherData"]["op_map"].values() if v is not None]
    assert {scope for scope, _ in known} >= {"ssm_proj", "ssm_scan", "mlp", "attn"}
    ticks = [e["args"] for e in dump["traceEvents"]
             if e.get("name") == "tick" and e.get("args", {}).get("decode_tokens")]
    assert ticks and all(
        {"ssm_state_rows", "ssm_scan_tokens", "state_slots_live"} <= set(a)
        for a in ticks)
    build = next(e for e in dump["traceEvents"] if e.get("name") == "engine_build")
    assert build["args"]["state_bytes"] == 3 * 4 * (4 * 16 * 16 + 3 * 128) * 4


def _reader_from(root: Path, name: str):
    spec = importlib.util.spec_from_file_location(
        "copy_" + name.replace(".", "_").replace("-", "_"),
        root / "benchmark" / "layers" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_the_new_readers_on_a_made_up_device_trace_checked_by_hand(traced):
    """What the chip's profile would hold: one operation under each new
    scope, 20 ticks of 10 ms busy; every number below is worked out here."""
    root, workload, _, dump = traced
    table = dump["otherData"]["op_map"]
    by_scope = {}
    for key, val in table.items():
        if val:
            by_scope.setdefault(val[0], key)
    ticks = [dict(start=100.0 + 0.01 * i, dur_s=0.01, args=dict(
        prefill_tokens=0, decode_tokens=4, active_slots=4, ssm_state_rows=3,
        ssm_scan_tokens=4)) for i in range(20)]
    run_rec = dict(
        workload=workload, seed=2**31 + 11, config=TINY_FALCON, replicas=1, tp=1,
        peaks={"hbm_gbps": 819, "bf16_tflops": 197},
        client=dict(requests=[], window=[100.0, 100.2]),
        host_trace=dict(ticks=ticks, phases=[]),
        device_trace=dict(
            busy_s=0.2, window_s=0.2, ticks=20, wall=[100.0, 100.2],
            ops_s={by_scope["ssm_scan"] + " fusion": 0.05,
                   by_scope["ssm_proj"] + " fusion": 0.03,
                   by_scope["mlp"] + " fusion": 0.12}))
    sys.path.insert(0, str(root / "benchmark" / "layers"))
    theirs = sys.modules.pop("tracefile", None)  # other tests hold this one
    try:
        read = {name: _reader_from(root, name) for name in NEW}
        assert read["ssm.scan_share"](run_rec) == pytest.approx(25.0)
        assert read["ssm.proj_share"](run_rec) == pytest.approx(15.0)
        # 3 rows x 3 layers x 2 x (4 heads x 16 x 16 x 4 B) = 73,728 B a tick
        # at 819 GB/s, over 0.05 s / 20 ticks = 2.5 ms under the scope
        want = 100.0 * (3 * 3 * 2 * 4096 / 819e9) / 0.0025
        assert read["ssm.state_roofline"](run_rec) == pytest.approx(want)
        # the whole tick: its bytes from costs_falcon_h1 over 10 ms a tick
        cost = costs.tick_cost(TINY_FALCON, tokens=4, rows=4, context_tokens=0,
                               state_rows=3, dtype="f32", cache_dtype="f32")
        assert cost["parts"]["state"] == 2 * 3 * 3 * (4096 + 3 * 128 * 4)
        want = 100.0 * max(cost["bytes"] / 819e9, cost["flops"] / 197e12) / 0.01
        assert read["falcon-h1.step_roofline"](run_rec) == pytest.approx(want)
        assert 0.0 < want < 100.0
        # another architecture, or a program without the counters or the
        # scopes (the parent of PR 34): nothing to read, nothing raised
        other = dict(run_rec, config=dict(TINY_FALCON, model_type="qwen2"))
        assert read["falcon-h1.step_roofline"](other) is None
        assert read["ssm.state_roofline"](other) is None
        bare = dict(run_rec, host_trace=dict(ticks=[dict(t, args=dict(
            prefill_tokens=0, decode_tokens=4, active_slots=4)) for t in ticks],
            phases=[]))
        assert read["falcon-h1.step_roofline"](bare) is None
        assert read["ssm.state_roofline"](bare) is None
    finally:
        sys.path.remove(str(root / "benchmark" / "layers"))
        sys.modules.pop("tracefile", None)
        if theirs is not None:
            sys.modules["tracefile"] = theirs


def test_scope_readers_read_nothing_from_a_map_without_the_scopes():
    """The parent's op map knows neither scope; an untraced run has no map."""
    run_rec = dict(workload="none", seed=0, config={}, peaks=None, client={},
                   device_trace=dict(busy_s=1.0, ops_s={}))
    for name in NEW:
        assert reader(name)(run_rec) is None


def _parity(root, workload, *more):
    proc = subprocess.run(
        [sys.executable, str(BENCH / "parity_falcon_h1.py"), "--data-root", str(root),
         "--workload", workload, "--seed", str(2**31 + 11), "--samples", "1",
         "--length", "40", *more],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    (row,) = json.loads((root / "benchmark" / "out" /
                         f"{workload}-{2**31 + 11}.parity.json").read_text())
    return proc, row


def test_parity_diagnostic_runs_on_the_tiny_cell(traced):
    root, workload, _, _ = traced
    proc, row = _parity(root, workload)
    assert proc.returncode == 0, proc.stderr[-2000:]
    # float32 program against float32 reference: rounding only
    assert row["finite"] and row["off"]["worst"] < 1e-4 and row["gap"]["worst"] < 1e-4
    assert all(share > 0.5 for share in row["state_over_skip_rms"])
    # the recurrence by itself, both of the program's forms, every layer:
    # a prompt of 20 tokens in one prefill chunk, then 20 one-token ticks
    assert row["state_dtype"] == "float32" and row["state_ok"] and row["prompt"] == 20
    assert {(r["layer"], r["form"]) for r in row["recurrence"]} == {
        (layer, form) for layer in range(3) for form in ("scan", "tick")}
    assert row["state_worst"] < 1e-5 < row["state_limit"]


def test_parity_diagnostic_refuses_a_bf16_state(traced):
    """The control the limit stands between: the program's state leaf kept
    in bf16 fails the run, by the serving form (the tick rounds it once a
    token; ``models.forward`` carries it in float32 inside one program)."""
    root, workload, _, _ = traced
    proc, row = _parity(root, workload, "--state-dtype", "bf16")
    assert proc.returncode == 1 and "FAIL" in proc.stdout, proc.stderr[-2000:]
    assert row["state_dtype"] == "bfloat16" and not row["state_ok"]
    worst = {form: max(max(r["y"]["worst"], r["h"]) for r in row["recurrence"]
                       if r["form"] == form) for form in ("scan", "tick")}
    assert worst["tick"] > 10 * row["state_limit"] > 10 * worst["scan"]
