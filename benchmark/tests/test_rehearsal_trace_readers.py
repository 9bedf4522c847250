"""A traced rehearsal (CPU, a temporary copy of the data, the way of
test_rehearsal.py) in which every reader PR 24 added runs: the host, HTTP
and set-up metrics read a value; the device-scope readers find no device
trace off the chip and are left out, and the op map they would read is in
the dump."""

import json

import pytest
import tiny_root
from test_rehearsal import run

HOST = ("tick.pack_ms", "tick.h2d_ms", "tick.dispatch_ms", "tick.deliver_ms",
        "tick.host_wait_ms", "sched.context_tokens_per_tick")
HTTP = ("http.accept_to_queue_p50_ms", "http.first_write_lag_p50_ms",
        "http.write_lag_p95_ms")
SETUP = ("setup.engine_build_s", "setup.warmup_s")
DEVICE = ("pool.move_share", "mlp.time_share", "proj.time_share",
          "step.attributed_share")


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root, workload = tiny_root.make(tmp_path_factory.mktemp("pr24"))
    _, result = run(root, workload, "--trace", "1")
    dump = json.loads((root / "benchmark" / "out" / f"{workload}-{2**31 + 11}"
                       / "host_trace.json").read_text())
    return result, dump


@pytest.mark.parametrize("name", HOST + HTTP + SETUP)
def test_reader_reads_a_value(traced, name):
    got = traced[0]["rehearsal_metrics"]
    assert name in got, sorted(got)
    assert got[name]["value"] >= 0.0


def test_the_cut_phases_add_up_to_what_the_uncut_one_was(traced):
    got = {k: v["value"] for k, v in traced[0]["rehearsal_metrics"].items()}
    # pack + h2d + dispatch + deliver + waiting for the device fit in a tick
    parts = sum(got[n] for n in ("tick.pack_ms", "tick.h2d_ms",
                                 "tick.dispatch_ms", "tick.deliver_ms"))
    assert 0.0 < parts <= got["tick.wall_ms"] * 1.5
    assert got["tick.host_wait_ms"] <= got["tick.wall_ms"]
    assert got["sched.context_tokens_per_tick"] >= 1.0


@pytest.mark.parametrize("name", DEVICE)
def test_device_scope_readers_are_left_out_off_the_chip(traced, name):
    assert name not in traced[0]["rehearsal_metrics"]


def test_the_dump_holds_what_the_device_readers_would_read(traced):
    op_map = traced[1]["otherData"]["op_map"]
    known = [v for v in op_map.values() if v is not None]
    assert {scope for scope, _ in known} >= {
        "embed", "qkv", "kv_write", "attn", "o_proj", "mlp", "tail"}
    assert "pool" in {kind for _, kind in known}
    assert all(key.startswith("%") and " " in key for key in op_map)
    names = {ev["name"] for ev in traced[1]["traceEvents"]
             if ev.get("cat") == "setup"}
    assert names >= {"load_place", "engine_build", "pool_alloc", "warmup",
                     "warmup.bucket", "listen"}
