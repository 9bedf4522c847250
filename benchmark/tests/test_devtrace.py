"""The trace reduction: on hand-made intervals, and on two ticks recorded
on the chip (fixtures/v5e_two_ticks.json)."""

import json
from pathlib import Path

import devtrace
import pytest

FIXTURE = Path(__file__).parent / "fixtures" / "v5e_two_ticks.json"


def trace_of(ops, host=(), modules=()):
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": [list(e) for e in ops]},
            {"name": "XLA Modules", "events": [list(e) for e in modules]}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [list(e) for e in host]}]}]}


def test_union_and_own_time_of_nested_operations():
    assert devtrace.union([(0, 100), (10, 30), (120, 130), (125, 140)]) == \
        [(0, 100), (120, 140)]
    ops = [("while", 0, 100), ("a", 10, 20), ("b", 40, 30), ("b", 75, 5), ("c", 120, 10)]
    assert devtrace.self_times([list(e) for e in ops]) == \
        {"while": 45.0, "a": 20.0, "b": 35.0, "c": 10.0}


def test_reduce_busy_idle_ticks_and_gaps():
    ops = [("while", 0, 100_000), ("a", 10_000, 20_000), ("c", 160_000, 40_000)]
    host = [("serve.mixed_dispatch", 5, 3), ("serve.mixed_dispatch", 150_000, 3),
            ("serve.other", 1, 1)]
    r = devtrace.reduce(trace_of(ops, host, [("m", 0, 100_000), ("m", 160_000, 40_000)]))
    assert r["window_s"] == pytest.approx(200e-6)
    assert r["busy_s"] == pytest.approx(140e-6)
    assert r["idle_share"] == pytest.approx(0.3)
    assert r["ticks"] == 2 and r["n_devices"] == 1
    assert r["gaps_ns"] == [(100_000, 160_000)]
    assert r["ops_s"]["while"] == pytest.approx(80e-6)
    # a window given from outside bounds busy time and the tick count
    half = devtrace.reduce(trace_of(ops, host), window_ns=(0, 100_000))
    assert half["busy_s"] == pytest.approx(100e-6) and half["ticks"] == 1
    assert devtrace.reduce({"planes": [{"name": "/host:CPU", "lines": []}]}) is None


def test_gaps_are_shared_out_over_host_phases():
    phases = [(90_000, 110_000, "host_sync"), (110_000, 150_000, "deliver"),
              (150_000, 155_000, "plan")]
    named = dict(devtrace.name_gaps([(100_000, 160_000), (300, 310)], phases))
    assert named["host_sync"] == pytest.approx(10e-6)
    assert named["deliver"] == pytest.approx(40e-6)
    assert named["plan"] == pytest.approx(5e-6)
    assert named["outside any tick"] == pytest.approx(5e-6)
    assert named["between operations (gaps under 20 us)"] == pytest.approx(10e-9)


def test_align_finds_the_offset_when_the_profile_saw_only_some_ticks():
    wall = [1000.0 + 50.0 * i + ((i * i * 7919) % 13) * 0.9 for i in range(40)]
    seen = [w * 1.0 - 123.0 for w in wall[7:30]]
    assert devtrace.align(seen, wall) == pytest.approx(-123.0)
    assert devtrace.align(seen[:2], wall) is None


def test_short_name_keeps_result_shape_and_opcode():
    hlo = ("%copy.89 = bf16[28,1026,64,2,128]{4,3,2,1,0:T(2,128)(2,1)} "
           "copy(bf16[28,1026,64,2,128]{4,3,2,1,0:T(2,128)(2,1)} %get-tuple-element.1058)")
    assert devtrace.short_name(hlo) == "%copy.89 bf16[28,1026,64,2,128] copy"
    assert devtrace.short_name("jit_mixed_step(123)") == "jit_mixed_step(123)"


def test_recorded_ticks_from_the_chip():
    fx = json.loads(FIXTURE.read_text())
    r = devtrace.reduce(fx["trace"])
    assert r["ticks"] == 2 and r["per_device"][0]["modules"] == 2
    assert r["window_s"] == pytest.approx(0.0816, rel=0.01)
    assert r["busy_s"] == pytest.approx(0.0632, rel=0.01)    # 2 x 31.6 ms
    assert r["idle_share"] == pytest.approx(0.225, abs=0.005)
    # own times add up to busy time: nothing counted twice under the layer scan
    assert sum(r["ops_s"].values()) == pytest.approx(r["busy_s"], rel=1e-3)
    top = list(r["ops_s"])[0]
    assert "ragged_paged_attention" in top
    assert devtrace.share_by_name(r, ["ragged"]) == pytest.approx(0.265, abs=0.005)
    assert devtrace.share_by_name(r, ["sample_epilogue"]) == pytest.approx(0.0195, abs=0.002)
    assert devtrace.share_by_name(r, ["no such kernel"]) == 0.0
    # the one long gap (between the ticks) goes to what the host did in it
    phases = [(s * 1e9, (s + d) * 1e9, n) for s, d, n in fx["phases"]]
    named = dict(devtrace.name_gaps(r["gaps_ns"], phases))
    assert list(named)[0] == "mixed_dispatch"
    assert named["mixed_dispatch"] == pytest.approx(0.0109, abs=0.001)
    assert named["deliver"] == pytest.approx(0.004, abs=0.001)
    long_gaps = sum(v for k, v in named.items() if not k.startswith("between"))
    assert long_gaps == pytest.approx(r["window_s"] - r["busy_s"], rel=0.01)
