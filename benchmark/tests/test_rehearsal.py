"""run.py end to end on a tiny configuration (CPU), and discovery: the
tiny cell, its configuration, its traffic mix and one more per-layer
reader are files and entries ADDED to a copy of the benchmark's data -
no file that was there is edited (tiny_root.make)."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest
import tiny_root

BENCH = Path(__file__).resolve().parents[1]


def run(root: Path, workload: str, *extra: str) -> tuple[subprocess.CompletedProcess, dict]:
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--rehearsal", "--data-root", str(root),
         "--workload", workload, "--seed", str(2**31 + 11), "--seconds", "3", *extra],
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, proc.stderr[-3000:] + proc.stdout[-2000:]
    return proc, json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    root, workload = tiny_root.make(tmp_path_factory.mktemp("closed"))
    proc, result = run(root, workload, "--trace", "1")
    return root, workload, proc, result


def test_rehearsal_says_so_and_carries_no_metric(traced):
    _, _, proc, result = traced
    assert result["rehearsal"] is True
    assert result["metrics"] == {}          # nothing under a metric's name
    assert result["device"]["platform"] == "cpu"
    assert "busy_s" not in result["device"]  # no device trace off the chip
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert "REHEARSAL" in proc.stdout


def test_discovery_picks_up_added_files(traced):
    root, workload, _, result = traced
    got = result["rehearsal_metrics"]
    # the reader dropped in beside the existing ones was found by name
    assert got["extra.measured_requests"]["value"] == result["attempted"]
    # the existing readers ran on the new cell with no edit: a .py
    # reader, a counter ratio and a gauge off /metrics, the host spans
    for name in ("loadgen.late_p95_ms", "sched.tokens_per_tick",
                 "sched.queue_wait_p50_ms", "tick.wall_ms", "tick.host_ms"):
        assert got[name]["value"] >= 0, name
    # device-trace readers found nothing to read and were left out
    assert "step.device_ms" not in got and "step_roofline" not in got
    # the existing files of the copy are byte-identical to the originals
    for sub in ("configs", "traffic", "cells", "layers", "e2e"):
        for f in (BENCH / sub).iterdir():
            if f.is_file():
                assert (root / "benchmark" / sub / f.name).read_bytes() == f.read_bytes()
    detail = json.loads((root / "benchmark" / "out" /
                         f"{workload}-{2**31 + 11}.json").read_text())
    assert detail["rehearsal"] and detail["requests"]["measured"] == result["attempted"]
    assert detail["resolution"]["tick"] == "unified"
    assert detail["reference"] and all(r["ok"] for r in detail["reference"])


def test_open_loop_rehearsal_reports_end_to_end(tmp_path):
    root, workload = tiny_root.make(tmp_path, loop="open")
    _, result = run(root, workload, "--trace", "0")
    got = result["rehearsal_metrics"]
    assert set(got) >= {"out_tok_s", "ttft_p50_s", "setup_s"}
    assert result["correct"] is True and result["rehearsal"] is True
    # about rate x seconds requests were due in the window (6/s x 3 s)
    assert 12 <= result["attempted"] <= 24


def test_unknown_device_kind_is_an_error():
    import run as harness

    assert harness.load_peaks(BENCH / "peaks.json", "TPU v5 lite")["hbm_gbps"] == 819
    with pytest.raises(LookupError, match="TPU v9"):
        harness.load_peaks(BENCH / "peaks.json", "TPU v9")


def test_mesh_and_replicas_are_data_of_the_configuration(tmp_path):
    """2 x (TP=2) on virtual CPU devices: host-made weights, the XLA tail
    the banner check expects under TP, the reference sharded over all."""
    root, workload = tiny_root.make(tmp_path, mesh="model=2", replicas=2)
    proc, result = run(root, workload, "--trace", "0")
    assert result["correct"] is True and result["failed"] == 0
    assert "2 replicas x (tp=2" in proc.stdout
    assert "(host)" in proc.stdout  # the weights were made on the host
