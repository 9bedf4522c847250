"""costs.py against hand arithmetic; the peaks table."""

import json
import subprocess
import sys
from pathlib import Path

import costs
import pytest

BENCH = Path(__file__).resolve().parents[1]


def config(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def test_qwen_1_5b_by_hand():
    c = config("qwen2.5-1.5b")
    h, f, v, layers = 1536, 8960, 151936, 28
    q, kv = 12 * 128, 2 * 128
    layer = h * q + 2 * h * kv + q * h + (q + 2 * kv) + 3 * h * f + 2 * h
    assert costs.layer_params(c) == layer
    assert costs.param_count(c) == layers * layer + v * h + h == 1_543_714_304
    assert costs.weight_bytes(c) == 3_087_428_608          # 3.09 GB, 2,944 MiB
    assert costs.kv_bytes_per_token(c) == 28 * 2 * 2 * 128 * 2 == 28_672
    # a tied head: the embedding matrix is read as the head, so all of it streams
    assert costs.streamed_weight_bytes(c) == costs.weight_bytes(c)
    assert c["sizes"]["weight_bytes_bf16"] == costs.weight_bytes(c)
    assert c["sizes"]["kv_bytes_per_token_bf16"] == costs.kv_bytes_per_token(c)


def test_qwen_3b_sizes():
    c = config("qwen2.5-3b")
    assert costs.param_count(c) == c["sizes"]["parameters"] == 3_085_938_688
    assert costs.kv_bytes_per_token(c) == 36 * 2 * 2 * 128 * 2 == 36_864


def test_untied_embedding_is_gathered_not_streamed():
    c = dict(config("qwen2.5-1.5b"), tie_word_embeddings=False)
    assert costs.weight_bytes(c) - costs.streamed_weight_bytes(c) == 151936 * 1536 * 2


def test_tick_cost_and_bound():
    c = config("qwen2.5-1.5b")
    cost = costs.tick_cost(c, tokens=64, rows=64, context_tokens=64 * 400)
    assert cost["bytes"] == 3_087_428_608 + 28_672 * (64 * 400 + 64)
    matmul = 2 * 28 * costs.layer_params(c) * 64
    head = 2 * 151936 * 1536 * 64
    attn = 4 * 128 * 12 * 28 * (64 * 400)
    assert cost["flops"] == matmul + head + attn
    peaks = {"hbm_gbps": 819, "bf16_tflops": 197}
    t, bound = costs.least_seconds(cost, peaks)
    assert bound == "memory" and t == pytest.approx(cost["bytes"] / 819e9)
    big = costs.tick_cost(c, tokens=4096, rows=64, context_tokens=64 * 400)
    assert costs.least_seconds(big, peaks)[1] == "compute"
    half = costs.tick_cost(c, tokens=64, rows=64, context_tokens=64 * 400, tp=2)
    assert half["bytes"] == cost["bytes"] / 2 and half["flops"] == cost["flops"] / 2


def test_peaks_table_names_its_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())
    v5e = peaks["TPU v5 lite"]
    assert (v5e["bf16_tflops"], v5e["int8_tops"], v5e["hbm_gbps"], v5e["hbm_gb"]) == \
        (197, 393, 819, 16)
    assert all("source" in p for p in peaks.values())


def test_no_tpu_means_no_result():
    """On the CPU, without --rehearsal: non-zero exit and nothing on stdout."""
    proc = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload",
         "qwen2.5-1.5b.decode-closed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
