"""A temporary copy of the benchmark's data with one tiny cell added.

Adding the cell edits NO file that is already there: it drops a
configuration, a traffic mix, a cell file and a per-layer reader into
the copy and appends entries to the copy's ``BENCHMARK.json`` - which
is exactly how a later PR adds one (the discovery test)."""

from __future__ import annotations

import json
import shutil
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent

TINY_CONFIG = {
    "model_type": "qwen2", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "max_position_embeddings": 512,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06, "tie_word_embeddings": True,
    "source": "none: a toy for the harness's own tests", "reduced": [],
    "serve": {"dtype": "f32", "cache_dtype": "f32", "block_size": 8,
              "mesh": "", "replicas": 1, "chips": 1},
}

TINY_CLOSED = {
    "loop": "closed", "ramp_s": 1.0, "block": 8,
    "prompt_tokens": {"dist": "uniform", "min": 4, "max": 24},
    "output_tokens": {"dist": "uniform", "min": 12, "max": 24},
    "stream_share": 1.0, "sharing": {"kind": "none"}, "bursts": None,
}

TINY_OPEN = dict(TINY_CLOSED, loop="open", stream_share=0.75, order_seed=3)

EXTRA_LAYER = '''"""A reader dropped in by the discovery test."""
import stats


def read(run):
    return float(len(stats.measured(run["client"])))
'''


def make(tmp: Path, *, loop: str = "closed", mesh: str = "",
         replicas: int = 1) -> tuple[Path, str]:
    """-> (data root, workload name)"""
    root = tmp / "data"
    (root / "benchmark").mkdir(parents=True)
    for sub in ("configs", "traffic", "cells", "layers", "e2e"):
        shutil.copytree(BENCH / sub, root / "benchmark" / sub)
    shutil.copy(BENCH / "peaks.json", root / "benchmark" / "peaks.json")
    with open(ROOT / "BENCHMARK.json") as f:
        bench = json.load(f)
    b = root / "benchmark"
    config = json.loads(json.dumps(TINY_CONFIG))
    config["serve"].update(mesh=mesh, replicas=replicas,
                           chips=4 if mesh or replicas > 1 else 1)
    (b / "configs" / "tiny.json").write_text(json.dumps(config))
    (b / "traffic" / "tiny-mix.json").write_text(
        json.dumps(TINY_CLOSED if loop == "closed" else TINY_OPEN))
    (b / "cells" / "tiny.tiny-mix.json").write_text(json.dumps(
        {"slots": 4, "num_blocks": 0, "clients": 4, "rate_rps": 6.0,
         "serve_flags": []}))
    (b / "layers" / "extra.measured_requests.py").write_text(EXTRA_LAYER)
    bench["configs"].append(dict(name="tiny", source="none", reduced=[],
                                 file="benchmark/configs/tiny.json", why="test"))
    bench["workloads"].append(dict(name="tiny.tiny-mix", config="tiny",
                                   traffic="tiny-mix", why="test",
                                   chips=config["serve"]["chips"]))
    bench["per_layer"].append(dict(
        name="extra.measured_requests", unit="requests", better="higher",
        source="host_clock", layer="load generator", moves="out_tok_s",
        workloads=["tiny.tiny-mix"]))
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root, "tiny.tiny-mix"
