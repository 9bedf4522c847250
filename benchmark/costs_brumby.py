"""Parameters, streamed bytes and operations of a ``brumby`` stack (Brumby-14B:
power-retention layers, a symmetric-power matrix state a kv head and NO pages,
dense SwiGLU, an untied head), read off the PROGRAM's declaration of the
configuration — ``ModelConfig.from_hf_dict`` and then ``param_shapes`` — not
from constants: a width that changes in the configuration's file changes
here.  ``costs.py`` knows one kind of layer and a K/V cache.

The state is counted as the MATHEMATICS needs it, whatever layout the program
holds (it pads the monomials to whole registers: 8,704 rows for 8,256, and
keeps the normaliser as ``k k^T``): a kv head's ``d (d + 1) / 2`` distinct
monomials, each a row of ``d`` value channels and one entry of the sum of
keys, float32 — ``8 x 8,256 x 129 x 4`` = 34,080,768 B a slot and layer at the
published widths, the bf16 K / V of 8,320 tokens of these heads.

What a tick has to move: every weight once (the untied embedding is only
gathered, one row a token), and the state of every row the tick touches, in
every layer, read once and written once.  Nothing reads the context: a decode
row costs at 300 tokens what it costs at 30,000.

Operations: a matmul costs 2 x its weights per token, the head per sampled
row; a layer's recurrence per token and kv head 2 x monomials x (d + 1) for
the decay and the rank-one term, and per QUERY head 2 x monomials x (d + 1)
for the read-out.
"""

from __future__ import annotations

import functools
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # llm_np_cp_tpu

from costs import ITEMSIZE, least_seconds  # noqa: E402,F401 - re-exported


@functools.lru_cache(maxsize=8)
def _declared(key: str):
    from llm_np_cp_tpu.config import ModelConfig
    from llm_np_cp_tpu.models.transformer import param_shapes

    config = ModelConfig.from_hf_dict(json.loads(key))
    return config, param_shapes(config)


def declared(c: dict):
    """``(ModelConfig, its parameter shapes)`` of the configuration's dict."""
    return _declared(json.dumps(c, sort_keys=True))


def parts(c: dict) -> dict[str, int]:
    """Parameters by part, counted from the program's shapes."""
    _, shapes = declared(c)
    return {"layers": sum(math.prod(shape) for group in shapes["layers"]
                          for shape in group.values()),
            "embedding": math.prod(shapes["embed_tokens"]),
            "head": math.prod(shapes["lm_head"]),
            "final_norm": math.prod(shapes["final_norm"])}


def param_count(c: dict) -> int:
    return sum(parts(c).values())


def weight_bytes(c: dict, dtype: str = "bf16") -> int:
    return param_count(c) * ITEMSIZE[dtype]


def monomials(c: dict) -> int:
    """Distinct monomials ``k_a k_b``, ``a <= b``, of one head."""
    d = c["head_dim"]
    return d * (d + 1) // 2


def state_bytes_per_row(c: dict) -> int:
    """One slot's state in ONE layer as the mathematics needs it (module
    docstring), float32 whatever is served."""
    return (c["num_key_value_heads"] * monomials(c) * (c["head_dim"] + 1)
            * ITEMSIZE["f32"])


def state_bytes_held_per_row(c: dict) -> int:
    """... and as the program holds it (``state_shapes``)."""
    config, _ = declared(c)
    return sum(math.prod(shape[2:]) * ITEMSIZE["f32"]
               for shape, _ in config.state_shapes(1, "bfloat16").values())


def state_update_bytes(c: dict, state_rows: float) -> float:
    """The least a tick that touches ``state_rows`` rows moves of the state:
    every layer's row read once and written once."""
    return state_rows * c["num_hidden_layers"] * 2 * state_bytes_per_row(c)


def streamed_params(c: dict) -> int:
    """Every weight a tick reads: all but the (untied, only gathered)
    embedding table."""
    p = parts(c)
    return p["layers"] + p["head"] + p["final_norm"]


def active_matmul_params(c: dict) -> int:
    """Weights EVERY token is multiplied by, head excluded: the layers'
    matrices (leaves of three axes: the stack of ``[in, out]``)."""
    _, shapes = declared(c)
    return sum(math.prod(shape) for group in shapes["layers"]
               for shape in group.values() if len(shape) == 3)


def tick_cost(c: dict, *, tokens: float, rows: float, state_rows: float,
              dtype: str = "bf16") -> dict:
    """Bytes and operations of one tick on the chip: ``tokens`` packed
    tokens, ``rows`` live rows (a sample each), ``state_rows`` rows whose
    state the tick touches."""
    nbytes = (streamed_params(c) * ITEMSIZE[dtype]
              + state_update_bytes(c, state_rows))
    per_head = 2 * monomials(c) * (c["head_dim"] + 1)
    flops = (2 * active_matmul_params(c) * tokens
             + 2 * parts(c)["head"] * rows
             + per_head * c["num_hidden_layers"] * tokens * (
                 c["num_key_value_heads"] + c["num_attention_heads"]))
    return dict(bytes=nbytes, flops=flops)
