"""Parameters, streamed bytes and operations of a ``ling_hybrid`` stack
(Ling-3.0: delta-rule linear-attention layers with a matrix state beside one
latent-attention layer a group, a leading dense block, then group-limited
sigmoid-routed experts of which this chip holds a share, one shared expert),
read off the PROGRAM's declaration of the configuration —
``ModelConfig.from_hf_dict`` and then ``param_shapes``, ``state_shapes``,
``kv_token_shapes``, ``experts_held`` — not from constants: a width that
changes in the configuration's file changes here.  ``costs.py`` knows one
kind of layer.

What a tick has to move (the roofline is for the work the algorithm needs;
padding the program adds is not counted):

- every weight outside the routed experts once (an untied embedding is only
  gathered, one row a token), and the held experts the tick TOUCHES
  (``experts_touched``, summed over the expert layers, as the step counts it);
- the matrix state of every row the tick touches, in every KDA layer, read
  once and written once (``H x d x d`` float32 a row and layer: 2 MiB at the
  published widths), and the convolution's history beside it likewise;
- the latent layers' rows of the live context read once, and the tick's own
  tokens written.

Operations: a matmul costs 2 x its weights per token, a held routed expert
per (token, expert) PAIR held, the head per sampled row; a KDA layer's
recurrence 6 x ``d^2`` a head and token (``S^T k``, the rank-one correction,
``S^T q``: a multiply-add each over the state); latent attention in its
absorbed form 2 x (2 x rank + rope) x heads per (token, attended position).
"""

from __future__ import annotations

import functools
import json
import math
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))  # llm_np_cp_tpu

from costs import ITEMSIZE, least_seconds  # noqa: E402,F401 - re-exported

EXPERT_LEAVES = ("w1", "w3", "w2")


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
    """Parameters by part, counted from the program's shapes: the held
    routed experts, everything else in the layers, embedding, head, the
    last norm."""
    config, shapes = declared(c)
    out = {"experts_held": 0, "layers_other": 0,
           "embedding": math.prod(shapes["embed_tokens"]),
           "head": math.prod(shapes["lm_head"]) if "lm_head" in shapes else 0,
           "final_norm": math.prod(shapes["final_norm"])}
    for group in shapes["layers"]:
        for name, shape in group.items():
            key = "experts_held" if name in EXPERT_LEAVES else "layers_other"
            out[key] += math.prod(shape)
    return out


def param_count(c: dict) -> int:
    return sum(parts(c).values())


def weight_bytes(c: dict, dtype: str = "bf16") -> int:
    return param_count(c) * ITEMSIZE[dtype]


def expert_params(c: dict) -> int:
    """ONE routed expert: its three matrices."""
    config, _ = declared(c)
    return 3 * config.hidden_size * config.moe_intermediate_size


def kda_state_bytes_per_row(c: dict) -> int:
    """One slot's matrix state in ONE KDA layer (float32 whatever is
    served), from ``state_shapes``."""
    config, _ = declared(c)
    shape, dtype = config.state_shapes(1, "bfloat16")["kda"]
    return math.prod(shape[2:]) * ITEMSIZE["f32" if dtype == "float32" else "bf16"]


def conv_state_bytes_per_row(c: dict, dtype: str = "bf16") -> int:
    """One slot's convolution history in ONE KDA layer."""
    config, _ = declared(c)
    shape, _ = config.state_shapes(1, "bfloat16")["conv"]
    return math.prod(shape[2:]) * ITEMSIZE[dtype]


def state_bytes(c: dict, slots: int, dtype: str = "bf16") -> int:
    """What ``slots`` slots hold beside the pages, over every KDA layer."""
    config, _ = declared(c)
    return slots * len(config.kda_layers) * (
        kda_state_bytes_per_row(c) + conv_state_bytes_per_row(c, dtype))


def latent_bytes_per_token(c: dict, dtype: str = "bf16") -> int:
    """What a token leaves in the pages, over every layer that has them
    (``kv_token_shapes``: the latent layers' one row), as the algorithm
    needs it."""
    config, _ = declared(c)
    return config.kv_bytes_per_token(ITEMSIZE[dtype])


def kda_state_update_bytes(c: dict, state_rows: float) -> float:
    """The least a tick that touches ``state_rows`` rows moves of the matrix
    state: every KDA layer's row read once and written once."""
    config, _ = declared(c)
    return (state_rows * len(config.kda_layers) * 2
            * kda_state_bytes_per_row(c))


def touched_expert_bytes(c: dict, experts_touched: float,
                         dtype: str = "bf16") -> float:
    return experts_touched * expert_params(c) * ITEMSIZE[dtype]


def dense_streamed_params(c: dict) -> int:
    """Every weight a tick reads whatever it routes: all but the routed
    experts and the (untied, only gathered) embedding table."""
    p = parts(c)
    return p["layers_other"] + p["head"] + p["final_norm"]


def active_matmul_params(c: dict) -> int:
    """Weights EVERY token is multiplied by, head and routed experts
    excluded: the layers' matrices (leaves of three axes: a run's stack of
    ``[in, out]``); filters, norms and the decay's scalars are no matmul."""
    _, shapes = declared(c)
    return sum(math.prod(shape) for group in shapes["layers"]
               for name, shape in group.items()
               if len(shape) == 3 and name not in EXPERT_LEAVES
               and not name.endswith("_conv"))


def tick_cost(c: dict, *, tokens: float, rows: float, context_tokens: float,
              experts_touched: float, pairs_held: float, state_rows: float,
              dtype: str = "bf16", cache_dtype: str = "bf16") -> dict:
    """Bytes and operations of one tick on the chip: ``tokens`` packed
    tokens, ``rows`` live rows, ``context_tokens`` the summed context of the
    live rows, ``experts_touched`` held experts that got a token and
    ``pairs_held`` (token, expert) pairs whose expert is held, both summed
    over the expert layers, ``state_rows`` rows whose state the tick
    touches."""
    config, _ = declared(c)
    n_kda, n_latent = len(config.kda_layers), len(config.attn_layers)
    latent = latent_bytes_per_token(c, cache_dtype)
    nbytes = (dense_streamed_params(c) * ITEMSIZE[dtype]
              + touched_expert_bytes(c, experts_touched, dtype)
              + kda_state_update_bytes(c, state_rows)
              + state_rows * n_kda * 2 * conv_state_bytes_per_row(c, dtype)
              + latent * (context_tokens + tokens))
    nh, d = config.num_attention_heads, config.kda_head_dim
    per_pos = 2 * (2 * config.kv_lora_rank + config.qk_rope_head_dim) * nh
    attended = n_latent * context_tokens * tokens / max(rows, 1.0)
    flops = (2 * active_matmul_params(c) * tokens
             + 2 * expert_params(c) * pairs_held
             + 2 * parts(c)["head"] * rows
             + 6 * d * d * nh * n_kda * tokens
             + per_pos * attended)
    return dict(bytes=nbytes, flops=flops)
