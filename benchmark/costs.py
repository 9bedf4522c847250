"""What a tick has to move and compute, from shapes alone.

The least the chip could take for one tick of the unified step is the
larger of bytes / peak bytes/s and operations / peak FLOP/s, where

- every weight the step multiplies by is read from HBM once per tick
  (the decoder stack, the final norm and the lm head; with a tied head
  the embedding matrix IS the head; an untied embedding is only
  gathered, one row per token);
- each live row reads the K and V of its whole context once per layer
  and every token of the tick writes its own K and V;
- a matmul costs 2 x its weight count per token, the lm head is paid
  per sampled ROW (not per token), and attention costs 4 x head_dim x
  query heads x context per token per layer (QK^T and PV).

Padding the program adds (decode rows padded to a query tile, packed
widths rounded to a bucket) is NOT counted: the roofline is for the
work the algorithm needs.  Under tensor parallelism the same totals are
divided by the ``tp`` chips that share a tick.
"""

from __future__ import annotations

ITEMSIZE = {"bf16": 2, "f32": 4, "int8": 1}


def layer_params(c: dict) -> int:
    h, f = c["hidden_size"], c["intermediate_size"]
    d = c.get("head_dim") or h // c["num_attention_heads"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    bias = q + 2 * kv if c.get("model_type") == "qwen2" or c.get("attention_bias") else 0
    return h * q + 2 * h * kv + q * h + bias + 3 * h * f + 2 * h


def head_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def param_count(c: dict) -> int:
    embed = head_params(c) * (1 if c.get("tie_word_embeddings") else 2)
    return c["num_hidden_layers"] * layer_params(c) + embed + c["hidden_size"]


def weight_bytes(c: dict, dtype: str = "bf16") -> int:
    return param_count(c) * ITEMSIZE[dtype]


def streamed_weight_bytes(c: dict, dtype: str = "bf16") -> int:
    """Weights a tick reads: everything but an untied embedding table."""
    n = c["num_hidden_layers"] * layer_params(c) + head_params(c) + c["hidden_size"]
    return n * ITEMSIZE[dtype]


def kv_bytes_per_token(c: dict, dtype: str = "bf16") -> int:
    d = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    return 2 * c["num_hidden_layers"] * c["num_key_value_heads"] * d * ITEMSIZE[dtype]


def tick_cost(c: dict, *, tokens: float, rows: float, context_tokens: float,
              dtype: str = "bf16", cache_dtype: str = "bf16", tp: int = 1) -> dict:
    """Bytes and operations of one tick ON ONE CHIP: ``tokens`` packed
    tokens (prefill + decode), ``rows`` sampled rows, ``context_tokens``
    the summed context length of the live rows."""
    d = c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]
    kv = kv_bytes_per_token(c, cache_dtype)
    nbytes = streamed_weight_bytes(c, dtype) + kv * (context_tokens + tokens)
    matmul = 2 * c["num_hidden_layers"] * layer_params(c) * tokens
    head = 2 * head_params(c) * rows
    # a token attends to its own row's context; context_tokens / rows is
    # the mean context, so tokens x that is the attended length in all
    attended = context_tokens * tokens / max(rows, 1.0)
    attn = 4 * d * c["num_attention_heads"] * c["num_hidden_layers"] * attended
    return dict(bytes=nbytes / tp, flops=(matmul + head + attn) / tp)


def least_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """(least time for that cost, which peak bounds it)."""
    t_mem = cost["bytes"] / (peaks["hbm_gbps"] * 1e9)
    t_flop = cost["flops"] / (peaks["bf16_tflops"] * 1e12)
    return (t_mem, "memory") if t_mem >= t_flop else (t_flop, "compute")
