"""Parameters, streamed bytes and operations of an ``lfm2_moe`` stack, from
its configuration keys (``costs.py`` knows one kind of layer; this
architecture has four: a conv or an attention operator, over a dense or an
expert feed-forward).

Per layer, from the published equations (PERF.md section 4):

- conv operator: ``in_proj`` H x 3H, ``out_proj`` H x H, one
  ``conv_L_cache``-tap filter a channel;
- attention operator: q and o H x (heads x D), k and v H x (kv heads x D),
  two D-wide norms (q, k);
- dense feed-forward: 3 x H x ``intermediate_size``;
- expert feed-forward: ``num_experts`` x 3 x H x ``moe_intermediate_size``,
  the gate H x experts and the selection bias;
- two H-wide norms a layer, one after the last, the embedding = the head.

What a tick has to move: every weight outside the experts once, the experts
the tick TOUCHES (``experts_touched``, summed over the expert layers, as the
step counts it) and nothing of the others, K/V of the attention layers only
for the live context, and a conv layer's state row read and written for every
live row.  Operations: a matmul costs 2 x its weights per token, an expert
layer ``num_experts_per_tok`` experts per token, the head per sampled row.
"""

from __future__ import annotations

from costs import ITEMSIZE, least_seconds  # noqa: F401 - re-exported

OPERATOR_OF = {"conv": "conv", "full_attention": "attn"}


def _head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def conv_operator_params(c: dict) -> int:
    h = c["hidden_size"]
    return h * 3 * h + h * h + h * c.get("conv_L_cache", 3)


def attn_operator_params(c: dict) -> int:
    h, d = c["hidden_size"], _head_dim(c)
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv + 2 * d


def dense_ff_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    """ONE expert: its three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["num_experts"] + (
        c["num_experts"] if c.get("use_expert_bias") else 0)


def expert_ff_params(c: dict) -> int:
    return c["num_experts"] * expert_params(c) + router_params(c)


def layer_kinds(c: dict) -> list[tuple[str, str]]:
    """``(operator, feed-forward)`` of every layer."""
    return [(OPERATOR_OF[kind],
             "dense" if i < c.get("num_dense_layers", 0) else "experts")
            for i, kind in enumerate(c["layer_types"])]


def counts(c: dict) -> dict[str, int]:
    kinds = layer_kinds(c)
    return {
        "conv": sum(op == "conv" for op, _ in kinds),
        "attn": sum(op == "attn" for op, _ in kinds),
        "dense": sum(ff == "dense" for _, ff in kinds),
        "experts": sum(ff == "experts" for _, ff in kinds),
    }


def head_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def norm_params(c: dict) -> int:
    return (2 * len(c["layer_types"]) + 1) * c["hidden_size"]


def param_count(c: dict) -> int:
    n = counts(c)
    return (n["conv"] * conv_operator_params(c) + n["attn"] * attn_operator_params(c)
            + n["dense"] * dense_ff_params(c) + n["experts"] * expert_ff_params(c)
            + head_params(c) + norm_params(c))


def weight_bytes(c: dict, dtype: str = "bf16") -> int:
    return param_count(c) * ITEMSIZE[dtype]


def kv_bytes_per_token(c: dict, dtype: str = "bf16") -> int:
    """K and V of the attention layers only: a conv layer has none."""
    return (2 * counts(c)["attn"] * c["num_key_value_heads"] * _head_dim(c)
            * ITEMSIZE[dtype])


def state_bytes_per_slot(c: dict, dtype: str = "bf16") -> int:
    return (counts(c)["conv"] * (c.get("conv_L_cache", 3) - 1) * c["hidden_size"]
            * ITEMSIZE[dtype])


def dense_streamed_params(c: dict) -> int:
    """Every weight a tick reads whatever it routes: all but the experts."""
    return param_count(c) - counts(c)["experts"] * c["num_experts"] * expert_params(c)


def touched_expert_bytes(c: dict, experts_touched: float, dtype: str = "bf16") -> float:
    """Bytes of the experts a tick touches (``experts_touched`` is summed
    over the expert layers)."""
    return experts_touched * expert_params(c) * ITEMSIZE[dtype]


def active_matmul_params(c: dict) -> int:
    """Weights one token is multiplied by, head excluded."""
    n = counts(c)
    return (n["conv"] * conv_operator_params(c) + n["attn"] * attn_operator_params(c)
            + n["dense"] * dense_ff_params(c)
            + n["experts"] * (c["num_experts_per_tok"] * expert_params(c)
                              + c["hidden_size"] * c["num_experts"]))


def tick_cost(c: dict, *, tokens: float, rows: float, context_tokens: float,
              experts_touched: float, dtype: str = "bf16",
              cache_dtype: str = "bf16") -> dict:
    """Bytes and operations of one tick on the chip: ``tokens`` packed
    tokens, ``rows`` live rows (sampled, and each with a state row),
    ``context_tokens`` the summed context of the live rows,
    ``experts_touched`` experts that got a token, summed over the layers."""
    d, n = _head_dim(c), counts(c)
    nbytes = (dense_streamed_params(c) * ITEMSIZE[dtype]
              + touched_expert_bytes(c, experts_touched, dtype)
              + kv_bytes_per_token(c, cache_dtype) * (context_tokens + tokens)
              + 2 * rows * state_bytes_per_slot(c, dtype))
    attended = context_tokens * tokens / max(rows, 1.0)
    flops = (2 * active_matmul_params(c) * tokens + 2 * head_params(c) * rows
             + 4 * d * c["num_attention_heads"] * n["attn"] * attended)
    return dict(bytes=nbytes, flops=flops)
