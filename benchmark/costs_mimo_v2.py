"""Parameters, streamed bytes and operations of a ``mimo_v2`` stack (global
and window attention layers with kv heads of their own, a leading dense
block, then sigmoid-routed experts with no shared expert), from its
configuration keys.  ``costs.py`` knows one kind of layer.

Per layer, from the published equations (PERF.md section 4):

- attention of a kind (``hybrid_layer_pattern`` 0 = global, 1 = window):
  ``Wq`` H x heads x ``head_dim``, ``Wk`` H x K x ``head_dim``, ``Wv`` H x K
  x ``v_head_dim``, ``Wo`` heads x ``v_head_dim`` x H, ``K`` =
  ``num_key_value_heads`` (global) / ``swa_num_key_value_heads`` (window);
  a window layer with ``add_swa_attention_sink_bias`` one float a head;
- dense feed-forward (``moe_layer_freq`` 0): 3 x H x ``intermediate_size``;
- expert feed-forward (1): ``n_routed_experts`` (the experts HELD) x 3 x H x
  ``moe_intermediate_size``, the router H x ``router_experts`` (every expert
  of the layer, whoever holds it) and its correction bias;
- two H-wide norms a layer, one after the last, embedding and untied head.

What a token leaves in a cache, per layer of a kind: ``K x (head_dim +
v_head_dim)`` values (global 4 x 320 x 2 B = 2,560 B, window 5,120 B).  A
global layer keeps every token; a window layer's queries see the last
``sliding_window`` positions, so what a tick has to READ of it is
``min(context, sliding_window + slice)`` positions a row.

What a tick has to move: every weight outside the routed experts once (an
untied embedding is only gathered), the held experts the tick TOUCHES
(``experts_touched``, summed over the expert layers, as the step counts it),
the global layers' pages of the live context and the window layers' pages
of the rows' windows read once, and the tick's own tokens written in both.
Operations: a matmul costs 2 x its weights per token, a held routed expert
per (token, expert) PAIR held, attention 2 x (``head_dim`` + ``v_head_dim``)
x heads per (token, attended position) and layer, the head per sampled row.
"""

from __future__ import annotations

from costs import ITEMSIZE, least_seconds  # noqa: F401 - re-exported


def router_width(c: dict) -> int:
    return c.get("router_experts", c["n_routed_experts"])


def kinds(c: dict) -> dict[str, int]:
    """Layers of each attention kind."""
    window = sum(c["hybrid_layer_pattern"])
    return {"global": c["num_hidden_layers"] - window, "window": window}


def kv_heads(c: dict, kind: str) -> int:
    return c["swa_num_key_value_heads" if kind == "window"
             else "num_key_value_heads"]


def attention_params(c: dict, kind: str) -> int:
    h, nh, d = c["hidden_size"], c["num_attention_heads"], c["head_dim"]
    dv, k = c.get("v_head_dim", d), kv_heads(c, kind)
    sink = nh if kind == "window" and c.get("add_swa_attention_sink_bias") else 0
    return h * nh * d + h * k * d + h * k * dv + nh * dv * h + sink


def dense_ff_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    """ONE routed expert: its three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_width(c) + router_width(c)


def counts(c: dict) -> dict[str, int]:
    experts = sum(c["moe_layer_freq"])
    return {"dense": c["num_hidden_layers"] - experts, "experts": experts}


def head_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def norm_params(c: dict) -> int:
    return (2 * c["num_hidden_layers"] + 1) * c["hidden_size"]


def attention_total(c: dict) -> int:
    return sum(n * attention_params(c, kind) for kind, n in kinds(c).items())


def param_count(c: dict) -> int:
    n = counts(c)
    return (attention_total(c) + n["dense"] * dense_ff_params(c)
            + n["experts"] * (c["n_routed_experts"] * expert_params(c)
                              + router_params(c))
            + 2 * head_params(c) + norm_params(c))


def weight_bytes(c: dict, dtype: str = "bf16") -> int:
    return param_count(c) * ITEMSIZE[dtype]


def kv_bytes_per_token(c: dict, kind: str, dtype: str = "bf16") -> int:
    """What a token holds in the layers of ``kind``, all of them, as the
    algorithm needs it (a window layer counted as if it kept the token)."""
    d = c["head_dim"]
    return (kinds(c)[kind] * kv_heads(c, kind) * (d + c.get("v_head_dim", d))
            * ITEMSIZE[dtype])


def window_positions(c: dict, context: float, written: float) -> float:
    """Positions of a row's context a window layer's queries see in a tick
    that writes ``written`` tokens of it."""
    return min(context, c["sliding_window"] - 1 + written)


def dense_streamed_params(c: dict) -> int:
    """Every weight a tick reads whatever it routes: all but the routed
    experts and the (untied, only gathered) embedding table."""
    return (param_count(c) - head_params(c)
            - counts(c)["experts"] * c["n_routed_experts"] * expert_params(c))


def active_matmul_params(c: dict) -> int:
    """Weights EVERY token is multiplied by, head and routed experts
    excluded (a sink is no matmul)."""
    n = counts(c)
    sinks = (kinds(c)["window"] * c["num_attention_heads"]
             if c.get("add_swa_attention_sink_bias") else 0)
    return (attention_total(c) - sinks + n["dense"] * dense_ff_params(c)
            + n["experts"] * c["hidden_size"] * router_width(c))


def attention_bytes(c: dict, pages_global: float, pages_window: float,
                    block_size: int, cache_dtype: str = "bf16") -> float:
    """Bytes the attention calls of a tick are asked to stream: per layer
    of a kind, the pages in every query tile's visible range (the tick
    arguments ``attn_pages_global`` / ``attn_pages_window``)."""
    return block_size * (
        pages_global * kv_bytes_per_token(c, "global", cache_dtype)
        + pages_window * kv_bytes_per_token(c, "window", cache_dtype))


def touched_expert_bytes(c: dict, experts_touched: float,
                         dtype: str = "bf16") -> float:
    return experts_touched * expert_params(c) * ITEMSIZE[dtype]


def tick_cost(c: dict, *, tokens: float, rows: float, context_tokens: float,
              experts_touched: float, pairs_held: float,
              dtype: str = "bf16", cache_dtype: str = "bf16") -> dict:
    """Bytes and operations of one tick on the chip: ``tokens`` packed
    tokens, ``rows`` live rows, ``context_tokens`` the summed context of
    the live rows, ``experts_touched`` held experts that got a token and
    ``pairs_held`` (token, expert) pairs whose expert is held, both summed
    over the expert layers."""
    rows = max(rows, 1.0)
    seen = rows * window_positions(c, context_tokens / rows, tokens / rows)
    kv_global = kv_bytes_per_token(c, "global", cache_dtype)
    kv_window = kv_bytes_per_token(c, "window", cache_dtype)
    nbytes = (dense_streamed_params(c) * ITEMSIZE[dtype]
              + touched_expert_bytes(c, experts_touched, dtype)
              + kv_global * (context_tokens + tokens)
              + kv_window * (seen + tokens))
    d = c["head_dim"]
    per_pos = 2 * (d + c.get("v_head_dim", d)) * c["num_attention_heads"]
    n = kinds(c)
    attended = (n["global"] * context_tokens + n["window"] * seen) * tokens / rows
    flops = (2 * active_matmul_params(c) * tokens
             + 2 * expert_params(c) * pairs_held
             + 2 * head_params(c) * rows
             + per_pos * attended)
    return dict(bytes=nbytes, flops=flops)
