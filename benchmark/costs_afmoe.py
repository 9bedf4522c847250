"""Parameters, streamed bytes and operations of an ``afmoe`` stack (Trinity:
gated GQA attention in window and global layers of ONE shape, four norms a
layer, leading dense blocks, then one shared expert beside sigmoid-routed
ones of which a chip holds a share), from its configuration keys.
``costs.py`` knows one kind of layer.

Per layer, from the published equations (PERF.md section 4):

- attention, either kind: ``Wq`` and the gate ``Wg`` H x heads x
  ``head_dim`` each, ``Wk`` and ``Wv`` H x K x ``head_dim``, ``Wo`` heads x
  ``head_dim`` x H, the q and k norms one weight of ``head_dim`` each;
- dense feed-forward (the first ``num_dense_layers``): 3 x H x
  ``intermediate_size``;
- expert feed-forward: ``num_experts`` (the experts HELD) x 3 x H x
  ``moe_intermediate_size``, ``num_shared_experts`` shared experts of the
  same width, the router H x ``router_experts`` (every expert of the layer,
  whoever holds it) and its selection bias;
- four H-wide norms a layer, one after the last, embedding and untied head
  (``vocab_size`` rows each: the share's slice where the file states one).

What a token leaves in a cache, per layer of either kind: ``K x 2 x
head_dim`` values (8 x 256 x 2 B = 4,096 B).  A global layer keeps every
token; a window layer's queries see the last ``sliding_window`` positions,
so what a tick has to READ of it is ``min(context, sliding_window - 1 +
slice)`` positions a row.

What a tick has to move: every weight outside the routed experts once (an
untied embedding is only gathered), the held experts the tick TOUCHES
(``experts_touched``, summed over the expert layers, as the step counts it),
the global layers' pages of the live context and the window layers' pages
of the rows' windows read once, and the tick's own tokens written in both.
What the attention KERNEL is asked to stream is more: every query tile
streams its row's visible pages again (``attention_bytes`` prices that, from
the tick arguments ``attn_pages_global`` / ``attn_pages_window``).
Operations: a matmul costs 2 x its weights per token, a held routed expert
per (token, expert) PAIR held, attention 4 x ``head_dim`` x heads per
(token, attended position) and layer, the head per sampled row.
"""

from __future__ import annotations

from costs import ITEMSIZE, least_seconds  # noqa: F401 - re-exported


def router_width(c: dict) -> int:
    return c.get("router_experts", c["num_experts"])


def kinds(c: dict) -> dict[str, int]:
    """Layers of each attention kind."""
    window = sum(t == "sliding_attention" for t in c["layer_types"])
    return {"global": c["num_hidden_layers"] - window, "window": window}


def counts(c: dict) -> dict[str, int]:
    dense = min(c.get("num_dense_layers", 0), c["num_hidden_layers"])
    return {"dense": dense, "experts": c["num_hidden_layers"] - dense}


def attention_params(c: dict) -> int:
    h, nh, nk, d = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    return 2 * h * nh * d + 2 * h * nk * d + nh * d * h + 2 * d


def dense_ff_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    """ONE routed expert: its three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    return c.get("num_shared_experts", 1) * expert_params(c)


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_width(c) + router_width(c)


def head_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def norm_params(c: dict) -> int:
    return (4 * c["num_hidden_layers"] + 1) * c["hidden_size"]


def held_expert_params(c: dict) -> int:
    """The routed experts HELD, all expert layers."""
    return counts(c)["experts"] * c["num_experts"] * expert_params(c)


def param_count(c: dict) -> int:
    n = counts(c)
    return (c["num_hidden_layers"] * attention_params(c)
            + n["dense"] * dense_ff_params(c) + held_expert_params(c)
            + n["experts"] * (shared_params(c) + router_params(c))
            + 2 * head_params(c) + norm_params(c))


def weight_bytes(c: dict, dtype: str = "bf16") -> int:
    return param_count(c) * ITEMSIZE[dtype]


def kv_bytes_per_token(c: dict, kind: str, dtype: str = "bf16") -> int:
    """What a token holds in the layers of ``kind``, all of them, as the
    algorithm needs it (a window layer counted as if it kept the token)."""
    return (kinds(c)[kind] * c["num_key_value_heads"] * 2 * c["head_dim"]
            * ITEMSIZE[dtype])


def window_blocks_per_slot(c: dict, widest_slice: int, block_size: int) -> int:
    """The ring a slot holds in the window class (the engine's rule,
    serve/block_pool.window_blocks_per_slot, restated from the shapes)."""
    return -(-(c["sliding_window"] - 1 + widest_slice) // block_size) + 1


def pool_bytes(c: dict, *, slots: int, global_blocks: int, widest_slice: int,
               block_size: int, cache_dtype: str = "bf16") -> dict[str, int]:
    """Bytes of each page class on the device."""
    ring = window_blocks_per_slot(c, widest_slice, block_size)
    return {
        "global": global_blocks * block_size * kv_bytes_per_token(
            c, "global", cache_dtype),
        "window": (1 + slots * ring) * block_size * kv_bytes_per_token(
            c, "window", cache_dtype),
    }


def window_positions(c: dict, context: float, written: float) -> float:
    """Positions of a row's context a window layer's queries see in a tick
    that writes ``written`` tokens of it."""
    return min(context, c["sliding_window"] - 1 + written)


def dense_streamed_params(c: dict) -> int:
    """Every weight a tick reads whatever it routes: all but the routed
    experts and the (untied, only gathered) embedding table."""
    return param_count(c) - head_params(c) - held_expert_params(c)


def active_matmul_params(c: dict) -> int:
    """Weights EVERY token is multiplied by, head and routed experts
    excluded (norm weights are no matmul)."""
    n = counts(c)
    return (c["num_hidden_layers"] * (attention_params(c) - 2 * c["head_dim"])
            + n["dense"] * dense_ff_params(c)
            + n["experts"] * (shared_params(c)
                              + c["hidden_size"] * router_width(c)))


def attention_bytes(c: dict, pages_global: float, pages_window: float,
                    block_size: int, cache_dtype: str = "bf16") -> float:
    """Bytes the attention calls of a tick are asked to stream: per layer
    of a kind, the pages in every query tile's visible range (the tick
    arguments ``attn_pages_global`` / ``attn_pages_window``: a tile
    re-reads its row's pages)."""
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
    per_pos = 4 * c["head_dim"] * c["num_attention_heads"]
    n = kinds(c)
    attended = (n["global"] * context_tokens + n["window"] * seen) * tokens / rows
    flops = (2 * active_matmul_params(c) * tokens
             + 2 * expert_params(c) * pairs_held
             + 2 * head_params(c) * rows
             + per_pos * attended)
    return dict(bytes=nbytes, flops=flops)
