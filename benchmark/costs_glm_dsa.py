"""Parameters, streamed bytes and operations of a ``glm_moe_dsa`` stack (GLM-5:
DeepSeek-V3's layer with a query latent under a sparse-attention indexer),
from its configuration keys.  The feed-forward side, the norms, the embedding
and the head are ``costs_deepseek_v3.py``'s; the attention side is here.

Per layer, from the layer's equations (benchmark/reference_glm_dsa.py):

- attention: ``q_a_proj`` H x q_rank, ``q_a_layernorm`` q_rank, ``q_b_proj``
  q_rank x heads x (nope + rope), ``kv_a_proj`` H x (rank + rope),
  ``kv_a_layernorm`` rank, ``kv_b_proj`` rank x heads x (nope + v), ``o_proj``
  heads x v x H;
- indexer: ``wq_b`` q_rank x heads_I x dim_I, ``wk`` H x dim_I, ``k_norm``
  2 x dim_I (a LayerNorm's weight and bias), ``weights_proj`` H x heads_I.

What a token leaves in a cache a layer: the latent row ``[c' | k_pe]`` (rank +
rope values) and ONE index key (dim_I values).

**The two byte bounds** (what the mathematics needs, whatever form a kernel
takes): a token that sees ``ctx`` positions reads ``ctx`` index keys to score
them (``index_bytes``: ctx x dim_I x 2 B = 256 B a position at the published
widths) and the ``min(ctx, index_topk)`` latent rows it attends
(``selected_row_bytes``: 1,152 B a row), a layer.  A kernel that walks every
page of the context under a mask reads more than the second and so reads
honestly LOW against it; nothing can read over 100 %.

What a tick has to move: every weight outside the routed experts once (an
untied embedding is only gathered), the held experts the tick TOUCHES, the two
bounds above summed over its tokens (tick args ``dsa_visible`` /
``dsa_selected``: positions a layer), the tick's own rows and keys written.
Operations: a matmul 2 x its weights per token, a held routed expert per
(token, expert) pair held, the index scores 2 x heads_I x dim_I per (token,
visible position), absorbed attention 2 x (rank + rope + rank) x heads per
(token, selected position), a layer each; the head per sampled row.
"""

from __future__ import annotations

import costs_deepseek_v3 as ds
from costs import ITEMSIZE, least_seconds  # noqa: F401 - re-exported


def attention_params(c: dict) -> int:
    h, nh, qr = c["hidden_size"], c["num_attention_heads"], c["q_lora_rank"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, dv = c["kv_lora_rank"], c["v_head_dim"]
    return (h * qr + qr + qr * nh * (dn + dr) + h * (rank + dr) + rank
            + rank * nh * (dn + dv) + nh * dv * h)


def indexer_params(c: dict) -> int:
    h, qr = c["hidden_size"], c["q_lora_rank"]
    ih, idim = c["index_n_heads"], c["index_head_dim"]
    return qr * ih * idim + h * idim + 2 * idim + h * ih


def layer_attention_params(c: dict) -> int:
    return attention_params(c) + indexer_params(c)


def param_count(c: dict) -> int:
    n = ds.counts(c)
    return (c["num_hidden_layers"] * layer_attention_params(c)
            + n["dense"] * ds.dense_ff_params(c)
            + n["experts"] * (c["n_routed_experts"] * ds.expert_params(c)
                              + ds.shared_params(c) + ds.router_params(c))
            + 2 * ds.head_params(c) + ds.norm_params(c))


def weight_bytes(c: dict, dtype: str = "bf16") -> int:
    return param_count(c) * ITEMSIZE[dtype]


def row_bytes(c: dict, dtype: str = "bf16") -> int:
    """A latent row ``[c' | k_pe]`` as the algorithm needs it, ONE layer."""
    return (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * ITEMSIZE[dtype]


def index_key_bytes(c: dict, dtype: str = "bf16") -> int:
    """An index key, ONE layer."""
    return c["index_head_dim"] * ITEMSIZE[dtype]


def cache_bytes_per_token(c: dict, dtype: str = "bf16") -> int:
    """What a token holds in a cache over all layers, as needed."""
    return c["num_hidden_layers"] * (row_bytes(c, dtype) + index_key_bytes(c, dtype))


def stored_bytes_per_token(c: dict, dtype: str = "bf16") -> int:
    """... and as the pool stores it: a row in whole rows of 128 lanes
    (serve/block_pool.latent_page_width), the key beside it."""
    row = -(-(c["kv_lora_rank"] + c["qk_rope_head_dim"]) // 128) * 128
    return c["num_hidden_layers"] * (row + c["index_head_dim"]) * ITEMSIZE[dtype]


def index_bytes(c: dict, visible: float, dtype: str = "bf16") -> float:
    """The index scores' byte bound, ONE layer: ``visible`` positions seen
    (summed over tokens), a key read for each."""
    return visible * index_key_bytes(c, dtype)


def selected_row_bytes(c: dict, selected: float, dtype: str = "bf16") -> float:
    """The sparse attention's byte bound, ONE layer: ``selected`` positions
    attended (summed over tokens: ``min(ctx, index_topk)`` each), a row read
    for each."""
    return selected * row_bytes(c, dtype)


def dense_streamed_params(c: dict) -> int:
    """Every weight a tick reads whatever it routes: all but the routed
    experts and the (untied, only gathered) embedding table."""
    return (param_count(c) - ds.head_params(c)
            - ds.counts(c)["experts"] * c["n_routed_experts"] * ds.expert_params(c))


def active_matmul_params(c: dict) -> int:
    """Weights EVERY token is multiplied by, head and routed experts
    excluded (the three norms' vectors are no matmul)."""
    n = ds.counts(c)
    norms = c["q_lora_rank"] + c["kv_lora_rank"] + 2 * c["index_head_dim"]
    return (c["num_hidden_layers"] * (layer_attention_params(c) - norms)
            + n["dense"] * ds.dense_ff_params(c)
            + n["experts"] * (ds.shared_params(c)
                              + c["hidden_size"] * ds.router_width(c)))


def tick_cost(c: dict, *, tokens: float, rows: float, visible: float,
              selected: float, experts_touched: float, pairs_held: float,
              dtype: str = "bf16", cache_dtype: str = "bf16") -> dict:
    """Bytes and operations of one tick on the chip: ``tokens`` packed tokens,
    ``rows`` sampled rows, ``visible`` / ``selected`` the positions ONE layer's
    tokens see / attend (tick args ``dsa_visible`` / ``dsa_selected``),
    ``experts_touched`` held experts that got a token and ``pairs_held``
    (token, expert) pairs whose expert is held, both summed over the expert
    layers."""
    layers = c["num_hidden_layers"]
    nbytes = (dense_streamed_params(c) * ITEMSIZE[dtype]
              + experts_touched * ds.expert_params(c) * ITEMSIZE[dtype]
              + layers * (index_bytes(c, visible, cache_dtype)
                          + selected_row_bytes(c, selected, cache_dtype))
              + cache_bytes_per_token(c, cache_dtype) * tokens)
    row_width = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    flops = (2 * active_matmul_params(c) * tokens
             + 2 * ds.expert_params(c) * pairs_held
             + 2 * ds.head_params(c) * rows
             + layers * (2 * c["index_n_heads"] * c["index_head_dim"] * visible
                         + 2 * (row_width + c["kv_lora_rank"])
                         * c["num_attention_heads"] * selected))
    return dict(bytes=nbytes, flops=flops)


def sizes(c: dict, published: dict) -> dict:
    """The configuration file's ``sizes`` (tested to the parameter)."""
    n = ds.counts(c)
    expert_layer_held = (layer_attention_params(c) + 2 * c["hidden_size"]
                         + ds.shared_params(c) + ds.router_params(c)
                         + c["n_routed_experts"] * ds.expert_params(c))
    whole = dict(c, **published, router_experts=published["n_routed_experts"])
    return {
        "parameters": param_count(c),
        "weight_bytes_bf16": weight_bytes(c),
        "cache_bytes_per_token_bf16": cache_bytes_per_token(c),
        "cache_bytes_per_token_stored_bf16": stored_bytes_per_token(c),
        "per_layer_parameters": {
            "attention": attention_params(c),
            "q_a_proj": c["hidden_size"] * c["q_lora_rank"],
            "q_b_proj": c["q_lora_rank"] * c["num_attention_heads"] * (
                c["qk_nope_head_dim"] + c["qk_rope_head_dim"]),
            "kv_a_proj": c["hidden_size"] * (
                c["kv_lora_rank"] + c["qk_rope_head_dim"]),
            "kv_b_proj": c["kv_lora_rank"] * c["num_attention_heads"] * (
                c["qk_nope_head_dim"] + c["v_head_dim"]),
            "o_proj": c["num_attention_heads"] * c["v_head_dim"] * c["hidden_size"],
            "indexer": indexer_params(c),
            "dense_feed_forward": ds.dense_ff_params(c),
            "one_routed_expert": ds.expert_params(c),
            "shared_expert": ds.shared_params(c),
            "router_and_bias": ds.router_params(c),
            "norms": 2 * c["hidden_size"],
            "dense_layer": (layer_attention_params(c) + 2 * c["hidden_size"]
                            + ds.dense_ff_params(c)),
            "expert_layer_held": expert_layer_held,
            "expert_layer_whole": expert_layer_held + (
                ds.router_width(c) - c["n_routed_experts"]) * ds.expert_params(c),
            "embedding": ds.head_params(c),
            "head": ds.head_params(c),
        },
        "layers": {"dense_feed_forward": n["dense"],
                   "expert_feed_forward": n["experts"]},
        "published": {
            **published,
            "parameters": param_count(whole),
            "weight_bytes_bf16": weight_bytes(whole),
            "cache_bytes_per_token_bf16": cache_bytes_per_token(whole),
        },
    }
