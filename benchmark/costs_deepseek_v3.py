"""Parameters, streamed bytes and operations of a ``deepseek_v3`` stack
(latent attention, a leading dense block, then sigmoid-routed experts beside
shared experts), from its configuration keys.  ``costs.py`` knows one kind of
layer and prices K and V per kv head; here a token leaves ONE latent row a
layer.

Per layer, from the published equations (PERF.md section 4):

- attention: ``q_proj`` H x heads x (nope + rope), ``kv_a_proj`` H x (rank +
  rope), ``kv_a_layernorm`` rank, ``kv_b_proj`` rank x heads x (nope + v),
  ``o_proj`` heads x v x H;
- dense feed-forward (layers < ``first_k_dense_replace``): 3 x H x
  ``intermediate_size``;
- expert feed-forward: ``n_routed_experts`` (the experts HELD) x 3 x H x
  ``moe_intermediate_size``, the shared experts 3 x H x ``n_shared_experts``
  x ``moe_intermediate_size``, the router H x ``router_experts`` (every expert
  of the layer, whoever holds it) and its correction bias;
- two H-wide norms a layer, one after the last, embedding and untied head.

What a tick has to move: every weight outside the routed experts once (an
untied embedding is only gathered), the held experts the tick TOUCHES
(``experts_touched``, summed over the expert layers, as the step counts it)
and nothing of the others, the latent rows of the live context read once and
the tick's own rows written.  Operations: a matmul costs 2 x its weights per
token (the absorbed form's ``q_nope W_UK`` and ``. W_UV`` are ``kv_b_proj``'s
weights, once), a held routed expert per (token, expert) PAIR held
(``pairs_held``), absorbed attention 2 x (rank + rope + rank) x heads per
(token, attended position) and layer, the head per sampled row.
"""

from __future__ import annotations

from costs import ITEMSIZE, least_seconds  # noqa: F401 - re-exported


def router_width(c: dict) -> int:
    return c.get("router_experts", c["n_routed_experts"])


def attention_params(c: dict) -> int:
    h, nh = c["hidden_size"], c["num_attention_heads"]
    dn, dr = c["qk_nope_head_dim"], c["qk_rope_head_dim"]
    rank, dv = c["kv_lora_rank"], c["v_head_dim"]
    return (h * nh * (dn + dr) + h * (rank + dr) + rank
            + rank * nh * (dn + dv) + nh * dv * h)


def dense_ff_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: dict) -> int:
    """ONE routed expert: its three matrices."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    return c.get("n_shared_experts", 0) * expert_params(c)


def router_params(c: dict) -> int:
    return c["hidden_size"] * router_width(c) + router_width(c)


def counts(c: dict) -> dict[str, int]:
    dense = c.get("first_k_dense_replace", 0)
    return {"dense": dense, "experts": c["num_hidden_layers"] - dense}


def head_params(c: dict) -> int:
    return c["vocab_size"] * c["hidden_size"]


def norm_params(c: dict) -> int:
    return (2 * c["num_hidden_layers"] + 1) * c["hidden_size"]


def param_count(c: dict) -> int:
    n = counts(c)
    return (c["num_hidden_layers"] * attention_params(c)
            + n["dense"] * dense_ff_params(c)
            + n["experts"] * (c["n_routed_experts"] * expert_params(c)
                              + shared_params(c) + router_params(c))
            + 2 * head_params(c) + norm_params(c))


def weight_bytes(c: dict, dtype: str = "bf16") -> int:
    return param_count(c) * ITEMSIZE[dtype]


def latent_bytes_per_token(c: dict, dtype: str = "bf16") -> int:
    """What a token holds in a cache over all layers, as the algorithm
    needs it: the program's one statement of a token's page
    (``ModelConfig.kv_token_shapes``: one row ``[c' | k_pe]`` a layer)."""
    import sys
    from pathlib import Path

    root = str(Path(__file__).resolve().parents[1])
    if root not in sys.path:
        sys.path.insert(0, root)
    from llm_np_cp_tpu.config import ModelConfig

    return ModelConfig.from_hf_dict(c).kv_bytes_per_token(ITEMSIZE[dtype])


def dense_streamed_params(c: dict) -> int:
    """Every weight a tick reads whatever it routes: all but the routed
    experts and the (untied, only gathered) embedding table."""
    return (param_count(c) - head_params(c)
            - counts(c)["experts"] * c["n_routed_experts"] * expert_params(c))


def active_matmul_params(c: dict) -> int:
    """Weights EVERY token is multiplied by, head and routed experts
    excluded (``kv_a_layernorm`` is no matmul)."""
    n = counts(c)
    return (c["num_hidden_layers"] * (attention_params(c) - c["kv_lora_rank"])
            + n["dense"] * dense_ff_params(c)
            + n["experts"] * (shared_params(c)
                              + c["hidden_size"] * router_width(c)))


def tick_cost(c: dict, *, tokens: float, rows: float, context_tokens: float,
              experts_touched: float, pairs_held: float,
              dtype: str = "bf16", cache_dtype: str = "bf16") -> dict:
    """Bytes and operations of one tick on the chip: ``tokens`` packed
    tokens, ``rows`` sampled rows, ``context_tokens`` the summed context of
    the live rows, ``experts_touched`` held experts that got a token and
    ``pairs_held`` (token, expert) pairs whose expert is held, both summed
    over the expert layers."""
    nbytes = (dense_streamed_params(c) * ITEMSIZE[dtype]
              + experts_touched * expert_params(c) * ITEMSIZE[dtype]
              + latent_bytes_per_token(c, cache_dtype) * (context_tokens + tokens))
    attended = context_tokens * tokens / max(rows, 1.0)
    row_width = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    flops = (2 * active_matmul_params(c) * tokens
             + 2 * expert_params(c) * pairs_held
             + 2 * head_params(c) * rows
             + 2 * (row_width + c["kv_lora_rank"]) * c["num_attention_heads"]
             * c["num_hidden_layers"] * attended)
    return dict(bytes=nbytes, flops=flops)
