"""DeepSeek-V3 family binding (``model_type: deepseek_v3``; Kanana-2):
checkpoint keys.  The stack is a leading run of dense blocks and then one
run an expert layer (``ModelConfig.layer_groups``), so a tensor's place is
``(run, leaf, index into the leaf)`` as for ``lfm2_moe``; the loader and
its inverse walk ``layer_tensors``.  All math lives in
``models/transformer.py`` (``latent_attention_block``, ``experts_block``)
and ``ops/moe.py``.

Published names (``modeling_deepseek_v3.py``, ``q_lora_rank: null``):
``self_attn.q_proj``, ``self_attn.kv_a_proj_with_mqa`` (columns ``[c |
k_pe]``), ``self_attn.kv_a_layernorm``, ``self_attn.kv_b_proj`` (per head
``[k_nope | v]``), ``self_attn.o_proj``; a dense block ``mlp.{gate,up,
down}_proj``; an expert block ``mlp.gate.weight`` (the router),
``mlp.gate.e_score_correction_bias`` (float32, selection only),
``mlp.experts.N.{gate,up,down}_proj`` and ``mlp.shared_experts.{gate,up,
down}_proj`` (one SwiGLU of ``n_shared_experts x moe_intermediate_size``).
A configuration that holds a share of the routed experts reads experts
``first_expert .. first_expert + held - 1`` of each layer and no other.
"""

from __future__ import annotations

from typing import Iterator

from llm_np_cp_tpu.config import ModelConfig

# HF key → (param name, transpose?)
TOP_KEY_MAP: dict[str, tuple[str, bool]] = {
    "model.embed_tokens.weight": ("embed_tokens", False),
    "model.norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}

_ATTN = {
    "input_layernorm.weight": ("ln_attn_in", False),
    "self_attn.q_proj.weight": ("q_proj", True),
    "self_attn.kv_a_proj_with_mqa.weight": ("kv_a_proj", True),
    "self_attn.kv_a_layernorm.weight": ("ln_kv_a", False),
    "self_attn.kv_b_proj.weight": ("kv_b_proj", True),
    "self_attn.o_proj.weight": ("o_proj", True),
    "post_attention_layernorm.weight": ("ln_mlp_in", False),
}
_DENSE = {
    "mlp.gate_proj.weight": ("gate_proj", True),
    "mlp.up_proj.weight": ("up_proj", True),
    "mlp.down_proj.weight": ("down_proj", True),
}
_EXPERTS = {
    "mlp.gate.weight": ("router", True),
    "mlp.gate.e_score_correction_bias": ("expert_bias", False),
}
_SHARED = {
    "mlp.shared_experts.gate_proj.weight": ("shared_gate", True),
    "mlp.shared_experts.up_proj.weight": ("shared_up", True),
    "mlp.shared_experts.down_proj.weight": ("shared_down", True),
}
_PER_EXPERT = {"gate_proj.weight": "w1", "up_proj.weight": "w3",
               "down_proj.weight": "w2"}


def layer_tensors(
    config: ModelConfig,
) -> Iterator[tuple[str, int, str, tuple[int, ...], bool]]:
    """Every per-layer checkpoint tensor this configuration holds:
    ``(HF key, run, leaf, index into the leaf, transpose?)``, as
    ``lfm2_moe.layer_tensors`` gives them."""
    for run, (_, ff, first, count) in enumerate(config.layer_groups()):
        for i in range(count):
            prefix = f"model.layers.{first + i}."
            table = dict(_ATTN)
            table.update(_DENSE if ff == "dense" else _EXPERTS)
            if ff == "experts" and config.shared_expert_intermediate_size:
                table.update(_SHARED)
            for suffix, (leaf, transpose) in table.items():
                yield prefix + suffix, run, leaf, (i,), transpose
            if ff == "experts":
                for e in range(config.experts_held):
                    for suffix, leaf in _PER_EXPERT.items():
                        yield (f"{prefix}mlp.experts."
                               f"{config.first_expert + e}.{suffix}",
                               run, leaf, (i, e), True)
