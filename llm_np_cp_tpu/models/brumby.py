"""Brumby family binding (``model_type: brumby``; Brumby-14B-Base): checkpoint
keys.  A Qwen3-14B skeleton with every attention layer replaced by power
retention: ONE run of like layers (``ModelConfig.layer_groups``), a tensor's
place ``(run, leaf, index into the leaf)`` as for ``lfm2_moe``.  All math
lives in ``models/transformer.py`` (``retention_block``, ``ff_block``) and
``ops/retention.py``.

Names.  Qwen3's (``self_attn.{q,k,v,o}_proj``, ``self_attn.{q,k}_norm`` of
``head_dim``, ``input_layernorm``, ``post_attention_layernorm``,
``mlp.{gate,up,down}_proj``) plus ``self_attn.g_proj.weight``, the forget
gate's ``[kv heads, hidden]`` — ASSUMED: there is no network here and the
catalog row carries no tensor index.  A loader of real weights checks every
key it is given against this table and refuses what it does not know, so a
wrong guess is an error at load time, not a wrong model.
"""

from __future__ import annotations

from typing import Iterator

from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.models.deepseek_v3 import _DENSE, TOP_KEY_MAP

__all__ = ["TOP_KEY_MAP", "layer_tensors"]

_RETENTION = {
    "input_layernorm.weight": ("ln_attn_in", False),
    "self_attn.q_proj.weight": ("q_proj", True),
    "self_attn.k_proj.weight": ("k_proj", True),
    "self_attn.v_proj.weight": ("v_proj", True),
    "self_attn.g_proj.weight": ("ret_gate_proj", True),
    "self_attn.q_norm.weight": ("ln_q", False),
    "self_attn.k_norm.weight": ("ln_k", False),
    "self_attn.o_proj.weight": ("o_proj", True),
    "post_attention_layernorm.weight": ("ln_mlp_in", False),
    **_DENSE,
}


def layer_tensors(
    config: ModelConfig,
) -> Iterator[tuple[str, int, str, tuple[int, ...], bool]]:
    """Every per-layer checkpoint tensor this configuration holds:
    ``(HF key, run, leaf, index into the leaf, transpose?)``, as
    ``lfm2_moe.layer_tensors`` gives them."""
    for run, (_, _, first, count) in enumerate(config.layer_groups()):
        for i in range(count):
            for suffix, (leaf, transpose) in _RETENTION.items():
                yield (f"model.layers.{first + i}.{suffix}", run, leaf, (i,),
                       transpose)
