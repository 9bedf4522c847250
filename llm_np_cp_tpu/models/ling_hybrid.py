"""Ling-3.0 family binding (``model_type: ling_hybrid``, this package's own
name for it): checkpoint keys.  The stack is groups of
``layer_group_size`` layers, the last of each latent attention
(DeepSeek-V3's operator, no query latent) and the others delta-rule linear
attention (KDA); a leading dense block, then one run an expert layer
(``ModelConfig.layer_groups``).  A tensor's place is ``(run, leaf, index
into the leaf)`` as for ``lfm2_moe``.  All math lives in
``models/transformer.py`` (``kda_block``, ``latent_attention_block``,
``experts_block``), ``ops/kda.py`` and ``ops/moe.py``.

Names.  ASSUMED: there is no network here and the catalog row carries no
tensor index.  A latent layer and the feed-forwards are named as
``modeling_deepseek_v3.py`` names them (the latent operator IS that one);
a KDA layer's tensors as the ``fla`` library's ``KimiDeltaAttention`` names
them for a full-rank decay projection (``no_kda_lora``): ``{q,k,v}_proj``,
``{q,k,v}_conv1d.weight`` (depthwise ``[C, 1, K]``), ``f_proj`` (the
decay's), ``b_proj`` (beta), ``A_log``, ``dt_bias``, ``g_proj`` (the output
gate, one a head), ``o_norm`` and ``o_proj``.  A loader of real weights
checks every key it is given against this table and refuses what it does
not know, so a wrong guess is an error at load time, not a wrong model.
"""

from __future__ import annotations

from typing import Iterator

from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.models.deepseek_v3 import (
    _ATTN,
    _DENSE,
    _EXPERTS,
    _PER_EXPERT,
    _SHARED,
    TOP_KEY_MAP,
)

__all__ = ["TOP_KEY_MAP", "F32_LEAVES", "layer_tensors"]

# the decay's own scalars stay float32 whatever is served
F32_LEAVES = frozenset(("kda_A_log", "kda_dt_bias"))

_KDA = {
    "input_layernorm.weight": ("ln_attn_in", False),
    "self_attn.q_proj.weight": ("kda_q_proj", True),
    "self_attn.k_proj.weight": ("kda_k_proj", True),
    "self_attn.v_proj.weight": ("kda_v_proj", True),
    "self_attn.q_conv1d.weight": ("kda_q_conv", False),  # stored [C, 1, K]
    "self_attn.k_conv1d.weight": ("kda_k_conv", False),
    "self_attn.v_conv1d.weight": ("kda_v_conv", False),
    "self_attn.f_proj.weight": ("kda_a_proj", True),
    "self_attn.b_proj.weight": ("kda_beta_proj", True),
    "self_attn.A_log": ("kda_A_log", False),
    "self_attn.dt_bias": ("kda_dt_bias", False),
    "self_attn.g_proj.weight": ("kda_gate_proj", True),
    "self_attn.o_norm.weight": ("ln_kda_out", False),
    "self_attn.o_proj.weight": ("kda_out_proj", True),
    "post_attention_layernorm.weight": ("ln_mlp_in", False),
}


def layer_tensors(
    config: ModelConfig,
) -> Iterator[tuple[str, int, str, tuple[int, ...], bool]]:
    """Every per-layer checkpoint tensor this configuration holds:
    ``(HF key, run, leaf, index into the leaf, transpose?)``, as
    ``lfm2_moe.layer_tensors`` gives them."""
    for run, (op, ff, first, count) in enumerate(config.layer_groups()):
        for i in range(count):
            prefix = f"model.layers.{first + i}."
            table = dict(_KDA if op == "kda" else _ATTN)
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
