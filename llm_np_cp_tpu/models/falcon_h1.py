"""Falcon-H1 family binding (``model_type: falcon_h1``): checkpoint keys.

Every block runs a Mamba-2 mixer beside GQA attention on one normed input,
so the stack is ONE run of like layers (``ModelConfig.layer_groups``) and
its params are the hybrid form's list with one stacked dict.
``layer_tensors`` is the table the loader (``utils/loading.py``) and its
inverse (``utils/synthetic.py``) walk, in the shape of
``models/lfm2_moe.layer_tensors``.  All math lives in
``models/transformer.py`` and ``ops/ssm.py``.

Published names (``modeling_falcon_h1.py``; ASSUMED here, there is no
network to read a checkpoint's index): a block has ``input_layernorm`` and
``pre_ff_layernorm``, ``self_attn.{q,k,v,o}_proj``, ``feed_forward.{gate,
up,down}_proj`` and ``mamba.{in_proj, conv1d, A_log, D, dt_bias, norm,
out_proj}`` (``conv1d.weight`` a depthwise Conv1d weight ``[C, 1, K]``,
``conv1d.bias`` beside it); the model ends in ``model.final_layernorm`` and
an untied ``lm_head``.
"""

from __future__ import annotations

from typing import Iterator

from llm_np_cp_tpu.config import ModelConfig

# HF key → (param name, transpose?)
TOP_KEY_MAP: dict[str, tuple[str, bool]] = {
    "model.embed_tokens.weight": ("embed_tokens", False),
    "model.final_layernorm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}

_LAYER = {
    "input_layernorm.weight": ("ln_attn_in", False),
    "self_attn.q_proj.weight": ("q_proj", True),
    "self_attn.k_proj.weight": ("k_proj", True),
    "self_attn.v_proj.weight": ("v_proj", True),
    "self_attn.o_proj.weight": ("o_proj", True),
    "mamba.in_proj.weight": ("ssm_in_proj", True),
    "mamba.conv1d.weight": ("ssm_conv", False),  # stored [C, 1, K]
    "mamba.conv1d.bias": ("ssm_conv_bias", False),
    "mamba.A_log": ("ssm_A_log", False),
    "mamba.D": ("ssm_D", False),
    "mamba.dt_bias": ("ssm_dt_bias", False),
    "mamba.norm.weight": ("ln_ssm", False),
    "mamba.out_proj.weight": ("ssm_out_proj", True),
    "pre_ff_layernorm.weight": ("ln_mlp_in", False),
    "feed_forward.gate_proj.weight": ("gate_proj", True),
    "feed_forward.up_proj.weight": ("up_proj", True),
    "feed_forward.down_proj.weight": ("down_proj", True),
}

# the recurrence's own scalars stay float32 whatever is served
F32_LEAVES = frozenset(("ssm_A_log", "ssm_D", "ssm_dt_bias"))


def layer_tensors(
    config: ModelConfig,
) -> Iterator[tuple[str, int, str, tuple[int, ...], bool]]:
    """Every per-layer checkpoint tensor: ``(HF key, run, leaf, index
    into the leaf, transpose?)``, as ``lfm2_moe.layer_tensors`` gives them."""
    for run, (_, _, first, count) in enumerate(config.layer_groups()):
        for i in range(count):
            for suffix, (leaf, transpose) in _LAYER.items():
                if leaf == "ssm_conv_bias" and not config.mamba_conv_bias:
                    continue
                yield (f"model.layers.{first + i}.{suffix}", run, leaf, (i,),
                       transpose)
