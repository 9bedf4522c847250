"""LFM2-MoE family binding (``model_type: lfm2_moe``): checkpoint keys.

The stack is more than one kind of layer, so the param pytree is a list
of RUNS of like layers (``ModelConfig.layer_groups``) and a checkpoint
tensor's place is ``(run, leaf, index into the leaf)`` - not
``leaf[layer]`` as for the homogeneous families.  ``layer_tensors`` is the
ONE table of that: the loader (``utils/loading.py``) and its inverse
(``utils/synthetic.py``) both walk it.  All math lives in
``models/transformer.py`` and ``ops/moe.py``.

Published names (``modeling_lfm2_moe.py``): every block has an
``operator_norm`` and an ``ffn_norm``; a conv block ``conv.in_proj``,
``conv.conv`` (a depthwise Conv1d weight ``[H, 1, L]``) and
``conv.out_proj``; an attention block ``self_attn.{q,k,v}_proj``,
``self_attn.out_proj`` and the two head-dim norms ``q_layernorm`` /
``k_layernorm``; a dense feed-forward ``feed_forward.w1 / w3 / w2``; an
expert feed-forward ``feed_forward.experts.N.w1 / w3 / w2``,
``feed_forward.gate`` and the float32 buffer ``feed_forward.expert_bias``.
"""

from __future__ import annotations

from typing import Iterator

from llm_np_cp_tpu.config import ModelConfig

# HF key → (param name, transpose?)
TOP_KEY_MAP: dict[str, tuple[str, bool]] = {
    "model.embed_tokens.weight": ("embed_tokens", False),
    "model.embedding_norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}

_CONV = {
    "operator_norm.weight": ("ln_conv_in", False),
    "conv.in_proj.weight": ("in_proj", True),
    "conv.conv.weight": ("conv_filter", False),  # stored [H, 1, L]
    "conv.out_proj.weight": ("out_proj", True),
}
_ATTN = {
    "operator_norm.weight": ("ln_attn_in", False),
    "self_attn.q_proj.weight": ("q_proj", True),
    "self_attn.k_proj.weight": ("k_proj", True),
    "self_attn.v_proj.weight": ("v_proj", True),
    "self_attn.out_proj.weight": ("o_proj", True),
    "self_attn.q_layernorm.weight": ("ln_q", False),
    "self_attn.k_layernorm.weight": ("ln_k", False),
}
_DENSE = {
    "ffn_norm.weight": ("ln_mlp_in", False),
    "feed_forward.w1.weight": ("gate_proj", True),
    "feed_forward.w3.weight": ("up_proj", True),
    "feed_forward.w2.weight": ("down_proj", True),
}
_EXPERTS = {
    "ffn_norm.weight": ("ln_mlp_in", False),
    "feed_forward.gate.weight": ("router", True),
    "feed_forward.expert_bias": ("expert_bias", False),
}
_PER_EXPERT = {"w1.weight": "w1", "w3.weight": "w3", "w2.weight": "w2"}


def layer_tensors(
    config: ModelConfig,
) -> Iterator[tuple[str, int, str, tuple[int, ...], bool]]:
    """Every per-layer checkpoint tensor: ``(HF key, run, leaf, index
    into the leaf, transpose?)``.  ``params["layers"][run][leaf][index]``
    is the tensor as the model multiplies by it (Linear weights
    transposed to ``(in, out)``; the conv filter ``[H, L]``, stored
    ``[H, 1, L]``)."""
    for run, (op, ff, first, count) in enumerate(config.layer_groups()):
        for i in range(count):
            prefix = f"model.layers.{first + i}."
            table = dict(_CONV if op == "conv" else _ATTN)
            table.update(_DENSE if ff == "dense" else _EXPERTS)
            for suffix, (leaf, transpose) in table.items():
                if leaf == "expert_bias" and not config.use_expert_bias:
                    continue
                yield prefix + suffix, run, leaf, (i,), transpose
            if ff == "experts":
                for e in range(config.num_experts):
                    for suffix, leaf in _PER_EXPERT.items():
                        yield (f"{prefix}feed_forward.experts.{e}.{suffix}",
                               run, leaf, (i, e), True)
