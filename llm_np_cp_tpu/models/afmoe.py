"""AFMoE family binding (``model_type: afmoe``; Trinity-Large): checkpoint
keys.  The stack is runs of like layers (``ModelConfig.layer_groups``): a
window layer (``"swa"``) and a global one (``"attn"``) hold tensors of the
same names and shapes and differ in what the forward does with them (RoPE
or none, a window or none) and in the pool's class; a tensor's place is
``(run, leaf, index into the leaf)`` as for ``lfm2_moe``.  All math lives
in ``models/transformer.py`` (``attention_block``: the gate, the q/k
norms, the post-norm; ``experts_block``: the post-norm of the sum) and
``ops/moe.py``.

Names (the family's modelling code as the configuration's ``assumed``
list reads it; no real checkpoint is loaded here):
``self_attn.{q,k,v,o}_proj``, ``self_attn.gate_proj`` (the output gate,
``[heads x head_dim, hidden]``), ``self_attn.{q,k}_norm`` (one weight of
``head_dim``), four norms a layer (``input_layernorm``,
``post_attention_layernorm``, ``pre_mlp_layernorm``,
``post_mlp_layernorm``); a dense block ``mlp.{gate,up,down}_proj``; an
expert block ``mlp.router.gate.weight``, ``mlp.expert_bias`` (float32,
selection only), ``mlp.shared_experts.{gate,up,down}_proj`` and
``mlp.experts.N.{gate,up,down}_proj``.  A configuration that holds a
share of the routed experts reads experts ``first_expert .. first_expert
+ held - 1`` of each layer and no other.
"""

from __future__ import annotations

from typing import Iterator

from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.models.deepseek_v3 import (
    _DENSE,
    _PER_EXPERT,
    _SHARED,
    TOP_KEY_MAP,
)

__all__ = ["TOP_KEY_MAP", "layer_tensors"]

_ATTN = {
    "input_layernorm.weight": ("ln_attn_in", False),
    "self_attn.q_proj.weight": ("q_proj", True),
    "self_attn.k_proj.weight": ("k_proj", True),
    "self_attn.v_proj.weight": ("v_proj", True),
    "self_attn.gate_proj.weight": ("attn_gate_proj", True),
    "self_attn.q_norm.weight": ("ln_q", False),
    "self_attn.k_norm.weight": ("ln_k", False),
    "self_attn.o_proj.weight": ("o_proj", True),
    "post_attention_layernorm.weight": ("ln_attn_out", False),
    "pre_mlp_layernorm.weight": ("ln_mlp_in", False),
    "post_mlp_layernorm.weight": ("ln_mlp_out", False),
}
_EXPERTS = {
    "mlp.router.gate.weight": ("router", True),
    "mlp.expert_bias": ("expert_bias", False),
}


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
            table.update(_DENSE if ff == "dense" else {**_EXPERTS, **_SHARED})
            for suffix, (leaf, transpose) in table.items():
                yield prefix + suffix, run, leaf, (i,), transpose
            if ff == "experts":
                for e in range(config.experts_held):
                    for suffix, leaf in _PER_EXPERT.items():
                        yield (f"{prefix}mlp.experts."
                               f"{config.first_expert + e}.{suffix}",
                               run, leaf, (i, e), True)
