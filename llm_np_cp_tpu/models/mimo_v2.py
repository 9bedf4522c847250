"""MiMo-V2 family binding (``model_type: mimo_v2``; MiMo-V2-Flash,
MiMo-V2.5): checkpoint keys.  The stack is a leading dense block and then
one run an expert layer (``ModelConfig.layer_groups``), a window layer
(``"swa"``) with k / v projections of its own kv heads and a sink logit a
query head; a tensor's place is ``(run, leaf, index into the leaf)`` as
for ``lfm2_moe``.  All math lives in ``models/transformer.py``
(``attention_block``, ``experts_block``), ``ops/attention.py`` (the sink)
and ``ops/moe.py``.

Published names (the family's modelling code; a checkpoint stores q, k
and v fused — ``attention_projection_layout: fused_qkv`` — and a loader
of real weights would cut ``self_attn.qkv_proj`` into the three below,
which no path here does: weights are seeded): ``self_attn.{q,k,v,o}_proj``,
``self_attn.attention_sink_bias`` (float32 ``[heads]``, window layers
only); a dense block ``mlp.{gate,up,down}_proj``; an expert block
``mlp.gate.weight`` (the router), ``mlp.gate.e_score_correction_bias``
(float32, selection only) and ``mlp.experts.N.{gate,up,down}_proj``.  A
configuration that holds a share of the routed experts reads experts
``first_expert .. first_expert + held - 1`` of each layer and no other.
"""

from __future__ import annotations

from typing import Iterator

from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.models.deepseek_v3 import (
    _DENSE,
    _EXPERTS,
    _PER_EXPERT,
    TOP_KEY_MAP,
)

__all__ = ["TOP_KEY_MAP", "F32_LEAVES", "layer_tensors"]

# kept float32 whatever is served: it joins float32 scores
F32_LEAVES = frozenset(("attn_sink",))

_ATTN = {
    "input_layernorm.weight": ("ln_attn_in", False),
    "self_attn.q_proj.weight": ("q_proj", True),
    "self_attn.k_proj.weight": ("k_proj", True),
    "self_attn.v_proj.weight": ("v_proj", True),
    "self_attn.o_proj.weight": ("o_proj", True),
    "post_attention_layernorm.weight": ("ln_mlp_in", False),
}
_SINK = {"self_attn.attention_sink_bias": ("attn_sink", False)}


def layer_tensors(
    config: ModelConfig,
) -> Iterator[tuple[str, int, str, tuple[int, ...], bool]]:
    """Every per-layer checkpoint tensor this configuration holds:
    ``(HF key, run, leaf, index into the leaf, transpose?)``, as
    ``lfm2_moe.layer_tensors`` gives them."""
    for run, (op, ff, first, count) in enumerate(config.layer_groups()):
        sink = config.attn_kind("window" if op == "swa" else "global").sink
        for i in range(count):
            prefix = f"model.layers.{first + i}."
            table = dict(_ATTN)
            table.update(_SINK if sink else {})
            table.update(_DENSE if ff == "dense" else _EXPERTS)
            for suffix, (leaf, transpose) in table.items():
                yield prefix + suffix, run, leaf, (i,), transpose
            if ff == "experts":
                for e in range(config.experts_held):
                    for suffix, leaf in _PER_EXPERT.items():
                        yield (f"{prefix}mlp.experts."
                               f"{config.first_expert + e}.{suffix}",
                               run, leaf, (i, e), True)
