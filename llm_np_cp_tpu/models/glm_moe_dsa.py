"""GLM-5 binding (``model_type: glm_moe_dsa``): checkpoint keys.  The stack
is DeepSeek-V3's (``models/deepseek_v3.py``: a leading run of dense blocks,
then one run an expert layer, the same ``mlp.*`` names and the same share of
the routed experts) with a QUERY latent and a sparse-attention indexer in
every layer.  All math lives in ``models/transformer.py``
(``latent_attention_block``), ``ops/sparse_index.py`` and ``ops/moe.py``.

Published names (the DeepSeek-V3.2 indexer's, assumed for GLM-5:
benchmark/configs/glm-5-5l-ep16.json): ``self_attn.q_a_proj``,
``self_attn.q_a_layernorm``, ``self_attn.q_b_proj`` in place of ``q_proj``;
``self_attn.indexer.wq_b`` (reads the query latent), ``self_attn.indexer.wk``,
``self_attn.indexer.k_norm`` (a LayerNorm: weight and bias) and
``self_attn.indexer.weights_proj``.  The multi-token-prediction layer
(``num_nextn_predict_layers``: ``model.layers.<num_hidden_layers>.*``) takes
no part in the model's logits and is not read.
"""

from __future__ import annotations

from typing import Iterator

from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.models import deepseek_v3

TOP_KEY_MAP = deepseek_v3.TOP_KEY_MAP

# what replaces ``self_attn.q_proj``, and the indexer's four tensors
_QUERY_LATENT = {
    "self_attn.q_a_proj.weight": ("q_a_proj", True),
    "self_attn.q_a_layernorm.weight": ("ln_q_a", False),
    "self_attn.q_b_proj.weight": ("q_b_proj", True),
}
_INDEXER = {
    "self_attn.indexer.wq_b.weight": ("idx_q_proj", True),
    "self_attn.indexer.wk.weight": ("idx_k_proj", True),
    "self_attn.indexer.k_norm.weight": ("ln_idx_k", False),
    "self_attn.indexer.k_norm.bias": ("idx_k_norm_bias", False),
    "self_attn.indexer.weights_proj.weight": ("idx_w_proj", True),
}


def layer_tensors(
    config: ModelConfig,
) -> Iterator[tuple[str, int, str, tuple[int, ...], bool]]:
    """Every per-layer checkpoint tensor this configuration holds, as
    ``deepseek_v3.layer_tensors`` gives them."""
    for key, run, leaf, index, transpose in deepseek_v3.layer_tensors(config):
        if leaf != "q_proj":
            yield key, run, leaf, index, transpose
            continue
        prefix = key[:-len("self_attn.q_proj.weight")]
        for suffix, (name, tr) in {**_QUERY_LATENT, **_INDEXER}.items():
            yield prefix + suffix, run, name, index, tr
