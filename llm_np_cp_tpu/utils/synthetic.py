"""Seeded synthetic checkpoints in the HF directory layout.

What a run with no network loads instead of a download: ``config.json``,
safetensors shards with an index, and a tokenizer — everything
``utils.loading.load_model`` needs to start — generated from a seed, so
the REAL load → place-on-device path runs at a model's published size
(``chip_smoke.py``; the loader tests use the same writer at toy sizes).

Key names are the inverse of the loader's own key maps
(``models/<family>.py``), so a tensor the loader would not find cannot
be written here.
"""

from __future__ import annotations

import json
from concurrent.futures import ThreadPoolExecutor
from pathlib import Path
from typing import Any, Mapping

import ml_dtypes
import numpy as np
from safetensors.numpy import save_file

from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.models.transformer import param_shapes
from llm_np_cp_tpu.utils.loading import (
    CONV1D_LEAVES,
    _key_maps,
    hybrid_family,
)

# elements drawn per RNG call — the unit of parallelism, so one large
# tensor (a 233M-element embedding table) still uses every worker
_CHUNK = 1 << 22


def _write_json(path: Path, obj: Any) -> None:
    with open(path, "w") as f:
        json.dump(obj, f)


def hf_config_dict(config: ModelConfig) -> dict[str, Any]:
    """The ``config.json`` mapping ``ModelConfig.from_hf_dict`` reads back
    into an equal config."""
    d: dict[str, Any] = {
        "model_type": config.model_type,
        "vocab_size": config.vocab_size,
        "hidden_size": config.hidden_size,
        "intermediate_size": config.intermediate_size,
        "num_hidden_layers": config.num_hidden_layers,
        "num_attention_heads": config.num_attention_heads,
        "num_key_value_heads": config.num_key_value_heads,
        "head_dim": config.head_dim,
        "max_position_embeddings": config.max_position_embeddings,
        "rope_theta": config.rope_theta,
        "rms_norm_eps": config.rms_norm_eps,
        "hidden_act": config.hidden_act,
        "tie_word_embeddings": config.tie_word_embeddings,
        # what a published checkpoint carries; AutoTokenizer reads it to
        # decide the directory is not a pre-fix Mistral tokenizer
        "transformers_version": "4.40.1",
    }
    if config.model_type != "qwen2":  # qwen2 implies its bias pattern
        d["attention_bias"] = config.attention_bias
    if config.mlp_bias:
        d["mlp_bias"] = True
    if config.rope_scaling_type == "llama3":
        d["rope_scaling"] = {
            "rope_type": "llama3",
            "factor": config.rope_scaling_factor,
            "low_freq_factor": config.rope_scaling_low_freq_factor,
            "high_freq_factor": config.rope_scaling_high_freq_factor,
            "original_max_position_embeddings":
                config.rope_scaling_original_max_position,
        }
    if config.model_type == "lfm2_moe":
        d.pop("rms_norm_eps")
        d.pop("attention_bias")
        d.update(
            norm_eps=config.rms_norm_eps,
            layer_types=list(config.layer_types),
            conv_L_cache=config.conv_L_cache,
            conv_bias=config.conv_bias,
            num_dense_layers=config.num_dense_layers,
            num_experts=config.num_experts,
            num_experts_per_tok=config.num_experts_per_tok,
            moe_intermediate_size=config.moe_intermediate_size,
            use_expert_bias=config.use_expert_bias,
            norm_topk_prob=config.norm_topk_prob,
            routed_scaling_factor=config.routed_scaling_factor,
        )
    if config.model_type == "falcon_h1":
        d.update(
            mamba_d_ssm=config.mamba_d_ssm,
            mamba_n_heads=config.mamba_n_heads,
            mamba_d_head=config.mamba_d_head,
            mamba_d_state=config.mamba_d_state,
            mamba_n_groups=config.mamba_n_groups,
            mamba_d_conv=config.mamba_d_conv,
            mamba_conv_bias=config.mamba_conv_bias,
            mamba_chunk_size=config.mamba_chunk_size,
            mlp_multipliers=list(config.mlp_multipliers),
            ssm_multipliers=list(config.ssm_multipliers),
            **{k: getattr(config, k) for k in (
                "embedding_multiplier", "lm_head_multiplier",
                "key_multiplier", "attention_in_multiplier",
                "attention_out_multiplier", "ssm_in_multiplier",
                "ssm_out_multiplier")},
        )
        if config.init_ssm_in_proj_std is not None:
            d["init_ssm_in_proj_std"] = config.init_ssm_in_proj_std
    if config.model_type in ("deepseek_v3", "glm_moe_dsa"):
        moe_i = config.moe_intermediate_size
        d.update(
            kv_lora_rank=config.kv_lora_rank,
            q_lora_rank=config.q_lora_rank,
            qk_nope_head_dim=config.qk_nope_head_dim,
            qk_rope_head_dim=config.qk_rope_head_dim,
            v_head_dim=config.v_head_dim,
            rope_interleave=config.rope_interleave, rope_scaling=None,
            n_routed_experts=config.experts_held,
            router_experts=config.num_experts,
            first_expert=config.first_expert,
            n_shared_experts=(
                (config.shared_expert_intermediate_size or 0) // moe_i),
            num_experts_per_tok=config.num_experts_per_tok,
            first_k_dense_replace=config.num_dense_layers,
            moe_intermediate_size=moe_i, moe_layer_freq=1,
            routed_scaling_factor=config.routed_scaling_factor,
            scoring_func="sigmoid", topk_method="noaux_tc",
            n_group=config.n_group, topk_group=config.topk_group,
            norm_topk_prob=config.norm_topk_prob,
        )
        if config.init_expert_out_std is not None:
            d["init_expert_out_std"] = config.init_expert_out_std
        if config.has_indexer:
            d.update(
                index_topk=config.index_topk,
                index_n_heads=config.index_n_heads,
                index_head_dim=config.index_head_dim,
                indexer_rope_interleave=config.indexer_rope_interleave)
    if config.model_type == "mimo_v2":
        depth = config.num_hidden_layers
        d.pop("rms_norm_eps")
        d.update(
            layernorm_epsilon=config.rms_norm_eps,
            hybrid_layer_pattern=[
                int(config.layer_is_sliding(i)) for i in range(depth)],
            moe_layer_freq=[
                int(i >= config.num_dense_layers) for i in range(depth)],
            sliding_window=config.sliding_window,
            swa_num_key_value_heads=config.swa_num_key_value_heads,
            swa_rope_theta=config.swa_rope_theta,
            add_swa_attention_sink_bias=config.swa_sink,
            add_full_attention_sink_bias=False,
            v_head_dim=config.value_dim,
            partial_rotary_factor=(
                (config.rope_dim or config.head_dim) + 0.5) / config.head_dim,
            attention_value_scale=config.attention_value_scale,
            n_routed_experts=config.experts_held,
            router_experts=config.num_experts,
            first_expert=config.first_expert,
            n_shared_experts=None,
            num_experts_per_tok=config.num_experts_per_tok,
            moe_intermediate_size=config.moe_intermediate_size,
            routed_scaling_factor=config.routed_scaling_factor,
            scoring_func="sigmoid", topk_method="noaux_tc",
            n_group=1, topk_group=1,
            norm_topk_prob=config.norm_topk_prob,
        )
        if config.init_expert_out_std is not None:
            d["init_expert_out_std"] = config.init_expert_out_std
    if config.model_type == "afmoe":
        depth = config.num_hidden_layers
        d.update(
            layer_types=[
                "sliding_attention" if config.layer_is_sliding(i)
                else "full_attention" for i in range(depth)],
            sliding_window=config.sliding_window,
            mup_enabled=config.scale_embeddings,
            num_dense_layers=config.num_dense_layers,
            num_experts=config.experts_held,
            router_experts=config.num_experts,
            first_expert=config.first_expert,
            num_shared_experts=1,
            num_experts_per_tok=config.num_experts_per_tok,
            moe_intermediate_size=config.moe_intermediate_size,
            route_norm=True, route_scale=config.routed_scaling_factor,
            score_func="sigmoid", rope_scaling=None,
            n_group=1, num_expert_groups=1, topk_group=1,
            num_limited_groups=1,
        )
        if config.init_expert_out_std is not None:
            d["init_expert_out_std"] = config.init_expert_out_std
    if config.model_type == "brumby":
        d.update(rope_scaling=None, use_sliding_window=False,
                 sliding_window=None,
                 max_window_layers=config.num_hidden_layers,
                 retention_degree=config.retention_degree)
    if config.model_type == "ling_hybrid":
        moe_i = config.moe_intermediate_size
        d.update(
            head_dim=config.kda_head_dim,
            layer_group_size=config.layer_group_size,
            short_conv_kernel_size=config.kda_conv_taps,
            kda_lower_bound=config.kda_lower_bound, kda_safe_gate=True,
            no_kda_lora=True, use_qk_norm=True,
            kv_lora_rank=config.kv_lora_rank, q_lora_rank=None,
            qk_nope_head_dim=config.qk_nope_head_dim,
            qk_rope_head_dim=config.qk_rope_head_dim,
            rotary_dim=config.qk_rope_head_dim,
            v_head_dim=config.v_head_dim,
            num_experts=config.experts_held,
            router_experts=config.num_experts,
            first_expert=config.first_expert,
            moe_shared_expert_intermediate_size=(
                config.shared_expert_intermediate_size or 0),
            num_experts_per_tok=config.num_experts_per_tok,
            first_k_dense_replace=config.num_dense_layers,
            moe_intermediate_size=moe_i,
            moe_router_enable_expert_bias=config.use_expert_bias,
            routed_scaling_factor=config.routed_scaling_factor,
            score_function="sigmoid",
            n_group=config.n_group, topk_group=config.topk_group,
            norm_topk_prob=config.norm_topk_prob,
        )
        for key in ("init_kda_log_decay", "init_expert_out_std"):
            if getattr(config, key) is not None:
                d[key] = getattr(config, key)
    if config.model_type == "gemma2":
        d.update(
            final_logit_softcapping=config.final_logit_softcapping,
            attn_logit_softcapping=config.attn_logit_softcapping,
            sliding_window=config.sliding_window,
            query_pre_attn_scalar=config.query_pre_attn_scalar,
            hidden_activation=config.hidden_act,
        )
    return d


def hf_tensor_shapes(config: ModelConfig) -> dict[str, tuple[int, ...]]:
    """HF tensor name → shape AS STORED (Linear weights ``[out, in]``),
    for every tensor the loader will ask for."""
    layer_map, top_map = _key_maps(config)
    shapes = param_shapes(config)

    def stored(shape: tuple[int, ...], transpose: bool) -> tuple[int, ...]:
        return shape[::-1] if transpose else shape

    out: dict[str, tuple[int, ...]] = {}
    for hf_key, (name, transpose) in top_map.items():
        if name in shapes and not isinstance(shapes[name], dict):
            out[hf_key] = stored(shapes[name], transpose)
    if config.is_hybrid:
        for key, run, leaf, index, transpose in hybrid_family(config).layer_tensors(config):
            shape = shapes["layers"][run][leaf][len(index):]
            if leaf in CONV1D_LEAVES:  # a depthwise Conv1d weight [H, 1, L]
                shape = (shape[0], 1, shape[1])
            out[key] = stored(shape, transpose)
        return out
    for suffix, (name, transpose) in layer_map.items():
        if name in shapes["layers"]:
            per_layer = shapes["layers"][name][1:]
            for i in range(config.num_hidden_layers):
                out[f"model.layers.{i}.{suffix}"] = stored(per_layer, transpose)
    return out


def hf_state_dict(params: Mapping[str, Any],
                  config: ModelConfig) -> dict[str, np.ndarray]:
    """A stacked host param pytree → HF-named tensors as stored
    (Linear weights back to ``[out, in]``): the loader's inverse."""
    layer_map, top_map = _key_maps(config)
    out: dict[str, np.ndarray] = {}
    for hf_key, (name, transpose) in top_map.items():
        if name in params:
            t = params[name]
            out[hf_key] = np.ascontiguousarray(t.T if transpose else t)
    if config.is_hybrid:
        for key, run, leaf, index, transpose in hybrid_family(config).layer_tensors(config):
            t = np.asarray(params["layers"][run][leaf][index])
            if leaf in CONV1D_LEAVES:
                t = t[:, None, :]
            out[key] = np.ascontiguousarray(t.T if transpose else t)
        return out
    for suffix, (name, transpose) in layer_map.items():
        if name in params["layers"]:
            for i, t in enumerate(params["layers"][name]):
                out[f"model.layers.{i}.{suffix}"] = np.ascontiguousarray(
                    t.T if transpose else t)
    return out


def _fill_chunk(flat: np.ndarray, start: int, seed: int, index: int) -> None:
    """N(0, 0.02²) into ``flat[start:start + _CHUNK]`` from a generator
    keyed on (seed, tensor index, chunk start) — the values do not
    depend on which worker ran the chunk."""
    part = np.empty(min(_CHUNK, flat.size - start), np.float32)
    np.random.default_rng([seed, index, start]).standard_normal(
        out=part, dtype=np.float32)
    part *= 0.02
    if flat.dtype == ml_dtypes.bfloat16:
        # round-to-nearest-even on the bit pattern: ml_dtypes' own cast
        # holds the GIL, which serializes the workers on a 1.5B-element
        # checkpoint (measured: 43 s → the RNG's own cost)
        bits = part.view(np.uint32)
        bits += 0x7FFF + ((bits >> 16) & 1)
        flat.view(np.uint16)[start:start + part.size] = bits >> 16
    else:
        flat[start:start + part.size] = part


def write_hf_checkpoint(
    out_dir: str | Path, config: ModelConfig,
    tensors: Mapping[str, np.ndarray], *, shards: int = 2,
    extra_config: Mapping[str, Any] | None = None,
) -> None:
    """``config.json`` + ``tensors`` split over ``shards`` safetensors
    files with an index (``shards=0``: config only)."""
    out_dir = Path(out_dir)
    keys = sorted(tensors)
    if shards > 0:
        per = -(-len(keys) // shards)
        weight_map = {}
        for si in range(shards):
            chunk = keys[si * per:(si + 1) * per]
            if not chunk:
                continue
            fn = f"model-{si:05d}-of-{shards:05d}.safetensors"
            save_file({k: tensors[k] for k in chunk}, str(out_dir / fn))
            weight_map.update({k: fn for k in chunk})
        _write_json(out_dir / "model.safetensors.index.json",
                    {"weight_map": weight_map})
    _write_json(out_dir / "config.json",
                {**hf_config_dict(config), **(extra_config or {})})


def write_random_checkpoint(
    out_dir: str | Path, config: ModelConfig, *, seed: int = 0,
    dtype: Any = ml_dtypes.bfloat16, layers_per_shard: int = 4,
    workers: int = 8,
) -> int:
    """A full random checkpoint for ``config``, streamed shard by shard
    (peak host memory: one shard).  Returns the bytes written."""
    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    np_dtype = np.dtype(dtype)
    shapes = hf_tensor_shapes(config)
    index = {name: i for i, name in enumerate(sorted(shapes))}

    def shard_of(name: str) -> int:
        if not name.startswith("model.layers."):
            return 0
        return 1 + int(name.split(".")[2]) // layers_per_shard

    groups: dict[int, list[str]] = {}
    for name in shapes:
        groups.setdefault(shard_of(name), []).append(name)
    weight_map: dict[str, str] = {}
    total = 0
    gamma = 0.0 if config.rms_norm_unit_offset else 1.0
    with ThreadPoolExecutor(workers) as pool:
        for si, names in sorted(groups.items()):
            # same distribution as models.transformer.init_params: norm
            # gammas at identity, everything else N(0, 0.02²)
            arrays = {
                n: (np.full(shapes[n], gamma, np_dtype)
                    if n.endswith("norm.weight")
                    else np.empty(shapes[n], np_dtype))
                for n in names
            }
            chunks = [
                (a.reshape(-1), start, seed, index[n])
                for n, a in arrays.items() if not n.endswith("norm.weight")
                for start in range(0, a.size, _CHUNK)
            ]
            list(pool.map(lambda c: _fill_chunk(*c), chunks))
            fn = f"model-{si:05d}-of-{len(groups):05d}.safetensors"
            save_file(arrays, str(out_dir / fn))
            weight_map.update({n: fn for n in names})
            total += sum(a.nbytes for a in arrays.values())
    _write_json(out_dir / "model.safetensors.index.json",
                {"metadata": {"total_size": total}, "weight_map": weight_map})
    _write_json(out_dir / "config.json", hf_config_dict(config))
    return total


def write_id_tokenizer(out_dir: str | Path, vocab_size: int) -> None:
    """A tokenizer ``AutoTokenizer.from_pretrained`` loads offline: one
    word ``t<i>`` per token id, whitespace-split.  Decoding any id the
    model can emit yields text, which is all the serve path asks of it
    when prompts arrive as token-id lists."""
    from tokenizers import Tokenizer, models, pre_tokenizers

    out_dir = Path(out_dir)
    out_dir.mkdir(parents=True, exist_ok=True)
    vocab = {f"t{i}": i for i in range(vocab_size)}
    tok = Tokenizer(models.WordLevel(vocab, unk_token="t0"))
    tok.pre_tokenizer = pre_tokenizers.WhitespaceSplit()
    tok.save(str(out_dir / "tokenizer.json"))
    _write_json(out_dir / "tokenizer_config.json",
                {"tokenizer_class": "PreTrainedTokenizerFast",
                 "unk_token": "t0", "clean_up_tokenization_spaces": False})

