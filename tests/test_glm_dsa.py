"""GLM-5 (``model_type: glm_moe_dsa``): the configuration as published, the
plain forward against the benchmark's independent float32 reference with the
indexer's ``index_topk`` below and above the sequence length, the query
latent, the selection forced to "all" against dense latent attention, the held
share of the routed experts, the checkpoint's tensor names.  The served path
is tests/test_glm_dsa_serve.py, the indexer's operations
tests/test_sparse_index.py."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.cache import KVCache
from llm_np_cp_tpu.config import KNOWN_MODEL_TYPES, ModelConfig, tiny_config
from llm_np_cp_tpu.models import forward, init_params
from llm_np_cp_tpu.utils.synthetic import hf_config_dict

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_glm_dsa as ref  # noqa: E402

# the catalog row, verbatim (model-configs/architectures.jsonl; the published
# config.json of zai-org/GLM-5)
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "head_dim": 64, "hidden_size": 6144,
    "index_head_dim": 128, "index_n_heads": 32, "index_topk": 2048,
    "indexer_rope_interleave": True, "intermediate_size": 12288,
    "kv_lora_rank": 512, "max_position_embeddings": 202752,
    "moe_intermediate_size": 2048, "moe_layer_freq": 1,
    "model_type": "glm_moe_dsa", "n_group": 1, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 64,
    "num_experts_per_tok": 8, "num_hidden_layers": 78,
    "num_key_value_heads": 64, "num_nextn_predict_layers": 1,
    "q_lora_rank": 2048, "qk_head_dim": 256, "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64, "rms_norm_eps": 1e-05, "rope_interleave": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 256, "vocab_size": 154880,
}
TOL = 2e-5  # float32 against float32 at the highest matmul precision

jforward = jax.jit(forward, static_argnums=(2,))


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("glm_moe_dsa")
    hf = hf_config_dict(cfg)
    assert ModelConfig.from_hf_dict(hf) == cfg
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, hf, params


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(1, 256, n)


def _gap(got, want) -> float:
    """Largest logit difference as a share of the reference's spread."""
    want = np.asarray(want)
    spread = float((want.max(-1) - want.mean(-1)).mean())
    return float(np.abs(np.asarray(got) - want).max()) / spread


def _forward(params, cfg, ids):
    with jax.default_matmul_precision("highest"):
        return jforward(params, jnp.asarray(ids)[None], cfg)[0][0]


# ----------------------------------------------------------------------
# configuration
# ----------------------------------------------------------------------

def test_from_hf_dict_reads_the_catalog_row_verbatim():
    assert "glm_moe_dsa" in KNOWN_MODEL_TYPES
    cfg = ModelConfig.from_hf_dict(PUBLISHED)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.vocab_size,
            cfg.num_hidden_layers) == (6144, 64, 154880, 78)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.head_dim) == (
        2048, 512, 192, 64, 256, 64)
    assert (cfg.index_n_heads, cfg.index_head_dim, cfg.index_topk,
            cfg.indexer_rope_interleave, cfg.has_indexer) == (
        32, 128, 2048, True, True)
    assert cfg.attn_scale == 256 ** -0.5 and cfg.rope_interleave
    assert cfg.rope_theta == 1e6 and cfg.rms_norm_eps == 1e-5
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.num_dense_layers, cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size, cfg.routed_scaling_factor) == (
        256, 256, 8, 3, 2048, 2048, 2.5)
    assert cfg.layer_groups()[0] == ("latent", "dense", 0, 3)
    assert len(cfg.layer_groups()) == 1 + 75 and not cfg.tie_word_embeddings
    # a token leaves a latent row and an index key a layer
    assert cfg.kv_token_shapes() == {"k": (576,)}
    assert cfg.kv_bytes_per_token(2) == 78 * (576 + 128) * 2


@pytest.mark.parametrize("change, match", [
    (dict(rope_scaling={"type": "yarn", "factor": 4}), "rope_scaling"),
    (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}), "rope_scaling"),
    (dict(n_group=8, topk_group=4), "n_group != 1"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(ep_size=8), "ep_size != 1"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(index_topk=0), "indexer"),
    (dict(router_experts=64, first_expert=0), "are not among the router's"),
    (dict(model_type="deepseek_v3"), "q_lora_rank"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_has_no_equations_is_refused_by_its_key(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_dict(dict(PUBLISHED, **change))


def test_the_benchmark_configuration_is_the_row_cut_to_one_chips_share():
    path = ROOT / "benchmark" / "configs" / "glm-5-5l-ep16.json"
    file = json.loads(path.read_text())
    cut = set(file["reduced"])
    assert cut == {"num_hidden_layers", "first_k_dense_replace",
                   "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        assert (file[key] == value) != (key in cut), key
    cfg = ModelConfig.from_hf_dict(file)
    assert (cfg.num_hidden_layers, cfg.num_dense_layers, cfg.num_experts,
            cfg.experts_held, cfg.first_expert, cfg.vocab_size) == (
        5, 1, 256, 16, 0, 19360)
    assert cfg.layer_groups() == (("latent", "dense", 0, 1),) + tuple(
        ("latent", "experts", i, 1) for i in range(1, 5))


# ----------------------------------------------------------------------
# the forward
# ----------------------------------------------------------------------

@pytest.mark.parametrize("topk", [12, 64], ids=["selecting", "dense"])
def test_forward_matches_reference(tiny, topk):
    """``index_topk`` 12 (a 40-token sequence selects from token 12 on) and
    64 (nothing is ever cut)."""
    cfg, hf, params = tiny
    cfg = dataclasses.replace(cfg, index_topk=topk)
    hf = dict(hf, index_topk=topk)
    ids = _ids(40)
    want, picked = ref.forward(params, hf, ids, return_selection=True)
    assert _gap(_forward(params, cfg, ids), want) < TOL
    assert np.array_equal(np.asarray(picked.sum(-1))[0],
                          np.minimum(np.arange(40) + 1, topk))


def test_attention_over_query_blocks_is_attention(tiny):
    cfg, hf, params = tiny
    ids = _ids(37, seed=3)
    want = ref.forward(params, hf, ids)
    assert _gap(ref.forward(params, hf, ids, q_block=8), want) < TOL
    # the program's blocks: a sequence at a time, 16 queries at a time
    from llm_np_cp_tpu.models import transformer

    def blocked(*a, **kw):
        return block(*a, **dict(kw, q_block=16))

    block = transformer.latent_attention_block
    try:
        transformer.latent_attention_block = blocked
        with jax.default_matmul_precision("highest"):
            got = forward(params, jnp.asarray(np.stack([ids, ids[::-1]])), cfg)[0]
    finally:
        transformer.latent_attention_block = block
    assert _gap(got[0], want) < TOL
    assert _gap(got[1], ref.forward(params, hf, ids[::-1])) < TOL


def test_with_the_selection_forced_to_all_the_block_is_dense_latent_attention(
        tiny, monkeypatch):
    """The layer with ``index_topk`` past the sequence == ``deepseek_v3``'s
    dense latent attention block (what ``kanana`` runs) of the same weights,
    handed the query the latent gives in place of its ``q_proj``'s."""
    from llm_np_cp_tpu.models import transformer
    from llm_np_cp_tpu.ops.norms import rms_norm
    from llm_np_cp_tpu.ops.rope import rope_cos_sin

    cfg, _, params = tiny
    cfg = dataclasses.replace(cfg, index_topk=1 << 20)
    w = {k: v[0] for k, v in params["layers"][0].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 19, 64), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(19)[None], (2, 19))
    cos, sin = rope_cos_sin(pos, cfg, dtype=jnp.float32)
    mask = jnp.tril(jnp.ones((19, 19), bool))[None]
    block = transformer.latent_attention_block
    with jax.default_matmul_precision("highest"):
        got, rows = block(w, x, config=cfg, cos=cos, sin=sin, mask=mask)
        picky, _ = block(w, x, config=dataclasses.replace(cfg, index_topk=4),
                         cos=cos, sin=sin, mask=mask)
        h = transformer.input_norm(w, x, cfg)
        q = rms_norm(h @ w["q_a_proj"], w["ln_q_a"],
                     eps=cfg.rms_norm_eps) @ w["q_b_proj"]
        # the dense block, its q_proj a marker that answers with ``q``
        dense_cfg = dataclasses.replace(
            tiny_config("deepseek_v3", v_head_dim=cfg.v_head_dim),
            rms_norm_eps=cfg.rms_norm_eps)
        dense_w = {k: v for k, v in w.items() if k in (
            "ln_attn_in", "kv_a_proj", "ln_kv_a", "kv_b_proj", "o_proj")}
        dense_w["q_proj"] = marker = jnp.zeros((64, q.shape[-1]))
        real = transformer._project
        monkeypatch.setattr(
            transformer, "_project",
            lambda a, m, out_dtype=None: q if m is marker else real(a, m, out_dtype))
        want, dense_rows = block(dense_w, x, config=dense_cfg, cos=cos,
                                 sin=sin, mask=mask)
    # the cache rows are the dense layer's, the index keys lie beside them
    assert isinstance(rows, tuple) and rows[1].shape == (2, 19, cfg.index_head_dim)
    assert np.array_equal(rows[0], dense_rows)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.abs(picky - want).max()) > 1e-3  # selecting changes it


def test_the_query_latent_is_normed_between_its_two_projections(tiny):
    cfg, hf, params = tiny
    ids = _ids(24, seed=5)
    want = ref.forward(params, hf, ids)
    # gammas of 1 would hide a norm's WEIGHT: scale them
    scaled = jax.tree.map(lambda a: a, params)
    scaled["layers"] = [dict(g, ln_q_a=g["ln_q_a"] * 1.5) for g in params["layers"]]
    moved = ref.forward(scaled, hf, ids)
    assert _gap(moved, want) > 1e-3
    assert _gap(_forward(scaled, cfg, ids), moved) < TOL


@pytest.mark.parametrize("control", sorted(ref.VARIANTS))
def test_a_broken_layer_fails_the_float32_tolerance(tiny, control):
    cfg, hf, params = tiny
    ids = _ids(40, seed=7)
    got = _forward(params, cfg, ids)
    assert _gap(got, ref.forward(params, hf, ids)) < TOL
    assert _gap(got, ref.forward(params, hf, ids, variant=control)) > 100 * TOL


def test_the_offline_cache_holds_no_index_keys_and_says_so(tiny):
    cfg, _, params = tiny
    cache = KVCache.init(cfg, 1, 32, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="no index keys"):
        forward(params, jnp.asarray(_ids(8))[None], cfg, cache)


# ----------------------------------------------------------------------
# one chip's share of the experts
# ----------------------------------------------------------------------

def test_sixteen_shares_of_the_routed_experts_and_the_shared_expert_once_add_up():
    """16 holders of 16 experts each of a router 256 wide (the deployment's
    split at toy widths), every one computing the layer with its share: the
    routed parts summed + the shared expert ONCE == the uncut layer."""
    from llm_np_cp_tpu.models.transformer import experts_block
    from llm_np_cp_tpu.ops.activations import ACT2FN

    cfg = tiny_config("glm_moe_dsa", num_experts=256, num_experts_per_tok=8)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    w = {k: v[0] for k, v in params["layers"][1].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64), jnp.float32)
    act = ACT2FN["silu"]
    with jax.default_matmul_precision("highest"):
        whole, chosen, load = experts_block(w, x, config=cfg, act=act)
        routed_only, _, _ = experts_block(
            {k: v for k, v in w.items() if not k.startswith("shared_")}, x,
            config=cfg, act=act)
        shared = whole - routed_only  # the shared expert's term
        total, loads = jnp.zeros_like(x), []
        for first in range(0, 256, 16):
            part_cfg = dataclasses.replace(
                cfg, num_experts_held=16, first_expert=first)
            part_w = dict(w, **{k: w[k][first:first + 16]
                                for k in ("w1", "w3", "w2")})
            out, part_chosen, part_load = experts_block(
                part_w, x, config=part_cfg, act=act)
            assert np.array_equal(part_chosen, chosen)  # one router
            loads.append(part_load)
            total = total + (out - x - shared)
    assert np.array_equal(jnp.concatenate(loads), load)
    assert int(load.sum()) == 2 * 9 * 8  # every (token, expert) pair once
    assert float(jnp.abs(total + x + shared - whole).max()) < 1e-5
    assert float(jnp.abs(shared).max()) > 1e-3


def test_a_held_share_is_the_reference_with_the_same_share(tiny):
    cfg, _, _ = tiny
    part = dataclasses.replace(cfg, num_experts_held=4, first_expert=2)
    part_hf = hf_config_dict(part)
    assert (part_hf["n_routed_experts"], part_hf["router_experts"],
            part_hf["first_expert"]) == (4, 8, 2)
    assert ModelConfig.from_hf_dict(part_hf) == part
    params = init_params(jax.random.PRNGKey(2), part, dtype=jnp.float32)
    assert params["layers"][1]["w1"].shape == (1, 4, 64, 32)
    ids = _ids(24, seed=9)
    assert _gap(_forward(params, part, ids), ref.forward(params, part_hf, ids)) < TOL


# ----------------------------------------------------------------------
# checkpoint names
# ----------------------------------------------------------------------

def test_hf_key_map_round_trip(tiny, tmp_path):
    from llm_np_cp_tpu.models import glm_moe_dsa
    from llm_np_cp_tpu.utils.loading import load_params
    from llm_np_cp_tpu.utils.synthetic import (
        hf_state_dict,
        write_hf_checkpoint,
    )

    cfg, _, _ = tiny
    part = dataclasses.replace(cfg, num_experts_held=4, first_expert=2)
    params = init_params(jax.random.PRNGKey(4), part, dtype=jnp.float32)
    tensors = hf_state_dict(jax.tree.map(np.asarray, params), part)
    keys = set(tensors)
    for name in ("q_a_proj.weight", "q_a_layernorm.weight", "q_b_proj.weight",
                 "kv_a_proj_with_mqa.weight", "kv_a_layernorm.weight",
                 "kv_b_proj.weight", "o_proj.weight", "indexer.wq_b.weight",
                 "indexer.wk.weight", "indexer.k_norm.weight",
                 "indexer.k_norm.bias", "indexer.weights_proj.weight"):
        assert f"model.layers.2.self_attn.{name}" in keys, name
    assert "model.layers.0.self_attn.q_proj.weight" not in keys
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in keys
    assert "model.layers.1.mlp.experts.2.gate_proj.weight" in keys
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in keys
    # the multi-token-prediction layer (layer ``num_hidden_layers``) is not read
    assert not any(k.startswith("model.layers.3.") for k in keys)
    assert tensors["model.layers.0.self_attn.indexer.wq_b.weight"].shape == (
        2 * 16, 24)  # stored [out, in]: reads the query latent
    assert len(list(glm_moe_dsa.layer_tensors(part))) == len(keys) - 3
    write_hf_checkpoint(tmp_path, part, tensors, shards=2)
    loaded, cfg2 = load_params(tmp_path, dtype=jnp.float32, use_native=False)
    assert cfg2 == part
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)
