"""DeepSeek-V3 family (``model_type: deepseek_v3``; Kanana-2-30B-A3B): the
configuration as published, the plain forward against the benchmark's
independent float32 reference, the held share of the routed experts, the
checkpoint's tensor names.  The served path is tests/test_serve_latent_pool.py,
the attention forms tests/test_latent_attention.py."""

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
import reference_deepseek_v3 as ref  # noqa: E402

# the catalog row, verbatim (model-configs/architectures.jsonl; the
# published config.json of kakaocorp/kanana-2-30b-a3b-instruct-2601)
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 48, "num_key_value_heads": 32, "q_lora_rank": None,
    "qk_head_dim": 192, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_interleave": True, "rope_scaling": None,
    "rope_theta": 1000000, "routed_scaling_factor": 2.448,
    "scoring_func": "sigmoid", "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256,
}
TOL = 2e-5  # float32 against float32 at the highest matmul precision

# (jitted: an eager forward dispatches every layer's operations one by one)
jforward = jax.jit(forward, static_argnums=(2,), static_argnames=("output_experts",))


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("deepseek_v3")
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


# ----------------------------------------------------------------------
# configuration
# ----------------------------------------------------------------------

def test_from_hf_dict_reads_the_catalog_row_verbatim():
    assert "deepseek_v3" in KNOWN_MODEL_TYPES
    cfg = ModelConfig.from_hf_dict(PUBLISHED)
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.vocab_size) == (
        2048, 32, 128256)
    assert (cfg.kv_lora_rank, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
            cfg.v_head_dim, cfg.head_dim) == (512, 128, 64, 128, 64)
    assert cfg.attn_scale == 192 ** -0.5 and cfg.rope_interleave
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert,
            cfg.num_experts_per_tok) == (128, 128, 0, 6)
    assert cfg.num_dense_layers == 1 and cfg.use_expert_bias
    assert (cfg.intermediate_size, cfg.moe_intermediate_size,
            cfg.shared_expert_intermediate_size) == (6144, 768, 1536)
    assert cfg.routed_scaling_factor == 2.448 and cfg.norm_topk_prob
    assert cfg.router_norm_eps == 1e-20 and not cfg.tie_word_embeddings
    # layer 0 one run, every expert layer a run of its own
    groups = cfg.layer_groups()
    assert groups[0] == ("latent", "dense", 0, 1) and len(groups) == 48
    assert all(g == ("latent", "experts", i, 1)
               for i, g in enumerate(groups[1:], start=1))
    assert cfg.is_hybrid and cfg.is_latent and not cfg.carries_state
    assert cfg.attn_layers == tuple(range(48))


def test_a_token_leaves_one_row_of_576_values_a_layer():
    cfg = ModelConfig.from_hf_dict(PUBLISHED)
    assert cfg.kv_token_shapes() == {"k": (576,)}
    assert cfg.kv_bytes_per_token(2) == 48 * 1152
    # this model's expanded K and V would be 20,480 B a token and layer
    assert 32 * (192 + 128) * 2 == 20480
    # a dense family's statement, for contrast: K and V per kv head
    qwen = tiny_config("qwen2")
    assert qwen.kv_token_shapes() == {"k": (2, 16), "v": (2, 16)}
    assert qwen.kv_bytes_per_token(2) == 3 * 2 * 2 * 16 * 2
    assert not qwen.is_hybrid  # the property is the stack's, not a key's


@pytest.mark.parametrize("change, match", [
    (dict(q_lora_rank=1536), "q_lora_rank"),
    (dict(rope_scaling={"rope_type": "yarn", "factor": 64}), "rope_scaling"),
    # (groups are data since PR 47; more groups kept than there are is
    # still refused)
    (dict(n_group=8, topk_group=9), "group-limited routing"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(topk_method="greedy"), "topk_method"),
    (dict(moe_layer_freq=2), "moe_layer_freq"),
    (dict(attention_bias=True), "attention_bias"),
    (dict(head_dim=128), "head_dim"),
    (dict(router_experts=64, first_expert=0), "are not among the router's"),
    (dict(model_type="deepseek_v4"), "unknown model_type 'deepseek_v4'"),
], ids=lambda v: v if isinstance(v, str) else "")
def test_what_has_no_equations_is_refused_by_its_key(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_dict(dict(PUBLISHED, **change))


def test_the_benchmark_configuration_is_the_row_cut_to_one_chips_share():
    path = ROOT / "benchmark" / "configs" / "kanana-2-30b-a3b-24l-ep8.json"
    file = json.loads(path.read_text())
    changed = {k for k, v in PUBLISHED.items() if file.get(k, "absent") != v}
    assert changed == set(file["reduced"]) == {
        "num_hidden_layers", "n_routed_experts"}
    cfg = ModelConfig.from_hf_dict(file)
    assert (cfg.num_hidden_layers, cfg.num_experts, cfg.experts_held,
            cfg.first_expert) == (24, 128, 16, 0)
    assert cfg.kv_bytes_per_token(2) == 27648
    assert "2 pipeline stages x 8 chips" in file["deployment"]
    assert file["sizes"]["parameters"] == 3155018624


# ----------------------------------------------------------------------
# the plain forward against the independent reference
# ----------------------------------------------------------------------

def test_forward_matches_reference(tiny):
    cfg, hf, params = tiny
    ids = _ids(40)
    with jax.default_matmul_precision("highest"):
        logits, _, aux = jforward(params, jnp.asarray(ids)[None], cfg,
                                 output_experts=True)
    want, chosen = ref.forward(params, hf, ids, return_experts=True)
    assert _gap(logits[0], want) < TOL
    assert np.array_equal(np.sort(aux["experts"][:, 0], -1),
                          np.sort(chosen, -1))
    # the reference computed in query blocks is the same function
    assert _gap(ref.forward(params, hf, ids, q_block=16), want) < TOL


def test_attention_over_query_blocks_is_attention(tiny):
    cfg, hf, params = tiny
    from llm_np_cp_tpu.models import transformer

    ids = jnp.asarray(_ids(37, seed=3))[None]
    with jax.default_matmul_precision("highest"):
        whole, _ = forward(params, ids, cfg)
        real = transformer.latent_attention_block
        try:  # blocks of 8 queries, the last one ragged
            transformer.latent_attention_block = (
                lambda *a, **kw: real(*a, **dict(kw, q_block=8)))
            blocked, _ = forward(params, ids, cfg)
        finally:
            transformer.latent_attention_block = real
    assert _gap(blocked[0], whole[0]) < TOL


def test_cache_prefill_then_decode_matches_full_forward(tiny):
    cfg, _, params = tiny
    ids = jnp.asarray(_ids(24, seed=5).reshape(2, 12))
    with jax.default_matmul_precision("highest"):
        full, _ = jforward(params, ids, cfg)
        cache = KVCache.init(cfg, 2, 16, dtype=jnp.float32)
        assert cache.k.shape == (3, 2, 16, 40) and cache.v is None
        parts = []
        for lo, hi in ((0, 7), (7, 11), (11, 12)):
            out, cache = jforward(params, ids[:, lo:hi], cfg, cache)
            parts.append(out)
    assert int(cache.length) == 12
    assert _gap(jnp.concatenate(parts, axis=1), full) < TOL
    with pytest.raises(NotImplementedError, match="int8"):
        KVCache.init(cfg, 1, 16, dtype=jnp.int8)


@pytest.mark.parametrize("control", [
    "bf16_params", "no_bias", "no_shared", "k_pe_unrotated", "halfsplit_rope",
    "no_kv_a_layernorm", "eps_1e-6"])
def test_a_broken_model_fails_the_float32_tolerance(tiny, control):
    """Each control is the forward with ONE equation changed: all but the
    last must fail ``TOL``; the normaliser's 1e-6 in place of 1e-20 moves a
    weight by a millionth and is below what float32 logits resolve (the
    configuration sets it, tests/test_moe.py shows the term itself)."""
    import dataclasses

    cfg, hf, params = tiny
    ids = _ids(32, seed=7)
    want = ref.forward(params, hf, ids)
    broken, bcfg = params, cfg
    if control == "bf16_params":
        broken = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
            and a.ndim > 1 else a, params)
    elif control == "no_bias":
        broken = dict(params, layers=[
            {k: (jnp.zeros_like(v) if k == "expert_bias" else v)
             for k, v in g.items()} for g in params["layers"]])
    elif control == "no_shared":
        broken = dict(params, layers=[
            {k: v for k, v in g.items() if not k.startswith("shared_")}
            for g in params["layers"]])
    elif control == "halfsplit_rope":
        bcfg = dataclasses.replace(cfg, rope_interleave=False)
    elif control == "eps_1e-6":
        bcfg = dataclasses.replace(cfg, router_norm_eps=1e-6)
    elif control == "no_kv_a_layernorm":
        # a gamma that undoes the norm's scaling is not available to a
        # test; the nearest broken model scales c by a constant instead
        broken = dict(params, layers=[
            {k: (v * 1.5 if k == "ln_kv_a" else v) for k, v in g.items()}
            for g in params["layers"]])
    with jax.default_matmul_precision("highest"):
        if control == "k_pe_unrotated":
            from llm_np_cp_tpu.models import transformer
            real = transformer.apply_rope
            try:  # the shared key part left where kv_a_proj put it
                transformer.apply_rope = lambda x, cos, sin, **kw: (
                    x if x.shape[-2] == 1 else real(x, cos, sin, **kw))
                # (traced anew: the patch must be what is compiled)
                got, _ = jax.jit(lambda p, i: forward(p, i, bcfg))(
                    broken, jnp.asarray(ids)[None])
            finally:
                transformer.apply_rope = real
        else:
            got, _ = jforward(broken, jnp.asarray(ids)[None], bcfg)
    gap = _gap(got[0], want)
    if control == "eps_1e-6":
        assert gap < TOL, gap
    else:
        assert gap > 10 * TOL, (control, gap)


# ----------------------------------------------------------------------
# the held share
# ----------------------------------------------------------------------

def test_the_shares_of_the_routed_experts_and_the_shared_experts_once_add_up(tiny):
    """Four holders of two experts each, every one computing the layer
    with its share: the routed parts summed + the shared experts ONCE ==
    the uncut layer (each holder's ``experts_block`` adds x and the shared
    experts, so three of the four are taken off again)."""
    import dataclasses

    from llm_np_cp_tpu.models.transformer import experts_block
    from llm_np_cp_tpu.ops.activations import ACT2FN

    cfg, _, params = tiny
    w = {k: v[0] for k, v in params["layers"][1].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64), jnp.float32)
    act = ACT2FN["silu"]
    with jax.default_matmul_precision("highest"):
        whole, chosen, load = experts_block(w, x, config=cfg, act=act)
        routed_only, _, _ = experts_block(
            {k: v for k, v in w.items() if not k.startswith("shared_")}, x,
            config=cfg, act=act)
        shared = whole - routed_only  # the shared experts' term
        total, loads = jnp.zeros_like(x), []
        for first in range(0, 8, 2):
            part_cfg = dataclasses.replace(
                cfg, num_experts_held=2, first_expert=first)
            part_w = dict(w, **{k: w[k][first:first + 2]
                                for k in ("w1", "w3", "w2")})
            out, part_chosen, part_load = experts_block(
                part_w, x, config=part_cfg, act=act)
            assert np.array_equal(part_chosen, chosen)  # one router
            assert part_load.shape == (2,)
            loads.append(part_load)
            total = total + (out - x - shared)
    assert np.array_equal(jnp.concatenate(loads), load)
    assert int(load.sum()) == 2 * 9 * 2  # every (token, expert) pair once
    assert float(jnp.abs(total + x + shared - whole).max()) < 1e-5
    assert float(jnp.abs(shared).max()) > 1e-3


def test_a_held_share_is_the_reference_with_the_same_share(tiny):
    import dataclasses

    cfg, hf, _ = tiny
    part = dataclasses.replace(cfg, num_experts_held=4, first_expert=2)
    part_hf = hf_config_dict(part)
    assert (part_hf["n_routed_experts"], part_hf["router_experts"],
            part_hf["first_expert"]) == (4, 8, 2)
    assert ModelConfig.from_hf_dict(part_hf) == part
    params = init_params(jax.random.PRNGKey(2), part, dtype=jnp.float32)
    assert params["layers"][1]["w1"].shape == (1, 4, 64, 32)
    assert params["layers"][1]["router"].shape == (1, 64, 8)
    assert params["layers"][1]["expert_bias"].dtype == jnp.float32
    assert float(jnp.abs(params["layers"][1]["expert_bias"]).max()) > 0
    ids = _ids(24, seed=9)
    with jax.default_matmul_precision("highest"):
        got, _ = jforward(params, jnp.asarray(ids)[None], part)
    assert _gap(got[0], ref.forward(params, part_hf, ids)) < TOL


# ----------------------------------------------------------------------
# checkpoint names
# ----------------------------------------------------------------------

def test_hf_key_map_round_trip(tiny, tmp_path):
    import dataclasses

    from llm_np_cp_tpu.models import deepseek_v3
    from llm_np_cp_tpu.utils.loading import load_params
    from llm_np_cp_tpu.utils.synthetic import (
        hf_state_dict,
        write_hf_checkpoint,
    )

    cfg, _, _ = tiny
    part = dataclasses.replace(cfg, num_experts_held=4, first_expert=2)
    params = init_params(jax.random.PRNGKey(4), part, dtype=jnp.float32)
    host = jax.tree.map(np.asarray, params)
    tensors = hf_state_dict(host, part)
    keys = set(tensors)
    assert "model.layers.0.self_attn.kv_a_proj_with_mqa.weight" in keys
    assert "model.layers.0.self_attn.kv_a_layernorm.weight" in keys
    assert "model.layers.0.mlp.gate_proj.weight" in keys
    assert "model.layers.1.mlp.gate.e_score_correction_bias" in keys
    assert "model.layers.2.mlp.shared_experts.down_proj.weight" in keys
    # the experts held keep the numbers the checkpoint gives them
    assert "model.layers.1.mlp.experts.2.gate_proj.weight" in keys
    assert "model.layers.1.mlp.experts.5.down_proj.weight" in keys
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in keys
    assert tensors["model.layers.1.self_attn.kv_b_proj.weight"].shape == (
        4 * (16 + 16), 32)  # stored [out, in]
    assert len(list(deepseek_v3.layer_tensors(part))) == len(keys) - 3
    write_hf_checkpoint(tmp_path, part, tensors, shards=2)
    loaded, cfg2 = load_params(tmp_path, dtype=jnp.float32, use_native=False)
    assert cfg2 == part
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


def test_offline_generator_runs_the_stack(tiny):
    from llm_np_cp_tpu.generate import Generator
    from llm_np_cp_tpu.ops.sampling import Sampler

    cfg, _, params = tiny
    prompt = _ids(9, seed=11)
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"))
    out = gen.generate(np.asarray(prompt)[None], max_new_tokens=5)
    seq = np.concatenate([prompt, np.asarray(out.tokens)[0, :4]])
    with jax.default_matmul_precision("highest"):
        logits, _ = jforward(params, jnp.asarray(seq)[None], cfg)
    assert np.asarray(out.tokens)[0].tolist() == (
        np.asarray(logits[0]).argmax(-1)[len(prompt) - 1:].tolist())


def test_a_configuration_file_says_how_large_a_random_routed_experts_answer_is(tiny):
    """``init_expert_out_std`` (seeded random weights only; no forward reads
    it): the scale ``w2`` of the routed experts is drawn at.  The benchmark's
    file states it and says why; a configuration without the key draws every
    matrix at 0.02."""
    import dataclasses

    cfg, hf, params = tiny
    assert cfg.init_expert_out_std is None
    small = ModelConfig.from_hf_dict(dict(hf, init_expert_out_std=0.004))
    assert small == dataclasses.replace(cfg, init_expert_out_std=0.004)
    drawn = init_params(jax.random.PRNGKey(0), small, dtype=jnp.float32)
    for name in ("w1", "w3", "router", "shared_down", "o_proj"):
        assert np.array_equal(drawn["layers"][1][name], params["layers"][1][name])
    assert float(jnp.std(params["layers"][1]["w2"])) == pytest.approx(0.02, rel=0.05)
    assert float(jnp.std(drawn["layers"][1]["w2"])) == pytest.approx(0.004, rel=0.05)
    file = json.loads((ROOT / "benchmark" / "configs"
                       / "kanana-2-30b-a3b-24l-ep8.json").read_text())
    assert ModelConfig.from_hf_dict(file).init_expert_out_std == file.get(
        "init_expert_out_std")
