"""AFMoE (``model_type: afmoe``; Trinity-Large): the configuration as
published, the plain forward against the benchmark's independent float32
reference (logits, not tokens) with the controls it must refuse; the share
of the routed experts under a post-norm of the SUM; one page shape in two
classes as the configuration declares it; the three older expert stacks
traced as they were.  The served path is tests/test_afmoe_serve.py, the cost
file and the cell benchmark/tests/test_afmoe_cell.py."""

import dataclasses
import hashlib
import json
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.config import KNOWN_MODEL_TYPES, ModelConfig, tiny_config
from llm_np_cp_tpu.models import forward, init_params
from llm_np_cp_tpu.models.transformer import (
    STEP_SCOPES,
    experts_block,
    experts_parts,
)
from llm_np_cp_tpu.ops.activations import ACT2FN
from llm_np_cp_tpu.ops.norms import rms_norm
from llm_np_cp_tpu.utils.synthetic import hf_config_dict

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_afmoe as ref  # noqa: E402

CELL_CONFIG = ROOT / "benchmark" / "configs" / "trinity-large-5l-ep8.json"
# the catalog row's list (model-configs/architectures.jsonl; the published
# config.json of arcee-ai/Trinity-Large-Preview): ``s s s f`` x 15
LAYER_TYPES = (["sliding_attention"] * 3 + ["full_attention"]) * 15
# float32 against float32 at the highest matmul precision: the program and
# the reference differ in summation order alone
TOL = 2e-5

jforward = jax.jit(forward, static_argnums=(2,))


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("afmoe")
    hf = hf_config_dict(cfg)
    assert ModelConfig.from_hf_dict(hf) == cfg
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    # norm gains away from 1, so that a norm in the wrong place shows
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 64))
    for group in params["layers"]:
        for name in group:
            if name.startswith("ln_"):
                group[name] = 1.0 + 0.3 * jax.random.normal(
                    next(keys), group[name].shape, jnp.float32)
    return cfg, hf, params


def _published() -> dict:
    """The catalog row from the cell's file: the cut keys restored."""
    with open(CELL_CONFIG) as f:
        d = json.load(f)
    d.update(num_hidden_layers=60, layer_types=LAYER_TYPES, num_dense_layers=6,
             num_experts=256, vocab_size=200192)
    for key in ("router_experts", "first_expert"):
        d.pop(key)
    return d


def _gap(got, want) -> float:
    """Largest logit difference as a share of the reference's spread."""
    want = np.asarray(want)
    spread = float((want.max(-1) - want.mean(-1)).mean())
    return float(np.abs(np.asarray(got, np.float32) - want).max()) / spread


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(1, 256, n)


# ----------------------------------------------------------------------
# configuration
# ----------------------------------------------------------------------

def test_from_hf_dict_reads_the_published_row():
    assert "afmoe" in KNOWN_MODEL_TYPES
    cfg = ModelConfig.from_hf_dict(_published())
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size) == (
        60, 3072, 200192)
    assert (len(cfg.global_layers), len(cfg.window_layers)) == (15, 45)
    assert cfg.global_layers[:3] == (3, 7, 11) and cfg.two_page_classes
    g, w = cfg.attn_kind("global"), cfg.attn_kind("window")
    # ONE shape, two kinds: the global layers carry no positional encoding
    assert (g.kv_heads, g.key_dim, g.value_dim, g.rope_theta, g.window,
            g.sink) == (8, 128, 128, None, None, False)
    assert (w.kv_heads, w.key_dim, w.value_dim, w.rope_theta, w.window,
            w.sink) == (8, 128, 128, 1e4, 4096, False)
    assert cfg.kv_token_shapes("global") == cfg.kv_token_shapes("window") == {
        "k": (8, 128), "v": (8, 128)}
    assert cfg.attn_output_gate and cfg.qk_norm and cfg.sandwich_norms
    assert cfg.scale_embeddings and not cfg.rms_norm_unit_offset
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.num_dense_layers, cfg.routed_scaling_factor,
            cfg.shared_expert_intermediate_size, cfg.router_norm_eps) == (
        256, 256, 4, 6, 2.448, 3072, 1e-20)
    assert cfg.layer_groups()[0] == ("swa", "dense", 0, 3)
    assert cfg.layer_groups()[1:4] == (
        ("attn", "dense", 3, 1), ("swa", "dense", 4, 2),
        ("swa", "experts", 6, 1))


def test_the_benchmark_configuration_is_the_row_cut_to_one_chips_share():
    with open(CELL_CONFIG) as f:
        d = json.load(f)
    cfg = ModelConfig.from_hf_dict(d)
    assert cfg.layer_groups() == (
        ("swa", "dense", 0, 1), ("swa", "experts", 1, 1),
        ("swa", "experts", 2, 1), ("attn", "experts", 3, 1),
        ("swa", "experts", 4, 1))
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert,
            cfg.vocab_size) == (256, 32, 0, 25024)
    # a token: 1 global layer x 4,096 B for as long as it lives, 4 window
    # layers x 4,096 B only while a later query can see it
    assert cfg.kv_bytes_per_token(2, "global") == 4096
    assert cfg.kv_bytes_per_token(2, "window") == 16384
    pub = _published()
    assert {k for k in pub if d.get(k) != pub[k]} == set(d["reduced"])


@pytest.mark.parametrize("change, match", [
    ({"n_group": 2}, "n_group"),
    ({"num_expert_groups": 4}, "num_expert_groups"),
    ({"topk_group": 2}, "topk_group"),
    ({"num_limited_groups": 2}, "num_limited_groups"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"score_func": "softmax"}, "score_func"),
    ({"route_norm": False}, "route_norm"),
    ({"num_shared_experts": 2}, "num_shared_experts"),
    ({"layer_types": LAYER_TYPES[:-1] + ["chunked_attention"]}, "layer_types"),
    ({"layer_types": LAYER_TYPES[:-1]}, "layer_types"),
    ({"attention_bias": True}, "attention_bias"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_what_has_no_equations_is_refused_by_its_key(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_dict({**_published(), **change})


def test_a_class_is_a_kinds_not_a_shapes():
    """One page shape in both kinds lands in two classes where the
    configuration says so; MiMo-V2 reads as it did; Gemma-2's alternating
    family keeps its one class."""
    cfg = tiny_config("afmoe")
    assert cfg.swa_num_key_value_heads is None and cfg.two_page_classes
    assert [cfg.layer_op(i) for i in range(5)] == [
        "swa", "swa", "attn", "swa", "swa"]
    assert cfg.window_layers == (0, 1, 3, 4) and cfg.global_layers == (2,)
    assert cfg.is_hybrid and not tiny_config(
        "afmoe", window_page_class=False).two_page_classes
    mimo = tiny_config("mimo_v2")
    assert mimo.two_page_classes and not mimo.window_page_class
    assert mimo.attn_kind("window").kv_heads == 2
    assert mimo.attn_kind("global").rope_theta == 1e7
    gemma = tiny_config("gemma2", num_hidden_layers=4)
    assert not gemma.two_page_classes and gemma.window_layers == ()
    assert gemma.attn_kind("window").rope_theta == gemma.rope_theta
    assert "attn_gate" in STEP_SCOPES


# ----------------------------------------------------------------------
# the plain forward
# ----------------------------------------------------------------------

def test_forward_matches_reference(tiny):
    cfg, hf, params = tiny
    ids = _ids(28)  # three and a half windows long
    with jax.default_matmul_precision("highest"):
        got, _ = jforward(params, jnp.asarray(ids)[None], cfg)
    assert _gap(got[0], ref.forward(params, hf, ids, q_block=16)) < TOL


@pytest.mark.parametrize("control", [
    "window_off_by_one", "rope_in_global", "no_gate", "post_norm_routed_only",
    "bf16_router", "bf16"])
def test_a_broken_model_fails_the_float32_tolerance(tiny, control):
    cfg, hf, params = tiny
    ids = _ids(28)
    with jax.default_matmul_precision("highest"):
        got, _ = jforward(params, jnp.asarray(ids)[None], cfg)
    if control == "bf16":  # the nearest precision below the stated one
        wrong = ref.forward(jax.tree.map(
            lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32
            and a.ndim > 1 else a, params), hf, ids)
    else:
        wrong = ref.forward(params, hf, ids, controls=(control,))
    assert _gap(got[0], wrong) > 10 * TOL


# ----------------------------------------------------------------------
# the held share of the routed experts, under a post-norm of the SUM
# ----------------------------------------------------------------------

def test_the_shares_pre_norm_parts_add_up_to_the_uncut_layers(tiny):
    """Four holders of four experts each (16 experts, top-4): the PRE-norm
    parts of all shares, the shared expert counted once, add up to the
    uncut layer's ``m``, and the post-norm of that sum is the uncut
    layer's; a share's own post-norm is of its PARTIAL sum."""
    cfg, hf, params = tiny
    w = {k: v[0] for k, v in params["layers"][1].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64), jnp.float32)
    act = ACT2FN["silu"]
    norm = lambda m: rms_norm(m, w["ln_mlp_out"], eps=cfg.rms_norm_eps)  # noqa: E731
    with jax.default_matmul_precision("highest"):
        whole, chosen, load = experts_block(w, x, config=cfg, act=act)
        routed_whole, shared, _, _ = experts_parts(w, x, config=cfg, act=act)
        shared = shared()
        total, loads = jnp.zeros_like(x), []
        for first in range(0, 16, 4):
            part_cfg = dataclasses.replace(
                cfg, num_experts_held=4, first_expert=first)
            part_w = dict(w, **{k: w[k][first:first + 4]
                                for k in ("w1", "w3", "w2")})
            routed, part_shared, part_chosen, part_load = experts_parts(
                part_w, x, config=part_cfg, act=act)
            assert np.array_equal(part_chosen.reshape(chosen.shape), chosen)
            assert float(jnp.abs(part_shared() - shared).max()) == 0.0
            loads.append(part_load)
            total = total + routed
            # what the share serves: the post-norm of ITS partial sum
            out, _, _ = experts_block(part_w, x, config=part_cfg, act=act)
            assert float(jnp.abs(out - (x + norm(routed + shared))).max()) < 1e-5
        # the reference's m of the uncut layer, from the same normed input
        h2 = ref.rms_norm(x[0], w["ln_mlp_in"], cfg.rms_norm_eps)
        m_ref = ref.routed_part(h2, w, hf)[0] + ref.swiglu(
            h2, w["shared_gate"], w["shared_up"], w["shared_down"])
    assert np.array_equal(jnp.concatenate(loads), load)
    assert int(load.sum()) == 2 * 9 * 4  # every (token, expert) pair once
    assert float(jnp.abs(total - routed_whole).max()) < 1e-5
    assert float(jnp.abs(x + norm(total + shared) - whole).max()) < 1e-5
    assert float(jnp.abs((total + shared)[0] - m_ref).max()) < 1e-5
    # ... and the sum of the shares' own outputs is NOT the layer's
    assert float(jnp.abs(norm(total) + norm(shared) - norm(total + shared)
                         ).max()) > 1e-2


def test_a_held_share_is_the_reference_with_the_same_share(tiny):
    cfg, _, _ = tiny
    part = dataclasses.replace(cfg, num_experts_held=4, first_expert=8)
    part_hf = hf_config_dict(part)
    assert (part_hf["num_experts"], part_hf["router_experts"],
            part_hf["first_expert"]) == (4, 16, 8)
    assert ModelConfig.from_hf_dict(part_hf) == part
    params = init_params(jax.random.PRNGKey(2), part, dtype=jnp.float32)
    layer = params["layers"][1]
    assert layer["w1"].shape == (1, 4, 64, 32)
    assert layer["router"].shape == (1, 64, 16)
    assert layer["attn_gate_proj"].shape == (1, 64, 64)
    assert layer["expert_bias"].dtype == jnp.float32
    assert {"ln_attn_out", "ln_mlp_out", "ln_q", "ln_k", "shared_gate"} <= set(layer)
    assert "ln_mlp_out" in params["layers"][0]  # the dense layer's too
    ids = _ids(28, seed=9)  # (the length the cases above compiled for)
    with jax.default_matmul_precision("highest"):
        got, _ = jforward(params, jnp.asarray(ids)[None], part)
    assert _gap(got[0], ref.forward(params, part_hf, ids)) < TOL


def test_hf_key_map_round_trip(tiny, tmp_path):
    from llm_np_cp_tpu.models import afmoe
    from llm_np_cp_tpu.utils.loading import load_params
    from llm_np_cp_tpu.utils.synthetic import hf_state_dict, write_hf_checkpoint

    cfg, _, _ = tiny
    part = dataclasses.replace(cfg, num_experts_held=4, first_expert=2)
    params = init_params(jax.random.PRNGKey(4), part, dtype=jnp.float32)
    tensors = hf_state_dict(jax.tree.map(np.asarray, params), part)
    keys = set(tensors)
    assert "model.layers.2.self_attn.gate_proj.weight" in keys
    assert "model.layers.1.mlp.expert_bias" in keys
    assert "model.layers.0.mlp.expert_bias" not in keys  # the dense layer
    assert "model.layers.1.mlp.experts.2.gate_proj.weight" in keys
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in keys
    assert "model.layers.1.mlp.shared_experts.up_proj.weight" in keys
    assert tensors["model.layers.3.post_mlp_layernorm.weight"].shape == (64,)
    assert tensors["model.layers.1.self_attn.q_norm.weight"].shape == (16,)
    assert len(list(afmoe.layer_tensors(part))) == len(keys) - 3
    write_hf_checkpoint(tmp_path, part, tensors, shards=2)
    loaded, cfg2 = load_params(tmp_path, dtype=jnp.float32, use_native=False)
    assert cfg2 == part
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


# ----------------------------------------------------------------------
# the older expert stacks trace as they did
# ----------------------------------------------------------------------

# sha256 of ``experts_block``'s jaxpr (addresses blanked) at commit 20f4a05,
# the parent of PR 50, which gave the block its second way of closing the
# residual: a pre-norm stack must not notice.  To renew after an intended
# change to the block: print ``_digest(arch)`` on the tree before it.
TRACED_AS_BEFORE = {
    "lfm2_moe": "8fbc3971a75bce05",
    "deepseek_v3": "7006ba3b91557ad9",
    "mimo_v2": "da2a1a68787030cb",
    "ling_hybrid": "c199111fdfe30f0d",
}


def _digest(arch: str) -> str:
    cfg = tiny_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    run = next(i for i, g in enumerate(cfg.layer_groups()) if g[1] == "experts")
    w = {k: v[0] for k, v in params["layers"][run].items()}
    x = jnp.zeros((2, 9, cfg.hidden_size), jnp.float32)
    live = jnp.ones((2, 9), jnp.bool_)
    text = str(jax.make_jaxpr(lambda w, x, live: experts_block(
        w, x, config=cfg, act=ACT2FN["silu"], live=live))(w, x, live))
    return hashlib.sha256(
        re.sub(r"0x[0-9a-f]+", "0x", text).encode()).hexdigest()[:16]


@pytest.mark.parametrize("arch", sorted(TRACED_AS_BEFORE))
def test_the_older_expert_stacks_trace_unchanged(arch):
    assert _digest(arch) == TRACED_AS_BEFORE[arch]
    # ... and the new stack's block is another program: a norm of the sum
    assert _digest("afmoe") not in TRACED_AS_BEFORE.values()
