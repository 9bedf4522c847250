"""Ling-3.0's language model (``model_type: ling_hybrid``) on the CPU at a
tiny size, seeded random float32 weights: the declaration (``from_hf_dict`` on
the catalog row's keys, each refusal by its key), ``models.forward`` with and
without the offline cache against ``benchmark/reference_ling_v3.py`` on
logits, group-limited routing against the reference for both families that
state it, the expert shares that must add up to the uncut layer, and the
checkpoint names.  The served engine is tests/test_ling_hybrid_serve.py, the
recurrence itself tests/test_kda.py.
"""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))

import reference_ling_v3 as ref  # noqa: E402

from llm_np_cp_tpu.cache import KVCache  # noqa: E402
from llm_np_cp_tpu.config import ModelConfig, tiny_config  # noqa: E402
from llm_np_cp_tpu.models.transformer import (  # noqa: E402
    experts_block,
    forward,
    init_params,
)
from llm_np_cp_tpu.ops import moe  # noqa: E402
from llm_np_cp_tpu.ops.activations import ACT2FN  # noqa: E402
from llm_np_cp_tpu.utils.synthetic import hf_config_dict  # noqa: E402

# largest logit difference as a share of the reference's spread: float32
# against float32, sums in another order
TOL = 2e-5
CELL_FILE = ROOT / "benchmark" / "configs" / "ling-3.0-flash-7l-ep4.json"


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("ling_hybrid")
    hf = hf_config_dict(cfg)
    assert cfg == ModelConfig.from_hf_dict(hf)
    return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32), hf


def _gap(got: np.ndarray, want: np.ndarray) -> float:
    spread = float((want.max(-1) - want.mean(-1)).mean())
    return float(np.abs(got - want).max()) / spread


_REF: dict = {}


def _reference(params, hf, seq) -> np.ndarray:
    """The reference's logits for ``seq``, computed on the sequence padded
    to a multiple of 32 tokens (causal: what follows a position cannot
    change it), so that a few compiled programs serve every length."""
    n = -(-len(seq) // 32) * 32
    if n not in _REF:
        _REF[n] = jax.jit(lambda p, ids: ref.forward(p, hf, ids))
    ids = np.zeros((n,), np.int32)
    ids[:len(seq)] = seq
    return np.asarray(_REF[n](params, ids))[:len(seq)]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


# ----------------------------------------------------------------------
# the declaration
# ----------------------------------------------------------------------

def test_the_cells_file_declares_five_kda_layers_to_one_latent_one():
    d = json.loads(CELL_FILE.read_text())
    cfg = ModelConfig.from_hf_dict(d)
    assert [cfg.layer_op(i) for i in range(7)] == ["kda"] * 5 + ["latent", "kda"]
    assert [cfg.layer_ff(i) for i in range(7)] == ["dense"] + ["experts"] * 6
    assert cfg.layer_groups() == (
        ("kda", "dense", 0, 1), ("kda", "experts", 1, 1),
        ("kda", "experts", 2, 1), ("kda", "experts", 3, 1),
        ("kda", "experts", 4, 1), ("latent", "experts", 5, 1),
        ("kda", "experts", 6, 1))
    assert cfg.attn_layers == (5,) and cfg.kda_layers == (0, 1, 2, 3, 4, 6)
    assert cfg.is_latent and cfg.carries_state and cfg.is_hybrid
    # one latent row a token in ONE layer; a 2 MiB float32 matrix a slot
    # and KDA layer beside the q | k | v history of the convolution
    assert cfg.kv_bytes_per_token() == (512 + 64) * 2
    assert cfg.state_shapes(64, "bfloat16") == {
        "conv": ((6, 64, 3, 3 * 4096), "bfloat16"),
        "kda": ((6, 64, 32, 128, 128), "float32")}
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert) == (512, 128, 0)
    assert (cfg.n_group, cfg.topk_group, cfg.num_experts_per_tok) == (8, 4, 8)
    assert cfg.routed_scaling_factor == 2.5 and cfg.router_norm_eps == 1e-20
    assert cfg.shared_expert_intermediate_size == 768
    assert cfg.kda_lower_bound == -5.0 and cfg.kda_conv_taps == 4
    assert cfg.attn_scale == 192 ** -0.5 and not cfg.rope_interleave
    assert not cfg.tie_word_embeddings and cfg.rope_theta == 6000000
    # the catalog row's own keys, uncut: 42 layers, 7 latent among them
    whole = ModelConfig.from_hf_dict(dict(
        d, num_hidden_layers=42, first_k_dense_replace=2, num_experts=512,
        router_experts=512, expert_swiglu_limit_list=[0] * 42,
        share_expert_swiglu_limit_list=[0] * 42))
    assert whole.attn_layers == (5, 11, 17, 23, 29, 35, 41)
    assert whole.num_experts_held is None and whole.num_dense_layers == 2


@pytest.mark.parametrize("key, value", [
    ("q_lora_rank", 1536), ("use_nGPT", True), ("value_norm", True),
    ("up_proj_norm", True), ("scale_router_input", True),
    ("use_kda_lora", True), ("mtp_use_kda", True),
    ("score_function", "softmax"), ("rope_scaling", {"type": "yarn"}),
    ("expert_swiglu_limit_list", [0, 0, 0, 4, 0, 0, 0]),
    ("share_expert_swiglu_limit_list", [0, 0, 0, 0, 0, 0, 7]),
    ("no_kda_lora", False), ("kda_safe_gate", False), ("group_norm_size", 4),
    ("num_kv_heads_for_linear_attn", 8), ("rotary_dim", 32),
    ("model_type", "ling_hybrid_v9"),
])
def test_what_has_no_equations_is_refused_by_its_key(key, value):
    d = json.loads(CELL_FILE.read_text())
    with pytest.raises(ValueError, match=key if key != "model_type"
                       else "unknown model_type 'ling_hybrid_v9'"):
        ModelConfig.from_hf_dict(dict(d, **{key: value}))


def test_seeded_decays_span_two_to_two_hundred_tokens(tiny):
    """``init_kda_log_decay``: a channel's log-decay at ``W_a x = 0`` lies
    in the stated span (times the head's rate, 0.8 to 1.25 of it), not at
    ``L / 2`` where the state forgets in two tokens."""
    cfg, params, _ = tiny
    w = params["layers"][0]
    bias = np.asarray(w["kda_dt_bias"][0], np.float64)
    g = cfg.kda_lower_bound / (1.0 + np.exp(-bias))
    assert -0.5 <= g.min() < -0.2 and -0.012 < g.max() <= -0.005
    assert w["kda_dt_bias"].dtype == w["kda_A_log"].dtype == jnp.float32
    rate = np.exp(np.asarray(w["kda_A_log"][0]))
    assert 0.8 <= rate.min() and rate.max() <= 1.25
    plain = init_params(jax.random.PRNGKey(0), dataclasses.replace(
        cfg, init_kda_log_decay=None), dtype=jnp.float32)
    assert not np.asarray(plain["layers"][0]["kda_dt_bias"]).any()


# ----------------------------------------------------------------------
# the model: forward against the reference
# ----------------------------------------------------------------------

def test_forward_matches_reference(tiny):
    cfg, params, hf = tiny
    assert ref.runs(hf) == [(op, ff, n) for op, ff, _, n in cfg.layer_groups()]
    ids = np.asarray(_prompts([45], seed=1)[0], np.int32)
    got, _, aux = jax.jit(lambda p, i: forward(
        p, i, cfg, output_experts=True))(params, ids[None])
    want, chosen = ref.forward(params, hf, ids, return_experts=True)
    assert _gap(np.asarray(got[0]), np.asarray(want)) <= TOL
    assert (np.sort(np.asarray(aux["experts"])[:, 0], -1)
            == np.sort(np.asarray(chosen), -1)).all()


def test_cache_prefill_then_decode_matches_full_forward(tiny):
    """The offline cache carries the ``kda`` leaf and the convolution's
    history beside ONE latent layer's rows a group: the parity oracle."""
    cfg, params, hf = tiny
    ids = np.asarray(_prompts([29], seed=2)[0], np.int32)
    cache = KVCache.init(cfg, 1, 64, dtype=jnp.float32)
    assert cache.k.shape[0] == 2 and cache.v is None
    assert cache.kda.shape == (4, 1, 4, 16, 16) and cache.kda.dtype == jnp.float32
    assert cache.conv.shape == (4, 1, 3, 3 * 64)
    step = jax.jit(lambda p, i, c: forward(p, i, cfg, c))
    first, cache = step(params, ids[None, :17], cache)
    outs = [first]
    for t in range(17, 29):
        one, cache = step(params, ids[None, t:t + 1], cache)
        outs.append(one)
    got = np.asarray(jnp.concatenate(outs, axis=1)[0])
    assert _gap(got, _reference(params, hf, ids)) <= TOL


# ----------------------------------------------------------------------
# the router: the group limit, for every family that states one
# ----------------------------------------------------------------------

@pytest.mark.parametrize("family", ["ling_hybrid", "deepseek_v3"])
def test_group_limited_routing_matches_the_reference(family):
    """16 experts in 4 groups of which 2 stay: the program's choice and
    weights against the reference's, a choice the limit changes for some
    token, and a bf16 router that flips one.  ``deepseek_v3`` takes the same
    keys from its file (it raised on them before PR 47)."""
    if family == "ling_hybrid":
        cfg = tiny_config("ling_hybrid")
    else:
        d = hf_config_dict(tiny_config("deepseek_v3", num_experts=16,
                                       num_experts_per_tok=4))
        cfg = ModelConfig.from_hf_dict(dict(d, n_group=4, topk_group=2))
    assert (cfg.n_group, cfg.topk_group) == (4, 2)
    hf = dict(n_group=4, topk_group=2, num_experts_per_tok=4,
              routed_scaling_factor=cfg.routed_scaling_factor)
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = jax.random.normal(ks[0], (256, 64))
    w = {"router": jax.random.normal(ks[1], (64, 16)) * 0.2,
         "expert_bias": jax.random.normal(ks[2], (16,)) * 0.05}
    kw = dict(top_k=4, scaling=cfg.routed_scaling_factor, norm_eps=1e-20)
    idx, wts = moe.route_sigmoid_topk(x, w["router"], w["expert_bias"],
                                      n_group=4, topk_group=2, **kw)
    with jax.default_matmul_precision("highest"):
        scores, chosen = ref.route(x, w, hf)
        weights = ref.routing_weights(scores, chosen, hf)
    assert (np.sort(idx, -1) == np.sort(chosen, -1)).all()
    got = jnp.zeros_like(scores).at[jnp.arange(256)[:, None], idx].add(wts)
    assert float(jnp.abs(got - weights).max()) <= 1e-6
    # a token's experts come from two groups of four
    assert (np.asarray([len({int(e) // 4 for e in row}) for row in idx]) <= 2).all()
    free, _ = moe.route_sigmoid_topk(x, w["router"], w["expert_bias"], **kw)
    assert (np.sort(free, -1) != np.sort(idx, -1)).any()
    rounded, _ = moe.route_sigmoid_topk(
        x, w["router"], w["expert_bias"], n_group=4, topk_group=2,
        score_dtype=jnp.bfloat16, **kw)
    assert (np.sort(rounded, -1) != np.sort(idx, -1)).any()


def test_without_groups_the_router_traces_no_group_mask():
    """``n_group == 1`` (Kanana's file, MiMo's, LFM2's): the program is what
    it was — one ``top_k`` and no -inf mask in the jaxpr."""
    x, w, b = jnp.ones((8, 64)), jnp.ones((64, 16)), jnp.zeros((16,))
    plain = str(jax.make_jaxpr(lambda *a: moe.route_sigmoid_topk(
        *a, top_k=4))(x, w, b))
    grouped = str(jax.make_jaxpr(lambda *a: moe.route_sigmoid_topk(
        *a, top_k=4, n_group=4, topk_group=2))(x, w, b))
    assert plain.count("top_k") == 1 and "inf" not in plain
    assert grouped.count("top_k") == 3 and "-inf" in grouped
    kanana = json.loads((ROOT / "benchmark" / "configs"
                         / "kanana-2-30b-a3b-24l-ep8.json").read_text())
    assert ModelConfig.from_hf_dict(kanana).n_group == 1
    with pytest.raises(ValueError, match="group-limited routing"):
        tiny_config("ling_hybrid", n_group=4, topk_group=5)


def test_the_four_expert_shares_add_up_to_the_uncut_layer(tiny):
    """One expert layer, its 16 routed experts cut into four shares of four
    as four chips of a stage would hold them (each routes over all 16): the
    shares' routed parts summed, with the shared expert counted ONCE, are the
    reference's uncut layer."""
    cfg, params, hf = tiny
    w = {name: leaf[0] for name, leaf in params["layers"][1].items()}
    x = jax.random.normal(jax.random.PRNGKey(9), (1, 40, 64))
    act = ACT2FN[cfg.hidden_act]
    a = ref.rms_norm(x[0], w["ln_mlp_in"], cfg.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        uncut, _ = ref.experts_ff(a, w, hf)
        shared_only = ref.swiglu(a, w["shared_gate"], w["shared_up"],
                                 w["shared_down"])
    total = jnp.zeros_like(uncut)
    for share in range(4):
        held = dataclasses.replace(cfg, num_experts_held=4, first_expert=4 * share)
        part = {k: (v[4 * share:4 * share + 4] if k in ("w1", "w3", "w2") else v)
                for k, v in w.items()}
        out, _, load = experts_block(part, x, config=held, act=act)
        total = total + (out[0] - x[0] - shared_only)
        assert load.shape == (4,)
    total = total + shared_only
    assert float(jnp.abs(total - uncut).max()) <= 1e-5 * float(
        jnp.abs(uncut).max() + 1)


def test_hf_key_map_round_trip(tiny, tmp_path):
    """A checkpoint written under the family's names loads back leaf for
    leaf (the decay's scalars and the router's bias float32)."""
    from llm_np_cp_tpu.utils.loading import load_params
    from llm_np_cp_tpu.utils.synthetic import (
        hf_state_dict,
        hf_tensor_shapes,
        write_hf_checkpoint,
    )

    cfg, params, _ = tiny
    host = jax.tree.map(np.asarray, params)
    tensors = hf_state_dict(host, cfg)
    assert {k: v.shape for k, v in tensors.items()} == hf_tensor_shapes(cfg)
    assert "model.layers.0.self_attn.f_proj.weight" in tensors
    assert tensors["model.layers.1.self_attn.k_conv1d.weight"].shape == (64, 1, 4)
    assert "model.layers.2.self_attn.kv_a_proj_with_mqa.weight" in tensors
    write_hf_checkpoint(tmp_path, cfg, tensors)
    for use_native in (False, True):
        loaded, cfg2 = load_params(tmp_path, dtype=jnp.float32,
                                   use_native=use_native, on_host=True)
        assert cfg2 == cfg
        for a, b in zip(jax.tree.leaves(host), jax.tree.leaves(loaded)):
            np.testing.assert_array_equal(a, b)
    served, _ = load_params(tmp_path, dtype=jnp.bfloat16, on_host=True)
    assert served["layers"][0]["kda_dt_bias"].dtype == np.float32
    assert served["layers"][0]["kda_A_log"].dtype == np.float32
