"""MiMo-V2 (``model_type: mimo_v2``; MiMo-V2.5): the configuration as
published, the plain forward against the benchmark's independent float32
reference (logits, not tokens) with the controls it must refuse; the ragged
kernel at ``Dk != Dv`` with a sink and a table that does not start at
position 0; the held share of the routed experts; the cost file to the
parameter.  The served path is tests/test_serve_window_pool.py."""

import dataclasses
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.config import KNOWN_MODEL_TYPES, ModelConfig, tiny_config
from llm_np_cp_tpu.models import forward, init_params
from llm_np_cp_tpu.utils.synthetic import hf_config_dict

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))
import costs_mimo_v2 as costs  # noqa: E402
import reference_mimo_v2 as ref  # noqa: E402

CELL_CONFIG = ROOT / "benchmark" / "configs" / "mimo-v2.5-7l-ep16.json"
# the catalog row's two lists (model-configs/architectures.jsonl; the
# published config.json of XiaomiMiMo/MiMo-V2.5): layer 0 global, then
# ``w w w w g`` and ``w w w w w g`` runs
PATTERN = [0, 1, 1, 1, 1, 0] + [1, 1, 1, 1, 1, 0] * 7
FREQ = [0] + [1] * 47
# float32 against float32 at the highest matmul precision: the program and
# the reference differ in summation order alone
TOL = 2e-5

jforward = jax.jit(forward, static_argnums=(2,))


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("mimo_v2")
    hf = hf_config_dict(cfg)
    assert ModelConfig.from_hf_dict(hf) == cfg
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, hf, params


def _published() -> dict:
    """The catalog row from the cell's file: the cut keys restored."""
    with open(CELL_CONFIG) as f:
        d = json.load(f)
    d.update(num_hidden_layers=48, hybrid_layer_pattern=PATTERN,
             moe_layer_freq=FREQ, n_routed_experts=256)
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
    assert "mimo_v2" in KNOWN_MODEL_TYPES
    cfg = ModelConfig.from_hf_dict(_published())
    assert (cfg.num_hidden_layers, cfg.hidden_size, cfg.vocab_size) == (
        48, 4096, 152576)
    assert (len(cfg.global_layers), len(cfg.window_layers)) == (9, 39)
    assert cfg.global_layers[:3] == (0, 5, 11) and cfg.two_page_classes
    g, w = cfg.attn_kind("global"), cfg.attn_kind("window")
    assert (g.kv_heads, g.key_dim, g.value_dim, g.rope_theta, g.window,
            g.sink) == (4, 192, 128, 1e7, None, False)
    assert (w.kv_heads, w.key_dim, w.value_dim, w.rope_theta, w.window,
            w.sink) == (8, 192, 128, 1e4, 128, True)
    assert cfg.rope_dim == 64 and cfg.attention_value_scale == 0.707
    assert cfg.kv_token_shapes("global") == {"k": (4, 192), "v": (4, 128)}
    assert cfg.kv_token_shapes("window") == {"k": (8, 192), "v": (8, 128)}
    assert (cfg.num_experts, cfg.experts_held, cfg.num_experts_per_tok,
            cfg.num_dense_layers, cfg.routed_scaling_factor) == (
        256, 256, 8, 1, 1.0)
    assert cfg.shared_expert_intermediate_size is None
    assert cfg.layer_groups()[:3] == (
        ("attn", "dense", 0, 1), ("swa", "experts", 1, 1),
        ("swa", "experts", 2, 1))


def test_the_benchmark_configuration_is_the_row_cut_to_one_chips_share():
    with open(CELL_CONFIG) as f:
        d = json.load(f)
    cfg = ModelConfig.from_hf_dict(d)
    assert [cfg.layer_op(i) for i in range(7)] == [
        "attn", "swa", "swa", "swa", "swa", "attn", "swa"]
    assert (cfg.num_experts, cfg.experts_held, cfg.first_expert) == (256, 16, 0)
    # a token: 2 global layers x 2,560 B for as long as it lives, 5 window
    # layers x 5,120 B only while a later query can see it
    assert cfg.kv_bytes_per_token(2, "global") == 5120
    assert cfg.kv_bytes_per_token(2, "window") == 25600
    assert cfg.kv_bytes_per_token(2) == 30720
    pub = _published()
    assert {k for k in pub if d.get(k) != pub[k]} == set(d["reduced"])


@pytest.mark.parametrize("change, match", [
    ({"add_full_attention_sink_bias": True}, "add_full_attention_sink_bias"),
    # (groups are data since PR 47; groups the experts do not divide into
    # are still refused)
    ({"n_group": 3}, "group-limited routing"),
    ({"n_shared_experts": 1}, "n_shared_experts"),
    ({"rope_scaling": {"rope_type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"hybrid_layer_pattern": PATTERN[:-1]}, "hybrid_layer_pattern"),
    ({"moe_layer_freq": FREQ + [1]}, "moe_layer_freq"),
    ({"moe_layer_freq": [0, 1, 0] + [1] * 45}, "leading zeros"),
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"attention_bias": True}, "attention_bias"),
    ({"swa_head_dim": 128}, "swa_head_dim"),
], ids=lambda v: v if isinstance(v, str) else None)
def test_what_has_no_equations_is_refused_by_its_key(change, match):
    with pytest.raises(ValueError, match=match):
        ModelConfig.from_hf_dict({**_published(), **change})


def test_gemma2_states_its_alternation_through_the_same_declaration():
    cfg = tiny_config("gemma2", num_hidden_layers=4)
    assert cfg.window_pattern == (1, 0) and not cfg.two_page_classes
    assert [cfg.layer_is_sliding(i) for i in range(4)] == [True, False] * 2
    assert cfg.attn_kind("window").window == cfg.sliding_window
    assert cfg.attn_kind("global").window is None and cfg.window_layers == ()
    # (same shapes: Gemma-2's window layers share the one page class)
    assert cfg.kv_token_shapes("window") == cfg.kv_token_shapes("global")
    other = dataclasses.replace(cfg, window_pattern=(0, 0, 1))
    assert [other.layer_is_sliding(i) for i in range(4)] == [
        False, False, True, False]


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
    "no_sink", "window_off_by_one", "no_value_scale", "rotate_all", "bf16"])
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


def test_attention_over_query_blocks_is_attention(monkeypatch):
    """Above a size from the shapes ``gqa_attention`` goes over the
    queries in blocks: the same rows, sink and window included."""
    from llm_np_cp_tpu.ops import attention as att

    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 40, 4, 24)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 40, 2, 24)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 40, 2, 16)), jnp.float32)
    sink = jnp.asarray(rng.standard_normal(4), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(40)[None], (2, 40))
    mask = att.causal_mask(pos, jnp.arange(40), window=8)
    whole = att.gqa_attention(q, k, v, mask, scale=0.2, sink=sink)
    assert att.query_block(2, 4, 40, 40) == 40
    # 4 sequences of 4,864 tokens at 64 heads: 24 GB of scores at once
    assert att.query_block(4, 64, 4864, 4864) == 48
    # ... and the longest check the benchmark had stays one einsum
    assert att.query_block(4, 16, 2560, 2560) == 2560
    monkeypatch.setattr(att, "QUERY_BLOCK_SCORE_BYTES", 4 * 2 * 4 * 40 * 16)
    monkeypatch.setattr(att, "_BLOCK_SCORE_BYTES", 4 * 2 * 4 * 40 * 16)
    assert att.query_block(2, 4, 40, 40) == 16
    blocks = att.gqa_attention(q, k, v, mask, scale=0.2, sink=sink)
    assert blocks.shape == (2, 40, 4, 16)
    assert float(jnp.abs(blocks - whole).max()) < 1e-6


def test_the_experts_go_over_a_long_forward_in_chunks_of_tokens(tiny, monkeypatch):
    from llm_np_cp_tpu.models import transformer
    from llm_np_cp_tpu.ops.activations import ACT2FN

    cfg, _, params = tiny
    w = {k: v[0] for k, v in params["layers"][1].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 20, 64), jnp.float32)
    live = jnp.ones((2, 20), jnp.bool_).at[1, 17:].set(False)
    run = lambda: transformer.experts_block(  # noqa: E731
        w, x, config=cfg, act=ACT2FN["silu"], live=live)
    with jax.default_matmul_precision("highest"):
        whole, chosen, load = run()
        monkeypatch.setattr(transformer, "EXPERT_CHUNK_PAIRS", 4 * 12)
        parts, chosen2, load2 = run()  # 40 tokens: 4 chunks of 12
    assert float(jnp.abs(parts - whole).max()) < 1e-6
    assert np.array_equal(chosen, chosen2) and np.array_equal(load, load2)
    assert int(load.sum()) == 37 * 4  # the dead tokens are routed nowhere


# ----------------------------------------------------------------------
# the held share of the routed experts
# ----------------------------------------------------------------------

def test_the_shares_of_the_routed_experts_add_up_to_the_uncut_layer(tiny):
    """Four holders of four experts each (16 experts, top-4): the routed
    parts summed == the uncut layer (no shared expert to count once)."""
    from llm_np_cp_tpu.models.transformer import experts_block
    from llm_np_cp_tpu.ops.activations import ACT2FN

    cfg, _, params = tiny
    w = {k: v[0] for k, v in params["layers"][1].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 9, 64), jnp.float32)
    act = ACT2FN["silu"]
    with jax.default_matmul_precision("highest"):
        whole, chosen, load = experts_block(w, x, config=cfg, act=act)
        total, loads = jnp.zeros_like(x), []
        for first in range(0, 16, 4):
            part_cfg = dataclasses.replace(
                cfg, num_experts_held=4, first_expert=first)
            part_w = dict(w, **{k: w[k][first:first + 4]
                                for k in ("w1", "w3", "w2")})
            out, part_chosen, part_load = experts_block(
                part_w, x, config=part_cfg, act=act)
            assert np.array_equal(part_chosen, chosen)  # one router
            loads.append(part_load)
            total = total + (out - x)
    assert np.array_equal(jnp.concatenate(loads), load)
    assert int(load.sum()) == 2 * 9 * 4  # every (token, expert) pair once
    assert float(jnp.abs(total + x - whole).max()) < 1e-5
    assert float(jnp.abs(whole - x).max()) > 1e-3


def test_a_held_share_is_the_reference_with_the_same_share(tiny):
    cfg, _, _ = tiny
    part = dataclasses.replace(cfg, num_experts_held=4, first_expert=8)
    part_hf = hf_config_dict(part)
    assert (part_hf["n_routed_experts"], part_hf["router_experts"],
            part_hf["first_expert"]) == (4, 16, 8)
    assert ModelConfig.from_hf_dict(part_hf) == part
    params = init_params(jax.random.PRNGKey(2), part, dtype=jnp.float32)
    assert params["layers"][1]["w1"].shape == (1, 4, 64, 32)
    assert params["layers"][1]["router"].shape == (1, 64, 16)
    assert params["layers"][1]["attn_sink"].dtype == jnp.float32
    assert "attn_sink" not in params["layers"][3]  # a global layer
    ids = _ids(28, seed=9)  # (the length the cases above compiled for)
    with jax.default_matmul_precision("highest"):
        got, _ = jforward(params, jnp.asarray(ids)[None], part)
    assert _gap(got[0], ref.forward(params, part_hf, ids)) < TOL


def test_hf_key_map_round_trip(tiny, tmp_path):
    from llm_np_cp_tpu.models import mimo_v2
    from llm_np_cp_tpu.utils.loading import load_params
    from llm_np_cp_tpu.utils.synthetic import hf_state_dict, write_hf_checkpoint

    cfg, _, _ = tiny
    part = dataclasses.replace(cfg, num_experts_held=4, first_expert=2)
    params = init_params(jax.random.PRNGKey(4), part, dtype=jnp.float32)
    tensors = hf_state_dict(jax.tree.map(np.asarray, params), part)
    keys = set(tensors)
    assert "model.layers.1.self_attn.attention_sink_bias" in keys
    assert "model.layers.0.self_attn.attention_sink_bias" not in keys
    assert "model.layers.1.mlp.experts.2.gate_proj.weight" in keys
    assert "model.layers.1.mlp.experts.0.gate_proj.weight" not in keys
    # stored [out, in]: a window layer's 2 kv heads, a global layer's 1
    assert tensors["model.layers.1.self_attn.k_proj.weight"].shape == (48, 64)
    assert tensors["model.layers.1.self_attn.v_proj.weight"].shape == (32, 64)
    assert tensors["model.layers.0.self_attn.k_proj.weight"].shape == (24, 64)
    assert len(list(mimo_v2.layer_tensors(part))) == len(keys) - 3
    write_hf_checkpoint(tmp_path, part, tensors, shards=2)
    loaded, cfg2 = load_params(tmp_path, dtype=jnp.float32, use_native=False)
    assert cfg2 == part
    for a, b in zip(jax.tree.leaves(loaded), jax.tree.leaves(params)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


# ----------------------------------------------------------------------
# the kernel: a value width that is not the key width, a sink, a table
# that starts past position 0
# ----------------------------------------------------------------------

def _ragged_case(kh, g, d, dv, merged, sink, base, bs=8, mb=4, win=12):
    from llm_np_cp_tpu.ops.pallas.decode_attention import RAGGED_Q_TILE as QT

    rng = np.random.default_rng(kh * 100 + d)
    rows = 3
    # a prefill slice of 11 tokens 20 slots into its row, two decode rows
    segs = [(0, 20, 11), (1, 37, 1), (2, 9, 1)]
    b0 = np.zeros(rows, np.int32)
    tables = np.zeros((rows, mb), np.int32)
    for row, qpos0, n in segs:
        first = max(0, qpos0 - win + 1) // bs if base else 0
        b0[row] = first
        for c in range((qpos0 + n - 1) // bs - first + 1):
            tables[row, c] = 1 + row * mb + c
    tile_row, tile_qpos0, tile_qlen, tok_row, tok_slot, live = [], [], [], [], [], []
    for row, qpos0, n in segs:
        for t0 in range(0, n, QT):
            tile_row.append(row)
            tile_qpos0.append(qpos0 + t0)
            tile_qlen.append(min(QT, n - t0))
            for i in range(QT):
                ok = i < min(QT, n - t0)
                tok_row.append(row)
                tok_slot.append(qpos0 + t0 + i if ok else 0)
                live.append(ok)
    t, h, nbk = len(tok_row), kh * g, 1 + rows * mb
    f32, i32 = jnp.float32, jnp.int32
    q = jnp.asarray(rng.standard_normal((t, h, d)), f32)
    kp = jnp.asarray(rng.standard_normal(
        (nbk, bs, kh * d) if merged else (nbk, bs, kh, d)), f32)
    vp = jnp.asarray(rng.standard_normal(
        (nbk, bs, kh * dv) if merged else (nbk, bs, kh, dv)), f32)
    kw = dict(scale=d ** -0.5,
              sink=jnp.asarray(3 + rng.standard_normal(h), f32) if sink else None,
              block0=jnp.asarray(b0) if base else None)
    tiles = (jnp.asarray(tile_row, i32), jnp.asarray(tile_qpos0, i32),
             jnp.asarray(tile_qlen, i32))
    toks = (jnp.asarray(tok_row, i32), jnp.asarray(tok_slot, i32),
            jnp.asarray(live))
    return (q, kp, vp, jnp.asarray(tables)), tiles, toks, (
        jnp.zeros(rows, i32), jnp.int32(win)), kw, np.asarray(live)


@pytest.mark.parametrize("kh,g,d,dv,merged,sink,base", [
    (2, 2, 24, 16, False, True, True),     # the tiny preset's window layer
    (2, 2, 192, 128, True, True, True),    # the model's widths: pairs of
    (4, 2, 192, 128, True, False, False),  # heads share three rows of lanes
    (2, 2, 64, 64, True, True, False),     # a sink on a head_dim-64 pool
], ids=["tiny-window", "mimo-window", "mimo-global", "sink-64"])
def test_ragged_kernel_matches_its_twin(kh, g, d, dv, merged, sink, base):
    from llm_np_cp_tpu.ops.pallas.decode_attention import (
        ragged_paged_attention,
        ragged_paged_attention_xla,
    )

    mb = 4 if base else 5
    win = 12 if base else 1 << 30
    pool, tiles, toks, tail, kw, live = _ragged_case(
        kh, g, d, dv, merged, sink, base, mb=mb, win=win)
    out = ragged_paged_attention(*pool, *tiles, *tail, interpret=True, **kw)
    twin = ragged_paged_attention_xla(*pool, *toks, *tail, **kw)
    assert out.shape == (len(live), kh * g, dv)
    assert float(jnp.abs(out - twin)[live].max()) < 2e-5
    if sink:  # a sink is not nothing
        bare = ragged_paged_attention_xla(*pool, *toks, *tail,
                                          **{**kw, "sink": None})
        assert float(jnp.abs(bare - twin)[live].max()) > 1e-3


# ----------------------------------------------------------------------
# the cost file, to the parameter
# ----------------------------------------------------------------------

def test_costs_count_the_cut_to_the_parameter():
    with open(CELL_CONFIG) as f:
        c = json.load(f)
    glob = 50331648 + 3145728 + 2097152 + 33554432
    wind = 50331648 + 6291456 + 4194304 + 33554432 + 64
    assert (costs.attention_params(c, "global"), costs.attention_params(
        c, "window")) == (glob, wind) == (89128960, 94371904)
    assert costs.dense_ff_params(c) == 201326592
    assert costs.expert_params(c) == 25165824  # 50.3 MB in bf16
    held = 16 * 25165824 + 1048576 + 256 + 8192
    assert (wind + held, glob + held) == (498082112, 492839168)
    total = (glob + 201326592 + 8192) + 5 * (wind + held) + (glob + held) \
        + 2 * 624951296 + 4096
    assert costs.param_count(c) == total == 4523620160 == c["sizes"]["parameters"]
    cfg = ModelConfig.from_hf_dict(c)
    shapes = jax.eval_shape(
        lambda: init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == total
    assert costs.kv_bytes_per_token(c, "global") == cfg.kv_bytes_per_token(
        2, "global") == 5120
    assert costs.kv_bytes_per_token(c, "window") == cfg.kv_bytes_per_token(
        2, "window") == 25600
    assert costs.param_count(_published()) == 308778780864


def test_a_ticks_bytes_and_operations_by_hand():
    with open(CELL_CONFIG) as f:
        c = json.load(f)
    # 64 decode rows of 3,400 tokens of context each, 96 experts touched
    cost = costs.tick_cost(c, tokens=64, rows=64, context_tokens=64 * 3400,
                           experts_touched=96, pairs_held=32)
    outside = 4523620160 - 624951296 - 6 * 16 * 25165824
    assert costs.dense_streamed_params(c) == outside
    want = (2 * outside + 96 * 50331648
            + 5120 * (64 * 3400 + 64)          # global pages: all of it
            + 25600 * (64 * 128 + 64))         # window pages: 128 a row
    assert cost["bytes"] == want
    assert costs.window_positions(c, 50, 1) == 50
    assert costs.window_positions(c, 3400, 128) == 255
    per_pos = 2 * (192 + 128) * 64
    assert cost["flops"] == (
        2 * costs.active_matmul_params(c) * 64 + 2 * 25165824 * 32
        + 2 * 624951296 * 64 + per_pos * (2 * 3400 + 5 * 128) * 64)
    # what the attention calls of a tick stream, from the tick's arguments
    assert costs.attention_bytes(c, 100, 10, 64) == 64 * (100 * 5120 + 10 * 25600)
