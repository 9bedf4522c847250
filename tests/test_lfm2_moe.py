"""LFM2-MoE (``model_type: lfm2_moe``): gated short-convolution layers with
a per-slot state beside the paged K/V pool, dropless sigmoid-routed experts,
a layer stack that is more than one kind of layer.

Everything is compared with the plain reference
(``benchmark/reference_lfm2_moe.py``: float32, no cache, every expert on
every token) on seeded random weights of the TINY preset
(``tiny_config("lfm2_moe")``: 2 dense blocks + two periods ``A c c c``, 8
experts top-2, hidden 64) - logits, never sampled tokens.

Tolerances, and why:

- ``TOL`` = 1e-4 of the logits' (max - mean) spread, float32 against
  float32: the program and the reference do the same sums in another order
  (a grouped matmul against 8 dense ones, a scan against a loop), which on
  these sizes differ by 1e-6 of the spread at most; 1e-4 leaves two orders
  of room and is three orders under what the lower precisions below do.
- a bf16 ROUTER flips expert choices (neighbouring scores differ by less
  than a bf16 ulp of 0.5 = 2e-3) and a flipped expert moves logits by 1e-2
  of the spread: it must FAIL ``TOL``; so must a conv state kept in int8
  (a 1/127 relative step on every carried value).
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

import reference_lfm2_moe as ref  # noqa: E402

import llm_np_cp_tpu.serve.engine as engine_mod  # noqa: E402
from llm_np_cp_tpu.cache import KVCache  # noqa: E402
from llm_np_cp_tpu.config import ModelConfig, tiny_config  # noqa: E402
from llm_np_cp_tpu.models.transformer import forward, init_params  # noqa: E402
from llm_np_cp_tpu.ops import moe  # noqa: E402
from llm_np_cp_tpu.ops.activations import ACT2FN  # noqa: E402
from llm_np_cp_tpu.ops.sampling import Sampler  # noqa: E402
from llm_np_cp_tpu.parallel.sharding import MeshPlan  # noqa: E402
from llm_np_cp_tpu.serve import ServeEngine  # noqa: E402

TOL = 1e-4

# the tiny preset as a published config.json would state it (what the plain
# reference and ``from_hf_dict`` read)
TINY_HF = {
    "model_type": "lfm2_moe", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 10,
    "layer_types": ["conv", "conv", "full_attention", "conv", "conv", "conv",
                    "full_attention", "conv", "conv", "conv"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "max_position_embeddings": 512, "rope_theta": 10000.0, "norm_eps": 1e-5,
    "conv_L_cache": 3, "conv_bias": False, "num_dense_layers": 2,
    "num_experts": 8, "num_experts_per_tok": 2, "norm_topk_prob": True,
    "use_expert_bias": True, "routed_scaling_factor": 1,
}


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("lfm2_moe")
    assert cfg == ModelConfig.from_hf_dict(TINY_HF)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _spread(logits: np.ndarray) -> float:
    return float((logits.max(-1) - logits.mean(-1)).mean())


def _gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest logit difference as a share of the reference's spread."""
    return float(np.abs(got - want).max()) / _spread(want)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


# ----------------------------------------------------------------------
# the model: forward against the reference
# ----------------------------------------------------------------------

def test_a_configuration_file_says_how_alike_its_random_experts_are():
    """``init_expert_specific`` is a key of a configuration's FILE (the
    benchmark's LFM2 configuration sets it and says why): without it every
    expert is an independent draw, with it the experts of a layer share
    most of a draw.  No forward reads it."""
    plain = ModelConfig.from_hf_dict(TINY_HF)
    alike = ModelConfig.from_hf_dict(dict(TINY_HF, init_expert_specific=0.25))
    assert plain.init_expert_specific is None
    assert alike.init_expert_specific == 0.25

    def corr(cfg):
        w1 = np.asarray(init_params(jax.random.PRNGKey(0), cfg, jnp.float32)
                        ["layers"][1]["w1"][0])  # [E, H, I]
        return float(np.corrcoef(w1[0].ravel(), w1[1].ravel())[0, 1])

    assert abs(corr(plain)) < 0.1
    assert corr(alike) == pytest.approx(1 / (1 + 0.25 ** 2), abs=0.03)


def test_stack_is_runs_of_like_layers(tiny):
    cfg, params = tiny
    assert cfg.layer_groups() == (
        ("conv", "dense", 0, 2), ("attn", "experts", 2, 1),
        ("conv", "experts", 3, 1), ("conv", "experts", 4, 1),
        ("conv", "experts", 5, 1), ("attn", "experts", 6, 1),
        ("conv", "experts", 7, 1), ("conv", "experts", 8, 1),
        ("conv", "experts", 9, 1))
    assert cfg.attn_layers == (2, 6) and len(cfg.conv_layers) == 8
    assert [g["ln_mlp_in"].shape[0] for g in params["layers"]] == [2] + [1] * 8
    assert ref.runs(TINY_HF) == [(op, ff, n) for op, ff, _, n in cfg.layer_groups()]
    # the selection bias is float32 and not zero, the conv filter has its taps
    assert params["layers"][1]["expert_bias"].dtype == jnp.float32
    assert float(jnp.abs(params["layers"][1]["expert_bias"]).min()) > 0
    assert params["layers"][0]["conv_filter"].shape == (2, 64, 3)


def test_forward_matches_reference(tiny):
    cfg, params = tiny
    ids = np.asarray(_prompts([48], seed=3))
    logits, _, aux = forward(params, ids, cfg, output_experts=True)
    want, chosen = ref.forward(params, TINY_HF, ids[0], return_experts=True)
    assert _gap(np.asarray(logits[0]), np.asarray(want)) < TOL
    assert np.array_equal(np.asarray(aux["experts"][:, 0]), np.asarray(chosen))


def test_cache_prefill_then_decode_matches_full_forward(tiny):
    """The offline path: ``KVCache`` holds K/V of the attention layers
    only and the conv state beside them."""
    cfg, params = tiny
    ids = np.asarray(_prompts([20], seed=4))
    want = np.asarray(ref.forward(params, TINY_HF, ids[0]))
    cache = KVCache.init(cfg, 1, 32, dtype=jnp.float32)
    assert cache.k.shape[0] == 2 and cache.conv.shape == (8, 1, 2, 64)
    parts = []
    for lo, hi in ((0, 7), (7, 8), (8, 9), (9, 20)):  # chunks and single steps
        out, cache = forward(params, ids[:, lo:hi], cfg, cache)
        parts.append(np.asarray(out[0]))
    assert _gap(np.concatenate(parts), want) < TOL


def test_left_padded_ragged_batch_matches_reference(tiny):
    """Padding before a sequence's start is not part of it: its gated input
    is zero and it is routed to no expert."""
    cfg, params = tiny
    a, b = _prompts([5, 9], seed=5)
    ids = np.zeros((2, 9), np.int32)
    ids[0, 4:], ids[1] = a, b
    mask = ids > 0
    cache = KVCache.init(cfg, 2, 16, dtype=jnp.float32)
    out, _ = forward(params, ids, cfg, cache, attn_mask=jnp.asarray(mask),
                     pad_offsets=jnp.asarray([4, 0], jnp.int32))
    assert _gap(np.asarray(out[0, 4:]), np.asarray(ref.forward(params, TINY_HF, a))) < TOL
    assert _gap(np.asarray(out[1]), np.asarray(ref.forward(params, TINY_HF, b))) < TOL


# ----------------------------------------------------------------------
# the served path: logits of the unified tick against the reference
# ----------------------------------------------------------------------

class Probe:
    """The logits the tick samples from, tick by tick: ``final_logits``
    (the XLA tail, ``sample_epilogue="off"``) wrapped with a callback."""

    def __init__(self, monkeypatch):
        self.ticks: list[np.ndarray] = []
        real = engine_mod.final_logits

        def probed(params, x, config, **kw):
            logits = real(params, x, config, **kw)
            jax.debug.callback(lambda a: self.ticks.append(np.asarray(a)), logits)
            return logits

        monkeypatch.setattr(engine_mod, "final_logits", probed)


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                       sample_epilogue="off", **kw)


def _serve(engine, probe, reqs, between=None):
    """Run to completion; per request the logits each of its tokens was
    sampled from (a requeued request's tokens are teacher-forced back, so
    every position is sampled once)."""
    got = {r.req_id: [] for r in reqs}
    tick = 0
    while True:
        n_before = {r.req_id: len(r.generated) for r in reqs}
        more = engine.step()
        jax.effects_barrier()
        for r in reqs:
            if len(r.generated) > n_before[r.req_id]:
                # an emitting row still holds its slot when step() returns,
                # unless it finished: then the slot it had is in the record
                slot = r.slot if r.slot is not None and r.slot >= 0 else r.extra["_slot"]
                got[r.req_id].append(probe.ticks[-1][slot, 0])
            if r.slot is not None and r.slot >= 0:
                r.extra["_slot"] = r.slot
        tick += 1
        if between is not None:
            between(tick)
        if not more:
            return got


def _assert_reference(params, reqs, got):
    for r in reqs:
        seq = list(r.prompt) + list(r.generated)
        want = np.asarray(ref.forward(params, TINY_HF, seq))
        p = len(r.prompt)
        have = np.stack(got[r.req_id])
        assert have.shape[0] == len(r.generated)
        assert _gap(have, want[p - 1:p - 1 + len(r.generated)]) < TOL, r.req_id


SERVE_CASES = {
    # a 21-token prompt in chunks of 8: two chunk boundaries inside it
    "chunk_boundary": dict(lengths=[21], new=5),
    # two sequences' chunks packed in one tick (budget 4 + 2 x 8 tokens)
    "two_prefills_one_tick": dict(lengths=[7, 6], new=4),
    # a short prompt decodes while a long one is still being prefilled
    "decode_beside_prefill": dict(lengths=[3, 30], new=8),
    # one slot: the second request runs in the slot the first one left
    "slot_reused": dict(lengths=[9, 11], new=4, engine=dict(max_slots=1)),
    # a pool too small for all three: one is evicted and requeued
    "evict_requeue": dict(lengths=[4, 5, 3], new=20,
                          engine=dict(max_slots=2, num_blocks=6)),
}


@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_served_logits_match_reference(tiny, monkeypatch, case):
    cfg, params = tiny
    spec = SERVE_CASES[case]
    probe = Probe(monkeypatch)
    engine = _engine(cfg, params, **spec.get("engine", {}))
    assert engine.mixed and engine.ragged_attn_impl == "pallas"
    reqs = [engine.submit(p, max_new_tokens=spec["new"], seed=i)
            for i, p in enumerate(_prompts(spec["lengths"], seed=11))]
    seen = {"packed": 0, "beside": 0}

    def between(_tick):
        running = engine.scheduler.running
        seen["beside"] += (any(r.prefilled for r in running)
                           and any(not r.prefilled for r in running))

    got = _serve(engine, probe, reqs, between)
    assert all(len(r.generated) == spec["new"] for r in reqs)
    if case == "decode_beside_prefill":
        assert seen["beside"] > 0, "no tick held a decode row beside a prefill"
    if case == "evict_requeue":
        assert engine.scheduler.n_preemptions > 0, "pool not tight enough"
    if case == "slot_reused":
        assert {r.extra["_slot"] for r in reqs} == {0}
    _assert_reference(params, reqs, got)
    assert engine.pool.free_list.num_allocated == 0


def test_pool_holds_attention_layers_only_and_state_beside_it(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    pages = engine.pool.pages
    assert pages.k.shape == (2, 48, 8, 2, 16)  # 2 of 10 layers have K/V
    # conv layers, slots, taps - 1, H: the one leaf of the state pytree
    assert {k: v.shape for k, v in pages.state.items()} == {"conv": (8, 4, 2, 64)}
    assert len(pages.pool_arrays()) == 2
    # the bucket set is the one a stack of one kind of layer gets
    plain = ServeEngine(
        init_params(jax.random.PRNGKey(0), tiny_config("qwen2"), dtype=jnp.float32),
        tiny_config("qwen2"), max_slots=4, num_blocks=48, block_size=8,
        max_seq_len=64, prefill_chunk=8, cache_dtype=jnp.float32)
    assert engine.mixed_buckets == plain.mixed_buckets
    assert plain.pool.pages.state is None


def test_a_sequences_logits_do_not_depend_on_the_rest_of_the_tick(tiny, monkeypatch):
    """Dropless: the same request alone and beside three others."""
    cfg, params = tiny
    prompts = _prompts([10, 14, 5, 9], seed=21)
    probe = Probe(monkeypatch)
    alone = _engine(cfg, params)
    r0 = alone.submit(prompts[0], max_new_tokens=6, seed=0)
    got_alone = _serve(alone, probe, [r0])[r0.req_id]
    probe.ticks.clear()
    crowd = _engine(cfg, params)
    reqs = [crowd.submit(p, max_new_tokens=6, seed=i) for i, p in enumerate(prompts)]
    got_crowd = _serve(crowd, probe, reqs)[reqs[0].req_id]
    assert reqs[0].generated == r0.generated
    # the same sums at another packed width: float32 rounding only
    assert _gap(np.stack(got_crowd), np.stack(got_alone)) < TOL


# ----------------------------------------------------------------------
# routing
# ----------------------------------------------------------------------

def _expert_layer(seed=0, *, e=8, h=64, i=32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    return dict(
        router=jax.random.normal(ks[0], (h, e)) * 0.2,
        w1=jax.random.normal(ks[1], (e, h, i)) * 0.1,
        w3=jax.random.normal(ks[2], (e, h, i)) * 0.1,
        w2=jax.random.normal(ks[3], (e, i, h)) * 0.1,
    ), jax.random.normal(ks[4], (24, h))


def _ref_layer(x, w, bias, chosen=None):
    cfg = dict(TINY_HF, use_expert_bias=bias is not None)
    w = dict(w, **({"expert_bias": bias} if bias is not None else {}))
    with jax.default_matmul_precision("highest"):
        return ref.experts_ff(x, w, cfg, chosen)


def test_bias_chooses_but_does_not_weigh():
    w, x = _expert_layer()
    bias = jnp.zeros(8).at[5].set(1.0)  # expert 5 always wins a place
    idx, wts = moe.route_sigmoid_topk(x, w["router"], bias, top_k=2)
    plain, _ = moe.route_sigmoid_topk(x, w["router"], None, top_k=2)
    assert (np.asarray(idx) == 5).any(axis=1).all()
    assert not np.array_equal(np.sort(idx, -1), np.sort(plain, -1))
    scores = np.asarray(jax.nn.sigmoid(x @ w["router"]))
    picked = np.take_along_axis(scores, np.asarray(idx), 1)
    np.testing.assert_allclose(
        np.asarray(wts), picked / (picked.sum(1, keepdims=True) + 1e-6), rtol=1e-6)
    out, chosen, _ = moe.moe_dropless(
        x, w["router"], bias, w["w1"], w["w3"], w["w2"], act=ACT2FN["silu"], top_k=2)
    want, ref_chosen = _ref_layer(x, w, bias)
    assert np.array_equal(np.asarray(chosen), np.asarray(ref_chosen))
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)


def test_dropless_under_skew_where_capacity_routing_drops():
    """Every token to the same two experts: all of them are computed."""
    w, x = _expert_layer(1)
    bias = jnp.zeros(8).at[jnp.array([2, 6])].set(5.0)
    out, chosen, load = moe.moe_dropless(
        x, w["router"], bias, w["w1"], w["w3"], w["w2"], act=ACT2FN["silu"], top_k=2)
    assert set(np.asarray(chosen).ravel()) == {2, 6}
    assert np.asarray(load).tolist() == [0, 0, 24, 0, 0, 0, 24, 0]
    want, _ = _ref_layer(x, w, bias)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    # the capacity layer keeps ceil(24 * 2 / 8 * 2) = 12 slots an expert
    # and drops the rest of such a batch: its output is not the reference's
    router = w["router"].at[:, jnp.array([2, 6])].add(10.0 * jnp.sign(x.mean(0))[:, None])
    capped, _ = moe.moe_mlp(x[None], router, w["w1"], w["w3"], w["w2"],
                            act=ACT2FN["silu"], top_k=2, capacity_factor=2.0)
    dropped = np.abs(np.asarray(capped[0])).sum(-1) == 0
    assert dropped.any(), "capacity routing dropped nothing: not a skewed batch"


def test_dead_lanes_reach_no_expert():
    w, x = _expert_layer(2)
    live = jnp.arange(24) < 16
    out, _, load = moe.moe_dropless(
        x, w["router"], None, w["w1"], w["w3"], w["w2"], act=ACT2FN["silu"],
        top_k=2, live=live)
    assert int(load.sum()) == 16 * 2
    assert float(jnp.abs(out[16:]).max()) == 0.0
    want, _ = _ref_layer(x[:16], w, None)
    np.testing.assert_allclose(np.asarray(out[:16]), np.asarray(want), atol=1e-5)


def test_shares_of_the_experts_add_up_to_the_layer():
    """A layer told which experts it holds computes their part: two halves
    (experts 0-3 and 4-7), each routing over all 8, add up to the whole."""
    w, x = _expert_layer(3)
    kw = dict(act=ACT2FN["silu"], top_k=2)
    whole, _, _ = moe.moe_dropless(x, w["router"], None, w["w1"], w["w3"], w["w2"], **kw)
    parts = [moe.moe_dropless(x, w["router"], None, w["w1"][lo:lo + 4],
                              w["w3"][lo:lo + 4], w["w2"][lo:lo + 4],
                              first_expert=lo, **kw) for lo in (0, 4)]
    np.testing.assert_allclose(
        np.asarray(parts[0][0] + parts[1][0]), np.asarray(whole), atol=1e-5)
    assert int(parts[0][2].sum() + parts[1][2].sum()) == 24 * 2


# ----------------------------------------------------------------------
# precision: the comparison is tight enough that a lower one fails it
# ----------------------------------------------------------------------

def test_bf16_router_fails_the_tolerance(tiny, monkeypatch):
    cfg, params = tiny
    ids = np.asarray(_prompts([48], seed=3))
    want = np.asarray(ref.forward(params, TINY_HF, ids[0]))
    real = moe.route_sigmoid_topk
    monkeypatch.setattr(
        moe, "route_sigmoid_topk",
        lambda *a, **kw: real(*a, **dict(kw, score_dtype=jnp.bfloat16)))
    jax.clear_caches()
    try:
        logits, _ = forward(params, ids, cfg)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert _gap(np.asarray(logits[0]), want) > 10 * TOL


def test_int8_conv_state_fails_the_tolerance(tiny):
    cfg, params = tiny
    # the tiny preset's gated inputs are of order 0.03 and its conv layers a
    # small part of the stream: widen in_proj (z = B * x grows 16-fold), so
    # that what the state carries is what the logits are made of
    params = dict(params, layers=[
        dict(g, in_proj=g["in_proj"] * 4.0) if "in_proj" in g else g
        for g in params["layers"]])
    hf = TINY_HF
    ids = np.asarray(_prompts([20], seed=4))
    want = np.asarray(ref.forward(params, hf, ids[0]))

    def run(quantize):
        cache = KVCache.init(cfg, 1, 32, dtype=jnp.float32)
        parts = []
        for t in range(ids.shape[1]):  # token by token: every z passes the state
            out, cache = forward(params, ids[:, t:t + 1], cfg, cache)
            if quantize:
                scale = jnp.max(jnp.abs(cache.conv), axis=-1, keepdims=True) / 127.0
                q = jnp.round(cache.conv / jnp.where(scale == 0, 1.0, scale))
                cache = cache._replace(conv=q.astype(jnp.int8).astype(jnp.float32) * scale)
            parts.append(np.asarray(out[0]))
        return np.concatenate(parts)

    assert _gap(run(False), want) < TOL
    assert _gap(run(True), want) > 10 * TOL


# ----------------------------------------------------------------------
# what is refused, by the flag that asked for it
# ----------------------------------------------------------------------

@pytest.mark.parametrize("kw, flag", [
    (dict(enable_prefix_cache=True), "--prefix-cache"),
    (dict(spec_k=2), "--spec-k"),
    (dict(mesh_plan=MeshPlan(model=2)), "--mesh model>1"),
])
def test_start_up_refusals_name_the_flag(tiny, kw, flag):
    cfg, params = tiny
    with pytest.raises(ValueError, match="conv layers.*refused: " + flag.replace(">", r"\>")):
        ServeEngine(params, cfg, max_slots=2, num_blocks=16, block_size=8,
                    max_seq_len=32, cache_dtype=jnp.float32, **kw)


def test_unknown_model_type_is_not_answered_as_a_llama():
    with pytest.raises(ValueError, match="unknown model_type 'lfm3'"):
        ModelConfig.from_hf_dict(dict(TINY_HF, model_type="lfm3"))
    # a config with no model_type at all is still the llama it always was
    d = {k: v for k, v in TINY_HF.items() if k != "model_type"}
    assert ModelConfig.from_hf_dict(d).model_type == "llama"


def test_layer_types_must_name_every_layer():
    with pytest.raises(ValueError, match="layer_types names 10 layers"):
        ModelConfig.from_hf_dict(dict(TINY_HF, num_hidden_layers=12))


# ----------------------------------------------------------------------
# spans and counters
# ----------------------------------------------------------------------

def test_tick_arguments_counters_and_scopes(tiny):
    from llm_np_cp_tpu.models.transformer import STEP_SCOPES
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    cfg, params = tiny
    tracer = TraceRecorder()
    engine = ServeEngine(params, cfg, max_slots=4, num_blocks=48, block_size=8,
                         max_seq_len=64, prefill_chunk=8,
                         cache_dtype=jnp.float32, tracer=tracer)
    assert engine.epilogue_impl == "fused"
    # (both prompts fit the first tick's budget of 20 — a chunk each, then
    # the leftover — so the two rows decode in step)
    for i, p in enumerate(_prompts([9, 11], seed=2)):
        engine.submit(p, max_new_tokens=5, seed=i)
    fetches = engine.n_host_fetches
    engine.run_until_complete()
    ticks = [e for e in tracer.events()
             if e.get("name") == "tick" and e["args"].get("decode_tokens")]
    assert ticks
    for ev in ticks:
        a = ev["args"]
        # 8 expert layers x 8 experts; 2 rows x top-2 = 4 pairs a layer
        assert 8 <= a["experts_touched"] <= 32
        assert a["expert_load_mean"] == pytest.approx(2 * 2 / 8)
        assert a["expert_load_mean"] <= a["expert_load_max"] <= 2
        assert a["state_slots_live"] <= 2 and a["host_fetches"] == 1
        # (row tiles are the Pallas grouped matmul's: on the CPU the
        # experts run lax.ragged_dot, which has none)
        assert "expert_row_tiles" not in a
        # ... and there XLA moves the rows of all the pairs routed: the
        # tick's live tokens x top-2 x 8 expert layers, every one held
        assert a["expert_rows_impl"] == "xla"
        assert a["pairs_routed"] == a["pairs_held"] == (
            a["prefill_tokens"] + a["decode_tokens"]) * 2 * 8
    # the counts came back with the tick's one fetch
    assert engine.n_host_fetches - fetches == engine.n_dispatches
    text = engine.metrics.prometheus()
    for name in ("moe_ticks_total", "moe_experts_touched_total",
                 'moe_expert_load_total{kind="max"}', "conv_state_slots_live"):
        assert name in text, name
    # the op map knows the new scopes; the conv state is the mixer's own,
    # not a pool move (PR 34: `pool.move_share` reads the K/V pool alone)
    assert {"conv", "moe_route", "moe_experts"} <= set(STEP_SCOPES)
    table = engine.device_op_map()
    scopes = {v[0] for v in table.values() if v}
    assert {"conv", "moe_route", "moe_experts", "mlp", "attn"} <= scopes
    # ... which is written in place, whole: no run of layers takes its rows
    # out of it or puts them back (PR 34)
    moves = [v for k, v in table.items() if "f32[8,4,2,64]" in k]
    assert moves, "no operation moves the conv state"
    assert any(v and v[0] == "conv" for v in moves), moves
    assert not any(v and v[1] for v in moves), moves
    assert not any("f32[1,4,2,64]" in k or "f32[2,4,2,64]" in k for k in table)
    # where the kernel runs (a row tile of 16, as on a TPU) each tick says
    # how many tiles its groups took, from the same fetched counts: at most
    # 2 rows an expert here, so one tile a touched (layer, expert)
    engine._expert_row_tile = lambda dense_width: 16
    seen = len(tracer.events())
    engine.submit(_prompts([9], seed=5)[0], max_new_tokens=3, seed=0)
    engine.run_until_complete()
    tiled = [e["args"] for e in tracer.events()[seen:]
             if e.get("name") == "tick" and "experts_touched" in e["args"]]
    assert tiled and all(a["expert_row_tile"] == 16 for a in tiled)
    assert all(a["expert_row_tiles"] == a["experts_touched"] for a in tiled)
    # (its calls do not move the rows of a preset 64 wide: the tick says
    # so from the same question ``moe_dropless`` asks)
    assert all(a["expert_rows_impl"] == "xla" for a in tiled)


# ----------------------------------------------------------------------
# checkpoints: the published names, there and back
# ----------------------------------------------------------------------

def test_hf_key_map_round_trip(tiny, tmp_path):
    from llm_np_cp_tpu.utils.loading import load_params
    from llm_np_cp_tpu.utils.synthetic import (
        hf_config_dict,
        hf_state_dict,
        hf_tensor_shapes,
        write_hf_checkpoint,
    )

    cfg, params = tiny
    assert ModelConfig.from_hf_dict(hf_config_dict(cfg)) == cfg
    host = jax.tree.map(np.asarray, params)
    tensors = hf_state_dict(host, cfg)
    assert {k: v.shape for k, v in tensors.items()} == hf_tensor_shapes(cfg)
    # the published names, stored as the published code stores them
    assert tensors["model.layers.0.conv.in_proj.weight"].shape == (192, 64)
    assert tensors["model.layers.0.conv.conv.weight"].shape == (64, 1, 3)
    assert tensors["model.layers.0.feed_forward.w1.weight"].shape == (128, 64)
    assert tensors["model.layers.2.self_attn.q_layernorm.weight"].shape == (16,)
    assert tensors["model.layers.2.self_attn.out_proj.weight"].shape == (64, 64)
    assert tensors["model.layers.3.feed_forward.experts.7.w2.weight"].shape == (64, 32)
    assert tensors["model.layers.3.feed_forward.gate.weight"].shape == (8, 64)
    assert tensors["model.layers.3.feed_forward.expert_bias"].dtype == np.float32
    assert "model.layers.3.operator_norm.weight" in tensors
    assert "model.embedding_norm.weight" in tensors
    write_hf_checkpoint(tmp_path, cfg, tensors)
    for use_native in (False, True):
        loaded, cfg2 = load_params(tmp_path, dtype=jnp.float32,
                                   use_native=use_native, on_host=True)
        assert cfg2 == cfg
        flat_a, tree_a = jax.tree.flatten(host)
        flat_b, tree_b = jax.tree.flatten(loaded)
        assert tree_a == tree_b
        for a, b in zip(flat_a, flat_b):
            np.testing.assert_array_equal(a, b)
    # one tensor short is an error that names it
    del tensors["model.layers.4.feed_forward.experts.2.w3.weight"]
    write_hf_checkpoint(tmp_path, cfg, tensors)
    with pytest.raises(ValueError, match="layers.4.feed_forward.experts.2.w3"):
        load_params(tmp_path, dtype=jnp.float32, on_host=True)


def test_offline_generator_runs_the_stack(tiny):
    """``Generator`` (chunked ragged prefill, fused decode scan) carries the
    conv state in its ``KVCache``: greedy tokens are the reference's argmax."""
    from llm_np_cp_tpu.generate import Generator

    cfg, params = tiny
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                    cache_dtype=jnp.float32)
    prompts = [np.asarray(p) for p in _prompts([5, 11], seed=8)]
    tokens = np.asarray(gen.generate_ragged(prompts, 5, seed=0).tokens)
    for prompt, got in zip(prompts, tokens):
        seq = list(prompt)
        for tok in got[:5]:
            logits = np.asarray(ref.forward(params, TINY_HF, seq))
            assert int(tok) == int(logits[-1].argmax())
            seq.append(int(tok))
