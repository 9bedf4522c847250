"""The latent (MLA) pool on the served path: chunked prefill, then decode
through pages of one row ``[c' | k_pe]`` a token and layer, in the unified
tick, against the benchmark's independent float32 reference of the WHOLE
sequence (logits, not tokens); the Pallas kernel (interpret mode here) and
its XLA twin; what a latent pool holds and how it is sized; what it refuses
at start-up; its scopes, tick arguments and the op map's pool marking."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import llm_np_cp_tpu.serve.engine as engine_mod
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.parallel.sharding import MeshPlan
from llm_np_cp_tpu.serve import ServeEngine
from llm_np_cp_tpu.serve.block_pool import latent_page_width
from llm_np_cp_tpu.serve.engine import pool_geometry
from llm_np_cp_tpu.utils.synthetic import hf_config_dict

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_deepseek_v3 as ref  # noqa: E402

TOL = 5e-5  # float32 program against the float32 reference at ``highest``
# a bf16 program against float32: every matmul rounds its operands to 8
# bits of mantissa, which over three layers moves a logit by about a
# hundredth of the spread; a wrong equation moves it by a tenth or more
TOL_BF16 = 0.08


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("deepseek_v3")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, hf_config_dict(cfg), params


def _gap(got, want) -> float:
    want = np.asarray(want)
    spread = float((want.max(-1) - want.mean(-1)).mean())
    return float(np.abs(np.asarray(got, np.float32) - want).max()) / spread


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


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


def _engine(cfg, params, attn="pallas", **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("cache_dtype", jnp.float32)
    engine = ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                         sample_epilogue="off", **kw)
    assert engine.mixed and engine.ragged_attn_impl == "pallas"
    if attn == "xla":  # the kernel's twin: what a failed probe falls back to
        engine.ragged_attn_impl = "xla"
        engine._mixed_step = engine._make_mixed_step()
    return engine


def _serve(engine, probe, reqs):
    """Run to completion; per request the logits each of its tokens was
    sampled from."""
    got = {r.req_id: [] for r in reqs}
    more = True
    while more:
        before = {r.req_id: len(r.generated) for r in reqs}
        more = engine.step()
        jax.effects_barrier()
        for r in reqs:
            if len(r.generated) > before[r.req_id]:
                slot = r.slot if r.slot is not None and r.slot >= 0 else r.extra["_slot"]
                got[r.req_id].append(probe.ticks[-1][slot, 0])
            if r.slot is not None and r.slot >= 0:
                r.extra["_slot"] = r.slot
    return got


def _worst_gap(params, hf, reqs, got) -> float:
    worst = 0.0
    for r in reqs:
        seq = list(r.prompt) + list(r.generated)
        want = np.asarray(ref.forward(params, hf, seq))
        p = len(r.prompt)
        have = np.stack(got[r.req_id])
        assert have.shape[0] == len(r.generated)
        worst = max(worst, _gap(have, want[p - 1:p - 1 + len(r.generated)]))
    return worst


SERVE_CASES = {
    # a 21-token prompt in chunks of 8 (two chunk boundaries inside it)
    # beside a short one that decodes while the long one still prefills
    "pallas": dict(lengths=[21, 3], new=6, attn="pallas"),
    "xla": dict(lengths=[21, 3], new=6, attn="xla"),
    # a pool too small for all three: one is evicted and re-prefilled
    # (the twin: what is evicted and rewritten does not depend on who reads)
    "evict_requeue": dict(lengths=[4, 5, 3], new=12, attn="xla",
                          engine=dict(max_slots=2, num_blocks=6)),
}


@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_served_logits_match_the_references_full_forward(tiny, monkeypatch, case):
    cfg, hf, params = tiny
    spec = SERVE_CASES[case]
    probe = Probe(monkeypatch)
    engine = _engine(cfg, params, spec["attn"], **spec.get("engine", {}))
    reqs = [engine.submit(p, max_new_tokens=spec["new"], seed=i)
            for i, p in enumerate(_prompts(spec["lengths"], seed=11))]
    got = _serve(engine, probe, reqs)
    assert all(len(r.generated) == spec["new"] for r in reqs)
    if case == "evict_requeue":
        assert engine.scheduler.n_preemptions > 0, "pool not tight enough"
    assert _worst_gap(params, hf, reqs, got) < TOL
    assert engine.pool.free_list.num_allocated == 0


def test_a_bf16_program_is_within_its_tolerance_and_a_bf16_cache_of_float32_is_not(
        tiny, monkeypatch):
    """bf16 weights and pages against the float32 reference of the SAME
    (bf16-rounded) weights: rounding only, inside ``TOL_BF16`` and far
    outside the float32 tolerance.  The lower-precision control: the
    float32 program with its latent rows kept in bf16 fails ``TOL``."""
    cfg, hf, params = tiny
    prompts = _prompts([13, 6], seed=5)
    probe = Probe(monkeypatch)
    half = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 and a.ndim > 1
        else a, params)
    engine = _engine(cfg, half, cache_dtype=jnp.bfloat16)
    reqs = [engine.submit(p, max_new_tokens=4, seed=i) for i, p in enumerate(prompts)]
    gap = _worst_gap(half, hf, reqs, _serve(engine, probe, reqs))
    assert 10 * TOL < gap < TOL_BF16, gap
    probe.ticks.clear()
    control = _engine(cfg, params, cache_dtype=jnp.bfloat16)
    reqs = [control.submit(p, max_new_tokens=4, seed=i) for i, p in enumerate(prompts)]
    gap = _worst_gap(params, hf, reqs, _serve(control, probe, reqs))
    assert gap > 10 * TOL, gap


# ----------------------------------------------------------------------
# what the pool holds, and how it is sized
# ----------------------------------------------------------------------

def test_the_pool_is_one_array_of_rows_with_no_head_axis(tiny):
    cfg, _, params = tiny
    engine = _engine(cfg, params)
    pages = engine.pool.pages
    # 3 layers x 48 blocks x 8 tokens x a row of 32 + 8 values, stored in
    # whole rows of 128 lanes; no V beside it, no scales, no state
    assert pages.k.shape == (3, 48, 8, 128) and pages.v is None
    assert pages.latent and not pages.merged and not pages.quantized
    assert (pages.head_dim, pages.kv_heads, pages.state) == (40, 1, None)
    assert len(pages.pool_arrays()) == 1
    assert cfg.kv_bytes_per_token(4) == 3 * 40 * 4
    assert engine._block_nbytes == 3 * 8 * 128 * 4
    assert (latent_page_width(576), latent_page_width(40),
            latent_page_width(640)) == (640, 128, 640)
    # the pool rides the tick flat and is written in place
    assert engine.pool_page_shape == "8x128"
    # the bucket set is the one every stack gets
    plain_cfg = tiny_config("qwen2")
    plain = ServeEngine(
        init_params(jax.random.PRNGKey(0), plain_cfg, dtype=jnp.float32),
        plain_cfg, max_slots=4, num_blocks=48, block_size=8, max_seq_len=64,
        prefill_chunk=8, cache_dtype=jnp.float32)
    assert engine.mixed_buckets == plain.mixed_buckets


def test_the_cells_pool_by_the_clis_worst_case_rule():
    # context-closed: prompts up to 1,536, answers up to 640, chunks of 128
    per_seq, blocks, max_seq = pool_geometry(1536, 640, 96, 64, prefill_chunk=128)
    assert (per_seq, blocks, max_seq) == (36, 96 * 36 + 2, 2304)
    # 3,458 blocks x 64 tokens x 24 layers: 1,152 B a token and layer as
    # the algorithm needs them, 1,280 as the pool stores them
    assert blocks * 64 * 24 * 1152 == 6_118_834_176  # 5,835 MiB
    assert blocks * 64 * 24 * 2 * latent_page_width(576) == 6_798_704_640


@pytest.mark.parametrize("kw, flag", [
    (dict(enable_prefix_cache=True), "--prefix-cache"),
    (dict(spec_k=2), "--speculative-serve / --spec-k"),
    (dict(cache_dtype=jnp.int8), "--cache-dtype int8"),
    (dict(mesh_plan=MeshPlan(model=2)), "--mesh model>1"),
    (dict(host_tier=object(), enable_prefix_cache=True), "--kv-tier host"),
])
def test_start_up_refusals_name_the_flag(tiny, kw, flag):
    cfg, _, params = tiny
    kw = dict(dict(cache_dtype=jnp.float32), **kw)
    with pytest.raises(ValueError, match="latent.*refused: " + flag.replace(">", r"\>")):
        ServeEngine(params, cfg, max_slots=2, num_blocks=16, block_size=8,
                    max_seq_len=32, **kw)


def test_without_the_kernel_the_tick_takes_its_xla_twin_and_says_so(
        tiny, monkeypatch, caplog):
    from llm_np_cp_tpu.ops.pallas import support

    cfg, _, params = tiny
    monkeypatch.setattr(support, "_FORCE_FAIL", True)
    support._probe.cache_clear()  # conftest clears it again afterwards
    with caplog.at_level("WARNING", logger="llm_np_cp_tpu"):
        engine = ServeEngine(params, cfg, max_slots=2, num_blocks=16,
                             block_size=8, max_seq_len=32,
                             cache_dtype=jnp.float32)
    assert engine.mixed and engine.ragged_attn_impl == "xla"
    assert any("ragged_latent_attention is unavailable" in r.getMessage()
               for r in caplog.records)


# ----------------------------------------------------------------------
# spans, counters, the op map
# ----------------------------------------------------------------------

def test_tick_arguments_counters_scopes_and_the_pool_marking(tiny):
    import dataclasses

    from llm_np_cp_tpu.models.transformer import STEP_SCOPES
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    cfg, _, _ = tiny
    # this engine holds experts 2..5 of the router's 8
    part = dataclasses.replace(cfg, num_experts_held=4, first_expert=2)
    params = init_params(jax.random.PRNGKey(1), part, dtype=jnp.float32)
    tracer = TraceRecorder()
    engine = ServeEngine(params, part, max_slots=4, num_blocks=48, block_size=8,
                         max_seq_len=64, prefill_chunk=8,
                         cache_dtype=jnp.float32, tracer=tracer)
    assert engine.epilogue_impl == "fused" and engine.ragged_attn_impl == "pallas"
    for i, p in enumerate(_prompts([9, 12], seed=2)):
        engine.submit(p, max_new_tokens=5, seed=i)
    engine.run_until_complete()
    events = tracer.events()
    build = next(e for e in events if e.get("name") == "engine_build")
    assert build["args"]["page_bytes_per_token"] == 3 * 40 * 4
    assert build["args"]["pool_bytes_per_token"] == 3 * 128 * 4
    assert build["args"]["experts_held"] == 4
    ticks = [e["args"] for e in events
             if e.get("name") == "tick" and e["args"].get("decode_tokens")]
    assert ticks
    held = 0
    for a in ticks:
        tokens = a["prefill_tokens"] + a["decode_tokens"]
        # 2 expert layers x 4 held experts; top-2: a pair is held or not
        assert 0 <= a["experts_touched"] <= 8
        assert 0 <= a["pairs_held"] <= 2 * 2 * tokens
        # the pairs the two layers' routers made (top-2 of 8, 4 held); on
        # the CPU XLA moves the rows of all of them (lax.ragged_dot's path)
        assert a["pairs_routed"] == 2 * 2 * tokens
        assert a["expert_rows_impl"] == "xla"
        assert a["expert_load_max"] <= tokens
        assert a["attn_pages"] > 0 and a["attn_grid_steps"] > 0
        assert a["attn_pages_per_step"] >= 1
        # the tiles of ONE token (the latent kernel's decode branch): a
        # decode row each, and a chunk of 9 = 8 + 1 tokens' tail
        assert a["decode_tokens"] <= a["attn_decode_tiles"] <= a["attn_live_tiles"]
        assert a["attn_live_tiles"] <= (
            a["decode_tokens"] + -(-a["prefill_tokens"] // 8) + 1)
        held += a["pairs_held"]
    assert held > 0
    from tools.summarize_trace import format_summary, tick_account

    acct = tick_account(tracer.events())
    every = [e["args"] for e in events
             if e.get("name") == "tick" and "pairs_held" in e["args"]]
    assert acct["pairs_held_share"] == pytest.approx(
        sum(a["pairs_held"] for a in every)
        / sum(a["pairs_routed"] for a in every))
    assert 0.2 < acct["pairs_held_share"] < 0.8  # half the experts are held
    assert acct["expert_rows_kernel_share"] == 0.0
    assert "of the pairs routed are held" in format_summary(tracer.events(), top=0)
    text = engine.metrics.prometheus()
    assert "moe_pairs_held_total" in text and "moe_experts_touched_total" in text
    total = next(float(line.split()[-1]) for line in text.splitlines()
                 if line.startswith("llm_serve_moe_pairs_held_total"))
    assert total >= held  # the warm-up's ticks count too
    assert "moe_shared" in STEP_SCOPES
    table = engine.device_op_map()
    scopes = {v[0] for v in table.values() if v}
    assert {"qkv", "kv_write", "attn", "o_proj", "mlp", "moe_route",
            "moe_experts", "moe_shared", "tail"} <= scopes
    # the latent pool is pool-shaped to the map, and what writes it is the
    # kv_write scatter: ``pool.move_share`` reads it
    pool_ops = [(k, v) for k, v in table.items() if v and v[1] == "pool"]
    assert pool_ops and all("f32[144,8,128]" in k for k, _ in pool_ops)
    assert any(v[0] == "kv_write" for _, v in pool_ops)
