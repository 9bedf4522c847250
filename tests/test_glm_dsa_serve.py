"""GLM-5's layer (``glm_moe_dsa``: a query latent under a sparse-attention
indexer) on the served path: chunked prefill, then decode through pages of
latent rows with an index key beside each, score -> select -> attend in the
unified tick, against the benchmark's independent float32 reference of the
WHOLE sequence (logits, not tokens); the three Pallas kernels (interpret mode
here) and their XLA twins; a context that crosses ``index_topk`` while it
decodes; a slot and its blocks reused by a shorter request; a re-prefill after
preemption; what the pool holds; the start-up refusals; spans and counters."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models import init_params
from llm_np_cp_tpu.parallel.sharding import MeshPlan
from llm_np_cp_tpu.serve import ServeEngine
from llm_np_cp_tpu.utils.synthetic import hf_config_dict

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_glm_dsa as ref  # noqa: E402
# the latent pool's own harness: the tick's logits by a callback, an engine at
# toy sizes (``attn="xla"``: the kernels' twins), a run to completion
from test_serve_latent_pool import (  # noqa: E402
    Probe,
    _engine,
    _gap,
    _prompts,
    _serve,
)

TOL = 5e-5  # float32 program against the float32 reference at ``highest``
TOPK = 12   # tiny_config's index_topk: a context past 12 tokens selects


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("glm_moe_dsa")
    assert cfg.index_topk == TOPK
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, hf_config_dict(cfg), params


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
    # a 21-token prompt in chunks of 8 (it selects from its 13th token on,
    # inside a chunk) beside a short one that decodes while the long one
    # still prefills and never selects
    "pallas": dict(lengths=[21, 3], new=6, attn="pallas"),
    "xla": dict(lengths=[21, 3], new=6, attn="xla"),
    # a context that CROSSES index_topk while it decodes: 9 tokens of prompt,
    # dense until the context is 12, selecting after
    "crosses_topk": dict(lengths=[9], new=10, attn="pallas"),
    # a pool too small for all three: one is evicted and re-prefilled past
    # index_topk (its index keys rewritten with its rows)
    "evict_requeue": dict(lengths=[14, 15, 13], new=12, attn="xla",
                          engine=dict(max_slots=2, num_blocks=7)),
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


def test_a_reused_slot_sees_none_of_the_previous_requests_index_keys(
        tiny, monkeypatch):
    """One slot, a pool of just its blocks: a long request fills them with
    rows and index keys past ``index_topk``; the shorter request after it
    gets the same slot and (LIFO) the same blocks, scores only the positions
    it wrote itself and matches the reference of ITS sequence alone."""
    cfg, hf, params = tiny
    probe = Probe(monkeypatch)
    # (the kernels' twins read the same pool through the same tables)
    engine = _engine(cfg, params, "xla", max_slots=1, num_blocks=6,
                     max_seq_len=40)
    long, short = _prompts([27, 14], seed=3)
    first = engine.submit(long, max_new_tokens=6, seed=0)
    got = _serve(engine, probe, [first])
    held = np.asarray(engine.pool.pages.v)
    assert np.abs(held[:, 1:]).max() > 0  # the keys are still in the blocks
    second = engine.submit(short, max_new_tokens=8, seed=1)
    got.update(_serve(engine, probe, [second]))
    assert _worst_gap(params, hf, [first, second], got) < TOL
    # ... and with the first request's keys in place of zeros nothing changed:
    # the same request into a fresh pool gives the same logits
    fresh = _engine(cfg, params, "xla", max_slots=1, num_blocks=6,
                    max_seq_len=40)
    again = fresh.submit(short, max_new_tokens=8, seed=1)
    clean = _serve(fresh, probe, [again])
    assert np.array_equal(np.stack(got[second.req_id]),
                          np.stack(clean[again.req_id]))


def test_a_control_is_refused_through_the_served_path(tiny, monkeypatch):
    """What the comparison is for: the served logits are far from the
    reference that attends the RECENT 12 positions, or everything."""
    cfg, hf, params = tiny
    probe = Probe(monkeypatch)
    engine = _engine(cfg, params, "xla")
    (req,) = [engine.submit(p, max_new_tokens=6, seed=0)
              for p in _prompts([25], seed=4)]
    got = np.stack(_serve(engine, probe, [req])[req.req_id])
    seq = list(req.prompt) + list(req.generated)
    for variant in (None, "recent", "dense", "no_index_rope"):
        want = np.asarray(ref.forward(params, hf, seq, variant=variant))[24:30]
        assert (_gap(got, want) < TOL) == (variant is None), variant


# ----------------------------------------------------------------------
# what the pool holds; what start-up refuses
# ----------------------------------------------------------------------

def test_the_pool_holds_an_index_key_beside_every_latent_row(tiny):
    cfg, _, params = tiny
    engine = _engine(cfg, params)
    pages = engine.pool.pages
    # 3 layers x 48 blocks x 8 tokens: a row of 32 + 8 values stored in whole
    # rows of 128 lanes, and an index key of 16 values beside it — the same
    # block ids, the same tables, no third allocator class
    assert pages.k.shape == (3, 48, 8, 128) and pages.v.shape == (3, 48, 8, 16)
    assert pages.latent and not pages.merged and not pages.quantized
    assert len(pages.pool_arrays()) == 2 and pages.window is None
    assert cfg.kv_bytes_per_token(4) == 3 * (40 + 16) * 4
    assert engine._block_nbytes == 3 * 8 * (128 + 16) * 4
    assert engine.pool.window is None and engine.pool.pages.state is None


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
    with pytest.raises(ValueError, match="glm_moe_dsa.*latent.*refused: "
                       + flag.replace(">", r"\>")):
        ServeEngine(params, cfg, max_slots=2, num_blocks=16, block_size=8,
                    max_seq_len=32, **kw)


def test_without_the_kernels_the_tick_takes_their_xla_twins_and_says_so(
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
    assert any("sparse_latent_attention is unavailable" in r.getMessage()
               for r in caplog.records)


# ----------------------------------------------------------------------
# spans, counters, the op map
# ----------------------------------------------------------------------

def test_tick_arguments_counters_and_scopes(tiny):
    from llm_np_cp_tpu.models.transformer import STEP_SCOPES
    from llm_np_cp_tpu.serve.tracing import TraceRecorder
    from tools.summarize_trace import format_summary, tick_account

    cfg, _, params = tiny
    tracer = TraceRecorder()
    engine = ServeEngine(params, cfg, max_slots=4, num_blocks=48, block_size=8,
                         max_seq_len=64, prefill_chunk=8,
                         cache_dtype=jnp.float32, tracer=tracer)
    assert engine.ragged_attn_impl == "pallas"
    for i, p in enumerate(_prompts([21, 3], seed=2)):
        engine.submit(p, max_new_tokens=5, seed=i)
    engine.run_until_complete()
    events = tracer.events()
    build = next(e for e in events if e.get("name") == "engine_build")
    assert build["args"]["page_bytes_per_token"] == 3 * (40 + 16) * 4
    assert build["args"]["pool_bytes_per_token"] == 3 * (128 + 16) * 4
    ticks = [e["args"] for e in events
             if e.get("name") == "tick" and "dsa_visible" in e["args"]]
    assert ticks
    for a in ticks:
        tokens = a["prefill_tokens"] + a["decode_tokens"]
        assert tokens <= a["dsa_selected"] <= a["dsa_visible"]
        assert a["dsa_selected"] <= TOPK * tokens
        assert 0 <= a["dsa_dense_tokens"] <= tokens
        # the scores walk the pages the attention walks
        assert a["dsa_index_pages"] == a["attn_pages"] > 0
    # the first dispatch: 17 + 3 prompt tokens at positions 0.. of two rows
    first = ticks[0]
    assert first["prefill_tokens"] == 20 and first["dsa_visible"] == 153 + 6
    assert first["dsa_selected"] == 78 + 5 * 12 + 6 and first["dsa_dense_tokens"] == 15
    # a decode tick of the long row alone: it sees its context, attends 12
    last = ticks[-1]
    assert last["decode_tokens"] >= 1 and last["dsa_selected"] < last["dsa_visible"]
    acct = tick_account(events)
    assert acct["dsa_selected_share"] == pytest.approx(
        sum(a["dsa_selected"] for a in ticks) / sum(a["dsa_visible"] for a in ticks))
    assert "sparse-attention indexer" in format_summary(events, top=0)
    text = engine.metrics.prometheus()
    for name in ("dsa_ticks_total", "dsa_visible_total", "dsa_selected_total",
                 "dsa_dense_tokens_total", "dsa_index_pages_total"):
        assert f"llm_serve_{name}" in text, name
    seen = next(float(line.split()[-1]) for line in text.splitlines()
                if line.startswith("llm_serve_dsa_visible_total"))
    assert seen >= sum(a["dsa_visible"] for a in ticks)  # the warm-up's count too
    assert {"dsa_proj", "dsa_score", "dsa_select", "dsa_attn"} <= set(STEP_SCOPES)
    table = engine.device_op_map()
    scopes = {v[0] for v in table.values() if v}
    assert {"qkv", "kv_write", "dsa_proj", "dsa_score", "dsa_select",
            "dsa_attn", "o_proj", "mlp", "moe_route", "moe_experts",
            "moe_shared", "tail"} <= scopes
    # both arrays of the pool are pool-shaped to the map and written under
    # kv_write: the rows and, beside them, the index keys
    pool_ops = [k for k, v in table.items() if v and v[1] == "pool"
                and v[0] == "kv_write"]
    assert any("f32[144,8,128]" in k for k in pool_ops)
    assert any("f32[144,8,16]" in k for k in pool_ops)
