"""Brumby's language model (``model_type: brumby``) through the SERVED engine
on the CPU at a tiny size, seeded random float32 weights — a stack with NO
page class: prefill segments beside decode rows and decode through the state,
a slot reused without a clear, each against ``benchmark/reference_brumby.py``'s
full forward on LOGITS; the engine ``cli serve`` builds against the offline
run on tokens; both forms of the state update; a pool, a scheduler and an
operand with no blocks; the start-up refusals by flag; and the scopes, tick
arguments and counters the per-layer metrics read.  The declaration and the
plain forward are tests/test_brumby.py, the recurrence tests/test_retention.py.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))

import reference_brumby as ref  # noqa: E402

import llm_np_cp_tpu.cli as cli  # noqa: E402
import llm_np_cp_tpu.serve.engine as engine_mod  # noqa: E402
from llm_np_cp_tpu.config import ModelConfig, tiny_config  # noqa: E402
from llm_np_cp_tpu.models.transformer import (  # noqa: E402
    STEP_SCOPES,
    forward,
    init_params,
)
from llm_np_cp_tpu.ops import retention  # noqa: E402
from llm_np_cp_tpu.ops.sampling import Sampler  # noqa: E402
from llm_np_cp_tpu.parallel.sharding import MeshPlan  # noqa: E402
from llm_np_cp_tpu.serve import ServeEngine  # noqa: E402
from llm_np_cp_tpu.serve.block_pool import BlockPool, NoBlocks  # noqa: E402
from llm_np_cp_tpu.serve.engine import mixed_operand_layout  # noqa: E402
from llm_np_cp_tpu.utils.synthetic import hf_config_dict  # noqa: E402

# largest logit difference as a share of the reference's spread: float32
# against float32, the state form against the attention form
TOL = 1e-4


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("brumby")
    hf = hf_config_dict(cfg)
    assert cfg == ModelConfig.from_hf_dict(hf)
    return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32), hf


def _gap(got: np.ndarray, want: np.ndarray) -> float:
    spread = float((want.max(-1) - want.mean(-1)).mean())
    return float(np.abs(got - want).max()) / spread


_REF: dict = {}


def _reference(params, hf, seq) -> np.ndarray:
    n = -(-len(seq) // 32) * 32
    if n not in _REF:
        _REF[n] = jax.jit(lambda p, ids: ref.forward(p, hf, ids))
    ids = np.zeros((n,), np.int32)
    ids[:len(seq)] = seq
    return np.asarray(_REF[n](params, ids))[:len(seq)]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


class Probe:
    """The logits every tick's XLA tail samples from, kept per tick."""

    def __init__(self, mp):
        self.ticks: list[np.ndarray] = []
        real = engine_mod.final_logits

        def probed(p, x, cfg, **kw):
            logits = real(p, x, cfg, **kw)
            jax.debug.callback(lambda a: self.ticks.append(np.asarray(a)), logits)
            return logits

        mp.setattr(engine_mod, "final_logits", probed)


@pytest.fixture(scope="module")
def probe():
    with pytest.MonkeyPatch.context() as mp:
        yield Probe(mp)


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_blocks", 0)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                       sample_epilogue="off", **kw)


@pytest.fixture(scope="module")
def shared(tiny, probe):
    """ONE engine for the serve cases (an idle engine is as good as a new
    one, which is what the cases show: every request starts from a zero
    state in whatever slot, and no slot is ever cleared)."""
    return _engine(*tiny[:2])


def _serve(engine, probe, reqs):
    got = {r.req_id: [] for r in reqs}
    while True:
        n_before = {r.req_id: len(r.generated) for r in reqs}
        more = engine.step()
        jax.effects_barrier()
        for r in reqs:
            if len(r.generated) > n_before[r.req_id]:
                slot = r.slot if r.slot is not None and r.slot >= 0 else r.extra["_slot"]
                got[r.req_id].append(probe.ticks[-1][slot, 0])
            if r.slot is not None and r.slot >= 0:
                r.extra["_slot"] = r.slot
        if not more:
            return got


SERVE_CASES = {
    # a 21-token prompt in segments (state handed from tick to tick) and a
    # short one that decodes beside it: mixed ticks
    "mixed_ticks": dict(lengths=[21, 3], new=6),
    # five requests over two slots: three start in a slot another has left,
    # on whatever state that one left there
    "a_slot_reused": dict(lengths=[5, 9, 12, 4, 7], new=5),
}


@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_served_logits_match_reference(tiny, probe, shared, case):
    cfg, params, hf = tiny
    spec = SERVE_CASES[case]
    reqs = [shared.submit(p, max_new_tokens=spec["new"], seed=i)
            for i, p in enumerate(_prompts(spec["lengths"], seed=len(case)))]
    got = _serve(shared, probe, reqs)
    for r in reqs:
        seq = list(r.prompt) + list(r.generated)
        want = _reference(params, hf, seq)
        p, have = len(r.prompt), np.stack(got[r.req_id])
        assert have.shape[0] == len(r.generated) == spec["new"]
        assert _gap(have, want[p - 1:p - 1 + len(r.generated)]) <= TOL, case
    if case == "a_slot_reused":
        # three of the five started on what another request had left in
        # their slot: nothing clears a slot, at admission or at the end
        left = np.asarray(shared.pool.pages.state["retention"])
        assert all(np.abs(left[:, slot]).max() > 0 for slot in (0, 1))
        assert shared.metrics.snapshot().get("preemptions", 0) == 0


def test_the_engine_cli_serve_builds_serves_the_offline_runs_tokens(tiny):
    """``cli serve --arch brumby``'s own engine build (its sizing rule, its
    flags' defaults): greedy tokens are the plain ``models.forward`` loop's."""
    cfg, params, _ = tiny
    args = cli.build_http_serve_parser("tiny").parse_args(
        ["--arch", "brumby", "--slots", "2", "--block-size", "8",
         "--prompt-len", "24", "--max-tokens", "6", "--dtype", "f32",
         "--cache-dtype", "f32"])
    engine, num_blocks = cli._build_serve_engine(
        args, params, cfg, prog="serve", quiet=True)
    assert num_blocks == 0 and engine.pool.num_blocks == 0
    assert engine.max_seq_len == cfg.max_position_embeddings
    prompts = _prompts([17, 5, 11], seed=4)
    reqs = [engine.submit(p, max_new_tokens=6, seed=i)
            for i, p in enumerate(prompts)]
    engine.run_until_complete()
    step = jax.jit(lambda p, ids: forward(p, ids, cfg)[0])
    for r, prompt in zip(reqs, prompts):
        seq = list(prompt)
        for _ in range(6):
            ids = np.zeros((1, 32), np.int32)
            ids[0, :len(seq)] = seq
            seq.append(int(np.argmax(np.asarray(
                step(params, ids))[0, len(seq) - 1])))
        assert r.generated == seq[len(prompt):] and r.finish_reason == "length"
    with pytest.raises(SystemExit, match="--arch qwen2"):
        cli._build_serve_engine(
            cli.build_http_serve_parser("tiny").parse_args(["--arch", "qwen2"]),
            params, cfg, prog="serve", quiet=True)


def test_a_pool_a_scheduler_and_an_operand_with_no_page_class(tiny, shared):
    cfg, params, _ = tiny
    pool = shared.pool
    assert isinstance(pool.free_list, NoBlocks) and not pool.paged
    assert (pool.num_blocks, pool.capacity, pool.num_free) == (0, 0, 0)
    assert pool.blocks_for(10_000) == 0 and pool.occupancy == 0.0
    pages = pool.pages
    assert pages.k.shape[:2] == (0, 0) and pages.k.size == 0
    assert set(pages.state) == {"retention", "retention_z"}
    assert pages.state["retention"].shape == (3, 2, 2, 64, 8)
    assert pages.state["retention_z"].shape == (3, 2, 2, 8, 8)
    assert all(a.dtype == jnp.float32 for a in pages.state.values())
    # asked for blocks all the same: there are none to have
    assert BlockPool(cfg, 40, 8, state_slots=2).num_blocks == 0
    stats = pool.stats()
    assert stats["capacity"] == stats["allocated"] == stats["request_held"] == 0
    assert stats["kv_bytes_total"] == 0
    # admission by free slot, no block to keep spare, a context bounded by
    # the model's positions alone
    assert shared.scheduler.decode_reserve == 0
    assert shared.max_seq_len == cfg.max_position_embeddings == 512
    assert shared.max_blocks_per_seq == 0 and shared._q_tile == 1
    with pytest.raises(ValueError, match="max_seq_len"):
        shared.submit(list(range(1, 500)), max_new_tokens=40)
    # the operand: the dense token axis and the rows' sections, no table,
    # no block, no tile; a program is its dense width
    layout, size = mixed_operand_layout(16, 16, 1, 2, 0, 1)
    assert set(layout) == {"tokens", "positions", "tok_row", "tok_live", "pads",
                           "last_idx", "sample_pos", "seeds", "verify_len"}
    assert size == 4 * 16 + 5 * 2  # four token sections, five a row (spec_w 1)
    assert all(t == d for t, d in shared.mixed_buckets)
    assert shared.mixed_buckets == ((8, 8), (16, 16), (18, 18))
    # ... and a stack WITH pages packs what it packed
    assert "tables" in mixed_operand_layout(16, 16, 8, 2, 4, 1)[0]


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_both_forms_of_the_state_update_serve_the_same_tokens(tiny, form):
    """Tick argument ``retention_state_impl``, gauge ``retention_state_kernel``
    and the ``probe.retention_state_update`` set-up span say which form ran
    (the kernel here in the interpreter, as a TPU's probe would answer); the
    tokens are the same."""
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    cfg, params, _ = tiny
    tracer = TraceRecorder()
    real = retention.state_update_impl
    with pytest.MonkeyPatch.context() as mp:
        if form == "pallas":
            mp.setattr(retention, "state_update_impl",
                       lambda s, interpret=None: real(s, True))
        engine = ServeEngine(params, cfg, max_slots=2, num_blocks=0,
                             block_size=8, max_seq_len=64, prefill_chunk=8,
                             cache_dtype=jnp.float32, tracer=tracer)
        assert engine.retention_state_impl == form
        reqs = [engine.submit(p, max_new_tokens=5, seed=i)
                for i, p in enumerate(_prompts([9, 12], seed=2))]
        engine.run_until_complete()
    span, = [e for e in tracer.events()
             if e.get("name") == "probe.retention_state_update"]
    assert span["args"]["ok"] is (form == "pallas")
    ticks = [e["args"] for e in tracer.events()
             if e.get("name") == "tick" and "retention_state_rows" in e["args"]]
    assert ticks and all(a["retention_state_impl"] == form for a in ticks)
    assert (f"retention_state_kernel {int(form == 'pallas')}"
            in engine.metrics.prometheus())
    _TOKENS.setdefault("served", [r.generated for r in reqs])
    assert [r.generated for r in reqs] == _TOKENS["served"]


_TOKENS: dict = {}


# ----------------------------------------------------------------------
# start-up refusals, spans and counters
# ----------------------------------------------------------------------

class _Tier:
    pass


@pytest.mark.parametrize("kw, pattern", [
    (dict(enable_prefix_cache=True),
     "power-retention layers.*refused: --prefix-cache"),
    (dict(enable_prefix_cache=True, host_tier=_Tier()),
     "power-retention layers.*refused: --prefix-cache"),
    (dict(spec_k=2), "power-retention layers.*refused: --spec-k"),
    (dict(mesh_plan=MeshPlan(model=2)),
     r"power-retention layers.*refused: --mesh model\>1"),
], ids=["prefix-cache", "tier", "spec-k", "mesh"])
def test_start_up_refusals_name_the_kind_and_the_flag(tiny, kw, pattern):
    cfg, params, _ = tiny
    with pytest.raises(ValueError, match=pattern):
        ServeEngine(params, cfg, max_slots=2, num_blocks=0, block_size=8,
                    max_seq_len=32, cache_dtype=jnp.float32, **kw)


def test_tick_arguments_counters_scopes_and_metrics_with_no_page_class(tiny):
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    cfg, params, _ = tiny
    tracer = TraceRecorder()
    engine = ServeEngine(params, cfg, max_slots=2, num_blocks=0, block_size=8,
                         max_seq_len=64, prefill_chunk=8,
                         cache_dtype=jnp.float32, tracer=tracer)
    for i, p in enumerate(_prompts([9, 12], seed=2)):
        engine.submit(p, max_new_tokens=5, seed=i)
    engine.run_until_complete()
    ticks = [e["args"] for e in tracer.events()
             if e.get("name") == "tick" and "retention_state_rows" in e["args"]]
    assert ticks and any(a["decode_tokens"] for a in ticks)
    rows = tokens = 0
    for a in ticks:
        assert 1 <= a["retention_state_rows"] <= 2
        assert a["retention_state_impl"] == "xla"
        assert a["retention_scan_tokens"] == a["prefill_tokens"] + a["decode_tokens"]
        assert a.get("attn_pages", 0) == 0
        rows += a["retention_state_rows"]
        tokens += a["retention_scan_tokens"]
    assert tokens == 9 + 12 + 2 * 4  # every prompt token once, 4 decode steps each
    text = engine.metrics.prometheus()
    assert f"retention_state_rows_total {rows}" in text
    assert f"retention_scan_tokens_total {tokens}" in text
    assert "retention_ticks_total" in text and "retention_state_kernel 0" in text
    assert "kda_ticks_total" not in text and "ssm_ticks_total" not in text
    # the pool gauges of a pool with no page class: 0, and no division by it
    assert "pool_occupancy 0" in text.replace("llm_serve_", "")
    snap = engine.metrics.snapshot()
    assert snap["preemptions"] == 0 and snap.get("occupancy_last", 0.0) == 0.0
    assert {"retention_proj", "retention_scan"} <= set(STEP_SCOPES)
    table = engine.device_op_map()
    scopes = {v[0] for v in table.values() if v}
    assert {"retention_proj", "retention_scan", "mlp", "embed", "tail"} <= scopes
    assert not {"attn", "qkv", "kv_write"} & scopes
    moves = [v for k, v in table.items() if "f32[3,2,2,64,8]" in k]
    assert moves and any(v and v[0] == "retention_scan" for v in moves), moves
    from tools.summarize_trace import format_summary, tick_account

    assert tick_account(tracer.events())["retention_state_rows"] == rows / len(ticks)
    assert "power-retention recurrence" in format_summary(tracer.events(), top=0)
