"""Speculative decoding inside the unified tick (ServeEngine spec_k).

The acceptance bar is the same output-invisibility contract every other
serve feature carries, applied to draft-then-verify: a spec-enabled
engine's streams must be TOKEN-IDENTICAL to the plain unified tick —
the verifier samples every packed position with the deterministic
(seed, content-pos) keys, so an accepted draft IS the token plain decode
would have emitted — across int8 pools, prefix sharing, aborts
mid-verify, eviction-requeue, journal replay, and teacher-forced
recovery.  Plus the claims that justify the mode: drafts ride the ONE
mixed dispatch per tick (host-side prompt lookup, no extra dispatches),
verify-width churn never recompiles past the warmed bucket ladder, and
a collapsing acceptance rate turns an individual request back into a
plain decode row.

CPU backend; the ragged Pallas kernel runs in interpret mode.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.generate import Generator
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import (
    DraftState,
    Scheduler,
    ServeEngine,
    ServeMetrics,
    poisson_trace,
)
from llm_np_cp_tpu.serve.scheduler import Request
from tools.compile_counter import (
    CompileCounter,
    assert_serve_compiles_bounded,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, spec_k=4, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                       spec_k=spec_k, **kw)


def _tokens(engine):
    return {r.req_id: r.generated for r in engine.scheduler.finished}


def _tiled_prompts(rng, vocab, lens, pattern=4):
    """Repetitive prompts (random pattern tiled to length): the
    prompt-lookup draft's win case, so verify rounds really run."""
    out = []
    for n in lens:
        base = rng.integers(1, vocab, size=pattern, dtype=np.int64)
        out.append(np.resize(base.astype(np.int32), n))
    return out


# ---------------------------------------------------------------------------
# DraftState (host-side prompt lookup)
# ---------------------------------------------------------------------------

def test_draft_state_proposes_prior_continuation():
    st = DraftState(ngram_max=3, ngram_min=2)
    st.extend([1, 2, 3, 4, 1, 2, 3])
    # suffix trigram (1,2,3) recurred: continuation of its PRIOR
    # occurrence is [4, 1, 2, 3]
    assert st.propose(4) == [4, 1, 2, 3]
    assert st.propose(2) == [4, 1]
    assert st.propose(0) == []


def test_draft_state_cycles_short_periods():
    st = DraftState()
    st.extend([7, 7, 7])
    # a 1-periodic tail proposes k drafts, not one (modular copy)
    assert st.propose(4) == [7, 7, 7, 7]


def test_draft_state_no_match_means_no_draft():
    st = DraftState()
    st.extend([1, 2, 3, 4, 5, 6])  # all n-grams distinct
    assert st.propose(4) == []
    st.extend([9])
    assert st.propose(4) == []


def test_draft_state_incremental_extend():
    whole = DraftState()
    whole.extend([5, 6, 5, 6, 5])
    inc = DraftState()
    inc.extend([5, 6])
    inc.extend([5])
    inc.extend([6, 5])
    assert inc.size == whole.size == 5
    assert inc.propose(3) == whole.propose(3) == [6, 5, 6]


def test_draft_state_rejects_bad_ngram_range():
    with pytest.raises(ValueError, match="ngram"):
        DraftState(ngram_max=1, ngram_min=2)


# ---------------------------------------------------------------------------
# Planner: verify widths are budgeted as tokens
# ---------------------------------------------------------------------------

class _Alloc:
    num_free = 10_000

    def alloc(self, n):
        return list(range(n))

    def free(self, ids):
        pass


def _running_request(rid, slot, draft_len=0):
    r = Request(req_id=rid, prompt=np.ones(4, np.int32), max_new_tokens=8)
    r.prefilled = True
    r.generated = [1]
    r.slot = slot
    r.draft_len = draft_len
    return r


def test_plan_tick_budgets_draft_widths_after_prefill():
    sched = Scheduler(_Alloc(), max_slots=4, block_size=8)
    rows = [_running_request(i, i, draft_len=3) for i in range(3)]
    sched.running.extend(rows)
    # budget 3 base + 4 slack: drafts trim to the slack, oldest first
    decode, prefill = sched.plan_tick(7, 8)
    assert decode == rows and prefill == []
    assert [r.draft_len for r in rows] == [3, 1, 0]
    planned = len(decode) + sum(r.draft_len for r in decode)
    assert planned <= 7


def test_plan_tick_drafts_never_starve_prefill():
    sched = Scheduler(_Alloc(), max_slots=4, block_size=8)
    dec = _running_request(0, 0, draft_len=4)
    pre = Request(req_id=1, prompt=np.ones(16, np.int32), max_new_tokens=4)
    pre.prefill_target = 16
    pre.slot = 1
    sched.running.extend([dec, pre])
    decode, prefill = sched.plan_tick(9, 8)
    # prefill takes the budget FIRST (1 decode + 8 chunk), the draft
    # gets only the remainder — speculation spends slack, never TTFT
    assert prefill == [(pre, 8)]
    assert dec.draft_len == 0
    dec.draft_len = 4  # plan_tick trims in place; re-propose
    decode, prefill = sched.plan_tick(13, 8)
    assert prefill == [(pre, 8)]
    assert dec.draft_len == 4


# ---------------------------------------------------------------------------
# The acceptance criterion: 32-request parity vs the plain unified tick
# ---------------------------------------------------------------------------

def test_spec_trace_parity_32_requests(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(0)
    trace = poisson_trace(
        rng, 32, rate_rps=40.0, prompt_len_range=(4, 14),
        max_new_tokens=8, vocab_size=cfg.vocab_size,
    )
    prompts = _tiled_prompts(rng, cfg.vocab_size,
                             [t["prompt"].size for t in trace])
    for t, p in zip(trace, prompts):
        t["prompt"] = p
        t["speculative"] = True

    def run(spec_k):
        engine = _engine(cfg, params, spec_k=spec_k)
        snap = engine.replay_trace(trace)
        assert snap["finished"] == 32
        return engine, snap

    spec, ssnap = run(4)
    plain, _ = run(0)
    assert _tokens(spec) == _tokens(plain)
    # verify rounds really ran, and they paid
    assert ssnap["spec_drafted_tokens"] > 0
    assert ssnap["spec_accepted_tokens"] > 0
    assert 0.0 <= ssnap["spec_accept_rate"] <= 1.0
    # drafting adds NO dispatches: verify lanes ride the one mixed
    # dispatch per tick
    assert spec.n_dispatches <= ssnap["ticks"]
    # ... and accepted drafts are free tokens: strictly fewer ticks than
    # plain decode on this repetitive workload
    assert ssnap["ticks"] < plain.metrics.snapshot()["ticks"]
    assert_serve_compiles_bounded(spec)
    # offline ground truth (the engine-vs-offline chain: spec == plain
    # == generate_ragged)
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                    cache_dtype=jnp.float32)
    for req in list(spec.scheduler.finished)[:6]:
        res = gen.generate_ragged([req.prompt], req.max_new_tokens,
                                  seed=req.seed)
        want = [int(t) for t in np.asarray(res.tokens)[0][: req.max_new_tokens]]
        assert req.generated == want


def test_spec_int8_pool_parity(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(11)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (8, 12, 5), pattern=3)

    def run(spec_k):
        engine = _engine(cfg, params, spec_k=spec_k, max_slots=3,
                         num_blocks=24, cache_dtype=jnp.int8)
        for j, p in enumerate(prompts):
            engine.submit(p, 6, seed=j, speculative=True)
        engine.run_until_complete()
        return engine

    spec = run(3)
    assert spec.pool.pages.quantized
    assert _tokens(spec) == _tokens(run(0))
    assert spec.metrics.snapshot().get("spec_drafted_tokens", 0) > 0


def test_spec_prefix_sharing_parity(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(3)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (20, 17), pattern=5)

    def run(spec_k):
        engine = _engine(cfg, params, spec_k=spec_k,
                         enable_prefix_cache=True)
        for rep in range(3):
            for j, p in enumerate(prompts):
                engine.submit(p, 5, seed=j, speculative=True)
        engine.run_until_complete()
        return engine

    spec = run(4)
    assert _tokens(spec) == _tokens(run(0))
    snap = spec.metrics.snapshot()
    assert snap["prefix_blocks_hit"] > 0
    assert snap.get("spec_drafted_tokens", 0) > 0
    fl = spec.pool.free_list
    assert fl.num_free + fl.num_allocated == fl.capacity


def test_spec_eviction_requeue_parity(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(7)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (4, 5, 3), pattern=3)

    def run(spec_k):
        engine = _engine(cfg, params, spec_k=spec_k, max_slots=2,
                         num_blocks=6)
        for j, p in enumerate(prompts):
            engine.submit(p, 20, seed=j, speculative=True)
        engine.run_until_complete()
        return engine

    spec = run(3)
    assert spec.scheduler.n_preemptions > 0, "pool not tight enough"
    assert _tokens(spec) == _tokens(run(0))
    assert spec.pool.free_list.num_allocated == 0


def test_spec_abort_mid_verify(tiny):
    """Abort while a request is actively speculating — including from
    its OWN token callback mid-accept-walk (the remaining verified
    samples must be discarded, blocks returned, peers unaffected)."""
    cfg, params = tiny
    rng = np.random.default_rng(9)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (10, 9), pattern=3)
    engine = _engine(cfg, params, spec_k=4, max_slots=2)

    killed: list[int] = []
    handed: list[int] = []

    def kill_after_3(req, tok, delta):
        # (the callback counts what IT was handed: the tick publishes
        # behind the next dispatch, so req.generated may already hold
        # the rest of the verify round the walk accepted)
        handed.append(tok)
        if len(handed) == 3:
            killed.append(req.req_id)
            engine.abort(req.req_id)

    r0 = engine.submit(prompts[0], 12, seed=0, speculative=True,
                       callback=kill_after_3)
    r1 = engine.submit(prompts[1], 8, seed=1, speculative=True)
    engine.run_until_complete()
    assert killed == [r0.req_id]
    assert r0.finish_reason == "aborted"
    assert len(r0.generated) == 3 and r0.generated == handed, (
        "the publish kept emitting past the abort, or the request keeps "
        "tokens its callback never saw"
    )
    assert engine.pool.stats()["request_held"] == 0
    assert r0.req_id not in engine._draft_states
    # the surviving stream matches plain decode exactly
    ref = _engine(cfg, params, spec_k=0)
    ref.submit(prompts[1], 8, seed=1, request_id=r1.req_id)
    ref.run_until_complete()
    assert r1.generated == _tokens(ref)[r1.req_id]


def test_spec_rolling_acceptance_fallback(tiny):
    """A request whose drafts keep missing turns back into a plain
    decode row (spec_off), with tokens unchanged."""
    cfg, params = tiny
    rng = np.random.default_rng(13)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (9, 8), pattern=3)
    # min_accept > 1 is unsatisfiable (accepted <= drafted), so the
    # FIRST full window trips the fallback deterministically
    spec = _engine(cfg, params, spec_k=3, spec_min_accept=2.0,
                   spec_window=2)
    reqs = [spec.submit(p, 10, seed=j, speculative=True)
            for j, p in enumerate(prompts)]
    spec.run_until_complete()
    assert any(r.extra.get("spec_off") for r in reqs), (
        "unsatisfiable acceptance floor never tripped the fallback"
    )
    assert _tokens(spec) == _tokens(
        (lambda e: (e, [e.submit(p, 10, seed=j) for j, p in
                        enumerate(prompts)], e.run_until_complete())[0])(
            _engine(cfg, params, spec_k=0))
    )


def test_spec_recovery_replay_parity_zero_recompiles(tiny):
    """clone_fresh shares the spec-enabled compiled step; teacher-forced
    recovery of mid-verify spec requests is token-identical to an
    uninterrupted plain run and compiles NOTHING."""
    cfg, params = tiny
    rng = np.random.default_rng(17)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (12, 7, 9), pattern=4)
    engine = _engine(cfg, params, spec_k=3, max_slots=2)
    engine.warmup([int(p.size) for p in prompts], max_new_tokens=8)
    live = [engine.submit(p, 8, seed=i, speculative=True)
            for i, p in enumerate(prompts)]
    for _ in range(3):
        engine.step()  # some mid-prefill, some mid-verify
    warm = dict(engine.compile_counts())

    counter = CompileCounter()
    with counter.watch():
        rebuilt = engine.clone_fresh()
        assert rebuilt.spec_k == engine.spec_k
        assert rebuilt._mixed_step is engine._mixed_step
        for r in live:
            rebuilt.recover(r.prompt, r.max_new_tokens,
                            request_id=r.req_id, seed=r.seed,
                            generated=list(r.generated), speculative=True)
        rebuilt.run_until_complete()
    assert counter.count == 0, (
        f"spec restart + recovery replay compiled: {counter.events}"
    )
    assert rebuilt.compile_counts() == warm

    ref = _engine(cfg, params, spec_k=0, max_slots=2)
    for i, p in enumerate(prompts):
        ref.submit(p, 8, seed=i, request_id=live[i].req_id)
    ref.run_until_complete()
    assert _tokens(rebuilt) == _tokens(ref)
    assert rebuilt.pool.stats()["request_held"] == 0


def test_spec_journal_replay_round_trip(tiny, tmp_path):
    """The journal records the speculative opt-in and watermarks carry
    ONLY accepted tokens, so a killed spec stream replays
    token-identically — and resumes drafting — on a rebuilt engine."""
    from llm_np_cp_tpu.serve.journal import RequestJournal

    cfg, params = tiny
    rng = np.random.default_rng(19)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (10, 8), pattern=3)
    jpath = str(tmp_path / "spec.journal")
    journal = RequestJournal(jpath)
    engine = _engine(cfg, params, spec_k=3, journal=journal)
    live = [engine.submit(p, 24, seed=j, speculative=True)
            for j, p in enumerate(prompts)]
    for _ in range(3):
        engine.step()  # several verify rounds land in the watermarks
    assert all(r.finish_reason is None for r in live), (
        "a stream finished before the simulated kill — raise the budget"
    )
    assert journal.flush(10.0)
    journal.close()  # the "kill": no terminals were written

    reopened = RequestJournal(jpath)
    replays = reopened.replay()
    assert len(replays) == 2
    for rec in replays:
        assert rec["spec"] is True
        # watermark tokens are exactly the accepted prefix
        rid = rec["rid"]
        src = next(r for r in live if r.req_id == rid)
        assert rec["tokens"] == src.generated[: len(rec["tokens"])]
    eng2 = _engine(cfg, params, spec_k=3, journal=reopened)
    for rec in replays:
        req = eng2.recover(
            rec["prompt"], rec["max_tokens"], request_id=rec["rid"],
            seed=rec["seed"], generated=rec["tokens"],
            speculative=rec["spec"],
        )
        assert req.speculative
    eng2.run_until_complete()
    reopened.close()

    ref = _engine(cfg, params, spec_k=0)
    for j, p in enumerate(prompts):
        ref.submit(p, 24, seed=j, request_id=live[j].req_id)
    ref.run_until_complete()
    assert _tokens(eng2) == _tokens(ref)


def test_spec_zero_compiles_across_verify_width_churn(tiny):
    """After the warmed bucket ladder, ticks whose verify widths churn
    (drafts 0..k per row, spec and plain rows mixed, prefill overlap)
    compile NOTHING — the verify lanes are a static [R, k+1] extension
    of the mixed step."""
    cfg, params = tiny
    engine = _engine(cfg, params, spec_k=3)
    rng = np.random.default_rng(4)
    lens = (4, 18, 7, 11)
    engine.warmup([int(n) for n in lens], max_new_tokens=8)
    warm = dict(engine.compile_counts())
    prompts = _tiled_prompts(rng, cfg.vocab_size, lens, pattern=4)

    counter = CompileCounter()
    with counter.watch():
        for rep in range(3):
            for i, p in enumerate(prompts):
                engine.submit(p, 3 + i, seed=rep * 10 + i,
                              speculative=(i % 2 == 0))
            engine.run_until_complete()
    assert counter.count == 0, (
        f"verify-width churn compiled: {counter.events}"
    )
    assert engine.compile_counts() == warm


# ---------------------------------------------------------------------------
# Gating & validation
# ---------------------------------------------------------------------------

def test_spec_rejects_bad_widths(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="spec_k"):
        _engine(cfg, params, spec_k=-1)
    # construction-time, not first-draft-tick-inside-the-supervisor
    with pytest.raises(ValueError, match="spec_ngram"):
        _engine(cfg, params, spec_k=2, spec_ngram=1)


def test_spec_stop_token_parity_and_terminal_draft_counted(tiny):
    """A drafted stop token ends the stream exactly where plain decode
    would (trailing verified samples discarded) AND counts as accepted —
    the draft paid off even though it was terminal."""
    cfg, params = tiny
    rng = np.random.default_rng(31)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (9, 12), pattern=3)
    # learn each stream's loop token from a pilot run, then use it as
    # the stop token: the drafts will propose it mid-window
    pilot = _engine(cfg, params, spec_k=0)
    for j, p in enumerate(prompts):
        pilot.submit(p, 10, seed=j)
    pilot.run_until_complete()
    stop = int(_tokens(pilot)[0][-1])

    def run(spec_k):
        engine = _engine(cfg, params, spec_k=spec_k, stop_tokens=(stop,))
        for j, p in enumerate(prompts):
            engine.submit(p, 10, seed=j, speculative=True)
        engine.run_until_complete()
        return engine

    spec = run(4)
    plain = run(0)
    assert _tokens(spec) == _tokens(plain)
    assert any(r.finish_reason == "stop" for r in spec.scheduler.finished)
    snap = spec.metrics.snapshot()
    assert snap.get("spec_drafted_tokens", 0) > 0
    # the accounting identity survives terminal drafts: every emitted
    # token is one admission first-token, one decode-row base token, or
    # one ACCEPTED draft — a drafted stop token must land in accepted,
    # not rejected
    assert snap["spec_accepted_tokens"] == (
        snap["total_generated_tokens"]
        - (len(prompts) + snap["preemptions"])
        - snap["mixed_decode_tokens"]
    )


def test_spec_on_the_xla_tick_keeps_speculating(tiny, monkeypatch):
    """With the ragged probe failing the tick
    runs over the XLA ragged attention and speculation STAYS on — the
    verifier is the tick's, not the kernel's — with the tokens plain
    decode emits."""
    import llm_np_cp_tpu.ops.pallas.support as support

    cfg, params = tiny
    rng = np.random.default_rng(17)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (9, 12), pattern=3)

    def run(**kw):
        eng = _engine(cfg, params, **kw)
        for j, p in enumerate(prompts):
            eng.submit(p, 10, seed=j, speculative=bool(kw.get("spec_k")))
        eng.run_until_complete()
        return eng

    plain = run(spec_k=0)
    monkeypatch.setattr(support, "_FORCE_FAIL", True)
    support._probe.cache_clear()  # conftest clears it again afterwards
    spec = run(spec_k=4)
    assert spec.mixed and spec.ragged_attn_impl == "xla"
    assert spec.spec_k == 4
    assert spec.metrics.snapshot()["spec_accepted_tokens"] > 0
    assert _tokens(spec) == _tokens(plain)


@pytest.mark.http
def test_spec_over_http_opt_in_parity(tiny):
    """The /v1/completions `"speculative": true` opt-in round-trips to
    the engine: a spec-enabled server returns the EXACT tokens a plain
    server returns for the same prompt/seed, verify rounds really run,
    and the scrape carries the spec series."""
    import asyncio
    import json as _json

    from llm_np_cp_tpu.serve.http.client import http_get, post_completion
    from llm_np_cp_tpu.serve.http.server import HttpServer

    cfg, params = tiny
    rng = np.random.default_rng(21)
    prompt = [int(t) for t in
              _tiled_prompts(rng, cfg.vocab_size, (12,), pattern=4)[0]]

    def serve_once(spec_k, payload_extra):
        engine = _engine(cfg, params, spec_k=spec_k, max_slots=2)
        out = {}

        async def main():
            srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
            await srv.start("127.0.0.1", 0)
            loop = asyncio.get_running_loop()
            st, obj = await loop.run_in_executor(
                None, post_completion, srv.host, srv.port,
                {"prompt": prompt, "max_tokens": 8, "seed": 3,
                 **payload_extra})
            assert st == 200, obj
            out["tokens"] = obj["choices"][0]["token_ids"]
            st, body = await loop.run_in_executor(
                None, http_get, srv.host, srv.port, "/metrics")
            assert st == 200
            out["scrape"] = body.decode()
            srv.begin_drain()
            await srv.serve_until_shutdown()

        asyncio.run(asyncio.wait_for(main(), timeout=120))
        return out

    spec = serve_once(4, {"speculative": True})
    plain = serve_once(0, {})
    assert spec["tokens"] == plain["tokens"]
    assert 'llm_serve_spec_tokens_total{kind="drafted"}' in spec["scrape"]
    assert "llm_serve_spec_accept_length_bucket" in spec["scrape"]
    assert "spec_tokens_total" not in plain["scrape"]


# ---------------------------------------------------------------------------
# Metrics: counters, accept-length histogram, Prometheus, replica labels
# ---------------------------------------------------------------------------

def test_spec_metrics_snapshot_and_histogram():
    m = ServeMetrics()
    m.on_spec(drafted=4, accepted=4)
    m.on_spec(drafted=4, accepted=1)
    m.on_spec(drafted=2, accepted=0)
    s = m.snapshot()
    assert s["spec_drafted_tokens"] == 10
    assert s["spec_accepted_tokens"] == 5
    assert s["spec_rejected_tokens"] == 5
    assert s["spec_rounds"] == 3
    assert s["spec_accept_rate"] == 0.5
    assert s["spec_accept_len_mean"] == pytest.approx(5 / 3)
    # histogram: accept lengths 4, 1, 0 over the integer buckets
    from llm_np_cp_tpu.serve.metrics import SPEC_ACCEPT_BUCKETS

    assert m.spec_hist[SPEC_ACCEPT_BUCKETS.index(0.0)] == 1
    assert m.spec_hist[SPEC_ACCEPT_BUCKETS.index(1.0)] == 1
    assert m.spec_hist[SPEC_ACCEPT_BUCKETS.index(4.0)] == 1
    assert m.spec_hist_sum == 5.0


def test_spec_metrics_prometheus_series_and_replica_labels():
    m = ServeMetrics()
    m.on_spec(drafted=3, accepted=2)
    text = m.prometheus()
    assert 'llm_serve_spec_tokens_total{kind="drafted"} 3' in text
    assert 'llm_serve_spec_tokens_total{kind="accepted"} 2' in text
    assert 'llm_serve_spec_tokens_total{kind="rejected"} 1' in text
    assert "llm_serve_spec_accept_rate" in text
    assert "llm_serve_spec_accept_length_bucket" in text
    assert 'llm_serve_spec_accept_length_count' in text
    # replica labels splice into every spec series (fleet aggregation)
    labeled = m.prometheus(const_labels={"replica": "3"})
    assert ('llm_serve_spec_tokens_total{kind="drafted",replica="3"} 3'
            in labeled)
    assert 'llm_serve_spec_accept_length_sum{replica="3"} 2' in labeled


def test_spec_metrics_absent_without_rounds():
    """A plain engine scrapes NO spec series (a constant-zero acceptance
    gauge would read as broken speculation on a fleet dashboard)."""
    m = ServeMetrics()
    s = m.snapshot()
    assert "spec_drafted_tokens" not in s
    text = m.prometheus()
    assert "spec_tokens_total" not in text
    assert "spec_accept_length" not in text


# ---------------------------------------------------------------------------
# Tracing: the draft phase + summarize_trace's spec columns
# ---------------------------------------------------------------------------

def test_spec_tick_args_and_summarize_utilization(tiny, tmp_path):
    """Spec ticks stamp the draft/verify split into their args; the
    summarize tool's mixed_utilization section reports drafted/accepted
    columns off a recorded fixture, matching the metrics counters."""
    import json

    from llm_np_cp_tpu.serve.tracing import (
        MIXED_TICK_PHASES,
        TraceRecorder,
    )
    from tools.summarize_trace import (
        format_summary,
        load_trace,
        mixed_utilization,
        phase_totals,
    )

    cfg, params = tiny
    assert "draft" in MIXED_TICK_PHASES
    tracer = TraceRecorder()
    engine = _engine(cfg, params, spec_k=3, tracer=tracer)
    rng = np.random.default_rng(5)
    prompts = _tiled_prompts(rng, cfg.vocab_size, (9, 12, 7), pattern=3)
    for j, p in enumerate(prompts):
        engine.submit(p, 8, seed=j, speculative=True)
    engine.run_until_complete()
    snap = engine.metrics.snapshot()
    assert snap["spec_drafted_tokens"] > 0

    path = tmp_path / "spec_trace.json"
    tracer.dump(str(path))
    loaded = load_trace(str(path))
    totals = phase_totals(loaded)
    for phase in MIXED_TICK_PHASES:
        assert phase in totals, f"missing phase {phase}"
    util = mixed_utilization(loaded)
    assert util is not None
    assert util["spec_draft_tokens"] == snap["spec_drafted_tokens"]
    assert util["spec_accept_tokens"] == snap["spec_accepted_tokens"]
    assert 0.0 <= util["spec_accept_rate"] <= 1.0
    out = format_summary(loaded, top=3)
    assert "speculative:" in out and "accept rate" in out
    # a plain mixed trace has no spec columns
    plain_events = [dict(e) for e in loaded]
    for ev in plain_events:
        args = ev.get("args")
        if args:
            args.pop("spec_draft_tokens", None)
            args.pop("spec_accept_tokens", None)
    bare = tmp_path / "plain.json"
    bare.write_text(json.dumps(plain_events))
    util2 = mixed_utilization(load_trace(str(bare)))
    assert util2 is not None and "spec_draft_tokens" not in util2
