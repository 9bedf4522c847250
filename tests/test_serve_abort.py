"""Abort correctness: cancelling a request must be invisible to everyone
else.

The invariants: every abort returns EVERY block the request held (pool
free count restored — ``request_held`` back to baseline), shared prefix
blocks are decref'd without corrupting their other sharers (whose tokens
must still match the offline run), the decode step never recompiles
across abort churn (tables are rebuilt per tick — abort is host-side
unwinding only), and the terminal-event plumbing reports the uniform
finish-reason vocabulary (stop/length/aborted/evicted-requeued) in
callbacks and the metrics snapshot alike.
"""

import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.generate import Generator
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import QueueFull, RequestState, ServeEngine
from tools.compile_counter import assert_serve_compiles_bounded


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"), **kw)


def _offline(cfg, params, req):
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                    cache_dtype=jnp.float32)
    res = gen.generate_ragged([req.prompt], req.max_new_tokens, seed=req.seed)
    return [int(t) for t in np.asarray(res.tokens)[0][: req.max_new_tokens]]


def test_abort_queued_request_frees_nothing_and_fires_event(tiny):
    """A queued request holds no blocks; abort removes it from the queue,
    fires the terminal event, and the pool is untouched."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=1)
    rng = np.random.default_rng(0)
    events = []
    a = engine.submit(rng.integers(1, cfg.vocab_size, size=6), 8)
    engine.step()  # a admitted into the single slot
    b = engine.submit(rng.integers(1, cfg.vocab_size, size=6), 8,
                      on_event=lambda r, e: events.append(e))
    assert b.state is RequestState.QUEUED
    held_before = engine.pool.stats()["request_held"]
    assert engine.abort(b.req_id)
    assert b.state is RequestState.ABORTED
    assert b.finish_reason == "aborted"
    assert events == ["aborted"]
    assert engine.pool.stats()["request_held"] == held_before
    engine.run_until_complete()
    assert a.generated == _offline(cfg, params, a)
    assert engine.pool.stats()["request_held"] == 0


def test_abort_mid_prefill_returns_all_blocks(tiny):
    """Abort right after the admission tick — the prompt's first slice
    (a lone row takes the whole budget, 18 tokens here) is in the pool,
    the rest is not, no token has been emitted: the freshly written
    prefill blocks all come back."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    rng = np.random.default_rng(1)
    req = engine.submit(rng.integers(1, cfg.vocab_size, size=30), 10)
    engine.step()  # admits + writes the first prefill slice
    assert req.state is RequestState.RUNNING
    assert 0 < req.prefill_done < req.prefill_target  # genuinely mid-prefill
    assert not req.generated
    assert engine.pool.stats()["request_held"] > 0
    assert engine.abort(req.req_id)
    assert engine.pool.stats()["request_held"] == 0
    assert engine.pool.free_list.num_allocated == 0
    assert not engine.scheduler.has_work


def test_abort_mid_decode_restores_pool_and_metrics(tiny):
    """Abort after several decode ticks: blocks return, the metrics
    snapshot counts the abort, and other requests finish with offline
    parity."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=2)
    rng = np.random.default_rng(2)
    keep = engine.submit(rng.integers(1, cfg.vocab_size, size=5), 12)
    kill = engine.submit(rng.integers(1, cfg.vocab_size, size=9), 12)
    for _ in range(4):
        engine.step()
    assert len(kill.generated) > 1  # genuinely mid-decode
    assert engine.abort(kill.req_id)
    assert engine.abort(kill.req_id) is False  # idempotent no-op
    engine.run_until_complete()
    assert keep.generated == _offline(cfg, params, keep)
    assert engine.pool.stats()["request_held"] == 0
    snap = engine.metrics.snapshot()
    assert snap["aborted"] == 1
    assert snap["finished"] == 1
    assert snap["finish_reasons"]["aborted"] == 1


def test_abort_decrefs_shared_prefix_without_corrupting_sharers(tiny):
    """Two requests share prompt-prefix blocks (refcounted).  Aborting
    one mid-decode must decref — not free — the shared blocks: the
    surviving sharer's tokens still match the offline run, and the final
    pool state is cache-only entries, all reclaimable."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    prompt = rng.integers(1, cfg.vocab_size, size=20)
    engine = _engine(cfg, params, num_blocks=48,
                     enable_prefix_cache=True)
    first = engine.submit(prompt, 4, seed=0)
    engine.run_until_complete()  # registers the prefix blocks
    assert first.generated == _offline(cfg, params, first)

    survivor = engine.submit(prompt, 6, seed=0)
    victim = engine.submit(prompt, 6, seed=0)
    engine.step()  # both admitted; prefix hits claimed
    assert survivor.n_shared_blocks > 0
    assert victim.n_shared_blocks > 0
    shared_ids = list(victim.block_ids[: victim.n_shared_blocks])
    refs_before = [engine.pool.free_list.refcount(b) for b in shared_ids]
    engine.step()
    assert engine.abort(victim.req_id)
    # exactly one reference dropped per shared block — not a hard free
    refs_after = [engine.pool.free_list.refcount(b) for b in shared_ids]
    assert refs_after == [r - 1 for r in refs_before]
    engine.run_until_complete()
    assert survivor.generated == _offline(cfg, params, survivor)
    stats = engine.pool.stats()
    assert stats["request_held"] == 0
    assert stats["cache_only"] == stats["allocated"]


def test_abort_churn_never_recompiles_decode(tiny):
    """The compile-counter lint over an abort-churn trace: interleaved
    submits and aborts across queued/running states stay within the
    static-shape bounds — the step compiles at most once a packed-width
    bucket."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    rng = np.random.default_rng(4)
    lens = (5, 9, 13)
    for round_ in range(4):
        live = [
            engine.submit(rng.integers(1, cfg.vocab_size, size=n), 8)
            for n in lens
        ]
        engine.step()
        engine.abort(live[round_ % len(live)].req_id)
        engine.run_until_complete()
    assert_serve_compiles_bounded(engine)
    counts = engine.compile_counts()
    assert set(counts) == {"mixed_step"}
    assert counts["mixed_step"] <= len(engine.mixed_buckets)
    assert engine.pool.stats()["request_held"] == 0


def test_deadline_expiry_aborts_with_reason(tiny):
    """A request past its deadline is aborted by the tick loop's sweep:
    terminal event 'aborted', blocks returned, engine drains."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=1)
    rng = np.random.default_rng(5)
    events = []
    req = engine.submit(
        rng.integers(1, cfg.vocab_size, size=6), 40, deadline_s=0.2,
        on_event=lambda r, e: events.append(e),
    )
    t0 = time.time()
    while engine.scheduler.has_work and time.time() - t0 < 30:
        engine.step()
    assert req.finish_reason == "aborted"
    assert events == ["aborted"]
    assert 0 < len(req.generated) < 40
    assert engine.pool.stats()["request_held"] == 0


def test_queue_cap_rejects_with_queue_full(tiny):
    """max_queue backpressure: submits past the cap raise QueueFull and
    count as rejects; preemption requeues are exempt from the cap."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=1, max_queue=2)
    rng = np.random.default_rng(6)
    engine.submit(rng.integers(1, cfg.vocab_size, size=5), 6)
    engine.step()  # admitted
    engine.submit(rng.integers(1, cfg.vocab_size, size=5), 6)
    engine.submit(rng.integers(1, cfg.vocab_size, size=5), 6)
    with pytest.raises(QueueFull):
        engine.submit(rng.integers(1, cfg.vocab_size, size=5), 6)
    assert engine.metrics.snapshot()["rejected"] == 1
    engine.run_until_complete()
    assert len(engine.scheduler.finished) == 3


def test_finish_reasons_uniform_in_events_and_snapshot(tiny):
    """stop/length/aborted all flow through on_event, Request
    .finish_reason, and the metrics snapshot with the same names; a
    preemption fires the non-terminal 'evicted-requeued' event."""
    cfg, params = tiny
    # stop-token run
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), stop_tokens=(7,),
        max_slots=2, num_blocks=24, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32,
    )
    rng = np.random.default_rng(7)
    events: dict[int, list[str]] = {}
    oe = lambda r, e: events.setdefault(r.req_id, []).append(e)
    reqs = [
        engine.submit(rng.integers(1, cfg.vocab_size, size=6), 24,
                      on_event=oe)
        for _ in range(3)
    ]
    engine.abort(reqs[2].req_id)
    engine.run_until_complete()
    for req in reqs:
        assert req.finish_reason in ("stop", "length", "aborted")
        assert events[req.req_id][-1] == req.finish_reason
    snap = engine.metrics.snapshot()
    assert sum(snap["finish_reasons"].values()) == 3
    assert snap["finish_reasons"].get("aborted") == 1

    # eviction path: a pool too small for two long requests
    engine2 = _engine(cfg, params, max_slots=2, num_blocks=6)
    events2 = []
    for n in (4, 5):
        engine2.submit(rng.integers(1, cfg.vocab_size, size=n), 20,
                       on_event=lambda r, e: events2.append(e))
    engine2.run_until_complete()
    assert engine2.scheduler.n_preemptions > 0
    assert "evicted-requeued" in events2
    assert events2.count("length") == 2


def test_metrics_bounded_retention_keeps_counters_exact():
    """max_samples (the long-running-server mode the HTTP runner sets)
    bounds every sample list while counters stay exact."""
    from llm_np_cp_tpu.serve.metrics import ServeMetrics

    m = ServeMetrics(max_samples=100)
    for i in range(1000):
        m.on_tick(queue_depth=i, occupancy=0.5, active_slots=1,
                  preemptions_total=0, kv_bytes=64)
    assert len(m.queue_depth) <= 100
    assert len(m.kv_bytes_tick) <= 100
    snap = m.snapshot()
    assert snap["ticks"] == 1000  # counter exact, window trimmed
    assert snap["queue_depth_last"] == 999.0


def test_metrics_concurrent_scrape_is_consistent(tiny):
    """The copy-on-read contract: hammer snapshot()+prometheus() from a
    scrape thread while the engine thread serves traffic — every
    snapshot is internally consistent and every exposition line parses.
    """
    import re

    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=2)
    rng = np.random.default_rng(8)
    stop = threading.Event()
    failures: list[str] = []
    line_re = re.compile(
        r"[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.]+(e[+-]?[0-9]+)?"
    )

    def scrape():
        while not stop.is_set():
            snap = engine.metrics.snapshot()
            if snap["finished"] + snap["aborted"] > snap["submitted"]:
                failures.append(f"terminal > submitted: {snap}")
            for line in engine.metrics.prometheus(
                extra_gauges={"inflight_streams": 1}
            ).splitlines():
                if not line.startswith("# ") and not line_re.fullmatch(line):
                    failures.append(f"bad exposition line: {line!r}")
                    break

    threads = [threading.Thread(target=scrape) for _ in range(2)]
    for t in threads:
        t.start()
    try:
        for _ in range(3):
            for n in (5, 9, 6, 11):
                engine.submit(rng.integers(1, cfg.vocab_size, size=n), 5)
            engine.run_until_complete()
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
    assert not failures, failures[:3]
    snap = engine.metrics.snapshot()
    assert snap["finished"] == 12


# ---------------------------------------------------------------------------
# Aborts against the unified tick's owed list (PR 35): tick N's tokens are
# published behind tick N+1's dispatch, so an abort can find tokens the
# request's callback has not been handed yet
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("where", ["between-ticks", "from-the-callback",
                                   "from-a-peers-callback"])
def test_abort_leaves_generated_equal_to_what_the_callback_was_handed(
        tiny, where):
    """Between ticks the owed tokens go out before the ``aborted`` event
    (as a tick that publishes at once would have handed them out by
    then); from inside a token callback they are dropped.  Either way the
    terminal follows the last token the callback saw, ``req.generated``
    is exactly what it was handed — a prefix of the offline stream —,
    the row's in-flight result is skipped, the pool is whole and the
    peer's stream is untouched."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=2)
    rng = np.random.default_rng(35)
    log: dict[int, list] = {0: [], 1: []}

    def on_event(req, event):
        log[req.req_id].append(event)

    def cb(req, tok, delta):
        log[req.req_id].append(tok)
        n = sum(1 for t in log[req.req_id] if not isinstance(t, str))
        if where == "from-the-callback" and req.req_id == 1 and n == 3:
            engine.abort(1)
        if where == "from-a-peers-callback" and req.req_id == 0 and n == 3:
            engine.abort(1)

    keep = engine.submit(rng.integers(1, cfg.vocab_size, size=5), 12,
                         request_id=0, callback=cb, on_event=on_event)
    kill = engine.submit(rng.integers(1, cfg.vocab_size, size=9), 12,
                         request_id=1, callback=cb, on_event=on_event)
    if where == "between-ticks":
        for _ in range(4):
            assert engine.step()
        owed = sum(1 for item in engine._owed if item[1] is kill)
        assert owed == 1 and len(kill.generated) == len(log[1]) + 1
        accepted = list(kill.generated)
        assert engine.abort(1)
        # tick 4's token went out first, then the terminal
        assert log[1] == accepted + ["aborted"]
        assert not any(item[1] is kill for item in engine._owed)
        # the peer's owed token stays owed: a tick follows
        assert any(item[1] is keep for item in engine._owed)
    engine.run_until_complete()
    assert kill.finish_reason == "aborted" and log[1][-1] == "aborted"
    assert log[1][:-1] == kill.generated
    assert kill.generated == _offline(cfg, params, kill)[:len(kill.generated)]
    if where == "between-ticks":
        assert kill.generated == accepted and len(accepted) > 1
    elif where == "from-the-callback":
        assert len(kill.generated) == 3
    else:
        # the peer's third token is published BEFORE this request's token
        # of the same tick (decode rows publish in row order): that one
        # was owed and is dropped
        assert 0 < len(kill.generated) < 3
    assert log[0][-1] == "length" and log[0][:-1] == keep.generated
    assert keep.generated == _offline(cfg, params, keep)
    assert engine.pool.stats()["request_held"] == 0 and not engine._owed
    snap = engine.metrics.snapshot()
    assert snap["aborted"] == 1 and snap["finished"] == 1
    # metrics count what was handed out, nothing dropped
    assert snap["total_generated_tokens"] == 12 + len(kill.generated)
