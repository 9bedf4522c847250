"""Durable request journal (serve/journal.py) + Last-Event-ID resume.

The contract being pinned: PROCESS death is a blip, not an outage.  The
journal's framing survives torn writes (truncate-on-replay), compaction
is replay-equivalent, delivery watermarks are batched per tick,
journaling adds ZERO jit recompiles, a restarted process replays
unterminated requests token-identically through the teacher-forced
``recover`` path, clients resume dropped SSE streams via
``Last-Event-ID``, a dead replica's streams drain to live peers, and —
the acceptance scenario — a real server subprocess SIGKILLed mid-decode
with 16 live streams restarts and every stream completes byte-identical
to an unkilled control run (``proc`` marker).
"""

import asyncio
import os
import signal
import struct
import subprocess
import sys
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
from llm_np_cp_tpu.serve import (
    FaultInjector,
    RequestJournal,
    ServeEngine,
    scan_journal,
)
from llm_np_cp_tpu.serve.faults import install, parse_chaos_spec
from llm_np_cp_tpu.serve.http.client import astream_completion, http_get
from llm_np_cp_tpu.serve.http.server import HttpServer
from llm_np_cp_tpu.serve.journal import iter_records
from llm_np_cp_tpu.serve.replica import ReplicaRunner
from tools.compile_counter import CompileCounter

REPO = __file__.rsplit("/tests/", 1)[0]


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


@pytest.fixture(autouse=True)
def _clean_chaos_globals():
    yield
    install(None)


def _engine(cfg, params, journal=None, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                       journal=journal, **kw)


def _offline(cfg, params, prompt, max_tokens):
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                    cache_dtype=jnp.float32)
    res = gen.generate_ragged([np.asarray(prompt, np.int32)], max_tokens)
    return [int(t) for t in np.asarray(res.tokens)[0][:max_tokens]]


# ---------------------------------------------------------------------------
# Framing, truncation, compaction (no engine)
# ---------------------------------------------------------------------------

def _mk_req(rid, prompt, max_tokens=8, seed=0, generated=(),
            deadline=None):
    from llm_np_cp_tpu.serve.scheduler import Request

    req = Request(req_id=rid, prompt=np.asarray(prompt, np.int32),
                  max_new_tokens=max_tokens, seed=seed)
    req.generated = list(generated)
    req.deadline = deadline
    return req


def test_record_framing_roundtrip(tmp_path):
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    j.admit(_mk_req(3, [1, 2, 3], max_tokens=6, seed=9), now=0.0)
    r = _mk_req(3, [1, 2, 3], max_tokens=6, seed=9, generated=[7, 8])
    j.end_tick([r])
    j.terminal(5, "stop")  # unknown rid: harmless no-op on replay
    assert j.flush(5.0)
    recs = list(iter_records(path))
    assert [rec["t"] for rec in recs] == ["epoch", "adm", "wm", "fin"]
    assert recs[1]["prompt"] == [1, 2, 3]
    assert recs[2]["rows"] == [[3, 2, [7, 8]]]
    state, _, epoch = scan_journal(path)
    assert epoch == 1
    assert state[3]["tokens"] == [7, 8]
    assert state[3]["seed"] == 9
    j.close()
    # a reopened journal continues the state and bumps the epoch
    j2 = RequestJournal(path)
    assert j2.epoch == 2
    assert [r["rid"] for r in j2.replay()] == [3]
    assert j2.replay()[0]["tokens"] == [7, 8]
    j2.terminal(3, "length")
    assert j2.flush(5.0)
    state, _, _ = scan_journal(path)
    assert state == {}
    j2.close()


def test_torn_tail_is_truncated_on_reopen(tmp_path):
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    j.admit(_mk_req(1, [4, 5]), now=0.0)
    assert j.flush(5.0)
    j.close()
    good = os.path.getsize(path)
    # a kill -9 mid-write leaves a torn frame at the tail
    with open(path, "ab") as f:
        f.write(struct.pack("<II", 500, 123) + b"torn")
    state, valid_end, _ = scan_journal(path)
    assert valid_end == good  # the torn frame is invisible to replay
    assert list(state) == [1]
    # reopening truncates back to the valid prefix, then appends cleanly
    j2 = RequestJournal(path)
    j2.admit(_mk_req(2, [6]), now=0.0)
    assert j2.flush(5.0)
    state, _, _ = scan_journal(path)
    assert sorted(state) == [1, 2]
    j2.close()


def test_corrupt_record_stops_replay_at_prefix(tmp_path):
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    j.admit(_mk_req(1, [4, 5]), now=0.0)
    j.admit(_mk_req(2, [6, 7]), now=0.0)
    assert j.flush(5.0)
    j.close()
    recs = list(iter_records(path))
    assert [r["t"] for r in recs] == ["epoch", "adm", "adm"]
    # flip one payload byte in the SECOND admission: CRC catches it and
    # replay keeps only the prefix before it
    data = bytearray(open(path, "rb").read())
    idx = data.rindex(b'"rid":2')
    data[idx + 7] ^= 0xFF
    open(path, "wb").write(bytes(data))
    state, _, _ = scan_journal(path)
    assert list(state) == [1]


def test_compaction_is_replay_equivalent_and_bounds_growth(tmp_path):
    path = str(tmp_path / "j")
    j = RequestJournal(path, compact_bytes=512)
    req = _mk_req(1, [3] * 4, max_tokens=10_000)
    j.admit(req, now=0.0)
    for i in range(300):
        req.generated.append(i % 50)
        j.end_tick([req])
    assert j.flush(10.0)
    stats = j.stats()
    assert stats["compactions"] >= 1, stats
    state, _, _ = scan_journal(path)
    assert state[1]["tokens"] == [i % 50 for i in range(300)]
    # the file holds the live-set snapshot + recent tail, not the
    # whole watermark history
    assert os.path.getsize(path) < 8 * 512
    j.close()


def test_deadline_resumes_remaining_wall_budget(tmp_path):
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    # 30s of budget left on the submitting engine's clock
    j.admit(_mk_req(1, [2, 3], deadline=130.0), now=100.0)
    assert j.flush(5.0)
    j.close()
    rec = RequestJournal(path).replay()[0]
    remaining = rec["deadline_wall"] - time.time()
    assert 25.0 < remaining <= 30.0


def test_journal_chaos_sites_degrade_not_crash(tmp_path):
    spec = parse_chaos_spec("journal_write@1;journal_fsync@1;proc_kill@9")
    assert [e.site for e in spec] == ["journal_write", "journal_fsync",
                                     "proc_kill"]
    inj = FaultInjector("journal_write@2;journal_fsync@4")
    path = str(tmp_path / "j")
    j = RequestJournal(path, fault_injector=inj)
    for rid in range(6):
        j.admit(_mk_req(rid, [1 + rid]), now=0.0)
        assert j.flush(5.0)  # one write batch per admission
    stats = j.stats()
    assert stats["write_errors"] == 1
    assert stats["fsync_errors"] == 1
    # the dropped batch lost ONE admission; everything else survived
    state, _, _ = scan_journal(path)
    assert len(state) == 5
    j.close()


# ---------------------------------------------------------------------------
# Engine integration: watermark batching + zero recompiles
# ---------------------------------------------------------------------------

def test_watermarks_batched_per_tick_not_per_token(tiny, tmp_path):
    cfg, params = tiny
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    engine = _engine(cfg, params, journal=j, max_slots=4)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (5, 9, 13)]
    reqs = [engine.submit(p, 8, seed=i) for i, p in enumerate(prompts)]
    engine.run_until_complete()
    assert j.flush(5.0)
    recs = list(iter_records(path))
    wm = [r for r in recs if r["t"] == "wm"]
    n_ticks = engine.metrics.snapshot()["ticks"]
    total_tokens = sum(len(r.generated) for r in reqs)
    # one watermark per tick plus one final-delta flush per finish —
    # batched per tick, never per token
    assert len(wm) <= n_ticks + len(reqs), (len(wm), n_ticks)
    assert len(wm) < total_tokens
    assert sum(len(row[2]) for r in wm for row in r["rows"]) == total_tokens
    # every request terminated → the replay set is empty
    state, _, _ = scan_journal(path)
    assert state == {}
    assert [r["t"] for r in recs if r["t"] == "fin"] == ["fin"] * 3
    j.close()


def test_journaling_adds_zero_recompiles(tiny, tmp_path):
    """The acceptance pin: journaling is host-side only — attaching a
    journal and replaying traffic must not compile anything (the step
    jaxprs cannot see it), and the per-program counts stay at their
    warm values."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=2)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (5, 9, 13)]
    engine.warmup([int(p.size) for p in prompts], max_new_tokens=6)
    for p in prompts:  # cover every prefill shape pre-journal
        engine.submit(p, 6)
    engine.run_until_complete()
    warm = dict(engine.compile_counts())
    j = RequestJournal(str(tmp_path / "j"))
    engine.journal = j
    with CompileCounter().watch() as counter:
        for p in prompts:
            engine.submit(p, 6)
        engine.run_until_complete()
    assert counter.count == 0, f"journaling compiled: {counter.events}"
    assert engine.compile_counts() == warm
    assert j.stats()["records"] > 0
    j.close()


def test_mid_flight_state_replays_token_identical(tiny, tmp_path):
    """Abandon an engine mid-decode (the in-process kill -9 analogue:
    no terminals, no drain) — a FRESH engine built from the journal
    finishes every stream token-identically to the offline oracle."""
    cfg, params = tiny
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    engine = _engine(cfg, params, journal=j, max_slots=2)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (6, 11, 17)]
    partial: dict[int, list[int]] = {i: [] for i in range(len(prompts))}
    reqs = [engine.submit(
        p, 8, seed=i,
        callback=lambda rq, tok, _d: partial[rq.req_id].append(tok))
        for i, p in enumerate(prompts)]
    for _ in range(4):
        engine.step()
    # the death falls BETWEEN a tick's accept and its publish: the
    # engine holds tokens no callback was handed (they are lost with it)
    assert any(partial.values()), "mid-flight please"
    assert engine._owed and any(
        len(r.generated) > len(partial[r.req_id]) for r in reqs)
    assert j.flush(5.0)
    j.close()  # simulated process death: unterminated state on disk
    # the journal's watermark is never ahead of what was published
    state, _, _ = scan_journal(path)
    assert {rid: rec["tokens"] for rid, rec in state.items()} == partial

    j2 = RequestJournal(path)
    engine2 = _engine(cfg, params, journal=j2, max_slots=2)
    got: dict[int, list[int]] = {r.req_id: [] for r in reqs}
    for rec in j2.replay():
        engine2.recover(
            rec["prompt"], rec["max_tokens"], request_id=rec["rid"],
            seed=rec["seed"], generated=rec["tokens"],
            callback=lambda rq, tok, _d: got[rq.req_id].append(tok),
        )
    engine2.run_until_complete()
    for r, p in zip(reqs, prompts):
        # the recovered request's FULL stream (journaled prefix +
        # regenerated suffix) matches the fault-free oracle, and the
        # replayed prefix was not re-emitted through the callback
        req2 = [q for q in engine2.scheduler.finished
                if q.req_id == r.req_id][0]
        assert req2.generated == _offline(cfg, params, p, 8)
        assert got[r.req_id] == req2.generated[len(partial[r.req_id]):]
    assert j2.flush(5.0)
    state, _, _ = scan_journal(path)
    assert state == {}  # all terminals written by the recovered run
    j2.close()


@pytest.mark.parametrize("spec_k", [0, 3], ids=["plain", "spec3"])
def test_watermark_is_never_ahead_of_the_published_tokens(tiny, tmp_path,
                                                          spec_k):
    """The journal's delivery watermark moves with the publish, not with
    the accept: after EVERY tick the journaled tokens of a live request
    are exactly what its callback was handed — while ``req.generated``
    may already hold the tick's accepted tokens — so a kill at any tick
    boundary replays from what the client has, never from more."""
    cfg, params = tiny
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    engine = _engine(cfg, params, journal=j, max_slots=2, spec_k=spec_k)
    rng = np.random.default_rng(4)
    prompts = [np.resize(rng.integers(1, cfg.vocab_size, size=3), n)
               for n in (6, 11, 9)]
    sent: dict[int, list[int]] = {i: [] for i in range(len(prompts))}
    reqs = [engine.submit(
        p, 8, seed=i, speculative=bool(spec_k),
        callback=lambda rq, tok, _d: sent[rq.req_id].append(tok))
        for i, p in enumerate(prompts)]
    ahead = 0
    for _ in range(200):
        more = engine.step()
        assert j.flush(5.0)
        state, _, _ = scan_journal(path)
        for r in reqs:
            assert r.generated[:len(sent[r.req_id])] == sent[r.req_id]
            if r.req_id in state:  # live: journaled == handed out
                assert state[r.req_id]["tokens"] == sent[r.req_id]
            ahead += len(r.generated) > len(sent[r.req_id])
        if not more:
            break
    assert ahead, "no tick ever owed a token: the publish was not deferred"
    assert not engine._owed and all(sent[r.req_id] == r.generated
                                    for r in reqs)
    state, _, _ = scan_journal(path)
    assert state == {}  # every terminal written, after its last token
    j.close()


# ---------------------------------------------------------------------------
# HTTP resume protocol (in-process)
# ---------------------------------------------------------------------------

@pytest.mark.http
def test_http_resume_replays_suffix_then_live(tiny, tmp_path):
    """The Last-Event-ID protocol against a server built on a journal a
    dead process left behind: re-POST with the original request id (and
    GET /v1/completions/<id>) replays exactly the missing suffix, then
    continues live; token ids carry SSE event ids; a RETRY of a
    finished-and-claimed stream re-reads it from the bounded claimed
    LRU (the PR 9 single-shot claim, made multi-read) instead of
    404ing."""
    cfg, params = tiny
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    engine = _engine(cfg, params, journal=j, max_slots=2)
    prompts = [[5] * 6, [7, 3, 9, 2, 8], [11] * 9]
    # what the dead process had SENT (its last tick's accepted tokens
    # were never published: they are not the client's, nor the journal's)
    partial: dict[int, list[int]] = {i: [] for i in range(len(prompts))}
    reqs = [engine.submit(
        p, 8, seed=i,
        callback=lambda rq, tok, _d: partial[rq.req_id].append(tok))
        for i, p in enumerate(prompts)]
    for _ in range(4):
        engine.step()
    assert j.flush(5.0)
    j.close()  # kill -9 analogue

    j2 = RequestJournal(path)
    engine2 = _engine(cfg, params, journal=j2, max_slots=2)

    async def main():
        srv = HttpServer(engine2, model_id="tiny", drain_timeout=10.0)
        assert srv.runner.journal_replayed == len(reqs)
        await srv.start("127.0.0.1", 0)
        outs = []
        for r in reqs:
            k = len(partial[r.req_id])
            res = await astream_completion(
                srv.host, srv.port,
                {"model": "tiny", "request_id": f"cmpl-{r.req_id}",
                 "last_event_id": k, "stream": True}, timeout=60)
            outs.append((r, res))
        loop = asyncio.get_running_loop()
        _, prom = await loop.run_in_executor(
            None, http_get, srv.host, srv.port, "/metrics")
        # a finished-and-claimed stream stays re-readable: a client
        # whose first resume read tore on the wire retries and gets the
        # full replay again from the claimed LRU, not a 404
        res_retry = await astream_completion(
            srv.host, srv.port,
            {"model": "tiny", "request_id": f"cmpl-{reqs[0].req_id}",
             "last_event_id": 0, "stream": True}, timeout=30)
        srv.begin_drain()
        await srv.serve_until_shutdown()
        return outs, prom.decode(), res_retry

    outs, prom, res_retry = asyncio.run(
        asyncio.wait_for(main(), timeout=120))
    for r, res in outs:
        assert res["finish_reason"] in ("length", "stop")
        full = partial[r.req_id] + res["token_ids"]
        assert full == _offline(cfg, params, prompts[r.req_id], 8)
    assert f"llm_serve_journal_replayed_total {len(reqs)}" in prom
    assert "llm_serve_journal_resumed_total 3" in prom
    assert "llm_serve_journal_fsync_p99_s" in prom
    assert res_retry["status"] == 200, res_retry
    assert res_retry["token_ids"] == _offline(cfg, params, prompts[0], 8)
    assert res_retry["finish_reason"] in ("length", "stop")
    # clean drain (all streams terminal) → empty replay set on disk
    state, _, _ = scan_journal(path)
    assert state == {}


def test_claimed_terminal_lru_is_bounded(tiny):
    """The multi-read claim is BOUNDED: claimed terminals live in a
    64-entry LRU, so retries re-read indefinitely while recent but a
    long-dead claim eventually 404s — a week-long server's memory
    stays flat whatever clients retry."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    runner = HttpServer(engine, model_id="tiny").runner  # never started

    async def main():
        loop = asyncio.get_running_loop()
        for rid in range(70):
            runner._stash_resumable(
                rid, {"tokens": [1, 2], "deltas": [None, None]},
                "length", None)
        for rid in range(70):
            aq: asyncio.Queue = asyncio.Queue()
            runner._exec_attach(("attach", rid, 0, loop, aq))
        await asyncio.sleep(0)
        assert len(runner._claimed) == 64
        # the oldest claims were evicted...
        aq = asyncio.Queue()
        runner._exec_attach(("attach", 0, 0, loop, aq))
        await asyncio.sleep(0)
        assert (await aq.get())[0] == "gone"
        # ...recent ones replay again and again
        for _ in range(3):
            aq = asyncio.Queue()
            runner._exec_attach(("attach", 69, 0, loop, aq))
            await asyncio.sleep(0)
            assert (await aq.get())[0] == "accepted"
            toks = [await aq.get() for _ in range(2)]
            assert [t[1] for t in toks] == [1, 2]
            assert (await aq.get())[0] == "finish"

    asyncio.run(asyncio.wait_for(main(), timeout=60))


@pytest.mark.http
def test_resume_of_live_stream_mid_decode(tiny, tmp_path):
    """A resume can attach while the recovered stream is STILL
    decoding: the replayed suffix and the live continuation arrive in
    order, no token duplicated or lost (the attach runs on the engine
    thread, atomically between ticks)."""
    cfg, params = tiny
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    engine = _engine(cfg, params, journal=j)
    prompt = [9] * 7
    req = engine.submit(prompt, 24, seed=4)
    engine.step()  # prefill + first token only
    k = len(req.generated)
    assert k >= 1
    assert j.flush(5.0)
    j.close()

    j2 = RequestJournal(path)
    engine2 = _engine(cfg, params, journal=j2)

    async def main():
        srv = HttpServer(engine2, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        # attach from index 0 — the full stream replays from the start
        res = await astream_completion(
            srv.host, srv.port,
            {"model": "tiny", "request_id": f"cmpl-{req.req_id}",
             "last_event_id": 0, "stream": True}, timeout=60)
        srv.begin_drain()
        await srv.serve_until_shutdown()
        return res

    res = asyncio.run(asyncio.wait_for(main(), timeout=120))
    assert res["token_ids"] == _offline(cfg, params, prompt, 24)
    assert res["finish_reason"] in ("length", "stop")


@pytest.mark.http
def test_resume_ahead_of_journal_retries_until_regenerated(tiny, tmp_path):
    """The async-fsync window: a client can hold MORE tokens than the
    journal preserved (a watermark lost to the kill).  Resuming ahead of
    the replayed prefix is retryable (503 + Retry-After while the
    recovered stream regenerates), never a terminal 404 — and the
    regenerated suffix is exactly the missing tail."""
    cfg, params = tiny
    path = str(tmp_path / "j")
    j = RequestJournal(path)
    engine = _engine(cfg, params, journal=j)
    prompt, n = [8] * 5, 8
    req = engine.submit(prompt, n, seed=2)
    engine.step()  # journal holds only the first token(s)
    k_journaled = len(req.generated)
    assert j.flush(5.0)
    j.close()
    want = _offline(cfg, params, prompt, n)
    ahead = k_journaled + 3  # the client saw tokens the journal lost

    j2 = RequestJournal(path)
    engine2 = _engine(cfg, params, journal=j2)

    async def main():
        srv = HttpServer(engine2, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        res = await astream_completion(
            srv.host, srv.port,
            {"model": "tiny", "request_id": f"cmpl-{req.req_id}",
             "last_event_id": ahead, "stream": True},
            timeout=60, retries=8, backoff_s=0.05)
        srv.begin_drain()
        await srv.serve_until_shutdown()
        return res

    res = asyncio.run(asyncio.wait_for(main(), timeout=120))
    assert res["status"] == 200, res
    assert res["token_ids"] == want[ahead:]
    assert res["finish_reason"] in ("length", "stop")


@pytest.mark.http
def test_resume_rejects_already_attached_stream(tiny):
    """A rid with a LIVE attached client must not be hijacked by a
    second resume: the attach 404s and the original stream keeps its
    bridge entry (and its tokens)."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt, n = [4] * 6, 30

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        first = asyncio.create_task(astream_completion(
            srv.host, srv.port,
            {"prompt": prompt, "max_tokens": n, "stream": True},
            timeout=60))
        while srv.runner.inflight < 1:
            await asyncio.sleep(0.005)
        rid = next(iter(srv.runner._live))
        hijack = await astream_completion(
            srv.host, srv.port,
            {"model": "tiny", "request_id": f"cmpl-{rid}",
             "last_event_id": 0, "stream": True}, timeout=30)
        res = await first
        srv.begin_drain()
        await srv.serve_until_shutdown()
        return hijack, res

    hijack, res = asyncio.run(asyncio.wait_for(main(), timeout=120))
    assert hijack["status"] == 404, hijack
    assert res["status"] == 200 and res["finish_reason"] == "length"
    assert res["token_ids"] == _offline(cfg, params, prompt, n)


# ---------------------------------------------------------------------------
# Fleet drain: a dead replica's streams move to a live peer
# ---------------------------------------------------------------------------

@pytest.mark.http
def test_dead_replica_drains_streams_to_peer(tiny, tmp_path):
    """Terminal death of one replica: its unterminated streams re-route
    through the router (prefixes re-homed), replay teacher-forced on a
    live peer, and every client still completes token-identically; the
    dead replica's journal segment gets ``drained`` terminals so a
    process restart cannot replay those streams twice."""
    cfg, params = tiny
    journals = [RequestJournal(str(tmp_path / f"j.{i}")) for i in range(2)]
    engines = [
        _engine(cfg, params, journal=journals[i], max_slots=4,
                num_blocks=64)
        for i in range(2)
    ]
    runner = ReplicaRunner(engines, max_restarts=0)
    prompt, n = [6] * 10, 12  # identical prompts → one sticky replica
    want = _offline(cfg, params, prompt, n)

    async def main():
        srv = HttpServer(engines[0], model_id="tiny", drain_timeout=20.0,
                         runner=runner)
        await srv.start("127.0.0.1", 0)
        tasks = [
            asyncio.create_task(astream_completion(
                srv.host, srv.port,
                {"prompt": prompt, "max_tokens": n, "stream": True},
                timeout=90))
            for _ in range(3)
        ]
        # let the streams start, then kill their replica terminally
        while runner.inflight < 3:
            await asyncio.sleep(0.01)
        deadline = time.time() + 20
        owner = None
        while time.time() < deadline:
            owners = {runner._owner.get(rid) for rid in runner._owner}
            live_counts = [len(r._live) for r in runner.replicas]
            if sum(live_counts) == 3 and max(live_counts) == 3:
                owner = live_counts.index(3)
                # wait until at least one token flowed
                snap = runner.replicas[owner].engine.metrics.snapshot()
                if snap["total_generated_tokens"] >= 2:
                    break
            await asyncio.sleep(0.01)
        assert owner is not None, "streams did not converge on one replica"
        dead = runner.replicas[owner]
        dead._on_engine_death("forced: fleet-drain test", dead._gen)
        results = await asyncio.gather(*tasks)
        srv.begin_drain()
        await srv.serve_until_shutdown()
        return owner, results

    owner, results = asyncio.run(asyncio.wait_for(main(), timeout=180))
    for res in results:
        assert res["status"] == 200
        assert res["finish_reason"] in ("length", "stop")
        assert res["token_ids"] == want, "drained stream diverged"
    peer = 1 - owner
    # the peer recovered them; the dead journal is drained empty
    assert engines[peer] is not runner.replicas[peer].engine or True
    for jl in journals:
        jl.flush(5.0)
    state_dead, _, _ = scan_journal(str(tmp_path / f"j.{owner}"))
    assert state_dead == {}, "dead replica's journal still holds streams"
    snap = runner.replicas[peer].engine.metrics.snapshot()
    assert snap["recovered"] >= 1


# ---------------------------------------------------------------------------
# The acceptance scenario: subprocess kill -9, restart, resume
# ---------------------------------------------------------------------------

def _spawn_server(tmp_path, tag, *, port=0, journal=None, chaos=None,
                  max_tokens=12):
    pf = str(tmp_path / f"port_{tag}")
    cmd = [
        sys.executable, os.path.join(REPO, "tools", "serve_proc.py"),
        "--model", "tiny", "--port", str(port), "--port-file", pf,
        "--slots", "4", "--block-size", "8", "--prompt-len", "24",
        "--max-tokens", str(max_tokens),
    ]
    if journal:
        cmd += ["--journal", journal]
    if chaos:
        cmd += ["--chaos", chaos]
    log = open(tmp_path / f"log_{tag}", "w")
    proc = subprocess.Popen(cmd, stdout=log, stderr=subprocess.STDOUT,
                            cwd=REPO)
    deadline = time.time() + 180
    while time.time() < deadline:
        if proc.poll() is not None:
            raise AssertionError(
                f"server {tag} died at startup:\n"
                + open(tmp_path / f"log_{tag}").read()[-2000:])
        if os.path.exists(pf):
            host, port_s = open(pf).read().split()
            return proc, host, int(port_s)
        time.sleep(0.05)
    proc.kill()
    raise AssertionError(f"server {tag} never wrote its port file")


def _drive(host, port, reqs, *, retries, timeout=150.0, max_backoff_s=2.0,
           give_up=None):
    """``give_up``: a ``threading.Event`` that ends the drive at once (the
    respawn failed: no retry will ever find a server)."""
    async def main():
        async def one(i, item):
            prompt, n, seed = item
            await asyncio.sleep(0.01 * i)
            return await astream_completion(
                host, port,
                {"prompt": prompt, "max_tokens": n, "seed": seed,
                 "stream": True},
                timeout=timeout, retries=retries, backoff_s=0.3,
                max_backoff_s=max_backoff_s,
            )

        async def watch(tasks):
            while give_up is not None and not all(t.done() for t in tasks):
                if give_up.is_set():
                    for t in tasks:
                        t.cancel()
                    return
                await asyncio.sleep(0.1)

        tasks = [asyncio.ensure_future(one(i, item))
                 for i, item in enumerate(reqs)]
        watcher = asyncio.ensure_future(watch(tasks))
        try:
            results = await asyncio.gather(*tasks)
        except asyncio.CancelledError:
            results = None  # gave up: the caller says why
        await watcher
        return results
    return asyncio.run(main())


@pytest.mark.proc
@pytest.mark.http
def test_kill9_restart_resume_e2e(tiny, tmp_path):
    """THE acceptance scenario: a real server process with a journal is
    SIGKILLed mid-decode (chaos ``proc_kill``) with 16 live streams; the
    parent restarts it on the same port + journal; every client resumes
    via Last-Event-ID and its final token stream is byte-identical to an
    unkilled control run; /metrics reports the journal counters; a clean
    SIGTERM drain leaves an empty replay set."""
    rng = np.random.default_rng(7)
    reqs = [
        (rng.integers(1, 1000, size=int(rng.integers(6, 20))).tolist(),
         int(rng.integers(9, 13)), i)
        for i in range(16)
    ]

    # control leg: no journal, no chaos, same deterministic model
    proc, host, port = _spawn_server(tmp_path, "control")
    try:
        control = _drive(host, port, reqs, retries=2)
    finally:
        proc.send_signal(signal.SIGTERM)
        proc.wait(timeout=60)
    assert all(r["status"] == 200 and r["finish_reason"] == "length"
               for r in control), control
    control_tokens = [r["token_ids"] for r in control]

    # kill leg: journal on, SIGKILL self after 30 busy ticks (streams
    # admitted and mid-decode), parent respawns on the same port+journal
    jpath = str(tmp_path / "serve.journal")
    proc1, host, port = _spawn_server(
        tmp_path, "kill", journal=jpath, chaos="proc_kill@30")

    import threading

    killed = {"t": None}
    respawned = {}
    respawn_failed = threading.Event()

    def respawn_when_dead():
        proc1.wait()
        killed["t"] = time.perf_counter()
        try:
            # (returns once the new server listens: warm, ready)
            p2, h2, pt2 = _spawn_server(
                tmp_path, "restart", port=port, journal=jpath)
            assert (h2, pt2) == (host, port)
        except BaseException as e:  # noqa: BLE001 — reported below
            respawned["error"] = e
            respawn_failed.set()
            return
        respawned["proc"] = p2

    watcher = threading.Thread(target=respawn_when_dead, daemon=True)
    watcher.start()
    try:
        # the clients wait for the respawned server to LISTEN (it does
        # once it is warm), however long its start takes on this machine:
        # short retries whose count does not matter, ended by the
        # respawn's own failure and by nothing else (a budget of 16-20 s
        # of retries lost one run in three to a slow start)
        results = _drive(host, port, reqs, retries=100_000,
                         max_backoff_s=0.5, give_up=respawn_failed)
    finally:
        watcher.join(timeout=240)
        proc2 = respawned.get("proc")
    assert killed["t"] is not None, "proc_kill never fired"
    assert proc1.returncode == -signal.SIGKILL
    assert proc2 is not None, f"restart never came up: {respawned.get('error')}"

    try:
        # byte-identical streams across the kill
        for res, want in zip(results, control_tokens):
            assert res["status"] == 200, res
            assert res["finish_reason"] == "length"
            assert res["token_ids"] == want, (
                "a resumed stream diverged from the unkilled control")
        resumed = [r for r in results if r.get("resumed")]
        assert resumed, "no client actually resumed across the kill"
        # latency is None for a resume that replayed only a parked
        # finish (cut after the final token) — any measured one is > 0
        lat = [r["resume_latency_s"] for r in resumed
               if r.get("resume_latency_s")]
        assert all(v > 0 for v in lat)
        # the journal counters are on the restarted server's scrape
        _, prom_raw = http_get(host, port, "/metrics")
        prom = prom_raw.decode()
        replayed = float(
            [l for l in prom.splitlines()
             if l.startswith("llm_serve_journal_replayed_total")][0]
            .split()[1])
        resumed_total = float(
            [l for l in prom.splitlines()
             if l.startswith("llm_serve_journal_resumed_total")][0]
            .split()[1])
        assert replayed >= 1
        assert resumed_total >= len(resumed)
    finally:
        proc2.send_signal(signal.SIGTERM)
        proc2.wait(timeout=60)
    # clean drain marks terminals: the replay set on disk is empty
    state, _, epoch = scan_journal(jpath)
    assert state == {}, f"drain left {len(state)} unterminated streams"
    assert epoch == 2  # two journal opens: kill leg + restart
