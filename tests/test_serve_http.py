"""The HTTP front-end (llm_np_cp_tpu/serve/http/).

Protocol tests drive a raw ``asyncio`` client against a live server on
``127.0.0.1:0`` (ephemeral loopback ports only — the ``http`` marker's
hermeticity contract): SSE framing bytes, the 400/404/405/429 error
paths, disconnect-triggered aborts, and the full acceptance scenario —
8+ concurrent streams with a forced disconnect, a deadline expiry, a
Prometheus scrape, and a SIGTERM drain, all parity-checked against
offline ``generate_ragged``.
"""

import asyncio
import json
import os
import re
import signal
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
from llm_np_cp_tpu.serve import ServeEngine
from llm_np_cp_tpu.serve.http.client import (
    astream_completion,
    http_get,
    post_completion,
)
from llm_np_cp_tpu.serve.http.protocol import (
    HTTPError,
    parse_completion_request,
)
from llm_np_cp_tpu.serve.http.server import HttpServer
from llm_np_cp_tpu.serve.http.sse import (
    DONE_SENTINEL,
    parse_sse_line,
    sse_event,
)

pytestmark = pytest.mark.http

PROM_LINE = re.compile(
    r"[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}]*\})? -?[0-9.]+(e[+-]?[0-9]+)?"
)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"), **kw)


def _offline_tokens(cfg, params, prompt, max_tokens):
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                    cache_dtype=jnp.float32)
    res = gen.generate_ragged([np.asarray(prompt, np.int32)], max_tokens)
    return [int(t) for t in np.asarray(res.tokens)[0][:max_tokens]]


async def _raw_post(host, port, payload):
    """POST /v1/completions over raw asyncio streams; returns
    ``(status, headers_dict, reader, writer)`` with the body unread."""
    body = json.dumps(payload).encode()
    reader, writer = await asyncio.open_connection(host, port)
    writer.write(
        b"POST /v1/completions HTTP/1.1\r\n"
        + f"Host: {host}\r\nContent-Length: {len(body)}\r\n".encode()
        + b"Content-Type: application/json\r\nConnection: close\r\n\r\n"
        + body
    )
    await writer.drain()
    status = int((await reader.readline()).split()[1])
    headers = {}
    while True:
        line = await reader.readline()
        if line in (b"\r\n", b"\n", b""):
            break
        k, _, v = line.decode().partition(":")
        headers[k.strip().lower()] = v.strip()
    return status, headers, reader, writer


# ---------------------------------------------------------------------------
# Pure protocol units (no sockets)
# ---------------------------------------------------------------------------

def test_sse_framing_roundtrip():
    frame = sse_event({"choices": [{"text": "ab", "token_id": 7}]})
    assert frame.startswith(b"data: ") and frame.endswith(b"\n\n")
    assert parse_sse_line(frame.strip()) == {
        "choices": [{"text": "ab", "token_id": 7}]
    }
    # token frames carry the delivered-token index as the SSE event id
    # (the Last-Event-ID resume handle, serve/journal.py)
    frame = sse_event({"choices": [{"token_id": 7}]}, event_id=3)
    assert frame.startswith(b"id: 3\ndata: ")
    assert parse_sse_line(DONE_SENTINEL.strip()) is None
    assert parse_sse_line(b": comment") is None
    # non-data SSE fields are skipped, not errors
    assert parse_sse_line(b"id: 3") is None
    assert parse_sse_line(b"event: weird") is None
    assert parse_sse_line(b"retry: 100") is None
    with pytest.raises(ValueError):
        parse_sse_line(b"garbage line")


def test_parse_completion_request_validation():
    ok = parse_completion_request(
        json.dumps({"prompt": [1, 2, 3], "max_tokens": 4,
                    "stream": True, "seed": 9}).encode(),
        model_id="m", tokenizer=None,
    )
    assert list(ok.prompt_ids) == [1, 2, 3] and ok.stream and ok.seed == 9

    def err(body, **kw):
        with pytest.raises(HTTPError) as ei:
            parse_completion_request(
                body if isinstance(body, bytes) else json.dumps(body).encode(),
                model_id="m", tokenizer=None, **kw)
        return ei.value

    assert err(b"{nope").status == 400
    assert err([1, 2]).status == 400  # not an object
    assert err({"prompt": [1], "model": "other"}).status == 404
    assert err({"prompt": []}).status == 400
    assert err({"prompt": "text needs tokenizer"}).status == 400
    assert err({"prompt": [1], "max_tokens": 0}).status == 400
    assert err({"prompt": [1], "stream": "yes"}).status == 400
    assert err({"prompt": [1], "timeout_s": -1}).status == 400
    assert err({"prompt": [1], "n": 2}).status == 400
    # the operator's per-request decode budget is a hard cap
    e = err({"prompt": [1], "max_tokens": 33}, max_tokens_cap=32)
    assert e.status == 400 and "cap" in e.message
    ok2 = parse_completion_request(
        json.dumps({"prompt": [1], "max_tokens": 32}).encode(),
        model_id="m", tokenizer=None, max_tokens_cap=32,
    )
    assert ok2.max_tokens == 32
    # speculative opt-in: default off, bool-validated
    assert ok.speculative is False
    ok3 = parse_completion_request(
        json.dumps({"prompt": [1], "speculative": True}).encode(),
        model_id="m", tokenizer=None,
    )
    assert ok3.speculative is True
    assert err({"prompt": [1], "speculative": "yes"}).status == 400


# ---------------------------------------------------------------------------
# Live-server protocol tests (ephemeral loopback ports)
# ---------------------------------------------------------------------------

def test_http_routes_errors_and_unary(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        host, port = srv.host, srv.port
        loop = asyncio.get_running_loop()

        st, body = await loop.run_in_executor(
            None, http_get, host, port, "/healthz")
        assert st == 200 and json.loads(body)["status"] == "ok"

        st, body = await loop.run_in_executor(
            None, http_get, host, port, "/nope")
        assert st == 404

        st, hdr, reader, writer = await _raw_post(
            host, port, {"prompt": [1, 2], "max_tokens": 2})
        raw = await reader.read()
        writer.close()
        assert st == 200
        obj = json.loads(raw)
        assert obj["choices"][0]["finish_reason"] == "length"
        assert len(obj["choices"][0]["token_ids"]) == 2
        assert obj["usage"]["prompt_tokens"] == 2

        # malformed JSON → 400 with an OpenAI-shaped error body
        reader, writer = await asyncio.open_connection(host, port)
        bad = b"{not json"
        writer.write(
            b"POST /v1/completions HTTP/1.1\r\n"
            + f"Content-Length: {len(bad)}\r\n\r\n".encode() + bad)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        rest = await reader.read()
        writer.close()
        assert status == 400
        assert b"invalid JSON" in rest

        st, obj = await loop.run_in_executor(
            None, post_completion, host, port,
            {"model": "other-model", "prompt": [1], "max_tokens": 2})
        assert st == 404 and obj["error"]["code"] == "model_not_found"

        # GET on the completions route
        st, _ = await loop.run_in_executor(
            None, http_get, host, port, "/v1/completions")
        assert st == 405

        # a request the pool can never hold → engine ValueError → 400
        st, obj = await loop.run_in_executor(
            None, post_completion, host, port,
            {"prompt": [1] * 60, "max_tokens": 60})
        assert st == 400 and "max_seq_len" in obj["error"]["message"]

        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


def test_http_sse_stream_framing_raw(tiny):
    """Raw SSE bytes: event-stream content type, one ``data:`` frame per
    token with token_id — each preceded by an ``id:`` line carrying the
    delivered-token index (the Last-Event-ID resume handle) — a final
    frame carrying finish_reason, then the [DONE] sentinel, then EOF —
    and the tokens match offline."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt, n = [3, 9, 4], 5

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        st, hdr, reader, writer = await _raw_post(
            srv.host, srv.port,
            {"prompt": prompt, "max_tokens": n, "stream": True})
        assert st == 200
        assert hdr["content-type"].startswith("text/event-stream")
        frames, event_ids, saw_done = [], [], False
        while True:
            line = await reader.readline()
            if not line:
                break
            if line.strip() == b"data: [DONE]":
                saw_done = True
                continue
            if line.startswith(b"id: "):
                event_ids.append(int(line.split()[1]))
                continue
            if line.strip():
                assert line.startswith(b"data: "), line
                frames.append(parse_sse_line(line))
        writer.close()
        assert saw_done
        token_frames = [f for f in frames
                        if f["choices"][0].get("token_id") is not None]
        final = frames[-1]["choices"][0]
        assert final["finish_reason"] == "length"
        assert [f["choices"][0]["token_id"] for f in token_frames] \
            == _offline_tokens(cfg, params, prompt, n)
        # event ids = 1-based delivered-token indices, one per token
        assert event_ids == list(range(1, len(token_frames) + 1))
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


def test_http_queue_full_returns_429_with_retry_after(tiny):
    """slots=1 + max_queue=1: with one request decoding and one queued,
    the third submit is rejected on the engine thread → 429 with a
    Retry-After header, counted in metrics."""
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=1, max_queue=1)

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        host, port = srv.host, srv.port
        # A: long-running stream; wait for its first token so it holds
        # the only decode slot
        st, _, reader_a, writer_a = await _raw_post(
            host, port, {"prompt": [5] * 6, "max_tokens": 40,
                         "stream": True})
        assert st == 200
        # first token frame: the id: line, then its data: line
        line = await reader_a.readline()
        if line.startswith(b"id: "):
            line = await reader_a.readline()
        assert line.startswith(b"data: ")
        # B: fills the one queue seat (poll the scheduler until it lands)
        st_b, _, reader_b, writer_b = await _raw_post(
            host, port, {"prompt": [6] * 6, "max_tokens": 4,
                         "stream": True})
        deadline = time.time() + 20
        while engine.scheduler.queue_depth < 1 and time.time() < deadline:
            await asyncio.sleep(0.01)
        assert engine.scheduler.queue_depth == 1
        # C: bounced
        st_c, hdr_c, reader_c, writer_c = await _raw_post(
            host, port, {"prompt": [7] * 6, "max_tokens": 4})
        body_c = await reader_c.read()
        writer_c.close()
        assert st_c == 429
        assert "retry-after" in hdr_c
        assert b"rate_limit_error" in body_c
        # disconnect A so B can finish quickly
        writer_a.close()
        await reader_b.read()  # B runs to completion
        writer_b.close()
        snap = engine.metrics.snapshot()
        assert snap["rejected"] == 1
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


def test_http_midstream_disconnect_aborts_and_frees_pool(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        res = await astream_completion(
            srv.host, srv.port,
            {"prompt": [8] * 9, "max_tokens": 40, "stream": True},
            disconnect_after=2,
        )
        assert res["finish_reason"] == "disconnected"
        deadline = time.time() + 20
        while time.time() < deadline:
            if (engine.metrics.snapshot()["aborted"] == 1
                    and engine.pool.stats()["request_held"] == 0):
                break
            await asyncio.sleep(0.02)
        assert engine.metrics.snapshot()["aborted"] == 1
        assert engine.pool.stats()["request_held"] == 0
        assert not engine.scheduler.has_work
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


def test_http_tick_thread_crash_fails_streams_and_health(tiny):
    """The dead-tick-thread backstop: if engine.step() raises, in-flight
    streams get a terminal event (no client hangs), /healthz flips 503
    'crashed', and new completions are refused with 503."""
    cfg, params = tiny
    engine = _engine(cfg, params)
    real_step = engine.step
    calls = {"n": 0}

    def exploding_step():
        calls["n"] += 1
        if calls["n"] > 2:
            raise RuntimeError("synthetic tick explosion")
        return real_step()

    engine.step = exploding_step

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=5.0)
        await srv.start("127.0.0.1", 0)
        host, port = srv.host, srv.port
        loop = asyncio.get_running_loop()
        res = await asyncio.wait_for(astream_completion(
            host, port, {"prompt": [5] * 6, "max_tokens": 40,
                         "stream": True}), timeout=30)
        assert res["finish_reason"] == "aborted"  # terminal, not a hang
        st, body = await loop.run_in_executor(
            None, http_get, host, port, "/healthz")
        assert st == 503 and json.loads(body)["status"] == "crashed"
        st, obj = await loop.run_in_executor(
            None, post_completion, host, port,
            {"prompt": [1], "max_tokens": 2})
        assert st == 503 and "crashed" in obj["error"]["message"]
        srv.begin_drain()
        await asyncio.wait_for(srv.serve_until_shutdown(), timeout=30)

    asyncio.run(asyncio.wait_for(main(), timeout=120))


def test_deadline_expiry_during_drain_aborts_and_drain_completes(tiny):
    """A per-request deadline that expires WHILE a SIGTERM drain is in
    progress must still be swept: the stream finishes ``aborted``, its
    blocks decref, and the drain completes promptly instead of waiting
    out the full --drain-timeout on a request that will never finish.

    No wall-clock race decides the order of events: the drain begins
    once the CLIENT has read its first token frame, and the deadline —
    far away on the real clock — expires when the test then moves the
    engine's clock past it."""
    cfg, params = tiny
    skew = [0.0]
    engine = _engine(cfg, params,
                     clock=lambda: time.perf_counter() + skew[0])

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=30.0)
        await srv.start("127.0.0.1", 0)
        loop = asyncio.get_running_loop()
        # a budget the deadline will cut short: without the sweep this
        # stream would pin the drain until it ran out its 40 tokens
        st, headers, reader, writer = await _raw_post(
            srv.host, srv.port,
            {"prompt": [7] * 9, "max_tokens": 40, "stream": True,
             "timeout_s": 600.0})
        assert st == 200
        chunks = []

        async def next_chunk():
            while True:
                line = await asyncio.wait_for(reader.readline(), timeout=60)
                assert line, "stream closed before [DONE]"
                if line.startswith(b"data: "):
                    return line[6:].strip()

        chunks.append(json.loads(await next_chunk()))  # the first token
        t_drain = loop.time()
        srv.begin_drain()
        skew[0] = 3600.0  # ... and now the deadline is in the past
        while (data := await next_chunk()) != b"[DONE]":
            chunks.append(json.loads(data))
        writer.close()
        await asyncio.wait_for(srv.serve_until_shutdown(), timeout=30)
        drain_s = loop.time() - t_drain
        finish = [c["choices"][0].get("finish_reason") for c in chunks]
        assert finish[-1] == "aborted" and not any(finish[:-1])
        n_tokens = sum(c["choices"][0].get("token_id") is not None
                       for c in chunks)
        assert 0 < n_tokens < 40
        # the sweep, not the drain timeout, ended it
        assert drain_s < 15.0, f"drain stalled for {drain_s:.1f}s"

    asyncio.run(asyncio.wait_for(main(), timeout=120))
    assert engine.pool.stats()["request_held"] == 0
    snap = engine.metrics.snapshot()
    assert snap["aborted"] == 1
    assert snap["finish_reasons"]["aborted"] == 1
    assert not engine.scheduler.has_work


# ---------------------------------------------------------------------------
# The acceptance scenario
# ---------------------------------------------------------------------------

def test_http_e2e_concurrent_streams_abort_deadline_sigterm_drain(tiny):
    """8 concurrent streaming requests (mixed + repeated prompts, prefix
    cache on) + 1 forced disconnect + 1 deadline expiry; completed
    streams match offline ``generate_ragged`` token-for-token, aborted
    requests free all their blocks, /metrics exposes queue depth / abort
    count / prefix_hit_rate in valid Prometheus text format, and the
    SIGTERM drain completes in-flight streams before the socket closes.
    """
    cfg, params = tiny
    engine = _engine(cfg, params, max_slots=4, num_blocks=64,
                     enable_prefix_cache=True)
    rng = np.random.default_rng(42)
    base = [rng.integers(1, cfg.vocab_size, size=n).tolist()
            for n in (20, 17, 9, 13)]
    # 8 normal requests over 4 distinct prompts (twins hit the prefix
    # cache), generous budgets so streams are still live at SIGTERM
    normal = [(base[i % 4], 10 + 2 * (i % 3)) for i in range(8)]

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=20.0)
        await srv.start("127.0.0.1", 0)
        host, port = srv.host, srv.port
        loop = asyncio.get_running_loop()

        async def delayed(coro, delay):
            await asyncio.sleep(delay)
            return await coro

        tasks = [
            asyncio.create_task(delayed(
                astream_completion(
                    host, port,
                    {"prompt": p, "max_tokens": m, "stream": True}),
                0.4 * (i // 4),  # second wave arrives after the first
                                 # registered its prefix blocks
            ))
            for i, (p, m) in enumerate(normal)
        ]
        disconnect_task = asyncio.create_task(astream_completion(
            host, port, {"prompt": [9] * 11, "max_tokens": 40,
                         "stream": True},
            disconnect_after=2,
        ))
        deadline_task = asyncio.create_task(astream_completion(
            host, port, {"prompt": [4] * 6, "max_tokens": 40,
                         "stream": True, "timeout_s": 0.4},
        ))

        # both aborts land (client disconnect + deadline sweep)...
        t_lim = time.time() + 30
        while time.time() < t_lim:
            if engine.metrics.snapshot()["aborted"] >= 2:
                break
            await asyncio.sleep(0.02)
        assert engine.metrics.snapshot()["aborted"] >= 2
        # ...and their blocks are back before anything else finishes the
        # run: only live (running) requests may hold blocks now
        # scrape while traffic is still flowing
        st, prom_raw = await loop.run_in_executor(
            None, http_get, host, port, "/metrics")
        assert st == 200
        prom = prom_raw.decode()
        for line in prom.splitlines():
            assert line.startswith("# ") or PROM_LINE.fullmatch(line), line
        for needed in ("llm_serve_queue_depth",
                       "llm_serve_requests_aborted_total",
                       "llm_serve_prefix_hit_rate"):
            assert re.search(rf"^{needed}(\{{[^}}]*\}})? ", prom,
                             re.M), needed
        aborted_val = float(re.search(
            r"^llm_serve_requests_aborted_total (\S+)", prom, re.M).group(1))
        assert aborted_val >= 2

        # SIGTERM mid-traffic: in-flight streams must complete
        if srv._signals:
            os.kill(os.getpid(), signal.SIGTERM)
        else:  # signal handler unavailable (non-main-thread loop)
            srv.begin_drain()
        results = await asyncio.gather(*tasks)
        disc = await disconnect_task
        dead = await deadline_task

        for (p, m), res in zip(normal, results):
            assert res["status"] == 200
            assert res["finish_reason"] == "length"
            assert res["token_ids"] == _offline_tokens(cfg, params, p, m), (
                "streamed tokens diverged from offline generate_ragged"
            )
        assert disc["finish_reason"] == "disconnected"
        assert dead["finish_reason"] == "aborted"
        assert 0 < len(dead["token_ids"]) < 40

        # drain completed only after the streams: now the socket closes
        await asyncio.wait_for(srv.serve_until_shutdown(), timeout=30)
        with pytest.raises(OSError):
            await asyncio.open_connection(host, port)

    asyncio.run(asyncio.wait_for(main(), timeout=180))

    # post-mortem: aborted requests freed everything; only prefix-cache
    # entries (cache's own references) remain and all are reclaimable
    stats = engine.pool.stats()
    assert stats["request_held"] == 0
    assert stats["cache_only"] == stats["allocated"]
    snap = engine.metrics.snapshot()
    assert snap["finished"] == 8
    assert snap["aborted"] == 2
    assert snap["finish_reasons"]["aborted"] == 2
    assert snap["finish_reasons"]["length"] == 8
    # the SIGTERM drain left nothing owed: every accepted token and every
    # terminal was handed to its stream before the server went away, and
    # the ticks hid their publish behind the next dispatch meanwhile
    assert not engine._owed
    assert snap["publish_overlapped_ticks"] > snap["publish_immediate_ticks"]


# ---------------------------------------------------------------------------
# The replay ledger against the deferred publish (PR 35): the tick hands
# tick N's tokens to the bridge behind tick N+1's dispatch; whatever cuts
# a stream short, the ledger holds exactly what the bridge pushed at the
# client, and the terminal is the last thing pushed
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("where", ["command-queue", "token-callback"])
def test_abort_leaves_the_replay_ledger_equal_to_what_the_client_was_sent(
        tiny, where):
    cfg, params = tiny
    engine = _engine(cfg, params)
    prompt, peer = [8] * 9, [5] * 6

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        runner = srv.runner
        pushed: dict[int, list] = {}
        ledgers: dict[int, dict] = {}
        push, stash, claim = (runner._push, runner._stash_resumable,
                              runner._claim_insert)

        def spy_push(rid, item):
            if item[0] in ("token", "finish"):
                pushed.setdefault(rid, []).append(item[1])
            push(rid, item)

        def spy_stash(rid, rec, reason, tail):
            ledgers[rid] = dict(tokens=list(rec["tokens"]), reason=reason)
            stash(rid, rec, reason, tail)

        def spy_claim(rid, fin):
            ledgers[rid] = dict(tokens=list(fin["tokens"]),
                                reason=fin["reason"])
            claim(rid, fin)

        runner._push, runner._stash_resumable, runner._claim_insert = (
            spy_push, spy_stash, spy_claim)
        if where == "token-callback":
            bridge = runner._bridge

            def aborting_bridge(gen):
                cb, on_event = bridge(gen)
                seen: dict[int, int] = {}

                def cb_then_abort(req, tok, delta):
                    cb(req, tok, delta)
                    seen[req.req_id] = seen.get(req.req_id, 0) + 1
                    if len(req.prompt) == len(prompt) and seen[req.req_id] == 3:
                        engine.abort(req.req_id)

                return cb_then_abort, on_event

            runner._bridge = aborting_bridge
        await srv.start("127.0.0.1", 0)
        cut, whole = await asyncio.gather(
            astream_completion(
                srv.host, srv.port,
                {"prompt": prompt, "max_tokens": 40, "stream": True},
                disconnect_after=2 if where == "command-queue" else None),
            astream_completion(
                srv.host, srv.port,
                {"prompt": peer, "max_tokens": 12, "stream": True}))
        deadline = time.time() + 20
        while time.time() < deadline and len(ledgers) < 2:
            await asyncio.sleep(0.02)
        srv.begin_drain()
        await srv.serve_until_shutdown()
        return cut, whole, pushed, ledgers

    cut, whole, pushed, ledgers = asyncio.run(
        asyncio.wait_for(main(), timeout=120))
    assert whole["finish_reason"] == "length"
    assert whole["token_ids"] == _offline_tokens(cfg, params, peer, 12)
    (rid_cut,) = [r for r, l in ledgers.items() if l["reason"] == "aborted"]
    (rid_whole,) = [r for r in ledgers if r != rid_cut]
    for rid in (rid_cut, rid_whole):
        # the terminal is the last thing pushed, after every token; the
        # ledger is the tokens pushed, no more and no fewer
        assert pushed[rid][-1] == ledgers[rid]["reason"]
        assert pushed[rid][:-1] == ledgers[rid]["tokens"]
    want = _offline_tokens(cfg, params, prompt, 40)
    sent = ledgers[rid_cut]["tokens"]
    assert sent == want[:len(sent)] and 0 < len(sent) < 40
    if where == "token-callback":
        assert cut["finish_reason"] == "aborted"
        assert cut["token_ids"] == sent and len(sent) == 3
    else:
        assert cut["finish_reason"] == "disconnected"
        assert cut["token_ids"] == sent[:2]
    assert ledgers[rid_whole]["tokens"] == whole["token_ids"]
    assert not engine._owed and not engine.scheduler.has_work
    assert engine.pool.stats()["request_held"] == 0
    snap = engine.metrics.snapshot()
    assert snap["aborted"] == 1 and snap["finished"] == 1
    assert snap["total_generated_tokens"] == 12 + len(sent)
