"""Dependency-free asyncio HTTP front-end over ``ServeEngine``.

Two threads, one contract:

- The **engine thread** (``EngineRunner``) owns the ``ServeEngine``
  exclusively — every engine entry point (submit/abort/step) runs there,
  so the engine itself never needs locks.  Handlers talk to it through a
  thread-safe command queue; admission decisions (queue-full → 429,
  capacity ValueError → 400) are made ON the engine thread where
  scheduler state is consistent, and the verdict comes back as the first
  event on the request's bridge queue.
- The **event loop** (``HttpServer``) speaks HTTP/1.1 over stdlib
  ``asyncio`` streams (no FastAPI/uvicorn — the container has neither,
  and a serving stack's front-end should not be the dependency
  surface).  Per-token events cross back via
  ``loop.call_soon_threadsafe`` onto per-request ``asyncio.Queue``s.

Endpoints:

- ``POST /v1/completions`` — OpenAI-compatible JSON; ``"stream": true``
  streams SSE chunks fed from the engine's per-request callbacks.
  Client disconnect mid-stream aborts the request (blocks decref back to
  the pool); ``timeout_s`` (or the server-wide ``--request-timeout``)
  becomes an engine deadline with the same abort path.
- ``GET /healthz`` — liveness + supervision state (``ok`` /
  ``degraded`` during a supervised engine restart / ``draining`` /
  ``crashed``).
- ``GET /metrics`` — Prometheus text format from ``ServeMetrics`` plus
  live pool/stream/supervision gauges (restarts_total,
  faults_injected_total, recovery latency, degraded).
- ``GET /debug/trace`` — the tracing ring buffer (serve/tracing.py) as
  Chrome/Perfetto trace-event JSON, when the server was started with
  tracing on (``--trace-ring`` / ``--trace-out``); 404 otherwise.  With
  tracing on, every completion's span starts at socket accept (an
  ``http`` bracket around the engine's queued/prefill/decode spans), so
  network+parse time is separable from queue wait.

Shutdown (SIGTERM/SIGINT): stop admission (503 on new completions),
finish in-flight streams up to ``drain_timeout``, abort stragglers, and
only then close the listening socket.

Failure handling: with supervision on (``max_restarts > 0``), a crashed
or hung (``tick_deadline``) engine tick thread triggers a bounded
exponential-backoff restart that rebuilds the engine + pool and replays
every in-flight request teacher-forced (token-identical recovery; see
``EngineRunner``).  With supervision off, a dead tick thread fails all
streams cleanly and wedges the server at 503, as before.
"""

from __future__ import annotations

import asyncio
import contextlib
import itertools
import json
import os
import queue as queue_mod
import signal
import sys
import threading
import time
from collections import deque
from typing import Any

from llm_np_cp_tpu.serve.http.protocol import (
    HTTPError,
    chunk_payload,
    completion_payload,
    error_body,
    parse_completion_request,
    parse_completion_rid,
    parse_last_event_id,
    parse_resume_request,
)
from llm_np_cp_tpu.serve.http.sse import DONE_SENTINEL, sse_event
from llm_np_cp_tpu.serve.metrics import ServeMetrics
from llm_np_cp_tpu.serve.scheduler import (
    QueueFull,
    TenantThrottled,
    first_stamps,
)
from llm_np_cp_tpu.serve.tracing import (
    gen_trace_id,
    make_traceparent,
    parse_traceparent,
)

TERMINAL_EVENTS = ("stop", "length", "aborted")
# what a response's disconnect watch puts into its event queue (``_watch``)
_DISCONNECTED = object()


class _ResumeEcho:
    """The one payload field ``_stream_response`` reads, for resumed
    streams (which carry no CompletionPayload)."""

    def __init__(self, echo_model: str) -> None:
        self.echo_model = echo_model
_REASONS = {
    200: "OK", 400: "Bad Request", 404: "Not Found",
    405: "Method Not Allowed", 408: "Request Timeout",
    409: "Conflict", 413: "Payload Too Large",
    429: "Too Many Requests",
    500: "Internal Server Error", 503: "Service Unavailable",
}
MAX_BODY_BYTES = 8 << 20


def _own_cpu_clock() -> int | None:
    """The calling thread's CPU clock, or None where the platform has
    none.  Taken BY the thread itself: a clock id outlives its thread as
    an error, a thread handle as a dangling pointer."""
    try:
        return time.pthread_getcpuclockid(threading.get_ident())
    except (AttributeError, OSError):
        return None


class EngineRunner:
    """Supervises the engine tick loop on a worker thread and bridges it
    to asyncio handlers.

    Commands (submit/abort) are drained at the top of every loop
    iteration, then one ``engine.step()`` runs if there is work;  when
    idle the loop blocks on the command queue (no spin).  Events flow
    back per request: ``("accepted",)`` / ``("rejected", retry_after)`` /
    ``("error", msg)`` on the admission verdict, ``("token", id, delta)``
    per generated token, ``("finish", reason, final_text_delta)``
    terminally.

    SUPERVISION (``max_restarts > 0``): a crashed tick thread — or one a
    watchdog declares hung because no tick heartbeat landed within
    ``tick_deadline`` — no longer takes the server down.  The runner
    bumps a *generation* counter (superseding the old thread: if it ever
    wakes it sees the stale generation and exits without touching the
    bridges), waits a bounded exponential backoff, rebuilds the engine +
    block pool (``ServeEngine.clone_fresh`` — the compiled steps are
    shared, so a restart never recompiles), and REPLAYS every in-flight
    request with its already-delivered tokens teacher-forced
    (``ServeEngine.recover`` — the evict-requeue discipline, so the
    recovered streams are token-identical to an uninterrupted run and no
    token is ever re-sent).  The command queue survives the restart, so
    submits that arrive during recovery just queue up; ``/healthz``
    reports ``degraded`` until the rebuilt engine completes its first
    loop pass.  Once ``max_restarts`` is exhausted (or with supervision
    off, the default for library users), the terminal-crash backstop
    behaves exactly as before: every stream gets a clean ``aborted``
    event, ``/healthz`` flips 503, new work is refused.
    """

    def __init__(self, engine: Any, *, request_timeout: float | None = None,
                 idle_poll_s: float = 0.02,
                 metrics_max_samples: int = 100_000,
                 tick_deadline: float | None = None,
                 max_restarts: int = 0,
                 restart_backoff_s: float = 0.5,
                 restart_window_s: float = 300.0) -> None:
        self.engine = engine
        self.faults = getattr(engine, "faults", None)
        # which replica this runner is in a fleet (ReplicaRunner sets
        # it); the canonical request log tags every line with it
        self.replica_index = 0
        self.request_timeout = request_timeout
        self.idle_poll_s = idle_poll_s
        self.tick_deadline = tick_deadline
        self.max_restarts = max_restarts
        self.restart_backoff_s = restart_backoff_s
        self.restart_window_s = restart_window_s
        # a server runs for weeks: bound the metrics sample lists
        # (counters stay exact; percentiles become a recent window) and
        # trim the scheduler's terminal-request ledgers below — nothing
        # in the HTTP layer reads them, and each entry pins its prompt
        # array and callback closures
        engine.metrics.max_samples = metrics_max_samples
        self._cmds: queue_mod.Queue = queue_mod.Queue()
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None
        self._watchdog: threading.Thread | None = None
        # the tick thread's and the loop thread's CPU clocks, read at
        # the scrape and nowhere else (``thread_cpu_seconds``); a tick
        # thread that ends leaves its CPU in ``_tick_cpu_retired``, so
        # the counter survives a supervised restart
        self._cpu_lock = threading.Lock()
        self._tick_cpu_clock: int | None = None
        self._tick_cpu_retired = 0.0
        self._loop_cpu_clock: int | None = None
        # rid → (loop, asyncio.Queue); written by both threads, but each
        # rid is registered exactly once (submit) and removed exactly
        # once (engine thread, on the terminal event / reject)
        self._live: dict[int, tuple[asyncio.AbstractEventLoop,
                                    asyncio.Queue]] = {}
        # set when the tick thread dies terminally (supervision off or
        # restart budget exhausted): the server turns /healthz unhealthy
        # and rejects new work instead of silently wedging every stream
        self.crashed: str | None = None
        # exactly one rolling upgrade at a time (the ReplicaRunner
        # fleet guard, fleet-of-one spelling): a second concurrent
        # detach would supersede the first rebuild's generation and its
        # replay snapshot would never run anywhere
        self._upgrade_lock = threading.Lock()
        # -- supervision state (everything below guarded by _sup_lock) -
        # reentrant: _exec holds it across engine.submit/abort (so the
        # generation check is atomic with the engine call), and abort's
        # terminal events re-enter it through the _bridge callbacks
        self._sup_lock = threading.RLock()
        # commands a superseded thread had in hand when it noticed the
        # generation bump: drained BEFORE the queue by the live thread,
        # preserving arrival order (a tail re-put would reorder a submit
        # behind its own abort)
        self._handback: deque = deque()
        self._gen = 0  # engine generation; a restart increments it
        # lifetime restart count (the restarts_total metric); the BUDGET
        # is restart INTENSITY — deaths inside restart_window_s — so a
        # week-long server does not spend its whole allowance on
        # isolated, fully-recovered blips months apart
        self.restarts = 0
        self._recent_deaths: list[float] = []
        self.recovering = False
        self.recovery_latency_s: list[float] = []
        self._death_t: float | None = None
        self._beat = time.monotonic()
        # the current restart's backoff delay: the watchdog extends its
        # staleness budget by this much while recovering, so a wedged
        # REBUILT engine is still caught (just a little later) instead
        # of recovery muting the watchdog outright
        self._backoff_delay = 0.0
        # replay ledger: rid → {prompt, max_tokens, seed, deadline_at,
        # tokens (+ text deltas) delivered so far}, insertion-ordered
        # (original FIFO) — everything a restart needs to teacher-force
        # the stream back, and what a Last-Event-ID resume replays
        self._inflight: dict[int, dict] = {}
        # terminal output of DETACHED streams (finished while no client
        # was attached — journal-recovered requests above all), kept so
        # a late resume still gets its suffix + finish; bounded LRU
        self._resumable: dict[int, dict] = {}
        # CLAIMED terminals (a resume already replayed them once), kept
        # in a smaller LRU so a client whose first resume read tore on
        # the wire can retry instead of 404ing — the PR 9 single-shot
        # claim made bounded multi-read
        self._claimed: dict[int, dict] = {}
        # a planned weight swap's (params, version, share_from) for the
        # next rebuild (rolling upgrade); under _sup_lock, consumed by
        # _rebuild_and_replay on the new tick thread
        self._pending_weights: tuple | None = None
        # fleet hook (serve/replica.ReplicaRunner): called from
        # _terminal_crash with the in-flight replay list; returns the
        # rids a live peer adopted (those streams are NOT abort-flushed)
        self.on_terminal_crash = None
        # durable request journal (serve/journal.py): replay the
        # unterminated requests a dead PROCESS left behind — runs here
        # in the constructor, before any thread exists, so engine access
        # stays single-threaded
        self.journal = getattr(engine, "journal", None)
        self.journal_replayed = 0
        self.journal_resumed = 0
        if self.journal is not None:
            self._replay_journal()
        # past every replayed rid, PARKED ones included: a request
        # recovered terminal (finish_recovered) never touches the
        # engine's _next_id, and re-issuing its rid would let a fresh
        # request shadow the parked stream a client is about to resume
        self._rid = itertools.count(max(
            getattr(engine, "_next_id", 0),
            max(self._resumable, default=-1) + 1,
        ))

    # -- journal replay + stream resume --------------------------------
    def _replay_journal(self) -> None:
        """Teacher-force every unterminated journaled request back into
        the engine (the ``kill -9`` analogue of the supervised restart's
        in-process replay).  Delivered tokens are forced, the REMAINING
        deadline budget is resumed (the journal stores deadlines as wall
        time; expired budgets get swept on the first tick), and the
        ledger is rebuilt so a client can re-attach via Last-Event-ID."""
        if self.journal is None:
            return
        now_wall = time.time()
        clock_now = self.engine.clock()
        for rec in self.journal.replay():
            deadline_at = None
            if rec.get("deadline_wall") is not None:
                # remaining budget on the NEW engine clock; negative =
                # expired while the process was down → swept first tick
                deadline_at = clock_now + (rec["deadline_wall"] - now_wall)
            self._replay_one(0, dict(
                rec, deadline_at=deadline_at,
                deltas=self._replay_deltas(rec["tokens"]),
            ), require_live=False)
            self.journal_replayed += 1

    def _replay_deltas(self, tokens: list) -> list:
        """Per-token text deltas for a journaled token prefix (a fresh
        detokenizer replayed over the same ids yields the same deltas
        the original stream emitted) — what a resuming client's replayed
        suffix carries as text."""
        tok = getattr(self.engine, "tokenizer", None)
        if tok is None or not tokens:
            return [None] * len(tokens)
        from llm_np_cp_tpu.generate import IncrementalDetok

        detok = IncrementalDetok(tok)
        return [detok.push(t) for t in tokens]

    def _replay_one(self, gen: int, rec: dict, *,
                    require_live: bool = True) -> None:
        """Recover ONE ledger/journal record into ``self.engine`` —
        the per-request move shared by the supervised restart's replay,
        the constructor's journal replay, and a fleet peer adopting a
        dead replica's stream.  ``require_live`` is the supervised-
        restart discipline (a stream whose client went away while the
        engine was down is dropped); journal/fleet replays keep
        detached requests generating for a later resume."""
        rid = rec["rid"]
        if require_live and rid not in self._live:
            # the stream went away while we were down — drop its ledger
            # entry too, or it would be re-scanned (and leak) on every
            # future restart
            with self._sup_lock:
                if gen == self._gen:
                    self._inflight.pop(rid, None)
            return
        engine = self.engine
        tokens = rec["tokens"]
        stops = tuple(getattr(engine, "stop_tokens", ()) or ())
        done = len(tokens) >= rec["max_tokens"]
        stopped = bool(tokens) and tokens[-1] in stops
        if done or stopped:
            # fully generated pre-crash; only the finish event was
            # lost — deliver it without re-running anything
            self._finish_replayed(gen, rec, "stop" if stopped else "length")
            return
        cb, on_event = self._bridge(gen)
        try:
            req = engine.recover(
                rec["prompt"], rec["max_tokens"], request_id=rid,
                seed=rec["seed"], generated=tokens, callback=cb,
                on_event=on_event, deadline_at=rec.get("deadline_at"),
                trace_id=rec.get("trace"),
                lineage={
                    "replays": int(rec.get("replays", 0)) + 1,
                    "drains": int(rec.get("drains", 0)),
                },
                speculative=bool(rec.get("spec", False)),
                tenant=rec.get("tenant", "default"),
                weights_version=rec.get("wv"),
                stamps=rec.get("stamps"),
            )
        except Exception as e:  # noqa: BLE001 — per-request fate
            # a request the rebuilt pool cannot re-admit fails alone,
            # not the whole replay
            self._finish_replayed(gen, rec, "aborted")
            print(f"[serve] recovery dropped request {rid}: {e}",
                  file=sys.stderr)
        else:
            # the request now lives on THIS runner's replica (a drain
            # adoption moved it) — the canonical log tags it here
            req.extra["replica"] = self.replica_index
            with self._sup_lock:
                if gen == self._gen:
                    self._inflight[rid] = dict(
                        rec, tokens=list(tokens),
                        replays=int(rec.get("replays", 0)) + 1,
                        deltas=list(rec.get("deltas") or
                                    [None] * len(tokens)),
                    )

    def _finish_replayed(self, gen: int, rec: dict, reason: str) -> None:
        """Terminal bookkeeping for a replayed request that needs no
        re-run: deliver the lost finish to an attached stream, or park
        the full output for a late Last-Event-ID resume."""
        rid = rec["rid"]
        with self._sup_lock:
            if gen != self._gen:
                return
            self._inflight.pop(rid, None)
        tail = self.engine.finish_recovered(
            rec["prompt"], rec["max_tokens"], request_id=rid,
            generated=rec["tokens"], reason=reason,
            trace_id=rec.get("trace"),
            lineage={
                "replays": int(rec.get("replays", 0)) + 1,
                "drains": int(rec.get("drains", 0)),
            },
            tenant=rec.get("tenant", "default"),
            weights_version=rec.get("wv"),
        )
        if rid in self._live:
            self._push(rid, ("finish", reason, tail))
            self._live.pop(rid, None)
            self._claim_insert(rid, self._fin_record(rec, reason, tail))
        else:
            self._stash_resumable(rid, rec, reason, tail)

    @staticmethod
    def _fin_record(rec: dict, reason: str,
                    tail: str | None) -> dict:
        """The ONE parked/claimed terminal record shape (the resume
        wire format) — built here for ``_stash_resumable`` and both
        ``_claim_insert`` call sites, so a new field cannot be added to
        one copy and silently missed in another."""
        return {
            "tokens": list(rec["tokens"]),
            "deltas": list(rec.get("deltas") or
                           [None] * len(rec["tokens"])),
            "reason": reason,
            "tail": tail,
            # a late resume's response still carries the request's
            # ORIGINAL trace context
            "trace": rec.get("trace"),
        }

    def _stash_resumable(self, rid: int, rec: dict, reason: str,
                         tail: str | None) -> None:
        """Park a DETACHED stream's terminal output (bounded LRU): a
        client resuming after the finish still gets its journaled
        suffix + finish exactly once."""
        self._resumable[rid] = self._fin_record(rec, reason, tail)
        while len(self._resumable) > 512:
            self._resumable.pop(next(iter(self._resumable)))

    def resume(self, rid: int, last_idx: int,
               loop: asyncio.AbstractEventLoop, aq: asyncio.Queue) -> None:
        """Re-attach a dropped SSE stream: replay delivered tokens from
        index ``last_idx`` (the client's Last-Event-ID), then continue
        live.  The attach runs ON the engine thread, atomically between
        ticks, so the replayed suffix and the live continuation can
        neither race nor duplicate."""
        self._cmds.put(("attach", rid, last_idx, loop, aq))
        if self.crashed:
            # same crash race answer as submit(): nobody will process
            # the command (duplicates are harmless — the handler stops
            # at the first terminal event)
            aq.put_nowait(("gone",
                           f"engine tick thread crashed: {self.crashed}"))

    # -- event-loop side ----------------------------------------------
    def start(self) -> None:
        self._loop_cpu_clock = _own_cpu_clock()  # the caller IS the loop
        self._spawn_thread(self._gen)
        if self.tick_deadline is not None:
            self._watchdog = threading.Thread(
                target=self._watch, name="serve-engine-watchdog",
                daemon=True,
            )
            self._watchdog.start()

    def stop(self, timeout: float = 10.0) -> None:
        self._stop.set()
        self._cmds.put(("wake",))
        thread = self._thread
        if thread is not None:
            thread.join(timeout=timeout)
        if self._watchdog is not None:
            self._watchdog.join(timeout=1.0)
        if self.journal is not None:
            # drain's aborts already journaled their terminals; flush
            # them so a CLEAN shutdown leaves an empty replay set
            self.journal.close()

    @property
    def inflight(self) -> int:
        """Live bridged requests (accepted, not yet terminal)."""
        return len(self._live)

    @property
    def state(self) -> str:
        """``ok`` | ``degraded`` (restart in progress) | ``crashed``."""
        if self.crashed:
            return "crashed"
        return "degraded" if self.recovering else "ok"

    def serving_engines(self) -> list:
        """Engines whose ActionPolicy verdicts may govern admission —
        a crashed engine's tick thread can never RELEASE a shed flag,
        so its frozen verdict must not shed the server forever."""
        return [] if self.crashed else [self.engine]

    def next_rid(self) -> int:
        return next(self._rid)

    def submit(self, rid: int, payload: Any,
               loop: asyncio.AbstractEventLoop, aq: asyncio.Queue) -> None:
        self._live[rid] = (loop, aq)
        # the command enters the tick thread's inbox: from here to the
        # engine's ``submit_time`` the request waits for the running
        # tick to end (stage ``inbox_wait``; the payload carries the
        # stamp, with the handler's ``received_time``, into ``submit``)
        payload.enqueue_time = t_enq = self.engine.clock()
        tr = getattr(self.engine, "tracer", None)
        if tr is not None:
            tr.request_instant(rid, "enqueued", ts_us=tr.us_at(t_enq))
        self._cmds.put(("submit", rid, payload))
        # crash race: if the tick thread died terminally between the
        # handler's pre-check and this registration, its backstop flush
        # may have already run — nobody will ever answer this command, so
        # answer it here (a duplicate event from the flush is harmless:
        # the handler stops at the first terminal one)
        if self.crashed and self._live.pop(rid, None) is not None:
            aq.put_nowait(("error",
                           f"engine tick thread crashed: {self.crashed}"))

    def abort(self, rid: int) -> None:
        self._cmds.put(("abort", rid))

    def abort_all(self) -> None:
        self._cmds.put(("abort_all",))

    # -- engine-thread side -------------------------------------------
    def _push(self, rid: int, item: tuple) -> None:
        ent = self._live.get(rid)
        if ent is None:
            return
        loop, aq = ent
        tr = getattr(self.engine, "tracer", None)
        if tr is not None and item[0] == "token":
            # the emit end of the emit-to-write lag (the write end is
            # stamped by the stream handler); the item keeps its shape
            tr.stamp_emit(rid)
        try:
            loop.call_soon_threadsafe(aq.put_nowait, item)
        except RuntimeError:
            # loop already closed (shutdown race) — nobody is reading
            self._live.pop(rid, None)

    def _bridge(self, gen: int) -> tuple:
        """Per-request engine callbacks for generation ``gen``.  The gen
        guard (under the supervision lock, so it is atomic with the
        restart's replay snapshot) makes a superseded engine mute: a hung
        thread that wakes mid-emit after a restart cannot append to the
        replay ledger or push duplicate tokens at a stream the rebuilt
        engine now owns."""

        def cb(req: Any, tok: int, delta: str | None) -> None:
            with self._sup_lock:
                if gen != self._gen:
                    return
                rec = self._inflight.get(req.req_id)
                if rec is not None:
                    rec["tokens"].append(int(tok))
                    deltas = rec.get("deltas")
                    if deltas is not None:
                        deltas.append(delta)
            self._push(req.req_id, ("token", int(tok), delta))

        def on_event(req: Any, event: str) -> None:
            if event not in TERMINAL_EVENTS:
                return
            with self._sup_lock:
                if gen != self._gen:
                    return
                rec = self._inflight.pop(req.req_id, None)
            if req.req_id not in self._live:
                # DETACHED terminal (a journal-recovered stream whose
                # client has not re-attached yet): park the output so a
                # late Last-Event-ID resume still completes
                if rec is not None:
                    self._stash_resumable(
                        req.req_id, rec, event,
                        req.extra.pop("final_text_delta", None))
                return
            tail = req.extra.pop("final_text_delta", None)
            self._push(req.req_id, ("finish", event, tail))
            self._live.pop(req.req_id, None)
            if rec is not None:
                # the DELIVERED terminal stays re-readable for a while
                # too: a client whose final read tore on the wire can
                # retry the whole stream from the claimed LRU
                self._claim_insert(
                    req.req_id, self._fin_record(rec, event, tail))

        return cb, on_event

    def _claim_insert(self, rid: int, fin: dict) -> None:
        """Park a terminal's full output in the CLAIMED LRU (bounded,
        most recent last): any recently finished stream can be
        re-replayed by a retrying client — the PR 9 single-shot claim,
        made bounded multi-read."""
        self._claimed.pop(rid, None)
        self._claimed[rid] = fin
        while len(self._claimed) > 64:
            self._claimed.pop(next(iter(self._claimed)))

    def _next_handback(self, gen: int) -> tuple | None:
        """Pop the next handed-back command — only for the LIVE
        generation (a stale thread popping and re-appending would rotate
        the hand-back order)."""
        with self._sup_lock:
            if gen == self._gen and self._handback:
                return self._handback.popleft()
        return None

    def _exec(self, cmd: tuple, gen: int) -> bool:
        """Execute one command for generation ``gen``.  The gen check and
        the engine call are ATOMIC under the supervision lock — a thread
        superseded between draining a command and executing it must not
        submit into an engine no thread will ever tick.  Returns False
        (after handing the command to the live generation, order
        preserved) when superseded."""
        with self._sup_lock:
            if gen != self._gen:
                self._handback.append(cmd)
                return False
            self._exec_inner(cmd, gen)
        return True

    def _exec_inner(self, cmd: tuple, gen: int) -> None:
        kind = cmd[0]
        if kind == "submit":
            _, rid, payload = cmd
            deadline = payload.timeout_s
            if self.request_timeout is not None:
                deadline = min(deadline or self.request_timeout,
                               self.request_timeout)
            cb, on_event = self._bridge(gen)
            try:
                req = self.engine.submit(
                    payload.prompt_ids, payload.max_tokens,
                    request_id=rid, seed=payload.seed, callback=cb,
                    on_event=on_event, deadline_s=deadline,
                    trace_id=getattr(payload, "trace_id", None),
                    speculative=getattr(payload, "speculative", False),
                    tenant=getattr(payload, "tenant", "default"),
                    received_time=getattr(payload, "received_time", None),
                    enqueue_time=getattr(payload, "enqueue_time", None),
                )
            except TenantThrottled as e:
                # same 429 + Retry-After contract as a full queue, but
                # the message names the tenant's cap, not the queue
                self._push(rid, ("rejected", 1, str(e)))
                self._live.pop(rid, None)
            except QueueFull:
                self._push(rid, ("rejected", 1))
                self._live.pop(rid, None)
            except ValueError as e:
                self._push(rid, ("error", str(e)))
                self._live.pop(rid, None)
            else:
                # route verdict + replica tag for the canonical request
                # log (the router filled payload.route_spilled)
                req.extra["replica"] = self.replica_index
                if getattr(payload, "route_spilled", False):
                    req.extra["spilled"] = True
                self._inflight[rid] = {
                    "rid": rid,
                    "prompt": payload.prompt_ids,
                    "max_tokens": payload.max_tokens,
                    "seed": payload.seed,
                    # the ABSOLUTE deadline on the engine clock (shared
                    # by clone_fresh rebuilds): recovery resumes the
                    # remaining budget instead of granting a fresh
                    # window per crash
                    "deadline_at": req.deadline,
                    # trace continuity + survival lineage: a restart
                    # replay or a drain-to-peer continues the SAME
                    # trace, with its replays/drains counters
                    "trace": req.extra.get("trace"),
                    "replays": 0,
                    "drains": 0,
                    # speculative opt-in: a restart replay resumes the
                    # same decoding mode (tokens identical either way)
                    "spec": bool(getattr(payload, "speculative", False)),
                    # the weight version that admitted this request — a
                    # restart replay or a drain-to-peer keeps reporting
                    # it, whatever weights the adopting engine runs
                    "wv": int(req.extra.get("weights_version", 0)),
                    # the tenant rides the recovery record too: a
                    # restart replay or drain-to-peer re-admits under
                    # the tenant that submitted the stream
                    "tenant": getattr(payload, "tenant", "default"),
                    "tokens": [],
                    # parallel text deltas, so a Last-Event-ID resume
                    # replays the exact text the stream would have
                    # carried
                    "deltas": [],
                }
                self._push(rid, ("accepted",))
        elif kind == "attach":
            self._exec_attach(cmd)
        elif kind == "recover":
            # a peer replica's drained stream (fleet adoption) — the
            # same teacher-forced move as a restart replay
            self._replay_one(gen, cmd[1], require_live=False)
        elif kind == "abort":
            self.engine.abort(cmd[1])
        elif kind == "abort_all":
            for rid in list(self._live):
                self.engine.abort(rid)

    def _exec_attach(self, cmd: tuple) -> None:
        """Attach a resuming client to a live or parked stream (on the
        engine thread — atomic with respect to token emission, so the
        replayed suffix and live continuation cannot interleave out of
        order).  Event ids are delivered-token indices: the client's
        Last-Event-ID is the count it HAS, so the replay starts there."""
        _, rid, last_idx, loop, aq = cmd
        rec = self._inflight.get(rid)
        fin = None
        if rec is None:
            fin = self._resumable.get(rid)
            if fin is None:
                # bounded multi-read: a terminal a resume already
                # claimed stays re-readable from the small claimed LRU,
                # so a client retrying after a flaky first read is not
                # 404'd (the PR 9 single-shot claim, loosened)
                fin = self._claimed.get(rid)
        src = rec if rec is not None else fin
        verdict = None
        if src is not None and rid in self._live:
            # already claimed: a duplicate resume (or a guessed id) must
            # not rebind the live bridge entry — that would hijack the
            # attached client's stream and strand it without a terminal
            verdict = ("gone",
                       f"request {rid} already has an attached stream")
        elif src is None:
            verdict = ("gone", f"unknown or expired request id {rid}")
        elif last_idx > len(src["tokens"]):
            if rec is not None:
                # the async-fsync window: the client can legitimately be
                # AHEAD of the journal (a watermark lost to the kill or
                # a dropped write batch) while the recovered request is
                # still regenerating its deterministic stream — tell the
                # client to retry shortly, not that the stream is gone
                verdict = ("busy",
                           f"request {rid} has regenerated "
                           f"{len(src['tokens'])} of the {last_idx} "
                           "tokens the client holds; retry shortly")
            else:
                verdict = ("gone",
                           f"Last-Event-ID {last_idx} is past the "
                           f"{len(src['tokens'])} tokens delivered for "
                           f"request {rid}")
        if verdict is not None:
            try:
                loop.call_soon_threadsafe(aq.put_nowait, verdict)
            except RuntimeError:
                pass
            return
        self._live[rid] = (loop, aq)
        self.journal_resumed += 1
        # the accepted verdict carries the stream's ORIGINAL trace id,
        # so the resumed response can emit the same traceparent the
        # first response did — a reconnect continues the trace
        self._push(rid, ("accepted", src.get("trace")))
        toks = src["tokens"][last_idx:]
        deltas = src.get("deltas") or []
        deltas = deltas[last_idx:]
        for i, tok in enumerate(toks):
            self._push(rid, ("token", int(tok),
                             deltas[i] if i < len(deltas) else None))
        if fin is not None:
            # the stream finished while detached: suffix + finish.  The
            # claim moves it to the bounded claimed-LRU (most recent
            # claim last) instead of discarding — a retry re-reads it
            # until the LRU evicts
            self._resumable.pop(rid, None)
            self._claim_insert(rid, fin)
            self._push(rid, ("finish", fin["reason"], fin["tail"]))
            self._live.pop(rid, None)

    # -- supervision ---------------------------------------------------
    def _spawn_thread(self, gen: int, *, delay: float = 0.0,
                      replay: list[dict] | None = None) -> None:
        self._thread = threading.Thread(
            target=self._run, args=(gen, delay, replay),
            name=f"serve-engine-tick-{gen}", daemon=True,
        )
        self._thread.start()

    def thread_cpu_seconds(self) -> dict[str, float]:
        """CPU seconds of the tick thread (every generation's) and of
        the event-loop thread as ``/metrics`` counters; a name is absent
        where the platform has no per-thread CPU clock."""
        out: dict[str, float] = {}
        with self._cpu_lock:  # against a tick thread retiring its clock
            tick, loop = self._tick_cpu_clock, self._loop_cpu_clock
            try:
                if tick is not None:
                    out["tick_thread_cpu_seconds_total"] = (
                        self._tick_cpu_retired + time.clock_gettime(tick))
                if loop is not None:
                    out["loop_thread_cpu_seconds_total"] = (
                        time.clock_gettime(loop))
            except OSError:
                pass  # a thread that is gone: the next scrape has it
        return out

    def _run(self, gen: int, delay: float = 0.0,
             replay: list[dict] | None = None) -> None:
        clock = _own_cpu_clock()
        with self._cpu_lock:
            self._tick_cpu_clock = clock
        try:
            if delay:
                time.sleep(delay)  # exponential backoff before rebuild
            if self._stop.is_set():
                return
            if gen == self._gen:
                self._beat = time.monotonic()  # backoff slept the clock off
            if replay is not None:
                self._rebuild_and_replay(gen, replay)
            self._loop(gen)
        except BaseException as e:  # noqa: BLE001 — supervisor boundary
            import traceback

            traceback.print_exc()
            self._on_engine_death(f"{type(e).__name__}: {e}", gen)
        finally:
            with self._cpu_lock:
                if clock is not None:
                    self._tick_cpu_retired += time.thread_time()
                if self._tick_cpu_clock == clock:
                    self._tick_cpu_clock = None

    def _loop(self, gen: int) -> None:
        engine = self.engine
        faults = self.faults
        while not self._stop.is_set() and gen == self._gen:
            cmd = self._next_handback(gen)
            if cmd is None:
                try:
                    block = not engine.scheduler.has_work
                    cmd = self._cmds.get(
                        block=block,
                        timeout=self.idle_poll_s if block else None,
                    )
                except queue_mod.Empty:
                    cmd = None
            while cmd is not None:
                if cmd[0] != "wake" and not self._exec(cmd, gen):
                    return  # superseded; _exec handed the command back
                cmd = self._next_handback(gen)
                if cmd is None:
                    try:
                        cmd = self._cmds.get_nowait()
                    except queue_mod.Empty:
                        cmd = None
            if self._stop.is_set() or gen != self._gen:
                break
            if engine.scheduler.has_work:
                if faults is not None:
                    hang = faults.trip("tick_hang")
                    if hang is not None:
                        time.sleep(hang)
                        if gen != self._gen:
                            return  # the watchdog already superseded us
                    if faults.trip("tick_crash") is not None:
                        from llm_np_cp_tpu.serve.faults import FaultInjected

                        raise FaultInjected("tick_crash")
                    if faults.trip("proc_kill") is not None:
                        # the kill -9 site: no drain, no flush, no
                        # atexit — exactly what the request journal's
                        # restart/resume path must survive
                        import os

                        print("[chaos] proc_kill: SIGKILL self",
                              file=sys.stderr, flush=True)
                        os.kill(os.getpid(), signal.SIGKILL)
                engine.step()
                # terminal requests already delivered their events
                # through the bridge — dropping them here keeps a
                # long-running server's memory flat
                engine.scheduler.finished.clear()
                engine.scheduler.aborted.clear()
            elif engine.actions is not None:
                # an idle server must still RELEASE auto-actions:
                # shed_load 503s the fresh work that would otherwise
                # produce the ticks on_tick releases through, so a
                # drained-idle server would shed forever once the
                # in-flight streams finished
                engine._actions_tick([])
            # tick heartbeat: the watchdog declares the engine hung when
            # this goes stale past tick_deadline (idle passes beat every
            # idle_poll_s, so only a stuck tick can starve it).  Gen
            # guard: a superseded hung thread that wakes here must not
            # freshen the heartbeat the NEW generation is judged by
            if gen == self._gen:
                self._beat = time.monotonic()
            if self.recovering:
                with self._sup_lock:
                    if gen == self._gen and self.recovering:
                        self.recovering = False
                        if self._death_t is not None:
                            self.recovery_latency_s.append(
                                time.monotonic() - self._death_t)
                            self._death_t = None

    def _rebuild_and_replay(self, gen: int, replay: list[dict]) -> None:
        """Fresh engine + pool (shared compiled steps), then resubmit
        every in-flight request with its delivered tokens teacher-forced.
        Runs ON the new tick thread, so engine access stays
        single-threaded."""
        old = self.engine
        tr = getattr(old, "tracer", None)
        t_restart = tr.now_us() if tr is not None else 0.0
        # Drop the dead engine's device slabs BEFORE the new pool is
        # allocated: restart peak memory must stay ~one pool, or an
        # HBM-sized production pool would OOM every rebuild and turn a
        # recoverable blip into a terminal 503.  A hung-but-alive thread
        # that later dispatches into the yanked pool fails in ITS
        # generation and is ignored.
        old.pool.pages = None
        with self._sup_lock:
            pend = self._pending_weights
        if pend is not None:
            # a planned weight swap rides the restart machinery: same
            # drain/replay/zombie-mute discipline, new params.  The
            # jitted steps take params as ARGUMENTS, so a same-shaped
            # swap reuses every warm compile; share_from (a peer that
            # already rolled) makes genuinely-new avals compile once
            # per fleet
            new_params, new_version, share_from = pend
            engine = old.clone_fresh(params=new_params,
                                     weights_version=new_version)
            if share_from is not None:
                engine.share_compiled_steps(share_from)
        else:
            engine = old.clone_fresh()
        # mute the zombie's counters: the clone shares the REAL metrics
        # object; a watchdog-superseded-but-alive thread finishing its
        # slow tick would otherwise keep writing on_token/on_finish into
        # it (engine internals have no gen guard — only the bridge does)
        # and double-count with the replay below.  The tracer is muted
        # the same way: a zombie tick must not interleave stale spans
        # into the timeline the rebuilt engine now owns — and so is the
        # journal: a zombie's stale watermarks must not corrupt the
        # delivered-count marks the rebuilt engine now advances.
        old.metrics = ServeMetrics(clock=old.clock)
        old.tracer = None
        old.journal = None
        # ...and the request log: a zombie's stale terminal lines must
        # not interleave with the rebuilt engine's canonical log — and
        # the sentinel: clone_fresh SHARES it (engine-thread-only
        # state), so a zombie tick observing concurrently with the
        # rebuilt engine would corrupt the EWMA baselines
        old.request_log = None
        old.sentinel = None
        # ...and the action policy: a zombie tick feeding stale signals
        # would corrupt the streak/burn state the rebuilt engine's
        # ticks now advance
        old.actions = None
        # ...and the host tier: the clone shares the REAL (process-
        # wide) tier; a zombie tick's late reclaim must not spill its
        # yanked pool's garbage into the shared host store, nor its
        # wall times pollute the breakeven measurements
        old.host_tier = None
        # ...and the tenant ledger: the clone shares the REAL ledger
        # (bills survive the restart); a zombie's stale terminals must
        # not double-charge a tenant the rebuilt engine re-runs
        old.tenants = None
        with self._sup_lock:
            if gen != self._gen:
                # superseded DURING the rebuild (it wedged long enough
                # for the watchdog to spawn a newer generation, which now
                # owns self.engine) — walk away without touching anything
                return
            self.engine = engine
            if pend is not None and self._pending_weights is pend:
                self._pending_weights = None

        for rec in replay:
            if gen != self._gen:
                return  # superseded mid-replay — the newer thread redoes it
            # the rebuilt engine shares the old one's clock: a replayed
            # request keeps the first stamps of its way to its first
            # token, as a preemption requeue does
            prev = old._requests.get(rec["rid"])
            if prev is not None:
                rec["stamps"] = first_stamps(prev)
            # an upgrade's leftover streams keep generating detached (a
            # journal-recovered client may attach later); a crash
            # restart's streams must have a live client
            self._replay_one(
                gen, rec,
                require_live=not rec.pop("detached_ok", False),
            )
            if gen == self._gen:
                self._beat = time.monotonic()
        if tr is not None:
            tr.complete("restart", t_restart, cat="supervisor", args={
                "gen": gen, "replayed": len(replay),
            })

    # -- planned lifecycle (rolling weight swap) -----------------------
    def detach_inflight(self) -> list[dict]:
        """Supersede the live tick generation and hand back the
        in-flight replay snapshot — the first half of a PLANNED swap
        (upgrade or removal), sharing the crash path's discipline: the
        old thread goes zombie (gen bump + handback), the snapshot is
        what peers adopt (drain) or the rebuilt engine replays."""
        with self._sup_lock:
            self._gen += 1
            self.recovering = True
            self._beat = time.monotonic()
            # the rebuild includes a params device_put — give the
            # watchdog the same grace a backoff restart gets
            self._backoff_delay = max(self._backoff_delay, 10.0)
            replay = [dict(rec, tokens=list(rec["tokens"]),
                           deltas=list(rec.get("deltas") or ()))
                      for rec in self._inflight.values()]
            self._inflight.clear()
        self._cmds.put(("wake",))  # unblock an idle superseded thread
        return replay

    def rebuild_upgraded(self, params: Any, version: int,
                         replay: list[dict], *,
                         share_from: Any = None) -> None:
        """Second half of the swap: spawn the new generation's tick
        thread, which rebuilds via ``clone_fresh(params=...)`` and
        replays ``replay`` teacher-forced (token-identical).
        ``share_from`` is a peer engine that already rolled — its
        jitted callables are adopted so new-weight avals compile once
        per FLEET.  Caller ran ``detach_inflight`` first."""
        with self._sup_lock:
            if self._stop.is_set():
                raise RuntimeError("runner is stopped")
            self._pending_weights = (params, int(version), share_from)
            new_gen = self._gen
        self._spawn_thread(new_gen, replay=replay)

    def await_recovered(self, timeout_s: float = 300.0) -> None:
        """Block until the rebuilt engine completes its first loop pass
        (``recovering`` clears) — the roll moves to the next replica
        only once this one is serving again."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if self.crashed:
                raise RuntimeError(
                    f"replica crashed during upgrade: {self.crashed}"
                )
            if not self.recovering:
                return
            time.sleep(0.01)
        raise TimeoutError(
            f"upgrade rebuild did not complete within {timeout_s:g}s"
        )

    def rolling_upgrade(self, params_fn: Any, *,
                        version: int | None = None,
                        timeout_s: float = 300.0) -> dict:
        """The fleet-of-one roll (``POST /admin/upgrade`` on a
        single-replica server): no peer to drain to, so in-flight
        streams are replayed IN PLACE on the rebuilt engine —
        teacher-forced, so delivered tokens never change; tokens still
        to come are sampled by the new weights (with one replica there
        is no same-version peer to finish them on, and the request's
        version tag records its admission version either way)."""
        from llm_np_cp_tpu.serve.lifecycle import load_upgrade_params

        if not self._upgrade_lock.acquire(blocking=False):
            raise RuntimeError("a rolling upgrade is already in progress")
        try:
            if self.crashed:
                raise RuntimeError(
                    f"cannot upgrade a crashed server: {self.crashed}"
                )
            params = load_upgrade_params(
                params_fn, replica=self.replica_index,
                faults=self.faults, metrics=self.engine.metrics,
                rolled=[], version=version,
            )
            if version is None:
                version = getattr(self.engine, "weights_version", 0) + 1
            replay = [dict(rec, detached_ok=True)
                      for rec in self.detach_inflight()]
            self.rebuild_upgraded(params, version, replay)
            try:
                self.await_recovered(timeout_s)
            except TimeoutError as e:
                # surface the same clean abort shape as a checkpoint
                # failure — the admin handler turns it into a 500
                # instead of a dropped connection; the supervisor
                # keeps rebuilding
                from llm_np_cp_tpu.serve.lifecycle import UpgradeAborted

                raise UpgradeAborted(
                    f"replica {self.replica_index} rebuild timed out: "
                    f"{e}", rolled=[], version=version,
                ) from e
            self.engine.metrics.on_lifecycle_action("upgrade_replica")
            return {"rolled": [self.replica_index], "version": version}
        finally:
            self._upgrade_lock.release()

    def _on_engine_death(self, reason: str, gen: int) -> None:
        """Crash/hang handler (from the dying thread or the watchdog):
        either schedule a supervised restart or go terminally dark."""
        now = time.monotonic()
        with self._sup_lock:
            if gen != self._gen:
                return  # a superseded thread died late — already handled
            # budget = restart intensity, not lifetime total: only
            # deaths within the window count (a crash LOOP exhausts it;
            # isolated recovered blips don't), and the backoff exponent
            # follows the same count so it too is per-incident
            self._recent_deaths = [
                t for t in self._recent_deaths
                if now - t < self.restart_window_s
            ]
            if self._stop.is_set() \
                    or len(self._recent_deaths) >= self.max_restarts:
                self._terminal_crash(reason)
                return
            self._recent_deaths.append(now)
            self.restarts += 1
            self._gen += 1
            self.recovering = True
            if self._death_t is None:
                self._death_t = now
            delay = min(
                self.restart_backoff_s
                * (2 ** (len(self._recent_deaths) - 1)),
                10.0,
            )
            self._backoff_delay = delay
            self._beat = time.monotonic()  # restart clock starts now
            replay = [dict(rec, tokens=list(rec["tokens"]))
                      for rec in self._inflight.values()]
            new_gen = self._gen
        tr = getattr(self.engine, "tracer", None)
        if tr is not None:
            tr.instant("engine-death", cat="supervisor", args={
                "reason": reason, "gen": gen, "restart": new_gen,
            })
        print(f"[serve] engine death ({reason}); supervised restart "
              f"{len(replay)} in-flight to replay, "
              f"{len(self._recent_deaths)}/{self.max_restarts} deaths in "
              f"window, backoff {delay:.2f}s", file=sys.stderr)
        self._spawn_thread(new_gen, delay=delay, replay=replay)

    def _terminal_crash(self, reason: str) -> None:
        """The pre-supervision backstop (caller holds ``_sup_lock``): a
        dead tick thread must not wedge the server — every in-flight
        stream gets a terminal event (clients see a clean end instead of
        hanging until their own timeouts), /healthz flips unhealthy, and
        new submits are refused."""
        self.crashed = reason
        tr = getattr(self.engine, "tracer", None)
        if tr is not None:
            tr.instant("engine-terminal-crash", cat="supervisor",
                       args={"reason": reason})
        # supersede a HUNG (still running) thread too: without the gen
        # bump it would wake and keep ticking — a zombie generation
        # burning the device for already-flushed streams
        self._gen += 1
        self.recovering = False
        # fleet drain (serve/replica.ReplicaRunner): a live peer can
        # ADOPT this runner's unterminated streams — those clients see a
        # pause and then the peer's token-identical continuation instead
        # of an abort
        adopted: set[int] = set()
        hook = self.on_terminal_crash
        if hook is not None and self._inflight:
            replay = [dict(rec, tokens=list(rec["tokens"]),
                           deltas=list(rec.get("deltas") or ()))
                      for rec in self._inflight.values()]
            adopted = hook(replay)
        for rid in list(self._live):
            if rid in adopted:
                continue  # a peer now owns this stream's bridge entry
            self._push(rid, ("finish", "aborted", None))
            self._live.pop(rid, None)
        # the flush IS these requests' terminal: journal it (the writer
        # thread outlives the tick thread), or the next process start
        # would replay streams whose clients already saw 'aborted' —
        # generating for nobody and inflating journal_replayed_total
        journal = self.journal
        if journal is not None:
            for rid in self._inflight:
                if rid not in adopted:
                    journal.terminal(rid, "aborted")
        self._inflight.clear()

    def _watch(self) -> None:
        """Watchdog: declare the engine hung when the tick heartbeat goes
        stale past ``tick_deadline`` (a tick stuck in a device call or an
        injected hang), and hand it to the death handler.  While a
        restart is in progress the staleness budget stretches by that
        restart's backoff delay — recovery never MUTES the watchdog, so
        a rebuilt engine that wedges in its replay or first tick is
        itself caught and handed back to the supervisor."""
        assert self.tick_deadline is not None
        interval = max(self.tick_deadline / 4.0, 0.01)
        while not self._stop.is_set() and not self.crashed:
            time.sleep(interval)
            with self._sup_lock:
                gen = self._gen
                beat = self._beat
                grace = self._backoff_delay if self.recovering else 0.0
            stale = time.monotonic() - beat
            if stale > self.tick_deadline + grace:
                self._on_engine_death(
                    f"engine tick hung ({stale:.2f}s > tick-deadline "
                    f"{self.tick_deadline:g}s + {grace:g}s restart grace)",
                    gen,
                )


class HttpServer:
    """The asyncio front: routing, SSE streaming, drain shutdown."""

    def __init__(
        self,
        engine: Any,
        *,
        model_id: str,
        tokenizer: Any = None,
        request_timeout: float | None = None,
        drain_timeout: float = 30.0,
        default_max_tokens: int = 16,
        max_tokens_cap: int | None = None,
        tick_deadline: float | None = None,
        max_restarts: int = 0,
        restart_backoff_s: float = 0.5,
        restart_window_s: float = 300.0,
        runner: Any = None,
        upgrade_loader: Any = None,
    ) -> None:
        self.engine = engine
        self.model_id = model_id
        # rolling weight swaps (POST /admin/upgrade): the loader maps
        # the request body to fresh params (the serve CLI wires a
        # checkpoint reload); None = the endpoint 404s with a hint.
        # One admin mutation at a time — a roll and a scale racing
        # would drain the same peers out from under each other
        self.upgrade_loader = upgrade_loader
        self._admin_lock = threading.Lock()
        self.tokenizer = tokenizer if tokenizer is not None \
            else getattr(engine, "tokenizer", None)
        self.drain_timeout = drain_timeout
        self.default_max_tokens = default_max_tokens
        self.max_tokens_cap = max_tokens_cap
        # ``runner`` injects a prebuilt fleet (serve/replica.ReplicaRunner
        # — N supervised engine replicas behind prefix-affinity routing);
        # default is the single-engine runner, exactly as before
        self.runner = runner if runner is not None else EngineRunner(
            engine, request_timeout=request_timeout,
            tick_deadline=tick_deadline, max_restarts=max_restarts,
            restart_backoff_s=restart_backoff_s,
            restart_window_s=restart_window_s,
        )
        self.draining = False
        self.host: str | None = None
        self.port: int | None = None
        self._server: asyncio.AbstractServer | None = None
        self._loop: asyncio.AbstractEventLoop | None = None
        self._done: asyncio.Event | None = None
        self._drain_task: asyncio.Task | None = None
        self._conn_tasks: set[asyncio.Task] = set()
        self._signals: list[int] = []

    # ------------------------------------------------------------------
    async def start(self, host: str = "127.0.0.1", port: int = 0) -> None:
        self._loop = asyncio.get_running_loop()
        self._done = asyncio.Event()
        self.runner.start()
        self._server = await asyncio.start_server(self._on_conn, host, port)
        sock = self._server.sockets[0]
        self.host, self.port = sock.getsockname()[:2]
        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._loop.add_signal_handler(sig, self.begin_drain)
                self._signals.append(sig)
            except (NotImplementedError, RuntimeError, ValueError):
                # not the main thread (CLI smoke tests run the server in
                # a worker thread) or an embedded loop — drain stays
                # reachable programmatically
                break

    def begin_drain(self) -> None:
        """Idempotent shutdown trigger — the SIGTERM handler and the
        test hook both land here."""
        if self._drain_task is None and self._loop is not None:
            self._drain_task = self._loop.create_task(self._drain())

    async def _drain(self) -> None:
        self.draining = True
        loop = asyncio.get_running_loop()
        deadline = loop.time() + self.drain_timeout
        while self.runner.inflight and loop.time() < deadline:
            await asyncio.sleep(0.02)
        if self.runner.inflight:
            self.runner.abort_all()
            grace = loop.time() + 5.0
            while self.runner.inflight and loop.time() < grace:
                await asyncio.sleep(0.02)
        # every stream got its terminal event; give the handlers a
        # bounded window to flush their last bytes BEFORE the socket
        # closes (the acceptance criterion for drain)
        flush_deadline = loop.time() + 5.0
        while self._conn_tasks and loop.time() < flush_deadline:
            await asyncio.sleep(0.02)
        assert self._server is not None
        self._server.close()
        await self._server.wait_closed()
        for sig in self._signals:
            with contextlib.suppress(Exception):
                self._loop.remove_signal_handler(sig)  # type: ignore[union-attr]
        self.runner.stop()
        assert self._done is not None
        self._done.set()

    async def serve_until_shutdown(self) -> None:
        assert self._done is not None, "call start() first"
        await self._done.wait()

    # ------------------------------------------------------------------
    @property
    def tracer(self) -> Any:
        """The live engine's trace recorder (rebinds across supervised
        restarts — the recorder object itself is shared), or None."""
        return getattr(self.runner.engine, "tracer", None)

    async def _on_conn(self, reader: asyncio.StreamReader,
                       writer: asyncio.StreamWriter) -> None:
        task = asyncio.current_task()
        if task is not None:
            self._conn_tasks.add(task)
        # request spans start AT SOCKET ACCEPT: time spent reading and
        # parsing the request is part of what the client experiences,
        # and must be separable from engine queue wait in the trace.
        # One read of the engine's clock serves the span and the
        # request's ``received_time`` stamp (``TraceRecorder.us_at`` puts
        # it on the trace's axis, also for a recorder that appears only
        # AFTER accept: the supervised-restart mute window)
        t_recv = self.runner.engine.clock()
        try:
            await self._handle(reader, writer, t_recv)
        except (ConnectionResetError, BrokenPipeError, asyncio.TimeoutError):
            pass
        finally:
            if task is not None:
                self._conn_tasks.discard(task)
            with contextlib.suppress(Exception):
                writer.close()
                await writer.wait_closed()

    async def _handle(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter,
                      t_recv: float | None = None) -> None:
        try:
            method, path, headers, body = await asyncio.wait_for(
                self._read_request(reader), timeout=30.0,
            )
        except HTTPError as e:
            await self._respond_error(writer, e)
            return
        except (asyncio.IncompleteReadError, ValueError,
                asyncio.TimeoutError):
            return  # torn/oversized request line — nothing to answer
        if method == "GET" and path == "/healthz":
            crashed = self.runner.crashed
            # degraded (supervised restart in progress) stays 200: the
            # server still accepts and queues work, so a load balancer
            # must not eject it mid-recovery — that would turn a blip
            # back into an outage
            status = 503 if (self.draining or crashed) else 200
            state = ("crashed" if crashed
                     else "draining" if self.draining
                     else self.runner.state)
            payload = {
                "status": state, "model": self.model_id,
                "restarts": self.runner.restarts,
                "weights_version": getattr(
                    self.runner.engine, "weights_version", 0),
            }
            mesh = getattr(self.runner.engine, "mesh_desc", None)
            if mesh:
                payload["mesh"] = mesh
            rings = getattr(getattr(self.runner.engine, "pool", None),
                            "window", None)
            if rings is not None:
                # a pool with a window class states both (the growing
                # class's blocks in use, the window class's and its ring)
                stats = self.runner.engine.pool.stats()
                payload["pool"] = {
                    "global_blocks_in_use": stats["allocated"],
                    "global_blocks_capacity": stats["capacity"],
                    "window_blocks_in_use": stats["window_blocks_in_use"],
                    "window_blocks_capacity": stats["window_blocks_capacity"],
                    "window_blocks_per_slot": stats["window_blocks_per_slot"],
                }
            replica_states = getattr(self.runner, "replica_states", None)
            if replica_states is not None:
                payload["replicas"] = replica_states()
            if crashed:
                payload["error"] = crashed
            await self._respond(writer, status, json.dumps(payload).encode())
        elif method == "GET" and path == "/metrics":
            await self._respond(
                writer, 200, self._render_metrics().encode(),
                content_type="text/plain; version=0.0.4; charset=utf-8",
            )
        elif method == "GET" and path == "/debug/slo":
            await self._respond_slo(writer)
        elif method == "GET" and path == "/debug/tenants":
            await self._respond_tenants(writer)
        elif method == "GET" and path == "/debug/trace":
            tracer = self.tracer
            if tracer is None:
                await self._respond_error(writer, HTTPError(
                    404, "tracing is off; start the server with "
                    "--trace-ring N (and/or --trace-out PATH)"))
            else:
                # point-in-time ring-buffer snapshot, loadable straight
                # into ui.perfetto.dev.  Serialized OFF the event loop:
                # a full ring is hundreds of thousands of dicts, and
                # json.dumps-ing them inline would stall every live SSE
                # stream — the instrument must not perturb what it
                # measures
                body = await asyncio.get_running_loop().run_in_executor(
                    None, lambda: json.dumps(tracer.to_dict()).encode())
                await self._respond(writer, 200, body)
        elif path == "/admin/upgrade":
            if method != "POST":
                await self._respond_error(writer, HTTPError(
                    405, "use POST for /admin/upgrade"))
            else:
                await self._admin_upgrade(writer, body)
        elif path == "/admin/scale":
            if method != "POST":
                await self._respond_error(writer, HTTPError(
                    405, "use POST for /admin/scale"))
            else:
                await self._admin_scale(writer, body)
        elif path == "/v1/completions":
            if method != "POST":
                await self._respond_error(writer, HTTPError(
                    405, "use POST for /v1/completions"))
            else:
                await self._completions(reader, writer, body, headers,
                                        t_recv)
        elif path.startswith("/v1/completions/"):
            # stream resume by id: GET /v1/completions/cmpl-N with a
            # Last-Event-ID header replays the journaled suffix over
            # SSE and continues live (serve/journal.py)
            if method != "GET":
                await self._respond_error(writer, HTTPError(
                    405, "use GET to resume a completion stream"))
                return
            try:
                rid = parse_completion_rid(path.rsplit("/", 1)[1])
                last_idx = parse_last_event_id(
                    headers.get("last-event-id"))
            except HTTPError as e:
                await self._respond_error(writer, e)
                return
            await self._resume(reader, writer, rid, last_idx,
                               self.model_id, t_recv)
        else:
            await self._respond_error(writer, HTTPError(
                404, f"no route for {method} {path}"))

    async def _read_request(
        self, reader: asyncio.StreamReader,
    ) -> tuple[str, str, dict[str, str], bytes]:
        line = await reader.readline()
        if not line:
            raise asyncio.IncompleteReadError(b"", None)
        parts = line.decode("latin-1").split()
        if len(parts) != 3:
            raise HTTPError(400, "malformed request line")
        method, path = parts[0].upper(), parts[1]
        headers: dict[str, str] = {}
        while True:
            hline = await reader.readline()
            if hline in (b"\r\n", b"\n", b""):
                break
            key, _, value = hline.decode("latin-1").partition(":")
            headers[key.strip().lower()] = value.strip()
        try:
            n = int(headers.get("content-length", "0"))
        except ValueError as e:
            raise HTTPError(400, "bad Content-Length") from e
        if n > MAX_BODY_BYTES:
            raise HTTPError(413, f"body exceeds {MAX_BODY_BYTES} bytes")
        body = await reader.readexactly(n) if n else b""
        return method, path, headers, body

    def _render_metrics(self) -> str:
        # durable-journal observables (zero when journaling is off):
        # what the restart/resume acceptance checks and an operator's
        # alerting read off the scrape
        journal_gauges = {
            "journal_replayed_total": float(
                getattr(self.runner, "journal_replayed", 0)),
            "journal_resumed_total": float(
                getattr(self.runner, "journal_resumed", 0)),
        }
        # OTLP span export (serve/otel.py): shipped/dropped counters so
        # a silent collector outage is visible on the scrape
        otel = getattr(self.tracer, "otel", None)
        if otel is not None:
            ostats = otel.stats()
            journal_gauges.update({
                "otlp_spans_exported_total": float(ostats["spans"]),
                "otlp_spans_dropped_total": float(ostats["dropped"]),
                "otlp_export_errors_total": float(
                    ostats["export_errors"]),
            })
        journal = getattr(self.runner, "journal", None)
        if journal is not None:
            jstats = journal.stats()
            journal_gauges.update({
                "journal_records_total": float(jstats["records"]),
                "journal_fsync_p99_s": jstats["fsync_p99_s"],
                "journal_write_errors_total": float(
                    jstats["write_errors"] + jstats["fsync_errors"]),
                "journal_epoch": float(jstats["epoch"]),
            })
        render = getattr(self.runner, "render_metrics", None)
        if render is not None:
            # replica fleet: per-replica series with replica labels +
            # router counters (serve/replica.ReplicaRunner)
            return render(extra_gauges={
                "draining": 1.0 if self.draining else 0.0,
                **journal_gauges,
            })
        cpu = getattr(self.runner, "thread_cpu_seconds", None)
        # the runner's engine, NOT self.engine: a supervised restart
        # rebinds it, and a scrape must see the live pool/scheduler
        engine = self.runner.engine
        stats = engine.pool.stats()
        faults = self.runner.faults
        recov = self.runner.recovery_latency_s
        wv = getattr(engine, "weights_version", 0)
        text = engine.metrics.prometheus(
            # the version label appears once an upgrade rolled (wv > 0)
            # — pre-upgrade series keep their exact labelsets
            const_labels={"version": str(wv)} if wv else None,
            extra_gauges={
            "weights_version": float(wv),
            "pool_blocks_free": stats["free"],
            "pool_blocks_request_held": stats["request_held"],
            "pool_blocks_cache_only": stats["cache_only"],
            "pool_kv_bytes_shard": stats["kv_bytes_shard"],
            "pool_kv_shards": stats["kv_shards"],
            **engine.pool_form_gauges(),
            **engine.weight_layout_gauges(),
            "inflight_streams": self.runner.inflight,
            "queue_depth_live": engine.scheduler.queue_depth,
            "draining": 1.0 if self.draining else 0.0,
            # supervision observables: the chaos e2e (and an operator's
            # alerting) read recovery off this scrape
            "restarts_total": self.runner.restarts,
            "faults_injected_total": (
                faults.injected_total if faults is not None else 0.0
            ),
            "degraded": 1.0 if self.runner.state == "degraded" else 0.0,
            "recovery_latency_s_last": recov[-1] if recov else 0.0,
            "decode_impl_degraded": (
                1.0 if engine.decode_degraded else 0.0
            ),
            **journal_gauges,
        }, extra_counters=cpu() if cpu is not None else None)
        tenants = getattr(engine, "tenants", None)
        if tenants is not None:
            # tenant-labeled series (serve/tenants.py) ride the same
            # scrape; the ledger bounds its own label cardinality
            text += tenants.prometheus(
                const_labels={"version": str(wv)} if wv else None,
            )
        return text

    async def _respond_slo(self, writer: asyncio.StreamWriter) -> None:
        """``GET /debug/slo``: the fleet's SLO accounting as one JSON —
        attainment, goodput, burn rates, summed across replicas with a
        per-replica breakdown.  404 + hint when no policy is attached
        (the ``/debug/trace`` discipline)."""
        from llm_np_cp_tpu.serve.slo import aggregate_slo

        replicas = getattr(self.runner, "replicas", None)
        runners = replicas if replicas is not None else [self.runner]
        trackers = [
            getattr(r.engine.metrics, "slo", None) for r in runners
        ]
        if not any(t is not None for t in trackers):
            await self._respond_error(writer, HTTPError(
                404, "SLO accounting is off; start the server with "
                "--slo-ttft/--slo-tpot"))
            return
        body = aggregate_slo(trackers)
        if replicas is not None:
            body["replicas"] = [
                t.snapshot() if t is not None else None for t in trackers
            ]
        await self._respond(writer, 200, json.dumps(body).encode())

    async def _respond_tenants(self, writer: asyncio.StreamWriter) -> None:
        """``GET /debug/tenants``: the fleet's per-tenant accounting as
        one JSON — requests, tokens, device-cost attribution, SLO
        detail, throttles — summed across replicas with a per-replica
        breakdown.  404 + hint when no ledger is attached (the
        ``/debug/slo`` discipline)."""
        from llm_np_cp_tpu.serve.tenants import aggregate_tenants

        replicas = getattr(self.runner, "replicas", None)
        runners = replicas if replicas is not None else [self.runner]
        ledgers = [
            getattr(r.engine, "tenants", None) for r in runners
        ]
        if not any(t is not None for t in ledgers):
            await self._respond_error(writer, HTTPError(
                404, "tenant accounting is off; start the server with "
                "--tenants"))
            return
        body = aggregate_tenants(ledgers)
        if replicas is not None:
            body["replicas"] = [
                t.snapshot() if t is not None else None for t in ledgers
            ]
        await self._respond(writer, 200, json.dumps(body).encode())

    # -- fleet lifecycle admin (serve/lifecycle.py) --------------------
    async def _admin_upgrade(self, writer: asyncio.StreamWriter,
                             body: bytes) -> None:
        """``POST /admin/upgrade``: roll the fleet onto fresh weights,
        one replica at a time, zero dropped streams.  Body (optional
        JSON): ``{"model": <checkpoint for the loader>, "version": N}``.
        Responds after the roll with ``{"rolled": [...], "version"}``;
        409 when a roll is already in progress, 500 with the rolled
        prefix when the roll aborted (checkpoint failure — the fleet
        keeps serving, mixed-version)."""
        from llm_np_cp_tpu.serve.lifecycle import UpgradeAborted

        if self.upgrade_loader is None:
            await self._respond_error(writer, HTTPError(
                404, "no upgrade loader configured; the serve CLI "
                "wires one (POST /admin/upgrade)"))
            return
        try:
            data = json.loads(body) if body else {}
            if not isinstance(data, dict):
                raise ValueError("body must be a JSON object")
        except ValueError as e:
            await self._respond_error(writer, HTTPError(
                400, f"bad JSON body: {e}"))
            return
        version = data.get("version")
        if version is not None and (
            not isinstance(version, int) or isinstance(version, bool)
            or version < 1
        ):
            await self._respond_error(writer, HTTPError(
                400, f"version must be a positive integer, "
                f"got {version!r}"))
            return
        if not self._admin_lock.acquire(blocking=False):
            await self._respond_error(writer, HTTPError(
                409, "an admin operation is already in progress"))
            return
        loader = self.upgrade_loader
        loop = asyncio.get_running_loop()
        try:
            result = await loop.run_in_executor(
                None,
                lambda: self.runner.rolling_upgrade(
                    lambda: loader(data), version=version,
                ),
            )
        except UpgradeAborted as e:
            await self._respond(writer, 500, json.dumps({
                "error": str(e), "rolled": e.rolled,
            }).encode())
            return
        except RuntimeError as e:
            # only a concurrent roll is a Conflict; a crashed/stopped
            # runner or an empty fleet is the server's unavailability,
            # and a 409 would invite the client to retry-until-done
            # against a fleet that can never finish a roll
            status = 409 if "in progress" in str(e) else 503
            await self._respond_error(writer, HTTPError(status, str(e)))
            return
        finally:
            self._admin_lock.release()
        await self._respond(writer, 200, json.dumps(result).encode())

    async def _admin_scale(self, writer: asyncio.StreamWriter,
                           body: bytes) -> None:
        """``POST /admin/scale`` ``{"replicas": N}``: elastic DP for
        the HTTP fleet — grow with warmed share-nothing clones, shrink
        with drain-to-peer removals."""
        if getattr(self.runner, "add_replica", None) is None:
            await self._respond_error(writer, HTTPError(
                400, "single-engine server cannot scale; start with "
                "--replicas N"))
            return
        try:
            data = json.loads(body) if body else {}
            n = data["replicas"]
            if not isinstance(n, int) or isinstance(n, bool) \
                    or not (1 <= n <= 64):
                raise ValueError(f"replicas must be in [1, 64], got {n!r}")
        except (KeyError, TypeError, ValueError) as e:
            await self._respond_error(writer, HTTPError(
                400, f'bad body (want {{"replicas": N}}): {e}'))
            return
        if not self._admin_lock.acquire(blocking=False):
            await self._respond_error(writer, HTTPError(
                409, "an admin operation is already in progress"))
            return

        def apply() -> tuple[list[int], list[int]]:
            added: list[int] = []
            removed: list[int] = []
            while self.runner.active_replicas() < n:
                added.append(self.runner.add_replica())
            while self.runner.active_replicas() > n:
                removed.append(self.runner.remove_replica())
            return added, removed

        loop = asyncio.get_running_loop()
        try:
            added, removed = await loop.run_in_executor(None, apply)
        except RuntimeError as e:
            await self._respond_error(writer, HTTPError(400, str(e)))
            return
        finally:
            self._admin_lock.release()
        await self._respond(writer, 200, json.dumps({
            "replicas": self.runner.active_replicas(),
            "added": added, "removed": removed,
            "states": self.runner.replica_states(),
        }).encode())

    def _shed_retry_after(self) -> float | None:
        """503-first load shedding: the max Retry-After across SERVING
        replicas whose ActionPolicy is shedding, or None when admission
        is open.  Only serving replicas vote (``serving_engines`` —
        removed/crashed replicas' tick threads can never release a shed
        flag, and a frozen verdict must not shed the fleet forever).
        Racy boolean reads by design (like the routing load reads) —
        one request admitted a tick early or late is noise."""
        worst = None
        for engine in self.runner.serving_engines():
            acts = getattr(engine, "actions", None)
            if acts is not None and acts.shedding:
                ra = acts.retry_after()
                worst = ra if worst is None else max(worst, ra)
        return worst

    # ------------------------------------------------------------------
    async def _completions(self, reader: asyncio.StreamReader,
                           writer: asyncio.StreamWriter,
                           body: bytes, headers: dict[str, str],
                           t_recv: float | None = None) -> None:
        if self.draining or self.runner.crashed:
            msg = ("engine tick thread crashed: " + self.runner.crashed
                   if self.runner.crashed
                   else "server is draining for shutdown")
            await self._respond_error(writer, HTTPError(
                503, msg, etype="server_error",
                headers=(("Retry-After", "1"),),
            ))
            return
        faults = self.runner.faults
        if faults is not None:
            retry_after = faults.trip("http_429")
            if retry_after is not None:
                # injected transient reject: exercises client
                # retry/backoff without having to saturate the queue
                await self._respond_error(writer, HTTPError(
                    429, "chaos: injected transient reject",
                    etype="rate_limit_error",
                    headers=(("Retry-After", f"{max(retry_after, 0):g}"),),
                ))
                return
        try:
            resume = parse_resume_request(
                body, headers, model_id=self.model_id)
            if resume is not None:
                # re-POST with the original request id: the resume
                # protocol's POST spelling (GET /v1/completions/<id> is
                # the other)
                rid, last_idx, echo_model = resume
                await self._resume(reader, writer, rid, last_idx,
                                   echo_model, t_recv)
                return
            # 503-first load shedding (serve/lifecycle.ActionPolicy):
            # when the SLO error budget burns past threshold, FRESH
            # admissions shed at the door with a burn-scaled
            # Retry-After — resumes above attach to work already done
            # and always pass
            shed = self._shed_retry_after()
            if shed is not None:
                await self._respond_error(writer, HTTPError(
                    503, "load shedding: SLO error budget is burning "
                    "past threshold; retry later",
                    etype="server_error",
                    headers=(("Retry-After", f"{shed:g}"),),
                ))
                return
            payload = parse_completion_request(
                body, model_id=self.model_id, tokenizer=self.tokenizer,
                default_max_tokens=self.default_max_tokens,
                max_tokens_cap=self.max_tokens_cap,
                header_tenant=headers.get("x-tenant-id"),
            )
        except HTTPError as e:
            await self._respond_error(writer, e)
            return

        # W3C trace context: continue the caller's trace or start one —
        # every request has ONE trace id from here through routing,
        # journal replay, and drain-to-peer (a malformed header means a
        # fresh trace, never a 400)
        ctx = parse_traceparent(headers.get("traceparent"))
        payload.trace_id = ctx[0] if ctx is not None else gen_trace_id()
        # socket accept, on the engine's clock: the first stamp of the
        # request's way to its first token (scheduler.TTFT_STAMPS)
        payload.received_time = t_recv

        loop = asyncio.get_running_loop()
        aq: asyncio.Queue = asyncio.Queue()
        rid = self.runner.next_rid()
        tracer = self.tracer
        if tracer is not None:
            # the http bracket span: accept → response done, enclosing
            # the engine's queued/prefill/decode spans on the same
            # track; it begins AT the request's ``received_time``
            tracer.async_begin(rid, "http",
                               ts_us=(tracer.us_at(t_recv)
                                      if t_recv is not None else None),
                               args={"stream": bool(payload.stream),
                                     "trace": payload.trace_id})
        try:
            await self._completions_inner(
                reader, writer, payload, rid, loop, aq)
        finally:
            if tracer is not None:
                tracer.stream_end(rid)
                tracer.async_end(rid, "http")

    async def _completions_inner(self, reader, writer, payload, rid,
                                 loop, aq) -> None:
        self.runner.submit(rid, payload, loop, aq)
        verdict = await aq.get()
        if verdict[0] == "rejected":
            msg = (verdict[2] + "; retry later" if len(verdict) > 2
                   else "request queue is full; retry later")
            await self._respond_error(writer, HTTPError(
                429, msg,
                etype="rate_limit_error",
                headers=(("Retry-After", str(verdict[1])),),
            ))
            return
        if verdict[0] == "error":
            await self._respond_error(writer, HTTPError(400, verdict[1]))
            return
        if verdict[0] == "finish":
            # terminal before acceptance: only the tick-thread crash
            # backstop produces this — the request never ran
            await self._respond_error(writer, HTTPError(
                503, "engine tick thread crashed before the request "
                "was accepted", etype="server_error",
            ))
            return
        created = int(time.time())
        # emit the trace context back: the client (or a proxy) can join
        # its own telemetry to this server's spans/logs by trace id
        tp = getattr(payload, "trace_id", None)
        resp_headers = (
            (("traceparent", make_traceparent(tp)),) if tp else ()
        )
        # Disconnect watch: drain (and DISCARD, bounded-memory) anything
        # else the client sends — we are Connection: close, so stray
        # bytes are pipelining we don't support — and complete only at
        # EOF, which for an HTTP/1.1 client means it hung up → abort.
        # (A client that half-closes its write side after the body is
        # indistinguishable from a disconnect here and is also aborted;
        # real HTTP clients don't half-close.)
        monitor = self._watch(reader, aq)
        try:
            if payload.stream:
                await self._stream_response(
                    writer, aq, rid, payload, created,
                    extra_headers=resp_headers)
            else:
                await self._unary_response(
                    writer, aq, rid, payload, created,
                    extra_headers=resp_headers)
        finally:
            monitor.cancel()
            with contextlib.suppress(asyncio.CancelledError):
                await monitor

    async def _resume(self, reader: asyncio.StreamReader,
                      writer: asyncio.StreamWriter, rid: int,
                      last_idx: int, echo_model: str,
                      t_recv: float | None = None) -> None:
        """Re-attach a dropped SSE stream (serve/journal.py resume
        protocol): replay the delivered-token suffix from the client's
        Last-Event-ID, then continue live.  404 when the id is unknown
        or already claimed — the client falls back to a fresh POST."""
        if self.draining or self.runner.crashed:
            await self._respond_error(writer, HTTPError(
                503, "server is draining for shutdown"
                if self.draining else
                "engine tick thread crashed: " + str(self.runner.crashed),
                etype="server_error", headers=(("Retry-After", "1"),),
            ))
            return
        loop = asyncio.get_running_loop()
        aq: asyncio.Queue = asyncio.Queue()
        tracer = self.tracer
        if tracer is not None:
            tracer.async_begin(rid, "http",
                               ts_us=(tracer.us_at(t_recv)
                                      if t_recv is not None else None),
                               args={"resume": True,
                                     "last_event_id": last_idx})
        try:
            self.runner.resume(rid, last_idx, loop, aq)
            verdict = await aq.get()
            if verdict[0] == "gone":
                await self._respond_error(writer, HTTPError(
                    404, verdict[1], code="unknown_completion"))
                return
            if verdict[0] == "busy":
                # the client is ahead of the journaled prefix while the
                # recovered stream regenerates — retryable, not terminal
                await self._respond_error(writer, HTTPError(
                    503, verdict[1], etype="server_error",
                    headers=(("Retry-After", "1"),),
                ))
                return
            if verdict[0] == "finish":
                await self._respond_error(writer, HTTPError(
                    503, "engine tick thread crashed before the resume "
                    "was attached", etype="server_error",
                ))
                return
            created = int(time.time())
            payload = _ResumeEcho(echo_model)
            # the attach verdict carries the original trace id (when
            # the ledger/parked entry kept one): the resumed stream
            # emits the SAME traceparent as the first response
            tp = verdict[1] if len(verdict) > 1 else None
            resume_headers = (
                (("traceparent", make_traceparent(tp)),) if tp else ()
            )
            monitor = self._watch(reader, aq)
            try:
                await self._stream_response(
                    writer, aq, rid, payload, created,
                    start_idx=last_idx, extra_headers=resume_headers)
            finally:
                monitor.cancel()
                with contextlib.suppress(asyncio.CancelledError):
                    await monitor
        finally:
            if tracer is not None:
                tracer.stream_end(rid)
                tracer.async_end(rid, "http")

    @staticmethod
    async def _watch_disconnect(reader: asyncio.StreamReader) -> None:
        while True:
            data = await reader.read(4096)
            if not data:
                return

    def _watch(self, reader: asyncio.StreamReader,
               aq: asyncio.Queue) -> asyncio.Future:
        """Start the disconnect watch of a response that reads ``aq``:
        when the client hangs up (or the watch is cancelled, after the
        response) ``_DISCONNECTED`` goes into the queue behind whatever
        the engine has put there, so the handler waits on ONE thing, the
        queue — a task and an ``asyncio.wait`` a token were a tenth of a
        frame's cost on the loop thread, which is what the smallest
        closed cell runs out of (PERF.md section 5)."""
        monitor = asyncio.ensure_future(self._watch_disconnect(reader))
        monitor.add_done_callback(lambda _f: aq.put_nowait(_DISCONNECTED))
        return monitor

    @staticmethod
    async def _next_event(aq: asyncio.Queue) -> tuple | None:
        """Next engine event, or None if the client disconnected first."""
        ev = await aq.get()
        return None if ev is _DISCONNECTED else ev

    async def _stream_response(self, writer, aq, rid,
                               payload, created, start_idx: int = 0,
                               extra_headers: tuple = ()) -> None:
        # delivered-token index, carried as the SSE event id on every
        # token frame: a client that reconnects with Last-Event-ID = the
        # last id it saw gets exactly the tokens it is missing
        idx = start_idx
        head = (
            "HTTP/1.1 200 OK\r\n"
            "Content-Type: text/event-stream\r\n"
            "Cache-Control: no-cache\r\n"
            "Connection: close\r\n"
        )
        for key, value in extra_headers:
            head += f"{key}: {value}\r\n"
        try:
            writer.write(head.encode() + b"\r\n")
            await writer.drain()
        except (ConnectionResetError, BrokenPipeError, OSError):
            # gone before the first byte: the request must not keep its
            # decode slot generating for a dead socket
            self.runner.abort(rid)
            return
        while True:
            ev = await self._next_event(aq)
            if ev is None:  # client went away mid-stream
                self.runner.abort(rid)
                return
            if ev[0] == "token":
                _, tok, delta = ev
                idx += 1
                frame = sse_event(chunk_payload(
                    rid, payload.echo_model, created,
                    text=delta or "", token_id=tok, finish_reason=None,
                ), event_id=idx)
            else:  # ("finish", reason, tail)
                _, reason, tail = ev
                frame = sse_event(chunk_payload(
                    rid, payload.echo_model, created,
                    text=tail or "", token_id=None, finish_reason=reason,
                )) + DONE_SENTINEL
            faults = self.runner.faults
            if faults is not None and faults.trip("http_reset") is not None:
                # injected socket reset mid-stream: the client sees a
                # hard RST, the request aborts like any disconnect
                writer.transport.abort()
                self.runner.abort(rid)
                return
            try:
                writer.write(frame)
                tracer = self.tracer
                if tracer is not None and ev[0] == "token":
                    tracer.frame_written(rid)
                await writer.drain()
            except (ConnectionResetError, BrokenPipeError, OSError):
                self.runner.abort(rid)
                return
            if ev[0] == "finish":
                return

    async def _unary_response(self, writer, aq, rid,
                              payload, created,
                              extra_headers: tuple = ()) -> None:
        token_ids: list[int] = []
        text_parts: list[str] = []
        while True:
            ev = await self._next_event(aq)
            if ev is None:
                self.runner.abort(rid)
                return
            if ev[0] == "token":
                token_ids.append(ev[1])
                if ev[2]:
                    text_parts.append(ev[2])
            else:
                reason, tail = ev[1], ev[2]
                if tail:
                    text_parts.append(tail)
                break
        body = json.dumps(completion_payload(
            rid, payload.echo_model, created,
            text="".join(text_parts), token_ids=token_ids,
            finish_reason=reason,
            prompt_tokens=int(payload.prompt_ids.size),
        )).encode()
        await self._respond(writer, 200, body,
                            extra_headers=extra_headers)

    # ------------------------------------------------------------------
    async def _respond(self, writer: asyncio.StreamWriter, status: int,
                       body: bytes,
                       content_type: str = "application/json",
                       extra_headers: tuple = ()) -> None:
        head = (
            f"HTTP/1.1 {status} {_REASONS.get(status, '')}\r\n"
            f"Content-Type: {content_type}\r\n"
            f"Content-Length: {len(body)}\r\n"
            "Connection: close\r\n"
        )
        for key, value in extra_headers:
            head += f"{key}: {value}\r\n"
        writer.write(head.encode() + b"\r\n" + body)
        with contextlib.suppress(ConnectionResetError, BrokenPipeError,
                                 OSError):
            await writer.drain()

    async def _respond_error(self, writer: asyncio.StreamWriter,
                             e: HTTPError) -> None:
        await self._respond(
            writer, e.status, error_body(e.message, e.etype, e.code),
            extra_headers=tuple(e.headers),
        )


async def run_server(
    engine: Any,
    *,
    model_id: str,
    tokenizer: Any = None,
    host: str = "127.0.0.1",
    port: int = 8000,
    request_timeout: float | None = None,
    drain_timeout: float = 30.0,
    default_max_tokens: int = 16,
    max_tokens_cap: int | None = None,
    tick_deadline: float | None = None,
    max_restarts: int = 0,
    restart_backoff_s: float = 0.5,
    restart_window_s: float = 300.0,
    port_file: str | None = None,
    exit_after_s: float | None = None,
    on_started: Any = None,
    runner: Any = None,
    upgrade_loader: Any = None,
) -> HttpServer:
    """Start serving and block until drain shutdown completes."""
    server = HttpServer(
        engine, model_id=model_id, tokenizer=tokenizer,
        request_timeout=request_timeout, drain_timeout=drain_timeout,
        default_max_tokens=default_max_tokens,
        max_tokens_cap=max_tokens_cap,
        tick_deadline=tick_deadline, max_restarts=max_restarts,
        restart_backoff_s=restart_backoff_s,
        restart_window_s=restart_window_s,
        runner=runner,
        upgrade_loader=upgrade_loader,
    )
    await server.start(host, port)
    if port_file:
        # appears whole: a poller that sees the file can read it at once
        with open(port_file + ".tmp", "w") as f:
            f.write(f"{server.host} {server.port}\n")
        os.replace(port_file + ".tmp", port_file)
    if exit_after_s is not None:
        asyncio.get_running_loop().call_later(
            exit_after_s, server.begin_drain)
    if on_started is not None:
        on_started(server)
    await server.serve_until_shutdown()
    return server


def serve_forever(engine: Any, **kwargs: Any) -> None:
    """Synchronous entry for the CLI: run the server on a fresh event
    loop until a drain shutdown (SIGTERM/SIGINT) completes."""
    asyncio.run(run_server(engine, **kwargs))
