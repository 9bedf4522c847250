"""Request-lifecycle + tick-phase tracing for the serving stack.

``ServeMetrics`` answers *how much* (counters, percentiles); this module
answers *where the time went*: when a p99 TTFT regresses or a chaos run
recovers slowly, the operator needs a timeline — queue wait vs prefill
chunks vs decode dispatch vs host sync vs SSE delivery — not another
percentile.  ``TraceRecorder`` collects that timeline as Chrome/Perfetto
trace-event JSON (stdlib only, like the rest of the HTTP stack; open the
dump at ui.perfetto.dev or chrome://tracing):

- **per-request spans** — async events (``ph`` b/e/n) on one track per
  request id: ``queued`` → ``prefill`` (prefix-cache hits annotated)
  → ``decode`` →
  a terminal ``finish`` instant (reason-tagged), with instants for
  ``evicted-requeued`` preemptions and ``recovery-replay`` resubmits
  after a supervised restart.  The HTTP layer brackets the whole thing
  with an ``http`` span starting at socket accept, so queue wait is
  visibly split from network/parse time, and follows the tokens down
  to the socket: a ``first_write`` instant when the first SSE frame of
  the request has been written, and, where it closes ``http``, a
  ``stream_end`` instant with the emit-to-write lag of its frames.
  Between ``http`` and ``first_write`` the way to the first token is cut
  where the work happens, at the stamps ``Request`` keeps on the engine
  clock (scheduler.TTFT_STAMPS; ``us_at`` puts each on this axis, so
  one clock read serves the counter and the span): ``http`` begins at
  ``received_time``, instants ``enqueued`` (the command in the tick
  thread's inbox), ``lane`` (the plan of the first tick that gave the
  row leftover of the prompt lane or completed its prompt; args
  ``rows_ahead``, ``fair_tokens``), ``last_chunk`` (the plan of the
  tick that carries its last prompt token; args the three tick counts
  and the dispatch's ``seq``), ``first_token`` (the accept; args
  ``seq``), and ``decode`` begins at ``first_emit_time``, just BEFORE
  the first token's callback.  A ``seq`` is the one the tick and its
  two profiler annotations carry: a request's final tick is found on
  the device's line by it.
- **per-tick phase spans** — complete events (``ph`` X) on the engine
  tick thread: one slice a phase of ``MIXED_TICK_PHASES`` (``admission``
  … ``mixed_dispatch`` … ``host_sync`` … ``account``) nested under
  one ``tick`` event.  The phases are measured at consecutive
  timestamps, so they sum to the tick span by construction — the
  invariant tests pin.
- the phases of the unified tick also run under
  ``jax.profiler.TraceAnnotation("serve.<phase>")``, so this host
  timeline lines up against a device profile captured with
  ``--jax-profile DIR`` (the live-TPU tuning workflow).
- **set-up spans** (``cat: "setup"``): load + place, engine build with
  pool allocation and kernel probes as children, one per warm-up
  bucket, listen.  Phases that ran before the recorder existed are
  stamped with the recorder's clock and appended afterwards
  (``us_at``).
- **compile spans** (``cat: "compile"``, ``watch_compiles``): every
  backend compile the process sees while the recorder is attached,
  with whether the persistent cache served it; the export names the
  tick phase or set-up span each fell in.
- **collector spans** (``cat: "gc"``, ``watch_gc``): every run of the
  garbage collector while the recorder watches, on the thread it ran
  on — a stall of the whole interpreter no other span shows; the
  export names the tick phase that held each (``within``).
- ``otherData`` of the dump carries what is not an event: the
  device-side **op map** (serve/opmap.py) from a profile's name for an
  operation of the step to its named scope.

ZERO-OVERHEAD WHEN OFF (the ``FaultInjector`` discipline): nothing
constructs a recorder unless tracing is requested (``--trace-out`` /
``--trace-ring``), and every hook in the engine/HTTP hot path is a
single ``is None`` check — no allocation, no call.  Pinned by
``tools/compile_counter.assert_tracing_hooks_guarded`` (an AST lint over
the hot-path modules) plus a zero-new-compiles test.

THREAD SAFETY: events arrive from the engine tick thread, the asyncio
event loop, the watchdog, and the supervisor's rebuild thread — one lock
serializes every append, and readers (``events()`` / ``to_dict()`` /
the ``GET /debug/trace`` handler) copy under it.  With ``ring=N`` the
recorder keeps only the newest N events (a long-running server must not
grow without bound); ``dropped`` counts what the ring displaced.
"""

from __future__ import annotations

import gc
import json
import os
import re
import threading
import time
import weakref
from bisect import bisect_left, bisect_right
from collections import deque
from typing import Any, Callable

# The request-lifecycle phase names, in order; ``request_phase``
# transitions between them (ending whatever span is open) and
# ``request_end`` closes the track with a reason-tagged ``finish``
# instant.  tools/summarize_trace.py renders these (plus the HTTP
# layer's "http" bracket) as its lifecycle columns — that tool stays
# stdlib-only, so it carries its own copy, pinned equal to this one by
# tests/test_serve_tracing.py.
REQUEST_PHASES = ("queued", "prefill", "decode")
# Tick-phase names, in tick order (ServeEngine._step_mixed; the one
# list — tools/summarize_trace.py mirrors it under this name): slices at
# consecutive timestamps that sum to the tick.  Prefill and decode share
# the single mixed dispatch, the token-budget planner has its own
# slice, and ``draft`` is the
# host-side speculative proposal pass (prompt-lookup over each
# speculating request's history — dictionary probes, no device work;
# ~0 on non-spec engines).  Tick args additionally carry the prefill_tokens/
# decode_tokens budget split — plus spec_draft_tokens/
# spec_accept_tokens on spec-enabled engines — for
# tools/summarize_trace.py's utilization line.
# The host's share of the dispatch is cut where the work changes kind:
# ``pack`` builds the one numpy operand, ``h2d`` places it (its slice
# carries the count and bytes of the transfer), ``mixed_dispatch`` is
# the jitted call alone (what the ``serve.mixed_dispatch`` annotation
# wraps), ``deliver`` the publish of the PREVIOUS tick's tokens (their
# callbacks, metrics, request log, the journal's watermark) while this
# tick's program runs — on the spot in a tick that dispatches nothing —,
# ``host_sync`` the one fetch, ``accept`` what the next plan reads of it
# (tokens into the requests, finish decided, slots and blocks released),
# ``account`` the metrics of the tick.  Tick args ``publish_rows`` /
# ``publish_overlapped`` say what ``deliver`` handed out and whether a
# dispatch was in flight; ``lane_tokens`` / ``lane_rows`` say which KIND
# of tick it was — the leftover of the prompt lane handed out beyond the
# rows' fair shares and the rows that got it (0 rows: a decode-only or
# fair-share-only tick).
MIXED_TICK_PHASES = (
    "admission", "draft", "grow", "plan", "pack", "h2d", "mixed_dispatch",
    "deliver", "host_sync", "accept", "account",
)
# jax.monitoring duration events the compile watcher reads
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"

# ----------------------------------------------------------------------
# W3C trace context (the `traceparent` header): the ONE request identity
# that survives the fleet.  A request routed by the PrefixRouter, killed
# with its process, journal-replayed, and drained to a peer replica
# keeps the SAME 32-hex trace id through every hop — span args carry it,
# so tools/summarize_trace.py --merge can stitch per-replica/per-process
# trace files back into one request-ordered timeline.
# Format: `00-<32 hex trace id>-<16 hex parent span id>-<2 hex flags>`.
# ----------------------------------------------------------------------
_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$"
)


def gen_trace_id() -> str:
    return os.urandom(16).hex()


def gen_span_id() -> str:
    return os.urandom(8).hex()


def parse_traceparent(header: str | None) -> tuple[str, str] | None:
    """``traceparent`` header → ``(trace_id, parent_span_id)``, or None
    when absent/malformed (a bad header means a FRESH trace, never a
    400 — trace context must not be able to fail a request)."""
    if not header:
        return None
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if m is None:
        return None
    version, trace_id, parent_id, _flags = m.groups()
    if version == "ff":  # forbidden version
        return None
    if trace_id == "0" * 32 or parent_id == "0" * 16:
        return None  # all-zero ids are invalid per spec
    return trace_id, parent_id


def make_traceparent(trace_id: str, span_id: str | None = None) -> str:
    """Render the header this server emits back (sampled flag set —
    we recorded the request, whatever upstream decided)."""
    return f"00-{trace_id}-{span_id or gen_span_id()}-01"


# Recorders that asked for compile spans.  jax.monitoring has no public
# way to drop ONE listener, so the module registers one forwarding
# listener the first time and recorders come and go from this set.
_compile_watchers: "weakref.WeakSet[TraceRecorder]" = weakref.WeakSet()
_compile_listener_installed = False


def _on_duration_event(event: str, duration_secs: float, **_kw: Any) -> None:
    if event == _BACKEND_COMPILE_EVENT or event == _CACHE_RETRIEVAL_EVENT:
        for rec in list(_compile_watchers):
            rec._on_compile_event(event, duration_secs)


# Recorders that asked for collector spans.  ``gc.callbacks`` holds the
# one forwarding hook below only while a recorder watches: the last one
# to leave (``unwatch_gc``, or its own collection) takes the hook out.
_gc_watchers: "weakref.WeakSet[TraceRecorder]" = weakref.WeakSet()


def _on_gc(phase: str, info: dict) -> None:
    watchers = list(_gc_watchers)
    if not watchers:
        _drop_gc_hook()
    for rec in watchers:
        rec._on_gc(phase, info)


def _drop_gc_hook() -> None:
    if _on_gc in gc.callbacks:
        gc.callbacks.remove(_on_gc)


class TraceRecorder:
    def __init__(
        self,
        *,
        clock: Callable[[], float] = time.perf_counter,
        ring: int | None = None,
    ) -> None:
        if ring is not None and ring < 1:
            raise ValueError(f"ring must be >= 1 or None, got {ring}")
        self.clock = clock
        self.ring = ring
        self._t0 = clock()
        # wall-clock anchor of the trace epoch: per-process perf_counter
        # timestamps are incommensurable across replicas/restarts, so
        # --merge rebases each file's events by its anchor before
        # stitching per-replica timelines together
        self.wall_epoch = time.time()
        self._pid = os.getpid()
        self._lock = threading.Lock()
        self._events: deque | list = (
            deque(maxlen=ring) if ring is not None else []
        )
        # optional OTLP span sink (serve/otel.OtlpExporter): every event
        # the recorder keeps is also offered to the exporter's pending
        # queue (enqueue only — its writer thread does the IO).  None =
        # one is-None check per event, the standard zero-overhead hook
        # discipline (tools/lint R4 covers the ``otel`` hook)
        self.otel: Any = None
        self.dropped = 0
        # rid → currently-open lifecycle phase name (exactly one per
        # live request; the http bracket span is tracked separately by
        # async_begin/async_end)
        self._req_phase: dict[int, str] = {}
        self._named_threads: set[int] = set()
        # what the dump carries beside events (``otherData``): the op
        # map, written once after warm-up
        self._other: dict[str, Any] = {}
        # backend compiles seen / of those, served by the persistent
        # cache (watch_compiles); warm-up reads the difference around a
        # bucket to say whether a compile ran
        self.n_compiles = 0
        self.n_cache_hits = 0
        self._compile_tl = threading.local()
        # rid → [emit stamps not yet written, frames, lag sum, lag max]
        self._streams: dict[int, list] = {}
        # collector slices (watch_gc).  Kept apart and appended WITHOUT
        # the lock: the collector can start on a thread that holds it
        # (any allocation inside ``_append``), and a hook that waited
        # for the lock there would never return.  Under a ring they take
        # at most a quarter of it, and the export keeps the total inside
        self._gc_events: deque | list = (
            deque(maxlen=max(ring // 4, 1)) if ring is not None else []
        )
        self._gc_tl = threading.local()

    # -- clock ---------------------------------------------------------
    def now_us(self) -> float:
        """Microseconds since recorder construction (the trace epoch)."""
        return (self.clock() - self._t0) * 1e6

    def us_at(self, clock_value: float) -> float:
        """A reading of the recorder's ``clock`` taken elsewhere (before
        the recorder existed, too: the result is then negative) on the
        trace's time axis."""
        return (clock_value - self._t0) * 1e6

    # -- what is not an event ------------------------------------------
    def set_other(self, key: str, value: Any) -> None:
        with self._lock:
            self._other[key] = value

    def get_other(self, key: str) -> Any:
        with self._lock:
            return self._other.get(key)

    @property
    def compile_misses(self) -> int:
        """Backend compiles the persistent cache did not serve."""
        with self._lock:
            return self.n_compiles - self.n_cache_hits

    # -- compile spans -------------------------------------------------
    def watch_compiles(self) -> None:
        """From now on every backend compile of this process becomes a
        ``cat: "compile"`` span on the compiling thread's track."""
        global _compile_listener_installed
        import jax.monitoring

        _compile_watchers.add(self)
        if not _compile_listener_installed:
            _compile_listener_installed = True
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration_event)

    def _on_compile_event(self, event: str, duration_secs: float) -> None:
        # the cache's retrieval time is reported INSIDE the compile it
        # served, on the same thread, just before the compile's own event
        tl = self._compile_tl
        if event == _CACHE_RETRIEVAL_EVENT:
            tl.hit = True
            return
        hit = getattr(tl, "hit", False)
        tl.hit = False
        end = self.now_us()
        with self._lock:
            self.n_compiles += 1
            self.n_cache_hits += hit
        self.complete("backend_compile", end - duration_secs * 1e6, end,
                      cat="compile", args={"cache_hit": hit})

    # -- collector spans -----------------------------------------------
    def watch_gc(self) -> None:
        """From now on every run of the garbage collector becomes a
        ``cat: "gc"`` slice on the thread it ran on (``generation``,
        ``collected``), until ``unwatch_gc``."""
        _gc_watchers.add(self)
        if _on_gc not in gc.callbacks:
            gc.callbacks.append(_on_gc)

    def unwatch_gc(self) -> None:
        _gc_watchers.discard(self)
        if not _gc_watchers:
            _drop_gc_hook()

    def _on_gc(self, phase: str, info: dict) -> None:
        tl = self._gc_tl
        if phase == "start":
            tl.t0 = self.now_us()
            return
        t0 = getattr(tl, "t0", None)
        if t0 is None:
            return  # the collection began before the recorder watched
        tl.t0 = None
        self._gc_events.append({
            "name": "gc", "cat": "gc", "ph": "X", "ts": t0,
            "dur": max(self.now_us() - t0, 0.0), "pid": self._pid,
            "tid": threading.get_ident(),
            "args": {"generation": info["generation"],
                     "collected": info["collected"],
                     "thread": threading.current_thread().name},
        })

    # -- low-level event append (callers hold no lock) -----------------
    def _ensure_thread_named(self, tid: int) -> None:
        # caller holds the lock; first event from a thread gets the
        # thread_name metadata event viewers use to label its track
        if tid not in self._named_threads:
            self._named_threads.add(tid)
            self._push({
                "name": "thread_name", "ph": "M", "pid": self._pid,
                "tid": tid,
                "args": {"name": threading.current_thread().name},
            })

    def _append(self, ev: dict, tid: int | None = None) -> None:
        tid = threading.get_ident() if tid is None else tid
        ev.setdefault("pid", self._pid)
        ev.setdefault("tid", tid)
        with self._lock:
            self._ensure_thread_named(tid)
            self._push(ev)

    def _push(self, ev: dict) -> None:
        # caller holds the lock; the exporter's offer() is a single
        # lock-protected append (recorder lock → exporter lock, never
        # the reverse — the exporter never calls back into the recorder)
        if self.ring is not None and len(self._events) == self.ring:
            self.dropped += 1
        self._events.append(ev)
        if self.otel is not None:
            self.otel.offer(ev)

    # -- synchronous (thread-track) events -----------------------------
    def complete(
        self, name: str, start_us: float, end_us: float | None = None,
        *, cat: str = "phase", args: dict | None = None,
    ) -> None:
        """One ``ph: X`` slice on the calling thread's track."""
        if end_us is None:
            end_us = self.now_us()
        ev: dict[str, Any] = {
            "name": name, "cat": cat, "ph": "X",
            "ts": start_us, "dur": max(end_us - start_us, 0.0),
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def instant(self, name: str, *, cat: str = "tick",
                args: dict | None = None) -> None:
        ev: dict[str, Any] = {
            "name": name, "cat": cat, "ph": "i", "ts": self.now_us(),
            "s": "t",
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def tick(
        self, start_us: float,
        phases: tuple[tuple, ...],
        *, args: dict | None = None,
    ) -> None:
        """One tick: the wrapper ``tick`` slice plus its phase slices,
        appended atomically (a ``/debug/trace`` read never sees a tick
        missing half its phases).  Phases are ``(name, t0_us, t1_us)``
        measured at consecutive timestamps, so their durations sum to
        the tick span by construction; a fourth element is the slice's
        own ``args``."""
        end_us = self.now_us()
        tid = threading.get_ident()
        events = [{
            "name": "tick", "cat": "tick", "ph": "X", "ts": start_us,
            "dur": max(end_us - start_us, 0.0), "pid": self._pid,
            "tid": tid, **({"args": args} if args else {}),
        }]
        for name, p0, p1, *pargs in phases:
            events.append({
                "name": name, "cat": "phase", "ph": "X", "ts": p0,
                "dur": max(p1 - p0, 0.0), "pid": self._pid, "tid": tid,
                **({"args": pargs[0]} if pargs else {}),
            })
        with self._lock:
            self._ensure_thread_named(tid)
            for ev in events:
                self._push(ev)

    # -- request-lifecycle (async-track) events ------------------------
    def async_begin(self, rid: int, name: str, *,
                    ts_us: float | None = None,
                    args: dict | None = None) -> None:
        ev: dict[str, Any] = {
            "name": name, "cat": "request", "ph": "b", "id": rid,
            "ts": self.now_us() if ts_us is None else ts_us,
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def async_end(self, rid: int, name: str, *,
                  ts_us: float | None = None) -> None:
        self._append({
            "name": name, "cat": "request", "ph": "e", "id": rid,
            "ts": self.now_us() if ts_us is None else ts_us,
        })

    def request_phase(self, rid: int, phase: str, *,
                      ts_us: float | None = None,
                      args: dict | None = None) -> None:
        """Transition request ``rid`` into ``phase``: end whatever
        lifecycle span is open and begin the new one (back-to-back, one
        timestamp — no gap, no overlap; ``ts_us``: a stamp taken a moment
        before the call)."""
        now = self.now_us() if ts_us is None else ts_us
        with self._lock:
            open_phase = self._req_phase.get(rid)
            self._req_phase[rid] = phase
        if open_phase is not None:
            self.async_end(rid, open_phase, ts_us=now)
        self.async_begin(rid, phase, ts_us=now, args=args)

    def request_instant(self, rid: int, name: str, *,
                        ts_us: float | None = None,
                        args: dict | None = None) -> None:
        """Async instant (``ph: n``) on the request's track —
        annotations like ``evicted-requeued`` / ``recovery-replay``."""
        ev: dict[str, Any] = {
            "name": name, "cat": "request", "ph": "n", "id": rid,
            "ts": self.now_us() if ts_us is None else ts_us,
        }
        if args:
            ev["args"] = args
        self._append(ev)

    def request_end(self, rid: int, reason: str, *,
                    args: dict | None = None) -> None:
        """Terminal: close the open lifecycle span and stamp a
        reason-tagged ``finish`` instant (span-vs-metrics parity counts
        these against the finish_reasons counters)."""
        now = self.now_us()
        with self._lock:
            open_phase = self._req_phase.pop(rid, None)
        if open_phase is not None:
            self.async_end(rid, open_phase, ts_us=now)
        merged = {"reason": reason}
        if args:
            merged.update(args)
        self._append({
            "name": "finish", "cat": "request", "ph": "n", "id": rid,
            "ts": now, "args": merged,
        })

    # -- the request track down to the socket --------------------------
    # The engine's ``decode`` span begins where a token is EMITTED on the
    # tick thread; the client sees it when the event loop has written
    # its SSE frame.  The HTTP layer stamps both ends here (the item it
    # hands across threads keeps its shape): ``stamp_emit`` on the tick
    # thread, ``frame_written`` on the loop after ``writer.write``,
    # ``stream_end`` where it closes the ``http`` span.
    # No lock per frame: the tick thread only appends to a stream's
    # deque (and creates the entry), the loop thread only pops from it
    # and owns the counters; both are single operations under the GIL.
    def stamp_emit(self, rid: int) -> None:
        st = self._streams.get(rid)
        if st is None:
            st = self._streams.setdefault(rid, [deque(), 0, 0.0, 0.0])
        st[0].append(self.now_us())

    def frame_written(self, rid: int) -> None:
        """One token frame of ``rid`` is written: its emit-to-write lag
        joins the stream's stats; the first one stamps ``first_write``."""
        st = self._streams.get(rid)
        if st is None or not st[0]:
            return  # emitted before the tracer was attached
        now = self.now_us()
        lag = now - st[0].popleft()
        st[1] += 1
        st[2] += lag
        st[3] = max(st[3], lag)
        if st[1] == 1:
            self.request_instant(rid, "first_write", ts_us=now,
                                 args={"lag_us": round(lag, 1)})

    def stream_end(self, rid: int) -> None:
        """The HTTP layer is done with ``rid``: a ``stream_end`` instant
        with the count, mean and max of emit-to-write lag over its token
        frames (nothing for a response that streamed none)."""
        st = self._streams.pop(rid, None)
        if st is None or not st[1]:
            return
        self.request_instant(rid, "stream_end", args={
            "frames": st[1], "lag_mean_us": round(st[2] / st[1], 1),
            "lag_max_us": round(st[3], 1),
        })

    # -- export --------------------------------------------------------
    def _merged(self) -> list[dict]:
        # caller holds the lock: the events and the collector's slices,
        # the oldest events making room where a ring bounds the total
        slices = list(self._gc_events)
        events = list(self._events)
        if self.ring is not None:
            events = events[max(len(events) + len(slices) - self.ring, 0):]
        return events + slices

    def __len__(self) -> int:
        with self._lock:
            n = len(self._events) + len(self._gc_events)
        return n if self.ring is None else min(n, self.ring)

    def events(self) -> list[dict]:
        """Point-in-time copy (the ring keeps mutating underneath)."""
        with self._lock:
            return self._merged()

    def to_dict(self) -> dict:
        with self._lock:
            events = self._merged()
            other = dict(self._other)
        return {
            "traceEvents": _name_sites(events),
            "displayTimeUnit": "ms",
            "otherData": {
                "dropped_events": self.dropped,
                "wall_epoch": self.wall_epoch,
                **other,
            },
        }

    def dump(self, path: str) -> int:
        """Write the Chrome trace-event JSON; returns the event count."""
        payload = self.to_dict()
        with open(path, "w") as f:
            json.dump(payload, f)
        return len(payload["traceEvents"])


def _name_sites(events: list[dict]) -> list[dict]:
    """Give every compile and collector span ``args.within``: the
    shortest tick phase or set-up span that holds its midpoint (None
    when it fell between them) — of its own thread for a compile, of any
    thread for a collection, which stops them all.  Done at export: a
    tick's phases are appended when the tick ends, after what it
    contained.  One pass over the events, each host span looked up among
    the midpoints it may hold."""
    # tid → [(midpoint, i)]; collections under None: every thread's
    mids: dict[int | None, list[tuple[float, int]]] = {}
    for i, ev in enumerate(events):
        cat = ev.get("cat")
        if cat == "compile" or cat == "gc":
            mids.setdefault(ev["tid"] if cat == "compile" else None,
                            []).append((ev["ts"] + ev["dur"] / 2, i))
    if not mids:
        return events
    for of_thread in mids.values():
        of_thread.sort()
    within: dict[int, dict] = {}  # span's index → its shortest holder
    for ev in events:
        if ev.get("ph") != "X" or ev.get("cat") not in ("phase", "setup"):
            continue
        for of_thread in (mids.get(ev["tid"], ()), mids.get(None, ())):
            lo = bisect_left(of_thread, (ev["ts"], -1))
            hi = bisect_right(of_thread,
                              (ev["ts"] + ev["dur"], len(events)))
            for _, i in of_thread[lo:hi]:
                if i not in within or ev["dur"] < within[i]["dur"]:
                    within[i] = ev
    out = list(events)
    for of_thread in mids.values():
        for _, i in of_thread:
            ev, holder = events[i], within.get(i)
            out[i] = {**ev, "args": {
                **ev.get("args", {}),
                "within": holder["name"] if holder else None}}
    return out
