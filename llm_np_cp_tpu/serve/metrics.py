"""Serving metrics: what an operator needs to see on one screen.

Collected by ``ServeEngine`` per tick and per request, exported as one
flat dict (``snapshot()``) so the CLI, bench.py, tests, and the HTTP
``/metrics`` endpoint consume the same numbers:

- ``queue_depth_*``        — requests waiting (sampled per tick)
- ``ttft_s_*``             — arrival (realtime replay) or submit → first
                             emitted token, per request
- ``decode_tok_s_*``       — per-request steady decode rate (tokens
                             after the first / time after first token)
- ``occupancy_*``          — fraction of allocatable blocks held
- ``active_slots_*``       — decode slots busy (batch efficiency)
- ``preemptions``          — evict-on-OOM count (requeues)
- ``aborted`` / ``rejected`` — cancelled requests (client disconnect or
                             deadline) and queue-full admission rejects
- ``finish_reasons``       — terminal outcome counts by reason
                             (``stop``/``length``/``aborted``)
- ``throughput_tok_s``     — total generated tokens / wall span
- ``prefix_hit_rate``      — prompt blocks reused from the prefix cache
                             / shareable prompt blocks requested
- ``kv_bytes_tick_*``      — K/V bytes the decode attention touches per
                             tick (the gather→paged observable: the XLA
                             gather path streams the full padded view,
                             the paged kernel only each row's visible
                             blocks)
- ``roofline_*`` / ``*_bytes_total`` / ``device_time_s_total`` — device
                             roofline telemetry (serve/telemetry.py):
                             achieved GB/s, utilization vs --hbm-gbps
                             and MFU per graded dispatch, plus the
                             exact byte/time ledgers per-request cost
                             attribution sums back to (present only
                             when a TelemetryModel is attached)
- ``queue_wait_s_*``       — the wait for a SLOT, counted from the
                             moment the tick thread took the command
                             (``submit_time`` is stamped between two
                             ticks) to the first admission: the wait for
                             the running tick to end is NOT in it
- ``prefill_s_*``          — a tick's dispatch + sync wall shared out by
                             token count, summed over the request's
                             prefill segments (re-prefills included): a
                             cost share, not a latency
- ``ttft_stage_<stage>_s_*`` — the latencies: a request's way to its
                             first token cut into consecutive stages
                             (scheduler.TTFT_STAGES: ``parse``,
                             ``inbox_wait``, ``slot_wait``,
                             ``lane_wait``, ``prefill``, ``final_tick``,
                             ``publish_lag``) from stamps taken where
                             the work happens, the same ones the request
                             track's instants sit at — so a scrape says
                             where a slow first token waited, without a
                             trace file.  ``lane_ticks`` (dispatching
                             ticks that handed a prompt leftover of the
                             lane) and ``prefill_starved_rows``
                             (mid-prefill rows a tick granted nothing)
                             count the lane beside them.

Percentiles are p50/p90/p99 over whatever was recorded — no windowing.

``ttft_s`` and ``decode_tok_s`` additionally maintain REAL Prometheus
histograms (cumulative ``_bucket``/``_sum``/``_count`` series over the
fixed ``TTFT_BUCKETS`` / ``DECODE_TOK_S_BUCKETS``): the bucket counters
are updated incrementally at record time, so they stay exact forever
even when ``max_samples`` trims the percentile windows — and unlike the
quantile gauges they aggregate correctly across replicas.

THREAD SAFETY: the engine tick loop mutates these counters from its own
thread while the HTTP scrape handler renders them from the event loop —
every record hook and ``snapshot()`` serialize on one lock, and
``snapshot()`` copies the value lists before computing percentiles, so a
scrape always sees a consistent point-in-time view (copy-on-read).
``prometheus()`` renders the text exposition format (0.0.4) from that
same snapshot.
"""

from __future__ import annotations

import bisect
import threading
import time
from collections import Counter
from typing import Any

import numpy as np

from llm_np_cp_tpu.serve.scheduler import TTFT_STAGES, Request, ttft_stages

# Fixed histogram buckets (upper bounds, seconds / tokens-per-second).
# Fixed so series are comparable across runs and joinable across
# replicas; spans roughly host-CPU test ticks to live-TPU serving.
TTFT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
                0.5, 1.0, 2.5, 5.0, 10.0)
DECODE_TOK_S_BUCKETS = (0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0,
                        200.0, 500.0, 1000.0)
# Speculative accept length per verify round (accepted draft tokens,
# 0..spec_k): integer upper bounds; the tail bucket absorbs any larger
# spec_k an operator configures
SPEC_ACCEPT_BUCKETS = (0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0)
# Roofline utilization per tick (achieved GB/s over --hbm-gbps, from
# serve/telemetry.py): log-ish lower buckets because CPU test runs sit
# far below the roofline while a healthy TPU tick should land in the
# top few buckets
ROOFLINE_UTIL_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                         0.2, 0.35, 0.5, 0.65, 0.8, 0.9, 1.0)


def _pcts(values: list[float], name: str) -> dict[str, float]:
    if not values:
        return {}
    arr = np.asarray(values, dtype=np.float64)
    return {
        f"{name}_p50": float(np.percentile(arr, 50)),
        f"{name}_p90": float(np.percentile(arr, 90)),
        f"{name}_p99": float(np.percentile(arr, 99)),
        f"{name}_mean": float(arr.mean()),
    }


class ServeMetrics:
    def __init__(self, clock=time.perf_counter,
                 max_samples: int | None = None,
                 slo: Any = None) -> None:
        self.clock = clock
        self._lock = threading.Lock()
        # SLO goodput accounting (serve/slo.SLOTracker): judged per
        # request at terminal time inside _record_latencies, under this
        # lock.  None (the default) = a single is-None check per
        # terminal — the zero-overhead hook discipline
        self.slo = slo
        # tick anomaly sentinel verdicts (serve/slo.TickSentinel via
        # ServeEngine._sentinel_observe): per-phase outlier counts,
        # exported as llm_serve_anomaly_ticks_total{phase=}
        self.anomaly_ticks: Counter[str] = Counter()
        # fleet lifecycle events (serve/lifecycle.ActionPolicy flips,
        # rolling upgrades, elastic add/remove), exported as
        # llm_serve_lifecycle_actions_total{action=}
        self.lifecycle_actions: Counter[str] = Counter()
        # bounded-retention mode for long-running servers: None (bench/
        # test traces — exact full-trace percentiles) keeps every sample;
        # an int caps each value list, dropping the oldest half on
        # overflow (percentiles become a recent-window view; counters
        # stay exact forever).  The HTTP runner sets this — an unbounded
        # list per tick would leak for the server's whole lifetime.
        self.max_samples = max_samples
        self.t_start = clock()
        self.t_last: float | None = None
        self.n_submitted = 0
        self.n_finished = 0
        self.n_aborted = 0
        self.n_rejected = 0
        self.n_recovered = 0
        self.n_ticks = 0
        self.preemptions = 0
        self.total_generated = 0
        self.finish_reasons: Counter[str] = Counter()
        self.ttft_s: list[float] = []
        self.decode_tok_s: list[float] = []
        # the wait for a slot (from the tick thread's take of the
        # command) and the prefill cost share, recorded at terminal time
        # from Request.admit_time / Request.prefill_s
        self.queue_wait_s: list[float] = []
        self.prefill_s: list[float] = []
        # ...and the latencies: the stages of the way to the first
        # token, of requests that emitted one (scheduler.ttft_stages)
        self.ttft_stage_s: dict[str, list[float]] = {
            stage: [] for stage in TTFT_STAGES}
        # exact cumulative histogram state (never trimmed): per-bucket
        # increments + running sum; bucket i counts values <= bucket[i],
        # the trailing slot is the +Inf overflow
        self.ttft_hist = [0] * (len(TTFT_BUCKETS) + 1)
        self.ttft_hist_sum = 0.0
        self.decode_hist = [0] * (len(DECODE_TOK_S_BUCKETS) + 1)
        self.decode_hist_sum = 0.0
        self.queue_depth: list[int] = []
        self.occupancy: list[float] = []
        self.active_slots: list[int] = []
        self.kv_bytes_tick: list[float] = []
        self.prefix_blocks_requested = 0
        self.prefix_blocks_hit = 0
        # prefix-cache LRU reclaim (always counted — reclaim used to be
        # silent, so drop-vs-spill behavior was invisible on a scrape)
        # + the host-RAM KV tier's flow (serve/host_tier.py): spill and
        # restore ledgers in blocks AND bytes, restore-latency samples,
        # and the resident/breakeven gauges the engine refreshes on
        # tier-active ticks.  Zero/absent unless a tier is attached.
        self.prefix_evicted_blocks = 0
        self.prefix_evicted_bytes = 0.0
        self.tier_spilled_blocks = 0
        self.tier_spilled_bytes = 0.0
        self.tier_restored_blocks = 0
        self.tier_restored_bytes = 0.0
        self.tier_restore_s: list[float] = []
        self.tier_resident_bytes = 0.0
        self.tier_breakeven: float | None = None
        # unified-tick (mixed_step) utilization: how this engine's token
        # budget was actually spent — exact counters, never trimmed
        self.mixed_prefill_tokens = 0
        self.mixed_decode_tokens = 0
        # ...and the rows that prefill spend went to, one a planned row
        # a tick: prefill tokens / segments is what a row gets of a tick
        # (a chunk where rows share the lane, the lane where one has it)
        self.prefill_segments = 0
        # ...the query tiles the packer laid with more than one token (a
        # prompt chunk's, a verify slice's) and the tokens in them: their
        # ratio is how wide a prefill tile was (8 lanes, or the wide
        # tile's 16-64 where the pages have one)
        self.prefill_tiles = 0
        self.prefill_tile_tokens = 0
        # ...dispatching ticks that handed a row more than its fair
        # share (the prompt lane's leftover), and mid-prefill rows a
        # tick granted nothing, summed over ticks
        self.lane_ticks = 0
        self.prefill_starved_rows = 0
        # ...and the lanes of the step's dense token axis it was
        # dispatched at, summed over dispatches: tokens / lanes is the
        # share of the matmuls' rows that hold a token
        self.mixed_dense_lanes = 0
        # the unified tick hands tick N's tokens out behind tick N+1's
        # dispatch: publishes that ran with a dispatch in flight, and
        # publishes made on the spot (no dispatch followed)
        self.publish_overlapped = 0
        self.publish_immediate = 0
        # dispatching ticks at whose fetch the device had already
        # finished: the host, not the step, set their length (asked only
        # while a recorder is attached: 0 without one)
        self.host_bound_ticks = 0
        # dropless expert layers (exact counters, one observation a
        # dispatching tick): experts that got at least one token, summed
        # over the expert layers; and of the worst layer the most tokens
        # an expert got beside the mean — max / mean is the skew a
        # grouped matmul pays for.  conv_state_slots: slots whose
        # short-convolution state is live (a gauge)
        self.moe_ticks = 0
        self.moe_experts_touched = 0
        self.moe_load_max = 0
        self.moe_load_mean = 0.0
        self.moe_pairs_held = 0
        self.conv_state_slots = 0
        # a pool with a window class (exact counters, one observation a
        # dispatching tick while a trace recorder is attached): the pages
        # one layer of each kind streams, the live query tiles and those
        # of them with more than one token (a prefill chunk's), the ring
        # blocks the tick's rows let go
        self.class_ticks = 0
        self.attn_pages_global = 0
        self.attn_pages_window = 0
        self.attn_live_tiles = 0
        self.attn_prefill_tiles = 0
        self.window_blocks_recycled = 0
        # state-space mixers (exact counters, one observation a
        # dispatching tick): rows whose recurrent state a dispatch read
        # and wrote, live tokens through the scan
        self.ssm_ticks = 0
        self.ssm_state_rows = 0
        self.ssm_scan_tokens = 0
        self.ssm_state_kernel = 0  # gauge: 1 where the Pallas kernel moves them
        # ... and of a stack with delta-rule linear-attention layers
        self.kda_ticks = 0
        self.kda_state_rows = 0
        self.kda_scan_tokens = 0
        self.kda_state_kernel = 0
        # ... and with power-retention layers
        self.retention_ticks = 0
        self.retention_state_rows = 0
        self.retention_scan_tokens = 0
        self.retention_state_kernel = 0
        # ... and with a sparse-attention indexer (per token and ONE layer)
        self.dsa_ticks = 0
        self.dsa_visible = 0
        self.dsa_selected = 0
        self.dsa_dense_tokens = 0
        self.dsa_index_pages = 0
        # speculative draft-then-verify accounting (exact counters +
        # a real accept-length histogram over SPEC_ACCEPT_BUCKETS —
        # one observation per verify round, value = accepted drafts)
        self.spec_drafted = 0
        self.spec_accepted = 0
        self.spec_rounds = 0
        self.spec_hist = [0] * (len(SPEC_ACCEPT_BUCKETS) + 1)
        self.spec_hist_sum = 0.0
        # device roofline telemetry (serve/telemetry.py): exact byte/
        # time ledgers (never trimmed — per-request attribution must
        # keep summing to them) plus per-dispatch gauge windows and a
        # real utilization histogram.  Empty/zero unless a
        # TelemetryModel is attached to the engine.
        self.roofline_ticks = 0
        self.kv_read_bytes_total = 0.0
        self.kv_write_bytes_total = 0.0
        self.weight_bytes_total = 0.0
        self.device_time_s_total = 0.0
        self.hbm_gbps: float | None = None
        self.roofline_gbps: list[float] = []
        self.roofline_util: list[float] = []
        self.mfu_tick: list[float] = []
        self.util_hist = [0] * (len(ROOFLINE_UTIL_BUCKETS) + 1)
        self.util_hist_sum = 0.0

    # -- record hooks (engine calls these) -----------------------------
    def on_submit(self, req: Request) -> None:
        with self._lock:
            if self.n_submitted == 0:
                # wall span starts at first traffic, not engine build —
                # idle time before the first request must not deflate
                # throughput
                self.t_start = self.clock()
            self.n_submitted += 1

    def on_reject(self) -> None:
        """A submit bounced off the queue-depth cap (HTTP 429)."""
        with self._lock:
            self.n_rejected += 1

    def on_recover(self) -> None:
        """A supervisor replayed an in-flight request into a rebuilt
        engine (teacher-forced resubmit).  Counted apart from submits —
        the request was already counted at its original submit, and
        finish/abort will still fire exactly once."""
        with self._lock:
            self.n_recovered += 1

    def _trim(self, values: list) -> None:
        # caller holds the lock
        if self.max_samples is not None and len(values) > self.max_samples:
            del values[: len(values) // 2]

    def on_tick(
        self, *, queue_depth: int, occupancy: float, active_slots: int,
        preemptions_total: int, kv_bytes: int = 0,
        prefill_tokens: int = 0, decode_tokens: int = 0,
        prefill_rows: int = 0,
        dense_lanes: int = 0, host_bound: bool = False,
        lane_tick: bool = False, starved_rows: int = 0,
        prefill_tiles: int = 0, prefill_tile_tokens: int = 0,
    ) -> None:
        with self._lock:
            self.host_bound_ticks += host_bound
            self.mixed_prefill_tokens += prefill_tokens
            self.prefill_segments += prefill_rows
            self.prefill_tiles += prefill_tiles
            self.prefill_tile_tokens += prefill_tile_tokens
            self.lane_ticks += lane_tick
            self.prefill_starved_rows += starved_rows
            self.mixed_decode_tokens += decode_tokens
            self.mixed_dense_lanes += dense_lanes
            self.n_ticks += 1
            self.t_last = self.clock()
            self.queue_depth.append(queue_depth)
            self.occupancy.append(occupancy)
            self.active_slots.append(active_slots)
            self.preemptions = preemptions_total
            if active_slots:
                # only decode ticks stream cache; idle/admission-only
                # ticks would dilute the per-tick gauge with zeros
                self.kv_bytes_tick.append(float(kv_bytes))
            for vals in (self.queue_depth, self.occupancy,
                         self.active_slots, self.kv_bytes_tick):
                self._trim(vals)

    def on_publish(self, overlapped: bool) -> None:
        """The unified tick handed out a tick's tokens: behind the next
        dispatch (``overlapped``) or on the spot."""
        with self._lock:
            if overlapped:
                self.publish_overlapped += 1
            else:
                self.publish_immediate += 1

    def on_anomaly(self, phase: str) -> None:
        """The tick sentinel named ``phase`` as an outlier this tick."""
        with self._lock:
            self.anomaly_ticks[phase] += 1

    def on_lifecycle_action(self, action: str) -> None:
        """One fleet lifecycle event: an ActionPolicy flip
        (shed_prefill_on/off, shed_load_on/off), a rolled replica
        (upgrade_replica), an aborted roll, or an elastic
        add/remove_replica."""
        with self._lock:
            self.lifecycle_actions[action] += 1

    def on_experts(self, *, touched: int, load_max: int, load_mean: float,
                   state_slots_live: int, pairs_held: int = 0) -> None:
        """One dispatching tick of a stack with dropless expert layers
        (and, beside them, conv layers with a per-slot state)."""
        with self._lock:
            self.moe_ticks += 1
            self.moe_experts_touched += touched
            self.moe_load_max += load_max
            self.moe_load_mean += load_mean
            self.moe_pairs_held += pairs_held
            self.conv_state_slots = state_slots_live

    def on_page_classes(self, *, pages_global: int, pages_window: int,
                        live_tiles: int, prefill_tiles: int,
                        recycled: int) -> None:
        """One dispatching tick of a pool with a window class: what one
        layer of each kind streams (pages, summed over the live query
        tiles: a tile re-reads its row's pages), the live tiles and the
        prefill tiles among them, the window blocks recycled."""
        with self._lock:
            self.class_ticks += 1
            self.attn_pages_global += pages_global
            self.attn_pages_window += pages_window
            self.attn_live_tiles += live_tiles
            self.attn_prefill_tiles += prefill_tiles
            self.window_blocks_recycled += recycled

    def on_ssm(self, *, rows: int, tokens: int, state_slots_live: int,
               kernel: bool) -> None:
        """One dispatching tick of a stack with state-space mixers: the
        rows whose recurrent state it read and wrote, the live tokens it
        sent through the scan, and whether the Pallas kernel moved those
        rows alone (else the compiler's passes over every row)."""
        with self._lock:
            self.ssm_ticks += 1
            self.ssm_state_rows += rows
            self.ssm_scan_tokens += tokens
            self.conv_state_slots = state_slots_live
            self.ssm_state_kernel = int(kernel)

    def on_kda(self, *, rows: int, tokens: int, state_slots_live: int,
               kernel: bool) -> None:
        """``on_ssm`` for a stack with delta-rule linear-attention layers:
        the rows whose matrix state a dispatch read and wrote, the live
        tokens through the recurrence, and which form advanced them."""
        with self._lock:
            self.kda_ticks += 1
            self.kda_state_rows += rows
            self.kda_scan_tokens += tokens
            self.conv_state_slots = state_slots_live
            self.kda_state_kernel = int(kernel)

    def on_retention(self, *, rows: int, tokens: int, state_slots_live: int,
                     kernel: bool) -> None:
        """``on_ssm`` for a stack with power-retention layers: the rows
        whose state a dispatch read and wrote, the live tokens through the
        recurrence, and which form advanced them."""
        with self._lock:
            self.retention_ticks += 1
            self.retention_state_rows += rows
            self.retention_scan_tokens += tokens
            self.conv_state_slots = state_slots_live
            self.retention_state_kernel = int(kernel)

    def on_dsa(self, *, visible: int, selected: int, dense_tokens: int,
               index_pages: int) -> None:
        """One dispatch of a stack with a sparse-attention indexer, by ONE
        layer: the positions its tokens may see and those they attend
        (summed over tokens), the tokens that see no more than
        ``index_topk`` and so attend everything, and the index-key pages
        the scores read."""
        with self._lock:
            self.dsa_ticks += 1
            self.dsa_visible += visible
            self.dsa_selected += selected
            self.dsa_dense_tokens += dense_tokens
            self.dsa_index_pages += index_pages

    def on_spec(self, *, drafted: int, accepted: int) -> None:
        """One speculative verify round for one request: ``drafted``
        candidate tokens rode the tick's dispatch, ``accepted`` of them
        matched the verifier's deterministic samples."""
        with self._lock:
            self.spec_drafted += drafted
            self.spec_accepted += accepted
            self.spec_rounds += 1
            self.spec_hist[
                bisect.bisect_left(SPEC_ACCEPT_BUCKETS, float(accepted))
            ] += 1
            self.spec_hist_sum += accepted

    def on_telemetry(self, tel: dict[str, Any]) -> None:
        """One telemetry record (serve/telemetry.py): a roofline-graded
        dispatch (the tick's one dispatch) feeds the byte/time ledgers,
        which per-request attribution sums back to, the per-tick gauges
        and the utilization histogram."""
        with self._lock:
            self.kv_read_bytes_total += tel["kv_read_bytes"]
            self.kv_write_bytes_total += tel["kv_write_bytes"]
            self.weight_bytes_total += tel["weight_bytes"]
            self.device_time_s_total += tel["device_time_s"]
            self.hbm_gbps = tel.get("hbm_gbps", self.hbm_gbps)
            self.roofline_ticks += 1
            util = tel["roofline_util"]
            self.roofline_gbps.append(tel["achieved_gbps"])
            self.roofline_util.append(util)
            self.mfu_tick.append(tel["mfu"])
            self.util_hist[
                bisect.bisect_left(ROOFLINE_UTIL_BUCKETS, util)
            ] += 1
            self.util_hist_sum += util
            for vals in (self.roofline_gbps, self.roofline_util,
                         self.mfu_tick):
                self._trim(vals)

    def on_prefix(self, *, requested: int, hits: int) -> None:
        """One prefill's prefix-cache outcome: ``requested`` shareable
        prompt blocks were looked up, ``hits`` were reused."""
        with self._lock:
            self.prefix_blocks_requested += requested
            self.prefix_blocks_hit += hits

    def on_prefix_evicted(self, *, blocks: int, nbytes: int) -> None:
        """LRU reclaim dropped ``blocks`` prefix-cache entries (their
        K/V bytes included) — with the host tier attached the same
        blocks ALSO count as spills; without it this is the only
        record a prefix was recomputable work thrown away."""
        with self._lock:
            self.prefix_evicted_blocks += blocks
            self.prefix_evicted_bytes += nbytes

    def on_tier_spill(self, *, blocks: int, nbytes: int) -> None:
        """``blocks`` evicted prefix blocks were handed to the host
        tier's writer thread instead of being dropped."""
        with self._lock:
            self.tier_spilled_blocks += blocks
            self.tier_spilled_bytes += nbytes

    def on_tier_restore(self, *, blocks: int, nbytes: int,
                        latency_s: float) -> None:
        """One admission's host-tier span landed back in the pool:
        ``blocks`` restored (``nbytes`` of K/V that did NOT re-prefill)
        after ``latency_s`` of writer-thread staging."""
        with self._lock:
            self.tier_restored_blocks += blocks
            self.tier_restored_bytes += nbytes
            self.tier_restore_s.append(latency_s)
            self._trim(self.tier_restore_s)

    def on_tier_gauge(self, *, resident_bytes: int,
                      breakeven: float | None) -> None:
        """Refresh the tier's live gauges: host bytes resident and the
        measured restore-vs-recompute breakeven ratio (>1 = restoring
        one block is cheaper than re-prefilling it; 0 until both sides
        are measured)."""
        with self._lock:
            self.tier_resident_bytes = float(resident_bytes)
            self.tier_breakeven = breakeven

    def on_token(self, req: Request) -> None:
        with self._lock:
            self.total_generated += 1

    def on_finish(self, req: Request) -> None:
        with self._lock:
            self.n_finished += 1
            self.finish_reasons[req.finish_reason or "length"] += 1
            self._record_latencies(req)

    def on_abort(self, req: Request) -> None:
        """Request cancelled (disconnect or deadline).  Counted apart
        from ``finished`` — its TTFT still records if a token got out."""
        with self._lock:
            self.n_aborted += 1
            self.finish_reasons["aborted"] += 1
            self._record_latencies(req)

    def _record_latencies(self, req: Request) -> None:
        # caller holds the lock
        if self.slo is not None:
            # every terminal gets an SLO verdict (ok / miss / untimed)
            # — aborts are misses, recovered-without-timestamps are
            # untimed, see serve/slo.SLOPolicy.verdict
            self.slo.observe(req)
        if req.submit_time is not None and req.first_token_time is not None:
            # realtime replay records the wall arrival, so TTFT includes
            # the wait before the tick loop noticed the request; the
            # virtual clock is incommensurable with wall time, so
            # virtual-mode TTFT is based at submit
            base = req.extra.get("arrival_wall", req.submit_time)
            ttft = req.first_token_time - base
            self.ttft_s.append(ttft)
            self._trim(self.ttft_s)
            self.ttft_hist[bisect.bisect_left(TTFT_BUCKETS, ttft)] += 1
            self.ttft_hist_sum += ttft
            n_after_first = len(req.generated) - 1
            span = (req.finish_time or self.clock()) - req.first_token_time
            if n_after_first > 0 and span > 0:
                rate = n_after_first / span
                self.decode_tok_s.append(rate)
                self._trim(self.decode_tok_s)
                self.decode_hist[
                    bisect.bisect_left(DECODE_TOK_S_BUCKETS, rate)
                ] += 1
                self.decode_hist_sum += rate
        if req.submit_time is not None and req.admit_time is not None:
            self.queue_wait_s.append(req.admit_time - req.submit_time)
            self._trim(self.queue_wait_s)
        if req.prefill_s:
            self.prefill_s.append(req.prefill_s)
            self._trim(self.prefill_s)
        if req.first_emit_time is not None:
            for stage, seconds in ttft_stages(req).items():
                self.ttft_stage_s[stage].append(seconds)
                self._trim(self.ttft_stage_s[stage])

    # ------------------------------------------------------------------
    def snapshot(self) -> dict[str, Any]:
        with self._lock:
            span = (self.t_last or self.clock()) - self.t_start
            out: dict[str, Any] = {
                "submitted": self.n_submitted,
                "finished": self.n_finished,
                "aborted": self.n_aborted,
                "rejected": self.n_rejected,
                "recovered": self.n_recovered,
                "ticks": self.n_ticks,
                "preemptions": self.preemptions,
                "total_generated_tokens": self.total_generated,
                "throughput_tok_s": (
                    self.total_generated / span if span > 0 else 0.0
                ),
                "wall_s": span,
                "finish_reasons": dict(self.finish_reasons),
            }
            # copy-on-read: percentile math sees frozen lists even while
            # the tick loop keeps appending
            ttft = list(self.ttft_s)
            decode = list(self.decode_tok_s)
            qwait = list(self.queue_wait_s)
            prefill = list(self.prefill_s)
            stages = {k: list(v) for k, v in self.ttft_stage_s.items()}
            qd = [float(q) for q in self.queue_depth]
            occ = list(self.occupancy)
            act = [float(a) for a in self.active_slots]
            kvb = list(self.kv_bytes_tick)
            prefix_req = self.prefix_blocks_requested
            prefix_hit = self.prefix_blocks_hit
            tier_restore = list(self.tier_restore_s)
            out["prefix_evicted_blocks"] = self.prefix_evicted_blocks
            out["prefix_evicted_bytes"] = self.prefix_evicted_bytes
            if (self.tier_spilled_blocks or self.tier_restored_blocks
                    or self.tier_breakeven is not None):
                # reported only once a tier is attached/active (the
                # spec/SLO discipline: fabricated zeros would read as a
                # wedged tier on a fleet dashboard)
                out["tier_spilled_blocks"] = self.tier_spilled_blocks
                out["tier_spilled_bytes"] = self.tier_spilled_bytes
                out["tier_restored_blocks"] = self.tier_restored_blocks
                out["tier_restored_bytes"] = self.tier_restored_bytes
                out["tier_resident_bytes"] = self.tier_resident_bytes
                out["tier_breakeven_ratio"] = self.tier_breakeven or 0.0
            out["mixed_prefill_tokens"] = self.mixed_prefill_tokens
            out["mixed_decode_tokens"] = self.mixed_decode_tokens
            out["prefill_segments"] = self.prefill_segments
            out["attn_prefill_tiles_packed"] = self.prefill_tiles
            out["attn_prefill_tile_tokens"] = (
                self.prefill_tile_tokens / self.prefill_tiles
                if self.prefill_tiles else 0.0)
            out["lane_ticks"] = self.lane_ticks
            out["prefill_starved_rows"] = self.prefill_starved_rows
            out["mixed_dense_lanes"] = self.mixed_dense_lanes
            out["publish_overlapped_ticks"] = self.publish_overlapped
            out["publish_immediate_ticks"] = self.publish_immediate
            out["host_bound_ticks"] = self.host_bound_ticks
            if self.moe_ticks:
                # only where an expert layer ran (like the spec block)
                out["moe_ticks"] = self.moe_ticks
                out["moe_experts_touched"] = self.moe_experts_touched
                out["moe_expert_load_max"] = self.moe_load_max
                out["moe_expert_load_mean"] = self.moe_load_mean
                out["moe_pairs_held"] = self.moe_pairs_held
                out["conv_state_slots_live"] = self.conv_state_slots
            if self.class_ticks:
                # only where a pool with a window class was traced
                out["page_class_ticks"] = self.class_ticks
                out["attn_pages_global"] = self.attn_pages_global
                out["attn_pages_window"] = self.attn_pages_window
                out["attn_live_tiles"] = self.attn_live_tiles
                out["attn_prefill_tiles"] = self.attn_prefill_tiles
                out["window_blocks_recycled"] = self.window_blocks_recycled
            if self.ssm_ticks:
                # only where a state-space mixer ran
                out["ssm_ticks"] = self.ssm_ticks
                out["ssm_state_rows"] = self.ssm_state_rows
                out["ssm_scan_tokens"] = self.ssm_scan_tokens
                out["ssm_state_slots_live"] = self.conv_state_slots
                out["ssm_state_kernel"] = self.ssm_state_kernel
            if self.kda_ticks:
                # only where a delta-rule layer ran
                out["kda_ticks"] = self.kda_ticks
                out["kda_state_rows"] = self.kda_state_rows
                out["kda_scan_tokens"] = self.kda_scan_tokens
                out["kda_state_slots_live"] = self.conv_state_slots
                out["kda_state_kernel"] = self.kda_state_kernel
            if self.retention_ticks:
                # only where a power-retention layer ran
                out["retention_ticks"] = self.retention_ticks
                out["retention_state_rows"] = self.retention_state_rows
                out["retention_scan_tokens"] = self.retention_scan_tokens
                out["retention_state_slots_live"] = self.conv_state_slots
                out["retention_state_kernel"] = self.retention_state_kernel
            if self.dsa_ticks:
                # only where a sparse-attention indexer ran
                out["dsa_ticks"] = self.dsa_ticks
                out["dsa_visible"] = self.dsa_visible
                out["dsa_selected"] = self.dsa_selected
                out["dsa_dense_tokens"] = self.dsa_dense_tokens
                out["dsa_index_pages"] = self.dsa_index_pages
            if self.spec_rounds:
                # reported only once a verify round ran (like the SLO
                # block): a fabricated 0-acceptance series on a
                # non-spec engine would read as "speculation broken"
                out["spec_drafted_tokens"] = self.spec_drafted
                out["spec_accepted_tokens"] = self.spec_accepted
                out["spec_rejected_tokens"] = (
                    self.spec_drafted - self.spec_accepted
                )
                out["spec_rounds"] = self.spec_rounds
                out["spec_accept_rate"] = (
                    self.spec_accepted / self.spec_drafted
                    if self.spec_drafted else 0.0
                )
                out["spec_accept_len_mean"] = (
                    self.spec_accepted / self.spec_rounds
                )
            if self.slo is not None:
                out.update(self.slo.snapshot())
            if self.anomaly_ticks:
                out["anomaly_ticks"] = dict(self.anomaly_ticks)
            if self.lifecycle_actions:
                out["lifecycle_actions"] = dict(self.lifecycle_actions)
            # roofline telemetry: emitted only once a graded dispatch
            # ran (the spec/SLO discipline — fabricated zeros would
            # read as a broken deployment on a fleet dashboard)
            rf_gbps = list(self.roofline_gbps)
            rf_util = list(self.roofline_util)
            rf_mfu = list(self.mfu_tick)
            if self.roofline_ticks:
                out["roofline_ticks"] = self.roofline_ticks
                out["hbm_gbps"] = self.hbm_gbps
                out["kv_read_bytes_total"] = self.kv_read_bytes_total
                out["kv_write_bytes_total"] = self.kv_write_bytes_total
                out["weight_bytes_total"] = self.weight_bytes_total
                out["device_time_s_total"] = self.device_time_s_total
                out["roofline_gbps_last"] = rf_gbps[-1]
                out["roofline_util_last"] = rf_util[-1]
                out["mfu_last"] = rf_mfu[-1]
        out.update(_pcts(ttft, "ttft_s"))
        out.update(_pcts(decode, "decode_tok_s"))
        out.update(_pcts(qwait, "queue_wait_s"))
        out.update(_pcts(prefill, "prefill_s"))
        for stage, vals in stages.items():
            out.update(_pcts(vals, f"ttft_stage_{stage}_s"))
        out.update(_pcts(qd, "queue_depth"))
        out.update(_pcts(occ, "occupancy"))
        out.update(_pcts(act, "active_slots"))
        out.update(_pcts(kvb, "kv_bytes_tick"))
        out.update(_pcts(tier_restore, "tier_restore_s"))
        out.update(_pcts(rf_gbps, "roofline_gbps"))
        out.update(_pcts(rf_util, "roofline_util"))
        out.update(_pcts(rf_mfu, "mfu"))
        # *_last: the most recent per-tick sample — the live gauge a
        # scrape wants, vs the trace-wide percentiles above
        if qd:
            out["queue_depth_last"] = qd[-1]
        if occ:
            out["occupancy_last"] = occ[-1]
        if act:
            out["active_slots_last"] = act[-1]
        out["kv_bytes_total"] = float(sum(kvb))
        out["prefix_blocks_requested"] = prefix_req
        out["prefix_blocks_hit"] = prefix_hit
        if prefix_req:
            out["prefix_hit_rate"] = prefix_hit / prefix_req
        return out

    # ------------------------------------------------------------------
    def prometheus(
        self, extra_gauges: dict[str, float] | None = None,
        prefix: str = "llm_serve",
        const_labels: dict[str, str] | None = None,
        extra_counters: dict[str, float] | None = None,
    ) -> str:
        """Text exposition format (0.0.4) for a ``GET /metrics`` scrape.

        Rendered from ``snapshot()`` (so a scrape is one locked copy, no
        torn reads).  ``extra_gauges`` lets the HTTP server add live
        gauges the metrics object cannot know (current queue depth, pool
        free blocks, in-flight streams), ``extra_counters`` the same for
        counters (the threads' CPU clocks); a key may carry labels of its
        own (``pool_page_shape{shape="64x512"}``).  ``const_labels`` are
        spliced into EVERY sample's labelset — how a multi-replica server
        tags each engine's series with ``replica="N"`` so counters and
        histograms aggregate across the fleet.
        """
        s = self.snapshot()
        lines: list[str] = []
        const = ",".join(
            f'{k}="{v}"' for k, v in (const_labels or {}).items()
        )

        def lab(labels: str) -> str:
            if not const:
                return labels
            if not labels:
                return "{" + const + "}"
            return labels[:-1] + "," + const + "}"

        def emit(name: str, mtype: str, help_: str,
                 samples: list[tuple[str, float]]) -> None:
            full = f"{prefix}_{name}"
            lines.append(f"# HELP {full} {help_}")
            lines.append(f"# TYPE {full} {mtype}")
            for labels, value in samples:
                lines.append(f"{full}{lab(labels)} {value:.10g}")

        emit("requests_submitted_total", "counter",
             "Requests accepted into the scheduler queue",
             [("", s["submitted"])])
        emit("requests_finished_total", "counter",
             "Requests that ran to a natural finish",
             [("", s["finished"])])
        emit("requests_aborted_total", "counter",
             "Requests cancelled (client disconnect or deadline)",
             [("", s["aborted"])])
        emit("requests_rejected_total", "counter",
             "Submits bounced off the queue-depth cap (HTTP 429)",
             [("", s["rejected"])])
        emit("requests_recovered_total", "counter",
             "In-flight requests replayed into a rebuilt engine after a "
             "supervised restart",
             [("", s["recovered"])])
        emit("finish_total", "counter",
             "Terminal events by finish reason",
             [(f'{{reason="{r}"}}', n)
              for r, n in sorted(s["finish_reasons"].items())] or
             [('{reason="stop"}', 0)])
        emit("preemptions_total", "counter",
             "Evict-on-OOM requeues", [("", s["preemptions"])])
        emit("tokens_generated_total", "counter",
             "Generated tokens across all requests",
             [("", s["total_generated_tokens"])])
        emit("ticks_total", "counter",
             "Scheduler ticks", [("", s["ticks"])])
        emit("queue_depth", "gauge",
             "Requests waiting for admission (last tick sample)",
             [("", s.get("queue_depth_last", 0.0))])
        emit("pool_occupancy", "gauge",
             "Fraction of allocatable KV blocks held (last tick sample)",
             [("", s.get("occupancy_last", 0.0))])
        emit("active_slots", "gauge",
             "Decode slots busy (last tick sample)",
             [("", s.get("active_slots_last", 0.0))])
        emit("prefix_hit_rate", "gauge",
             "Prompt blocks reused from the prefix cache / shareable "
             "blocks requested",
             [("", s.get("prefix_hit_rate", 0.0))])
        emit("prefix_evicted_total", "counter",
             "Prefix-cache blocks LRU-reclaimed under pool pressure "
             "(spilled to the host tier when --kv-tier host, dropped "
             "otherwise)",
             [("", s["prefix_evicted_blocks"])])
        # -- host-RAM KV tier (only once a tier is attached — constant
        # zeros would read as a wedged tier on a fleet dashboard)
        if "tier_spilled_blocks" in s:
            emit("kv_tier_blocks_total", "counter",
                 "Host-tier block flow: spill = evicted prefix blocks "
                 "copied to host RAM, restore = blocks staged back as "
                 "pool blocks instead of re-prefilling",
                 [('{op="spill"}', s["tier_spilled_blocks"]),
                  ('{op="restore"}', s["tier_restored_blocks"])])
            emit("kv_tier_bytes_total", "counter",
                 "Host-tier byte flow (the restored-bytes ledger is "
                 "prefill work the tier saved)",
                 [('{op="spill"}', s["tier_spilled_bytes"]),
                  ('{op="restore"}', s["tier_restored_bytes"])])
            emit("kv_tier_resident_bytes", "gauge",
                 "Host RAM currently holding spilled KV blocks",
                 [("", s["tier_resident_bytes"])])
            emit("kv_tier_breakeven_ratio", "gauge",
                 "Measured restore-vs-recompute breakeven (re-prefill "
                 "seconds per block / restore seconds per block; >1 = "
                 "restoring is cheaper; 0 = not yet measured)",
                 [("", s["tier_breakeven_ratio"])])
        emit("kv_bytes_tick_mean", "gauge",
             "Mean K/V bytes decode attention touches per tick",
             [("", s.get("kv_bytes_tick_mean", 0.0))])
        emit("mixed_tokens_total", "counter",
             "Unified-tick token budget spent, split by work kind",
             [('{kind="prefill"}', s["mixed_prefill_tokens"]),
              ('{kind="decode"}', s["mixed_decode_tokens"])])
        emit("prefill_segments_total", "counter",
             "Prefill segments planned: one a mid-prefill row a tick",
             [("", s["prefill_segments"])])
        emit("prefill_segment_tokens_total", "counter",
             "Prompt tokens in those segments (mixed_tokens_total's "
             "prefill kind without a label, so that a ratio of counter "
             "deltas reads it): tokens / segments is what a row gets of "
             "one tick",
             [("", s["mixed_prefill_tokens"])])
        emit("attn_prefill_tile_tokens", "gauge",
             "Mean tokens a query tile of more than one token held (a "
             "prompt chunk's, a verify slice's): 8 lanes a tile at most, "
             "or the wide tile's where the pages have one",
             [("", s["attn_prefill_tile_tokens"])])
        emit("attn_prefill_tiles_packed_total", "counter",
             "Query tiles the packer laid with more than one token",
             [("", s["attn_prefill_tiles_packed"])])
        emit("lane_ticks_total", "counter",
             "Dispatching ticks that handed a mid-prefill row more than "
             "its fair share of the prompt lane (min(chunk, remaining)): "
             "lane_ticks_total / ticks_total is how busy the lane was",
             [("", s["lane_ticks"])])
        emit("prefill_starved_rows_total", "counter",
             "Mid-prefill rows a tick granted no prompt token, summed "
             "over ticks (the budget ran out before their fair share)",
             [("", s["prefill_starved_rows"])])
        emit("mixed_dense_lanes_total", "counter",
             "Lanes of the unified step's dense token axis, summed over "
             "dispatches (mixed_tokens_total / this = the share of "
             "lanes that hold a token)",
             [("", s["mixed_dense_lanes"])])
        emit("publish_overlapped_ticks_total", "counter",
             "Unified ticks whose tokens were handed out behind the next "
             "tick's dispatch (off the device's critical path)",
             [("", s["publish_overlapped_ticks"])])
        emit("publish_immediate_ticks_total", "counter",
             "Unified ticks whose tokens were handed out on the spot "
             "(no dispatch followed)",
             [("", s["publish_immediate_ticks"])])
        emit("host_bound_ticks_total", "counter",
             "Unified ticks at whose fetch the device had already "
             "finished: the host, not the device step, set their length "
             "(counted while a trace recorder is attached)",
             [("", s["host_bound_ticks"])])
        if "moe_ticks" in s:
            emit("moe_ticks_total", "counter",
                 "Dispatching ticks that ran dropless expert layers",
                 [("", s["moe_ticks"])])
            emit("moe_experts_touched_total", "counter",
                 "Experts that got at least one token, summed over the "
                 "expert layers and over ticks",
                 [("", s["moe_experts_touched"])])
            emit("moe_expert_load_total", "counter",
                 "Tokens an expert of the most loaded layer got, summed "
                 "over ticks: the most loaded expert and the mean "
                 "(max / mean is the skew a grouped matmul pays for)",
                 [('{kind="max"}', s["moe_expert_load_max"]),
                  ('{kind="mean"}', s["moe_expert_load_mean"])])
            emit("moe_pairs_held_total", "counter",
                 "(token, expert) pairs whose expert this engine holds, "
                 "summed over the expert layers and over ticks",
                 [("", s["moe_pairs_held"])])
            emit("conv_state_slots_live", "gauge",
                 "Slots whose short-convolution state is live",
                 [("", s["conv_state_slots_live"])])
        if "page_class_ticks" in s:
            emit("page_class_ticks_total", "counter",
                 "Dispatching ticks of a pool with a window class "
                 "(counted while a trace recorder is attached)",
                 [("", s["page_class_ticks"])])
            emit("attn_pages_streamed_total", "counter",
                 "Pages ONE layer of a kind streams, summed over the "
                 "live query tiles (a tile re-reads its row's pages) "
                 "and over ticks",
                 [('{kind="global"}', s["attn_pages_global"]),
                  ('{kind="window"}', s["attn_pages_window"])])
            emit("attn_query_tiles_total", "counter",
                 "Live query tiles of the dispatches, and those of them "
                 "that hold more than one token (a prefill chunk's)",
                 [('{kind="live"}', s["attn_live_tiles"]),
                  ('{kind="prefill"}', s["attn_prefill_tiles"])])
            emit("window_ring_recycled_blocks_total", "counter",
                 "Window-class blocks the dispatches' rows let go (their "
                 "ring entries are written next), summed over ticks",
                 [("", s["window_blocks_recycled"])])
        if "ssm_ticks" in s:
            emit("ssm_ticks_total", "counter",
                 "Dispatching ticks that ran state-space mixers",
                 [("", s["ssm_ticks"])])
            emit("ssm_state_rows_total", "counter",
                 "Rows whose recurrent state a dispatch read and wrote "
                 "(every state-space layer's), summed over ticks",
                 [("", s["ssm_state_rows"])])
            emit("ssm_scan_tokens_total", "counter",
                 "Live tokens through the state-space scan, summed over "
                 "ticks",
                 [("", s["ssm_scan_tokens"])])
            emit("ssm_state_slots_live", "gauge",
                 "Slots whose recurrent state is live",
                 [("", s["ssm_state_slots_live"])])
            emit("ssm_state_kernel", "gauge",
                 "1 where the Pallas state-update kernel advances the "
                 "rows a tick touches, 0 where the compiler's passes "
                 "advance every row",
                 [("", s["ssm_state_kernel"])])
        if "kda_ticks" in s:
            emit("kda_ticks_total", "counter",
                 "Dispatching ticks that ran delta-rule linear-attention "
                 "layers",
                 [("", s["kda_ticks"])])
            emit("kda_state_rows_total", "counter",
                 "Rows whose matrix state a dispatch read and wrote (every "
                 "delta-rule layer's), summed over ticks",
                 [("", s["kda_state_rows"])])
            emit("kda_scan_tokens_total", "counter",
                 "Live tokens through the delta-rule recurrence, summed "
                 "over ticks",
                 [("", s["kda_scan_tokens"])])
            emit("kda_state_slots_live", "gauge",
                 "Slots whose matrix state is live",
                 [("", s["kda_state_slots_live"])])
            emit("kda_state_kernel", "gauge",
                 "1 where the Pallas state-update kernel advances the "
                 "rows a tick touches, 0 where its twin advances every row",
                 [("", s["kda_state_kernel"])])
        if "retention_ticks" in s:
            emit("retention_ticks_total", "counter",
                 "Dispatching ticks that ran power-retention layers",
                 [("", s["retention_ticks"])])
            emit("retention_state_rows_total", "counter",
                 "Rows whose power-retention state a dispatch read and "
                 "wrote (every layer's), summed over ticks",
                 [("", s["retention_state_rows"])])
            emit("retention_scan_tokens_total", "counter",
                 "Live tokens through the power-retention recurrence, "
                 "summed over ticks",
                 [("", s["retention_scan_tokens"])])
            emit("retention_state_slots_live", "gauge",
                 "Slots whose power-retention state is live",
                 [("", s["retention_state_slots_live"])])
            emit("retention_state_kernel", "gauge",
                 "1 where the Pallas state-update kernel advances the "
                 "rows a tick touches, 0 where its twin advances every row",
                 [("", s["retention_state_kernel"])])
        if "dsa_ticks" in s:
            emit("dsa_ticks_total", "counter",
                 "Dispatching ticks that ran a sparse-attention indexer",
                 [("", s["dsa_ticks"])])
            emit("dsa_visible_total", "counter",
                 "Positions the dispatched tokens may see (one layer's), "
                 "summed over tokens and ticks",
                 [("", s["dsa_visible"])])
            emit("dsa_selected_total", "counter",
                 "Positions the dispatched tokens attend after the "
                 "indexer's selection (one layer's), summed likewise",
                 [("", s["dsa_selected"])])
            emit("dsa_dense_tokens_total", "counter",
                 "Dispatched tokens that see no more than index_topk "
                 "positions and attend all of them",
                 [("", s["dsa_dense_tokens"])])
            emit("dsa_index_pages_total", "counter",
                 "Index-key pages one layer's scores read, summed over "
                 "query tiles and ticks",
                 [("", s["dsa_index_pages"])])
        # -- speculative decoding (only once a verify round ran — a
        # constant-zero series on a plain engine would read as a broken
        # speculation deployment on a fleet dashboard)
        if "spec_drafted_tokens" in s:
            emit("spec_tokens_total", "counter",
                 "Speculative draft tokens by verify outcome",
                 [('{kind="drafted"}', s["spec_drafted_tokens"]),
                  ('{kind="accepted"}', s["spec_accepted_tokens"]),
                  ('{kind="rejected"}', s["spec_rejected_tokens"])])
            emit("spec_accept_rate", "gauge",
                 "Accepted / drafted speculative tokens over the "
                 "traffic span",
                 [("", s["spec_accept_rate"])])
        emit("throughput_tok_s", "gauge",
             "Generated tokens per second over the traffic span",
             [("", s["throughput_tok_s"])])
        # -- SLO goodput accounting (only when a policy is attached:
        # series that are always 0-with-no-policy would read as "a
        # perfect SLO" on a dashboard that aggregates the fleet)
        if "slo_ok" in s:
            emit("goodput_tok_s", "gauge",
                 "SLO-attaining tokens per second over the traffic span "
                 "(tokens of requests that met every latency target)",
                 [("", s["goodput_tok_s"])])
            if "slo_attainment" in s:
                # omitted (not defaulted) until a timed verdict exists:
                # a fabricated 1.0 would read as a perfect SLO
                emit("slo_attainment", "gauge",
                     "Fraction of timed terminal requests meeting the "
                     "SLO",
                     [("", s["slo_attainment"])])
            emit("slo_requests_total", "counter",
                 "Terminal requests by SLO verdict (untimed = recovered "
                 "with no surviving timestamps; excluded from attainment)",
                 [('{verdict="ok"}', s["slo_ok"]),
                  ('{verdict="miss"}', s["slo_miss"]),
                  ('{verdict="untimed"}', s["slo_untimed"])])
            burn = [
                (f'{{window="{k[len("slo_burn_rate_"):]}"}}', s[k])
                for k in sorted(s) if k.startswith("slo_burn_rate_")
            ]
            if burn:
                emit("slo_burn_rate", "gauge",
                     "Error-budget burn rate per window (observed miss "
                     "rate / budgeted miss rate; >1 = overspending)",
                     burn)
        # -- device roofline telemetry (only once a graded dispatch ran
        # — serve/telemetry.py; constant zeros would read as a stalled
        # device on a fleet dashboard)
        if "roofline_ticks" in s:
            emit("device_bytes_total", "counter",
                 "Modeled HBM traffic by kind (analytic byte model, "
                 "serve/telemetry.py)",
                 [('{kind="kv_read"}', s["kv_read_bytes_total"]),
                  ('{kind="kv_write"}', s["kv_write_bytes_total"]),
                  ('{kind="weight"}', s["weight_bytes_total"])])
            emit("device_time_seconds_total", "counter",
                 "Measured dispatch-to-host-sync wall attributed to "
                 "device work",
                 [("", s["device_time_s_total"])])
            emit("roofline_gbps", "gauge",
                 "Achieved GB/s of the last graded dispatch (modeled "
                 "bytes / measured wall)",
                 [("", s["roofline_gbps_last"])])
            emit("roofline_util", "gauge",
                 "Achieved GB/s over the --hbm-gbps roofline, last "
                 "graded dispatch",
                 [("", s["roofline_util_last"])])
            emit("mfu", "gauge",
                 "Model FLOP utilization estimate, last graded dispatch",
                 [("", s["mfu_last"])])
            emit("hbm_gbps_target", "gauge",
                 "The HBM roofline utilization is graded against",
                 [("", s["hbm_gbps"] or 0.0)])
        if s.get("anomaly_ticks"):
            emit("anomaly_ticks_total", "counter",
                 "Ticks where the sentinel flagged this phase as an "
                 "outlier vs its rolling baseline",
                 [(f'{{phase="{p}"}}', n)
                  for p, n in sorted(s["anomaly_ticks"].items())])
        if s.get("lifecycle_actions"):
            emit("lifecycle_actions_total", "counter",
                 "Fleet lifecycle events: auto-action flips "
                 "(shed_prefill/shed_load on/off), rolled replicas, "
                 "elastic add/remove",
                 [(f'{{action="{a}"}}', n)
                  for a, n in sorted(s["lifecycle_actions"].items())])
        # -- real histograms: cumulative _bucket/_sum/_count from the
        # incrementally-maintained counters (exact forever, unlike the
        # trimmed percentile windows; aggregable across replicas)
        with self._lock:
            ttft_hist = list(self.ttft_hist)
            ttft_hist_sum = self.ttft_hist_sum
            decode_hist = list(self.decode_hist)
            decode_hist_sum = self.decode_hist_sum
            spec_hist = list(self.spec_hist)
            spec_hist_sum = self.spec_hist_sum
            spec_rounds = self.spec_rounds
            util_hist = list(self.util_hist)
            util_hist_sum = self.util_hist_sum
            roofline_ticks = self.roofline_ticks

        def emit_hist(name: str, help_: str, buckets: tuple,
                      counts: list[int], total: float) -> None:
            full = f"{prefix}_{name}"
            lines.append(f"# HELP {full} {help_}")
            lines.append(f"# TYPE {full} histogram")
            cum = 0
            for le, n in zip(buckets, counts):
                cum += n
                labels = lab('{le="%.10g"}' % le)
                lines.append(f"{full}_bucket{labels} {cum}")
            cum += counts[-1]
            labels = lab('{le="+Inf"}')
            lines.append(f"{full}_bucket{labels} {cum}")
            lines.append(f"{full}_sum{lab('')} {total:.10g}")
            lines.append(f"{full}_count{lab('')} {cum}")

        emit_hist("ttft_seconds",
                  "Submit/arrival to first token, per request",
                  TTFT_BUCKETS, ttft_hist, ttft_hist_sum)
        emit_hist("decode_tok_s",
                  "Per-request steady decode rate (tokens after the "
                  "first / time after first token)",
                  DECODE_TOK_S_BUCKETS, decode_hist, decode_hist_sum)
        if spec_rounds:
            emit_hist("spec_accept_length",
                      "Accepted draft tokens per speculative verify "
                      "round",
                      SPEC_ACCEPT_BUCKETS, spec_hist, spec_hist_sum)
        if roofline_ticks:
            emit_hist("roofline_util_hist",
                      "Roofline utilization per graded dispatch "
                      "(achieved GB/s over --hbm-gbps)",
                      ROOFLINE_UTIL_BUCKETS, util_hist, util_hist_sum)

        # -- trace-wide quantile gauges alongside the histograms (the
        # single-process view; percentile windows, see max_samples) and
        # the per-request splits, no trace file needed: the wait for a
        # slot, the prefill cost share, and below them the stage family
        quantiles = (("0.5", "p50"), ("0.9", "p90"), ("0.99", "p99"))
        for base, help_ in (
            ("ttft_s", "TTFT quantiles over the recorded window"),
            ("decode_tok_s",
             "Decode-rate quantiles over the recorded window"),
            ("queue_wait_s",
             "The wait for a decode slot per request: the tick thread's "
             "take of the command to first admission (the wait for the "
             "running tick is ttft_stage inbox_wait)"),
            ("prefill_s",
             "A request's token share of its prefill ticks' dispatch + "
             "sync wall, re-prefills included: a cost share, not a "
             "latency (see ttft_stage_seconds_quantile)"),
            ("tier_restore_s",
             "Host-tier restore staging latency per restored span"),
            ("roofline_gbps",
             "Achieved-GB/s quantiles over the recorded dispatch "
             "window"),
            ("roofline_util",
             "Roofline-utilization quantiles over the recorded "
             "dispatch window"),
        ):
            samples = [(f'{{quantile="{q}"}}', s[f"{base}_{p}"])
                       for q, p in quantiles if f"{base}_{p}" in s]
            if samples:
                emit(f"{base}_quantile", "gauge", help_, samples)
        samples = [(f'{{stage="{stage}",quantile="{q}"}}',
                    s[f"ttft_stage_{stage}_s_{p}"])
                   for stage in TTFT_STAGES for q, p in quantiles
                   if f"ttft_stage_{stage}_s_{p}" in s]
        if samples:
            emit("ttft_stage_seconds_quantile", "gauge",
                 "A request's way to its first token in consecutive "
                 "stages on the engine clock: parse (socket accept to "
                 "the tick thread's inbox), inbox_wait (the running "
                 "tick), slot_wait (= queue_wait_s), lane_wait "
                 "(admission to the prompt lane), prefill (lane to the "
                 "last chunk's tick), final_tick (that tick to the "
                 "accept), publish_lag (accept to the emit)", samples)
        for kind, extras in (("gauge", extra_gauges),
                             ("counter", extra_counters)):
            series: dict[str, list[tuple[str, float]]] = {}
            for key, value in (extras or {}).items():
                # ``name{label="x"}``: a sample with labels of its own
                # (one series a name, however many labelsets it has)
                name, brace, labels = key.partition("{")
                series.setdefault(name, []).append(
                    (brace + labels, float(value)))
            for name, samples in series.items():
                emit(name, kind, f"Live server {kind}", samples)
        return "\n".join(lines) + "\n"

    def format(self) -> str:
        """One operator-readable block (the CLI prints this)."""
        s = self.snapshot()

        def g(key: str, fmt: str = "{:.3f}") -> str:
            return fmt.format(s[key]) if key in s else "-"

        mb_tick = (
            f"{s['kv_bytes_tick_mean'] / 2**20:.2f}"
            if "kv_bytes_tick_mean" in s else "-"
        )
        prefix = (
            f"{s['prefix_hit_rate']:.2f} "
            f"({s['prefix_blocks_hit']}/{s['prefix_blocks_requested']} blocks)"
            if "prefix_hit_rate" in s else "-"
        )
        aborts = (
            f", {s['aborted']} aborted" if s["aborted"] else ""
        ) + (
            f", {s['rejected']} rejected" if s["rejected"] else ""
        )
        spec = (
            f"\nspeculative: {s['spec_accept_rate']:.2f} accept rate "
            f"({s['spec_accepted_tokens']}/{s['spec_drafted_tokens']} "
            f"drafts over {s['spec_rounds']} rounds, "
            f"mean accept len {s['spec_accept_len_mean']:.2f})"
            if "spec_drafted_tokens" in s else ""
        )
        tier = (
            f"\nkv tier: {s['tier_restored_blocks']} blocks restored "
            f"({s['tier_restored_bytes'] / 2**20:.2f} MiB of prefill "
            f"saved), {s['tier_spilled_blocks']} spilled, "
            f"{s['prefix_evicted_blocks']} evictions, breakeven "
            f"{s['tier_breakeven_ratio']:.2f}"
            if "tier_spilled_blocks" in s else ""
        )
        roofline = (
            f"\nroofline: {s['roofline_gbps_mean']:.2f} GB/s mean "
            f"({s['roofline_util_mean']:.2%} of {s['hbm_gbps']:g} GB/s, "
            f"p99 util {s.get('roofline_util_p99', 0.0):.2%}, "
            f"mfu {s['mfu_mean']:.4%}) over {s['roofline_ticks']} "
            "graded dispatches"
            if "roofline_ticks" in s else ""
        )
        return (
            f"requests: {s['submitted']} submitted, {s['finished']} finished"
            f"{aborts}, "
            f"{s['preemptions']} preemptions over {s['ticks']} ticks\n"
            f"throughput: {s['throughput_tok_s']:.1f} tok/s total "
            f"({s['total_generated_tokens']} tokens in {s['wall_s']:.2f}s)\n"
            f"ttft_s      p50 {g('ttft_s_p50')}  p90 {g('ttft_s_p90')}  "
            f"p99 {g('ttft_s_p99')}\n"
            f"queue_wait_s p50 {g('queue_wait_s_p50')}  "
            f"p99 {g('queue_wait_s_p99')}; "
            f"prefill_s p50 {g('prefill_s_p50')}  "
            f"p99 {g('prefill_s_p99')}\n"
            "first token by stage, p50 s: " + "  ".join(
                f"{stage} {g(f'ttft_stage_{stage}_s_p50', '{:.4f}')}"
                for stage in TTFT_STAGES) + "\n"
            f"decode_tok_s p50 {g('decode_tok_s_p50', '{:.1f}')}  "
            f"p90 {g('decode_tok_s_p90', '{:.1f}')}\n"
            f"queue_depth p50 {g('queue_depth_p50', '{:.1f}')}  "
            f"p99 {g('queue_depth_p99', '{:.1f}')}; "
            f"occupancy p50 {g('occupancy_p50', '{:.2f}')}  "
            f"p99 {g('occupancy_p99', '{:.2f}')}; "
            f"active_slots mean {g('active_slots_mean', '{:.2f}')}\n"
            f"kv MiB/tick mean {mb_tick}; prefix cache hit rate {prefix}"
            f"{spec}{tier}{roofline}"
        )
