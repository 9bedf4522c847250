"""Continuous-batching serving engine over a paged KV block pool.

The layers below this package are batch-job shaped: ``Generator`` takes
one fixed batch and sizes a contiguous cache slab per call.  Serving
"heavy traffic from millions of users" (ROADMAP north star) needs the
request level instead: a queue, admission control, and a shared KV pool
whose granularity is a *block*, not a whole request — the design argued
by *Ragged Paged Attention* (PAPERS.md) for TPU inference.

Modules:
- ``block_pool``  — fixed-size KV blocks in one preallocated slab per
  layer, a free-list allocator, per-request block tables (int8 blocks
  reuse quant.quantize_kv/dequantize_kv).
- ``scheduler``   — continuous batching: admit queued requests into
  decode slots as others finish, evict-on-OOM with requeue; pure
  Python/NumPy, so policies are testable without a model.
- ``engine``      — ``ServeEngine``: jit-stable prefill/decode steps
  over the packed active batch with per-request streaming callbacks;
  decode K/V access is gathered through block tables (``"xla"``/
  ``"flash_decode"``) or zero-gather via the block-table-native Pallas
  kernel (``"paged"``).
- ``prefix_cache`` — refcounted prompt-prefix block sharing: chained
  content hashes → pool block ids, claimed at admission so matching
  prefill chunks are skipped entirely.
- ``spec``        — host-side draft streams for speculative serving
  (``DraftState``: prompt-lookup n-gram drafting over each request's
  own token history); the unified tick packs the drafts as ragged
  verify slices into its ONE dispatch and accepts the longest prefix
  matching the deterministic (seed, content-pos) samples — accepted
  streams are token-identical to plain decode.
- ``faults``      — deterministic, seeded fault injection
  (``FaultInjector``): chaos specs schedule decode/prefill faults, hung
  or crashed ticks, transient checkpoint IO errors, and HTTP
  resets/429s through injection points threaded across the stack;
  no-op (one is-None check) by default.
- ``metrics``     — queue depth, TTFT, per-request decode tok/s, pool
  occupancy, preemptions, aborts/rejects, prefix hit-rate, K/V bytes per
  tick, per-request queue-wait/prefill phase splits; exported as a dict
  and as Prometheus text with real TTFT/decode-rate histograms
  (thread-safe copy-on-read snapshots — the HTTP scrape handler reads
  while the engine thread writes).
- ``tracing``     — request-lifecycle spans (queued → prefill → decode
  → finish, with eviction/recovery annotations) and per-tick phase
  slices as Chrome/Perfetto trace-event JSON (``TraceRecorder``);
  zero-overhead is-None hooks when off, ring-buffered for the
  ``GET /debug/trace`` endpoint, dumped via ``--trace-out``.
- ``journal``     — durable request journal (``RequestJournal``):
  admissions, per-tick delivery watermarks, and terminals CRC-framed
  and fsync'd off the tick thread; a killed process (``kill -9``, OOM,
  rolling deploy) replays unterminated requests token-identically on
  restart, and clients resume dropped SSE streams via
  ``Last-Event-ID``; zero-overhead is-None hooks when off.
- ``slo``         — SLO goodput accounting (``SLOPolicy``/
  ``SLOTracker``: attainment, goodput_tok_s, multi-window error-budget
  burn rates) and the ``TickSentinel`` per-phase anomaly detector;
  zero-overhead is-None hooks when off.
- ``telemetry``   — device roofline telemetry (``TelemetryModel``): an
  analytic per-tick byte/FLOP model (weights streamed per dispatch, KV
  read/written from the planned tick composition, int8-aware) combined
  with the measured dispatch wall → achieved GB/s, utilization vs the
  HBM roofline, an MFU estimate, and per-request cost attribution
  (exact KV bytes + token-share of weights/device time, conserving);
  zero-overhead is-None hooks when off.
- ``otel``        — stdlib OTLP/HTTP JSON span export
  (``OtlpExporter``): converts ``TraceRecorder`` events to OTLP
  ResourceSpans and ships them off-thread to a collector, batched,
  drop-and-count on failure.
- ``request_log`` — the canonical request log (``RequestLog``): one
  wide-event JSON line per terminal request (trace id, route, prefix
  reuse, survival lineage, per-phase latencies, SLO verdict), written
  off the tick thread with the journal's writer discipline.
- ``tenants``     — multi-tenant accounting (``TenantLedger``):
  per-tenant request/token/device-cost totals, per-tenant SLO burn,
  fair-share prefill ordering, per-tenant in-flight caps, and
  bounded-cardinality tenant-labeled Prometheus series; ``X-Tenant-Id``
  identities normalized through ``normalize_tenant``; zero-overhead
  is-None hooks when off.
- ``replica``     — mesh-scale-out: ``ReplicaSet``/``ReplicaRunner``
  run N data-parallel engine replicas (each optionally TP-sharded via
  ``ServeEngine(mesh_plan=...)`` on its own mesh slice) behind a
  ``PrefixRouter`` that keys on the prefix cache's chained content
  hash, so shared-prompt traffic lands on the replica already holding
  its blocks; spill-to-least-loaded under queue pressure, per-replica
  abort/drain/supervised recovery.
- ``lifecycle``   — zero-downtime fleet operations: rolling checkpoint
  upgrades (drain-to-peer, clone_fresh on new weights, compiled steps
  re-jitted once per fleet, per-request weight-version tagging),
  elastic add/remove replicas with an optional ``Autoscaler`` policy,
  and the ``ActionPolicy`` closing the loop from sentinel/SLO signals
  to shed-prefill and 503-first load-shedding auto-actions.
- ``http``        — the OpenAI-compatible streaming HTTP front-end
  (``serve`` CLI subcommand): SSE token streams, abort on disconnect or
  deadline, 429 backpressure off the scheduler's queue cap, Prometheus
  ``/metrics``, SIGTERM drain.
"""

from llm_np_cp_tpu.serve.block_pool import BlockPool, FreeList
from llm_np_cp_tpu.serve.faults import FaultInjected, FaultInjector
from llm_np_cp_tpu.serve.engine import (
    ServeEngine,
    pool_geometry,
    worst_case_slots,
)
from llm_np_cp_tpu.serve.journal import RequestJournal, scan_journal
from llm_np_cp_tpu.serve.lifecycle import (
    ActionPolicy,
    Autoscaler,
    LifecycleController,
    UpgradeAborted,
)
from llm_np_cp_tpu.serve.metrics import ServeMetrics
from llm_np_cp_tpu.serve.otel import OtlpExporter
from llm_np_cp_tpu.serve.prefix_cache import PrefixCache, prefix_block_keys
from llm_np_cp_tpu.serve.request_log import RequestLog, read_request_log
from llm_np_cp_tpu.serve.slo import (
    SLOPolicy,
    SLOTracker,
    TickSentinel,
    aggregate_slo,
)
from llm_np_cp_tpu.serve.replica import (
    PrefixRouter,
    ReplicaRunner,
    ReplicaSet,
)
from llm_np_cp_tpu.serve.scheduler import (
    QueueFull,
    Request,
    RequestState,
    Scheduler,
    TenantThrottled,
)
from llm_np_cp_tpu.serve.spec import DraftState
from llm_np_cp_tpu.serve.telemetry import TelemetryModel
from llm_np_cp_tpu.serve.tenants import (
    TenantLedger,
    aggregate_tenants,
    normalize_tenant,
)
from llm_np_cp_tpu.serve.trace import poisson_trace
from llm_np_cp_tpu.serve.tracing import TraceRecorder

__all__ = [
    "ActionPolicy",
    "Autoscaler",
    "BlockPool",
    "DraftState",
    "LifecycleController",
    "UpgradeAborted",
    "FaultInjected",
    "FaultInjector",
    "FreeList",
    "OtlpExporter",
    "PrefixCache",
    "PrefixRouter",
    "QueueFull",
    "ReplicaRunner",
    "ReplicaSet",
    "Request",
    "RequestJournal",
    "RequestLog",
    "RequestState",
    "SLOPolicy",
    "SLOTracker",
    "Scheduler",
    "ServeEngine",
    "ServeMetrics",
    "TelemetryModel",
    "TenantLedger",
    "TenantThrottled",
    "TickSentinel",
    "TraceRecorder",
    "aggregate_slo",
    "aggregate_tenants",
    "normalize_tenant",
    "poisson_trace",
    "pool_geometry",
    "prefix_block_keys",
    "read_request_log",
    "scan_journal",
    "worst_case_slots",
]
