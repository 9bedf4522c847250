"""R3 — thread-affinity: an ownership checker over ``serve/``.

The serve stack's threading contract (serve/http/server.py module
docstring): the ENGINE THREAD owns the ``ServeEngine`` and everything
under it — scheduler queues, the block pool free list — exclusively, so
none of it is locked; the asyncio EVENT LOOP owns the HTTP handlers and
talks to the engine only through the command queue; the SUPERVISOR
watchdog owns crash/hang handling.  Cross-thread state (metrics
counters, the runner's replay ledger) is lock-protected.

The rule makes that contract machine-checked, seeded from the
annotation tables below (precise, not heuristic):

- ``DOMAIN_TABLE`` assigns every function a domain (``engine`` /
  ``loop`` / ``supervisor`` / ``shared`` / ``router`` / ``journal``)
  by (file, qualname) glob — first match wins.  A linted module may
  extend/override with a module-level ``LINT_THREAD_DOMAINS =
  {"Qualname.glob": "domain"}`` literal (how the bite fixture declares
  itself).
- ``DOMAIN_OWNED`` lists domain-owned attributes by dotted-chain
  suffix: engine-thread state (scheduler queues, pool pages), the
  PrefixRouter's routing state (the ROADMAP router-ownership domain —
  loop-owned in HTTP mode, engine-owned in direct mode, so ALL
  mutations must go through the router's own methods), and the journal
  writer thread's file/mirror state.  MUTATING one (assign/augassign/
  del, mutator method calls, subscript stores) from outside its owning
  domain is a finding.  Plain reads are deliberately not flagged: the
  stack's benign racy reads (queue depth gauges for scrapes/routing)
  are part of the documented design.
- ``LOCK_STATE`` lists lock-protected attribute groups.  Mutating one
  outside a ``with <base>.<lock>:`` block is a finding unless the
  function is in the group's ``lock_assumed`` set ("caller holds the
  lock" helpers) or is the constructor.  Modules may declare
  ``LINT_LOCKED_STATE = {"Class": {"lock": "_lock", "attrs": [...]}}``.
"""

from __future__ import annotations

import ast
import fnmatch

from tools.lint.core import Finding, SourceFile, attr_chain, walk_within

RULE_ID = "R3"

# (path suffix glob, qualname glob, domain) — first match wins
DOMAIN_TABLE: tuple[tuple[str, str, str], ...] = (
    ("serve/http/server.py", "EngineRunner._loop*", "engine"),
    ("serve/http/server.py", "EngineRunner._exec*", "engine"),
    ("serve/http/server.py", "EngineRunner._run", "engine"),
    ("serve/http/server.py", "EngineRunner._rebuild_and_replay*", "engine"),
    ("serve/http/server.py", "EngineRunner._replay_one", "engine"),
    ("serve/http/server.py", "EngineRunner._finish_replayed", "engine"),
    ("serve/http/server.py", "EngineRunner._stash_resumable", "engine"),
    ("serve/http/server.py", "EngineRunner._bridge*", "engine"),
    ("serve/http/server.py", "EngineRunner._next_handback", "engine"),
    ("serve/http/server.py", "EngineRunner._watch", "supervisor"),
    ("serve/http/server.py", "EngineRunner._on_engine_death", "supervisor"),
    ("serve/http/server.py", "EngineRunner._terminal_crash", "supervisor"),
    ("serve/http/server.py", "*", "loop"),
    ("serve/http/*.py", "*", "loop"),
    # the journal WRITER THREAD owns the file handle + compaction
    # mirror; everything else in serve/journal.py runs on the engine
    # tick thread (the enqueue-side hooks)
    ("serve/journal.py", "RequestJournal._writer*", "journal"),
    ("serve/journal.py", "*", "engine"),
    # the request-log WRITER THREAD owns its file handle (same shape as
    # the journal: engine-side hooks only enqueue under the lock)
    ("serve/request_log.py", "RequestLog._writer*", "reqlog"),
    ("serve/request_log.py", "*", "engine"),
    # the host-RAM KV tier's WRITER THREAD owns the host block store
    # (spills insert, capacity evicts, restores read/stage); the
    # enqueue side runs from whatever thread holds the engine (tick
    # thread, fleet drain on loop/supervisor threads), so the job
    # queue, completion map and counters are lock-protected shared
    ("serve/host_tier.py", "HostTier._writer*", "host_tier"),
    ("serve/host_tier.py", "*", "engine"),
    # the OTLP exporter's WRITER THREAD owns the open-span map and the
    # HTTP plumbing; offer() is called from WHATEVER thread holds the
    # recorder (engine tick, event loop, supervisor), so the enqueue
    # side is shared and everything it touches is lock-protected
    ("serve/otel.py", "OtlpExporter._writer*", "otel"),
    ("serve/otel.py", "OtlpExporter._convert", "otel"),
    ("serve/otel.py", "OtlpExporter._span_from", "otel"),
    ("serve/otel.py", "OtlpExporter._export", "otel"),
    ("serve/otel.py", "*", "shared"),
    # the ROADMAP router-ownership domain: PrefixRouter's own methods
    # are the only code allowed to mutate routing state — the fleet is
    # loop-owned in HTTP mode (ReplicaRunner) and engine-owned in
    # direct mode (ReplicaSet), so the single-writer contract is "all
    # router-state mutations go through the PrefixRouter API"
    ("serve/replica.py", "PrefixRouter.*", "router"),
    ("serve/replica.py", "ReplicaRunner.*", "loop"),
    ("serve/replica.py", "*", "engine"),
    # fleet lifecycle (serve/lifecycle.py): the controller's roll state
    # is lifecycle-domain-owned — only LifecycleController methods may
    # mutate it; everything else in the module (ActionPolicy above all)
    # runs on the engine tick thread, with the sentinel/tracker →
    # ActionPolicy signal flow lock-grouped below
    ("serve/lifecycle.py", "LifecycleController.*", "lifecycle"),
    ("serve/lifecycle.py", "*", "engine"),
    ("serve/metrics.py", "*", "shared"),
    # the tenant ledger (serve/tenants.py) is metrics-shaped shared
    # state: the engine tick thread folds terminals in, the scrape and
    # /debug/tenants endpoints read from the asyncio thread
    ("serve/tenants.py", "*", "shared"),
    ("serve/tracing.py", "*", "shared"),
    ("serve/faults.py", "*", "shared"),
    ("serve/*.py", "*", "engine"),
)

# engine-thread-owned state, matched as a suffix of the access chain
OWNED_STATE: tuple[tuple[str, ...], ...] = (
    ("scheduler", "queue"),
    ("scheduler", "running"),
    ("scheduler", "finished"),
    ("scheduler", "aborted"),
    ("scheduler", "_free_slots"),
    ("free_list", "_free"),
    ("free_list", "_ref"),
    ("pool", "pages"),
    ("engine", "_requests"),
    ("engine", "_detok"),
)

# router-owned state (the PrefixRouter ownership domain): MUTATED only
# by PrefixRouter's own methods — ReplicaRunner (loop) and ReplicaSet
# (engine) both hold a router, so reaching into its sticky map or
# verdict counters from either owner's code is a finding; they must
# call route()/forget_replica() instead.
ROUTER_STATE: tuple[tuple[str, ...], ...] = (
    ("router", "_sticky"),
    ("router", "_rr"),
    ("router", "routed"),
    ("router", "spilled"),
)

# journal-writer-thread-owned state (serve/journal.py): the ``_w``
# prefix marks attributes only the writer thread touches — the open
# file handle, the live-request mirror compaction snapshots from, and
# the bytes-since-compaction counter.  Engine-side hooks communicate
# through the lock-protected pending queue only.
JOURNAL_STATE: tuple[tuple[str, ...], ...] = (
    ("_wfile",),
    ("_wlive",),
    ("_wsince",),
)

# request-log-writer-thread-owned state (serve/request_log.py): the
# ``_w`` naming convention again — only the writer thread touches the
# open file handle and the lines-written counter
REQLOG_STATE: tuple[tuple[str, ...], ...] = (
    ("_wlog",),
    ("_wlines",),
)

# otlp-exporter-writer-thread-owned state (serve/otel.py): the ``_w``
# naming convention again — only the writer thread matches async
# begin/end pairs in the open-span map.  Everything shared with the
# offer() side goes through the lock-protected pending queue.
OTEL_STATE: tuple[tuple[str, ...], ...] = (
    ("_wopen",),
)

# host-tier-writer-thread-owned state (serve/host_tier.py): the ``_w``
# naming convention — only the writer thread inserts/evicts host
# blocks and maintains the resident byte count.  The engine side READS
# the store lock-free (dict lookups, benign race: a lost entry is a
# restore miss the engine already re-prefills) and communicates
# mutations through the lock-protected job queue.
HOST_TIER_STATE: tuple[tuple[str, ...], ...] = (
    ("_wentries",),
    ("_wbytes",),
)

# lifecycle-controller-owned state (serve/lifecycle.py): the in-flight
# roll flag and history — only LifecycleController methods (the
# lifecycle domain) drive a roll; handlers and tick code must call
# rolling_upgrade()/autoscale_tick() instead of poking the state
LIFECYCLE_STATE: tuple[tuple[str, ...], ...] = (
    ("_roll_active",),
    ("_roll_history",),
)

# (owning domain, state table, remediation hint)
DOMAIN_OWNED: tuple[tuple[str, tuple, str], ...] = (
    ("engine", OWNED_STATE,
     "route through the engine command queue instead"),
    ("router", ROUTER_STATE,
     "go through the PrefixRouter API (route/forget_replica) instead"),
    ("journal", JOURNAL_STATE,
     "enqueue a record for the writer thread instead"),
    ("reqlog", REQLOG_STATE,
     "enqueue a record for the writer thread instead"),
    ("otel", OTEL_STATE,
     "offer() the event for the writer thread instead"),
    ("host_tier", HOST_TIER_STATE,
     "enqueue a spill/restore job for the writer thread instead"),
    ("lifecycle", LIFECYCLE_STATE,
     "drive the roll through LifecycleController methods instead"),
)

# lock-protected groups: attrs of a class that may only be MUTATED under
# ``with self.<lock>:`` (or from a lock_assumed helper)
LOCK_STATE: tuple[dict, ...] = (
    {
        "file": "serve/metrics.py",
        "class": "ServeMetrics",
        "lock": "_lock",
        "attrs": {
            "n_submitted", "n_finished", "n_aborted", "n_rejected",
            "n_recovered", "n_ticks", "preemptions", "total_generated",
            "finish_reasons", "ttft_s", "decode_tok_s", "queue_wait_s",
            "prefill_s", "ttft_hist", "ttft_hist_sum", "decode_hist",
            "decode_hist_sum", "queue_depth", "occupancy", "active_slots",
            "kv_bytes_tick", "prefix_blocks_requested",
            "prefix_blocks_hit", "mixed_prefill_tokens",
            "mixed_decode_tokens", "mixed_dense_lanes",
            "prefill_segments", "prefill_tiles", "prefill_tile_tokens",
            "publish_overlapped", "publish_immediate", "t_start",
            "t_last",
            "anomaly_ticks", "lifecycle_actions",
            "roofline_ticks", "kv_read_bytes_total",
            "kv_write_bytes_total", "weight_bytes_total",
            "device_time_s_total", "hbm_gbps", "roofline_gbps",
            "roofline_util", "mfu_tick", "util_hist", "util_hist_sum",
            "retention_ticks", "retention_state_rows",
            "retention_scan_tokens", "retention_state_kernel",
            "dsa_ticks", "dsa_visible", "dsa_selected", "dsa_dense_tokens",
            "dsa_index_pages",
        },
        # "caller holds the lock" helpers — annotated, not inferred
        "lock_assumed": {"_record_latencies", "_trim"},
    },
    {
        "file": "serve/http/server.py",
        "class": "EngineRunner",
        "lock": "_sup_lock",
        "attrs": {
            "_inflight", "_handback", "_recent_deaths", "_death_t",
            "_backoff_delay", "recovering", "_gen",
            "_pending_weights",
        },
        "lock_assumed": {"_exec_inner", "_terminal_crash"},
    },
    {
        "file": "serve/faults.py",
        "class": "FaultInjector",
        "lock": "_lock",
        "attrs": {"hits", "injected", "_rngs"},
        "lock_assumed": set(),
    },
    {
        # the journal's engine↔writer boundary: the pending queue and
        # the stats counters are the ONLY shared state, and every
        # mutation takes the lock
        "file": "serve/journal.py",
        "class": "RequestJournal",
        "lock": "_lock",
        "attrs": {"_pending", "_stopping", "n_records", "bytes_written",
                  "n_fsyncs", "fsync_s", "n_write_errors",
                  "n_fsync_errors", "n_compactions"},
        "lock_assumed": set(),
    },
    {
        # the request log's engine↔writer boundary, same contract
        "file": "serve/request_log.py",
        "class": "RequestLog",
        "lock": "_lock",
        "attrs": {"_pending", "_stopping", "n_records",
                  "n_write_errors"},
        "lock_assumed": set(),
    },
    {
        # the OTLP exporter's offer↔writer boundary: the pending queue
        # and the ship/drop counters are the only shared state
        "file": "serve/otel.py",
        "class": "OtlpExporter",
        "lock": "_lock",
        "attrs": {"_pending", "_stopping", "n_spans", "n_batches",
                  "n_dropped", "n_export_errors"},
        "lock_assumed": set(),
    },
    {
        # the host tier's enqueue↔writer boundary: the job queue, the
        # staged-restore completion map, the ticket counter, the flow
        # counters, and the breakeven measurements are the shared state
        "file": "serve/host_tier.py",
        "class": "HostTier",
        "lock": "_lock",
        "attrs": {"_pending", "_done", "_abandoned",
                  "_pending_spill_keys", "_stopping",
                  "_next_ticket", "n_spilled", "spilled_bytes",
                  "n_restored", "restored_bytes", "n_restore_miss",
                  "n_dropped", "n_skipped", "restore_s",
                  "restore_s_per_block", "restore_gbps",
                  "prefill_tok_s", "_probed_bytes"},
        "lock_assumed": set(),
    },
    {
        # the tenant ledger's engine↔scrape boundary: per-tenant
        # counter maps and the lazy SLO tracker map are the shared
        # state; every mutation takes the ledger's lock
        "file": "serve/tenants.py",
        "class": "TenantLedger",
        "lock": "_lock",
        "attrs": {"_tenants", "_slo"},
        "lock_assumed": {"_entry"},
    },
    {
        # the sentinel/tracker → ActionPolicy signal flow: the engine
        # tick thread writes the verdict state + counters, the HTTP
        # loop reads them for the 503 shedding check and the scrape —
        # every mutation takes the policy's lock
        "file": "serve/lifecycle.py",
        "class": "ActionPolicy",
        "lock": "_lock",
        "attrs": {"shed_prefill", "shed_load", "retry_after_s",
                  "last_burn", "actions_total", "_anom_streak",
                  "_clean_ticks", "_last_flip"},
        "lock_assumed": {"_can_flip"},
    },
)

_MUTATORS = {
    "append", "extend", "insert", "pop", "popleft", "appendleft", "clear",
    "remove", "discard", "add", "update", "setdefault", "sort", "reverse",
}


def _module_overrides(sf: SourceFile, name: str) -> dict:
    """Parse a module-level ``LINT_* = {literal}`` annotation."""
    for node in sf.tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == name
        ):
            try:
                return ast.literal_eval(node.value)
            except ValueError:
                return {}
    return {}


def _domain_of(sf: SourceFile, qualname: str, overrides: dict) -> str:
    for pat, dom in overrides.items():
        if fnmatch.fnmatch(qualname, pat):
            return dom
    for file_glob, qual_glob, dom in DOMAIN_TABLE:
        if fnmatch.fnmatch(sf.rel, "*" + file_glob) and fnmatch.fnmatch(
            qualname, qual_glob
        ):
            return dom
    return "engine"


def _mutations(fn: ast.AST):
    """Yield ``(chain, lineno, how)`` for every attribute-chain mutation
    in the function's own body (nested defs are their own scope)."""
    for node in walk_within(fn, skip_nested=True):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = (
                node.targets if isinstance(node, ast.Assign)
                else [node.target]
            )
            for t in targets:
                elts = t.elts if isinstance(t, (ast.Tuple, ast.List)) else [t]
                for el in elts:
                    if isinstance(el, ast.Subscript):
                        el = el.value
                    chain = attr_chain(el)
                    if chain and len(chain) > 1:
                        yield chain, node.lineno, "assignment"
        elif isinstance(node, ast.Delete):
            for t in node.targets:
                if isinstance(t, ast.Subscript):
                    t = t.value
                chain = attr_chain(t)
                if chain and len(chain) > 1:
                    yield chain, node.lineno, "del"
        elif isinstance(node, ast.Call):
            if isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _MUTATORS:
                chain = attr_chain(node.func.value)
                if chain and len(chain) > 1:
                    yield chain, node.lineno, f".{node.func.attr}()"


def _under_lock(sf: SourceFile, node_line: int, fn: ast.AST,
                base: tuple[str, ...], lock: str) -> bool:
    """Is the line inside a ``with <base>.<lock>:`` block of ``fn``?"""
    want = base + (lock,)
    for node in ast.walk(fn):
        if not isinstance(node, ast.With):
            continue
        for item in node.items:
            if attr_chain(item.context_expr) == want:
                end = getattr(node, "end_lineno", node.lineno)
                if node.lineno <= node_line <= end:
                    return True
    return False


class _Rule:
    id = RULE_ID
    name = "thread-affinity"
    targets = ("llm_np_cp_tpu/serve/**/*.py",)

    def check(self, sf: SourceFile) -> list[Finding]:
        out: list[Finding] = []
        dom_over = _module_overrides(sf, "LINT_THREAD_DOMAINS")
        lock_over = _module_overrides(sf, "LINT_LOCKED_STATE")
        lock_groups = list(LOCK_STATE) + [
            {"file": sf.rel, "class": cls, "lock": spec["lock"],
             "attrs": set(spec["attrs"]),
             "lock_assumed": set(spec.get("lock_assumed", ()))}
            for cls, spec in lock_over.items()
        ]
        for qualname, fn in sf.iter_functions():
            domain = _domain_of(sf, qualname, dom_over)
            fn_name = qualname.rsplit(".", 1)[-1]
            cls_name = qualname.split(".")[0] if "." in qualname else None
            for chain, lineno, how in _mutations(fn):
                # -- domain-owned state mutated outside its domain -----
                # (constructors are exempt: object construction is
                # single-threaded by nature)
                if fn_name != "__init__":
                    for owner, table, hint in DOMAIN_OWNED:
                        if domain == owner:
                            continue
                        if any(chain[-len(s):] == s for s in table):
                            out.append(Finding(
                                rule=self.id, path=sf.rel, line=lineno,
                                message=(
                                    f"{how} on {owner}-thread-owned "
                                    f"state '{'.'.join(chain)}' from "
                                    f"{domain}-domain {qualname}() — "
                                    f"{hint}"
                                ),
                            ))
                            break
                # -- lock-protected state outside its lock -------------
                for grp in lock_groups:
                    if cls_name != grp["class"] \
                            or not sf.rel.endswith(grp["file"]):
                        continue
                    if len(chain) < 2 or chain[-1] not in grp["attrs"]:
                        continue
                    if fn_name == "__init__" \
                            or fn_name in grp["lock_assumed"]:
                        continue
                    base = chain[:-1]
                    if not _under_lock(sf, lineno, fn, base, grp["lock"]):
                        out.append(Finding(
                            rule=self.id, path=sf.rel, line=lineno,
                            message=(
                                f"{how} on lock-protected "
                                f"'{'.'.join(chain)}' outside "
                                f"'with {'.'.join(base)}."
                                f"{grp['lock']}:' in {qualname}() — "
                                "take the owning lock or add the "
                                "function to the rule's lock_assumed "
                                "annotation with a comment saying why"
                            ),
                        ))
        return out


RULE = _Rule()
