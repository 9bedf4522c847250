"""Compile-counter: the serve/ static-shape lint.

The serving engine's whole design rests on jit-stable steps: a tick must
never retrace (a recompile mid-traffic is a multi-second stall for every
queued request).  This module gives tests and CI three independent
probes:

- ``CompileCounter`` — a ``jax.monitoring`` listener counting backend
  compile events process-wide; wrap a block of ticks and assert zero new
  compiles.
- ``assert_serve_compiles_bounded(engine)`` — checks the engine's own
  per-program compile counts (``ServeEngine.compile_counts()``) against
  the static-shape contract: the tick's one step compiles once per
  program of ``mixed_buckets``, never per tick.
- ``assert_tracing_hooks_guarded()`` — the tracing-off discipline lint:
  every ``serve/tracing.py`` hook must sit behind an ``is None`` check,
  so with tracing off the per-tick cost is attribute loads + branches —
  no Python allocations and no calls on the hot path.  Now a shim over
  rule R4 of the static-analysis suite (``python -m tools.lint``),
  which generalizes it to the FaultInjector hook and all serve modules.

Run from tests (tests/test_serve_static_shapes.py,
tests/test_serve_tracing.py); usable standalone:

    python tools/compile_counter.py   # self-check on a tiny synthetic trace
"""

from __future__ import annotations

import contextlib
from typing import Iterator

# Event keys that indicate an XLA computation was compiled: every
# compile request records ``/jax/compilation_cache/compile_requests_use_cache``
# (persistent-cache hit or not); match loosely on purpose.  With
# ``jax_enable_compilation_cache`` switched off JAX records NO such event
# and the counter is blind — a process that counts must leave it on.
_COMPILE_MARKERS = ("compile", "lowering")


class CompileCounter:
    """Counts jax compile-ish monitoring events while active."""

    def __init__(self) -> None:
        self.events: list[str] = []

    @property
    def count(self) -> int:
        return len(self.events)

    def _listener(self, event: str, **kw) -> None:
        if any(m in event for m in _COMPILE_MARKERS):
            self.events.append(event)

    @contextlib.contextmanager
    def watch(self) -> Iterator["CompileCounter"]:
        from jax import monitoring

        monitoring.register_event_listener(self._listener)
        try:
            yield self
        finally:
            monitoring.unregister_event_listener(self._listener)


def assert_serve_compiles_bounded(engine) -> None:
    """The static-shape contract of the serve tick: the engine has ONE
    jitted step, ``mixed_step``, which compiles at most once per program
    (``engine.mixed_buckets``: ``(packed, dense)`` width pairs, one more
    than the tile ladder has rungs) regardless of the prefill:decode row
    composition, prompt-length buckets, prefix hits or refcount state.
    Anything above that bound means the step's shapes depend on per-tick
    state — the exact bug this lint exists to catch.  No other program
    may exist beside it but the host tier's two.
    """
    counts = engine.compile_counts()
    problems = []
    # the host tier's two programs (present only with the tier
    # attached): block ids are traced and the block layout fixed, so
    # each must stay at ONE compile however many blocks spill/restore
    for prog in ("restore_block", "slice_block"):
        n = counts.pop(prog, None)
        if n is not None and n > 1:
            problems.append(
                f"{prog} compiled {n}x (must be <= 1: the host tier's "
                "programs take the block id as a traced scalar, so "
                "spills/restores never specialize per block)"
            )
    if set(counts) != {"mixed_step"}:
        problems.append(
            f"the engine reports programs {sorted(counts)}; only "
            "mixed_step may exist"
        )
    if counts.get("mixed_step", 0) > len(engine.mixed_buckets):
        problems.append(
            f"mixed_step compiled {counts['mixed_step']}x for "
            f"{len(engine.mixed_buckets)} (packed, dense) width "
            "programs (must be <= one per program, never per tick or per "
            "prefill:decode composition)"
        )
    if any(v < 0 for v in counts.values()):
        problems.append(
            f"compile counts unavailable on this jax version: {counts}"
        )
    if problems:
        raise AssertionError(
            "serve/ static-shape lint failed:\n  " + "\n  ".join(problems)
        )


# serve hot-path modules whose tracing hooks the lint below pins
_TRACED_HOT_PATHS = (
    "llm_np_cp_tpu/serve/engine.py",
    "llm_np_cp_tpu/serve/http/server.py",
)


def assert_tracing_hooks_guarded(files: tuple[str, ...] = _TRACED_HOT_PATHS,
                                 ) -> None:
    """The tracing-off zero-overhead lint — DEPRECATION SHIM.

    The AST pass that lived here is now rule **R4 (guarded-hook)** of
    the serve-stack static-analysis suite (``python -m tools.lint``),
    which extends it to the FaultInjector hook and every serve hot-path
    module.  This wrapper keeps the original surface for existing
    callers/tests: same default files, same AssertionError text shape
    (``... without an 'is (not) None' guard``), tracer hook only.
    """
    from tools.lint.rules.guarded_hook import scan_hook_guard_files

    problems = scan_hook_guard_files(tuple(files), hooks=("tracer",))
    if problems:
        raise AssertionError(
            "tracing-off zero-overhead lint failed:\n  "
            + "\n  ".join(problems)
        )


def _self_check() -> None:
    import os
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import jax
    import jax.numpy as jnp
    import numpy as np

    # a lint self-check must never take the chip; the mesh section
    # below needs virtual devices (set before the backend initializes)
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
    from llm_np_cp_tpu.config import tiny_config
    from llm_np_cp_tpu.models.transformer import init_params
    from llm_np_cp_tpu.ops.sampling import Sampler
    from llm_np_cp_tpu.serve.engine import ServeEngine

    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    # prompt-length buckets and prefix sharing: ticks across prompt
    # lengths, repeated prompts (refcount churn: claim, share, release)
    # must stay within the bound — one compile a program, none a tick
    eng = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
        num_blocks=32, block_size=8, max_seq_len=64, cache_dtype=jnp.float32,
        enable_prefix_cache=True,
    )
    rng = np.random.default_rng(0)
    prompts = [rng.integers(1, 200, size=n) for n in (5, 9, 13, 17)]
    eng.warmup([int(p.size) for p in prompts], max_new_tokens=6)
    for _ in range(3):  # repeats after round 1 hit the prefix cache
        for p in prompts:
            eng.submit(p, 6)
    eng.run_until_complete()
    assert eng.metrics.prefix_blocks_hit > 0, "no prefix hits — bad workload"
    assert_serve_compiles_bounded(eng)
    print(f"compile counts OK (prefix sharing): {eng.compile_counts()}")

    # abort churn: cancelling requests queued / mid-decode, with prefix
    # sharers still live, must stay inside the SAME bounds — abort is
    # host-side unwinding only (operands rebuilt per tick), so no new
    # program appears
    warm = dict(eng.compile_counts())
    for round_ in range(3):
        live = [eng.submit(p, 6) for p in prompts]
        eng.step()  # admit + the first chunks of whoever fits
        eng.abort(live[0].req_id)              # mid-prefill (or queued)
        eng.abort(live[-1].req_id)             # queue tail
        eng.run_until_complete()
    assert eng.compile_counts() == warm, (
        f"abort churn recompiled: {warm} -> {eng.compile_counts()}"
    )
    held = eng.pool.stats()["request_held"]
    assert held == 0, f"abort churn leaked {held} blocks"
    print(f"compile counts OK (abort churn): {eng.compile_counts()}")

    # supervised restart + recovery replay: a rebuilt engine
    # (clone_fresh, identical geometry) SHARES the compiled step, and
    # replaying in-flight requests teacher-forced (engine.recover — the
    # evict-requeue path across a rebuild) must not compile ANYTHING
    # new — restart cost is pool rebuild + replay prefills, never a
    # retrace
    live = [eng.submit(p, 6) for p in prompts]
    for _ in range(2):
        eng.step()  # some requests mid-decode, some still queued
    eng.publish_owed()
    rebuilt = eng.clone_fresh()
    for r in live:
        rebuilt.recover(
            r.prompt, r.max_new_tokens, request_id=r.req_id, seed=r.seed,
            generated=list(r.generated),
        )
    rebuilt.run_until_complete()
    assert rebuilt._mixed_step is eng._mixed_step
    assert rebuilt.compile_counts() == warm, (
        f"engine restart + recovery replay recompiled: "
        f"{warm} -> {rebuilt.compile_counts()}"
    )
    held = rebuilt.pool.stats()["request_held"]
    assert held == 0, f"recovery replay leaked {held} blocks"
    print(f"compile counts OK (restart+recovery): {rebuilt.compile_counts()}")

    # the unified tick: after warmup compiles every packed-width bucket,
    # churning the ragged composition (prefill-heavy, decode-only, and
    # mixed ticks; varied prompt lengths and budgets-worth of chunk
    # slices) must trigger ZERO further compiles
    eng = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
        num_blocks=32, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32,
        enable_prefix_cache=True,
    )
    mixed_prompts = [rng.integers(1, 200, size=n) for n in (26, 4, 17, 9)]
    eng.warmup([int(p.size) for p in mixed_prompts], max_new_tokens=8)
    warm = dict(eng.compile_counts())
    with CompileCounter().watch() as counter:
        for rep in range(3):  # round 2+ hits the prefix cache too
            for i, p in enumerate(mixed_prompts):
                eng.submit(p, 3 + i)
            eng.run_until_complete()
    assert counter.count == 0, (
        f"unified-tick composition churn compiled: {counter.events}"
    )
    assert eng.compile_counts() == warm
    assert_serve_compiles_bounded(eng)
    held = eng.pool.stats()["request_held"]
    assert held == 0, f"unified tick leaked {held} blocks"
    print(f"compile counts OK (unified tick): {eng.compile_counts()}")

    # the tiered KV prefix cache (--kv-tier host): a pool too small for
    # the prefix working set churns through spill (LRU reclaim) and
    # restore (repeat admissions) every round — restore-heavy ticks
    # must SHARE the warmed mixed step, the tier's only program is the
    # single restore_block landing step (warmed in warmup), and
    # clone_fresh must CARRY the tier (host entries survive a rebuild:
    # the zeroed pool restores instead of re-prefilling) while sharing
    # both compiled callables — tier-on churn compiles NOTHING
    from llm_np_cp_tpu.serve.host_tier import HostTier

    tier = HostTier(64 << 20)
    eng = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
        num_blocks=12, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32,
        enable_prefix_cache=True, host_tier=tier,
    )
    tier_prompts = [rng.integers(1, 200, size=24) for _ in range(6)]
    eng.warmup([int(p.size) for p in tier_prompts], max_new_tokens=6)
    warm = dict(eng.compile_counts())
    assert warm.get("restore_block") == 1, (
        f"restore_block not warmed exactly once: {warm}"
    )
    assert warm.get("slice_block") == 1, (
        f"slice_block not warmed exactly once: {warm}"
    )
    with CompileCounter().watch() as counter:
        for rep in range(3):  # rounds 2+ restore from the host tier
            for p in tier_prompts:
                eng.submit(p, 4)
                eng.run_until_complete()
            tier.drain()
    assert counter.count == 0, (
        f"tier-on composition churn compiled: {counter.events}"
    )
    assert eng.compile_counts() == warm
    tier_stats = tier.stats()
    assert tier_stats["restored_blocks"] > 0, (
        "tier never restored — bad self-check workload"
    )
    assert_serve_compiles_bounded(eng)
    live = [eng.submit(p, 4) for p in tier_prompts[:2]]
    eng.step()
    rebuilt = eng.clone_fresh()
    assert rebuilt.host_tier is tier, "clone_fresh dropped the tier"
    assert rebuilt._restore_block is eng._restore_block, (
        "clone_fresh did not share the restore_block program"
    )
    assert rebuilt._mixed_step is eng._mixed_step
    with CompileCounter().watch() as counter:
        for r in live:
            rebuilt.recover(
                r.prompt, r.max_new_tokens, request_id=r.req_id,
                seed=r.seed, generated=list(r.generated),
            )
        rebuilt.run_until_complete()
    assert counter.count == 0, (
        f"tiered restart + recovery replay compiled: {counter.events}"
    )
    tier.close()
    print(f"compile counts OK (kv tier): {eng.compile_counts()}, "
          f"{tier_stats['restored_blocks']} restored / "
          f"{tier_stats['spilled_blocks']} spilled")

    # speculative serving (spec_k > 0): the verify lanes are a STATIC
    # [R, spec_k+1] extension of the mixed step, so per-tick verify-width
    # churn (drafts of 0..k tokens per row, rows flipping between spec
    # and plain, fallback kicking in) must compile NOTHING after the
    # warmed bucket ladder — and a spec-enabled clone_fresh restart must
    # share the compiled step, with teacher-forced recovery of a spec
    # request compiling nothing either.
    eng = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
        num_blocks=32, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32, spec_k=3,
    )
    # repetitive prompts so prompt-lookup actually proposes (verify
    # widths churn through 0..k); one random prompt keeps plain rows in
    # the same ticks
    base = rng.integers(1, 200, size=4)
    spec_prompts = [np.tile(base, 4), rng.integers(1, 200, size=9),
                    np.tile(rng.integers(1, 200, size=3), 5)]
    eng.warmup([int(p.size) for p in spec_prompts], max_new_tokens=10)
    warm = dict(eng.compile_counts())
    with CompileCounter().watch() as counter:
        for rep in range(3):
            for i, p in enumerate(spec_prompts):
                eng.submit(p, 8 + i, seed=rep * 10 + i, speculative=True)
            eng.run_until_complete()
    assert counter.count == 0, (
        f"spec verify-width churn compiled: {counter.events}"
    )
    assert eng.compile_counts() == warm
    snap = eng.metrics.snapshot()
    assert snap.get("spec_drafted_tokens", 0) > 0, (
        "spec workload never drafted — bad self-check workload"
    )
    live = [eng.submit(p, 8, speculative=True) for p in spec_prompts]
    for _ in range(3):
        eng.step()  # some rows mid-verify
    rebuilt = eng.clone_fresh()
    assert rebuilt._mixed_step is eng._mixed_step, (
        "spec-enabled clone_fresh did not share the compiled mixed step"
    )
    with CompileCounter().watch() as counter:
        for r in live:
            rebuilt.recover(
                r.prompt, r.max_new_tokens, request_id=r.req_id,
                seed=r.seed, generated=list(r.generated),
                speculative=True,
            )
        rebuilt.run_until_complete()
    assert counter.count == 0, (
        f"spec restart + recovery replay compiled: {counter.events}"
    )
    assert rebuilt.compile_counts() == warm
    held = rebuilt.pool.stats()["request_held"]
    assert held == 0, f"spec recovery leaked {held} blocks"
    print(f"compile counts OK (speculative): {rebuilt.compile_counts()}")

    # the fused sampling epilogue (tick-tail fusion): on this backend
    # the default engine resolves epilogue=fused (greedy sampler, float
    # head, probe pass) — composition/bucket churn with the fused tail
    # must compile NOTHING after warmup, clone_fresh must SHARE the
    # fused step, and a runtime degrade to the XLA tail recompiles the
    # step once for the PROCESS: a subsequent clone_fresh restart
    # shares the degraded step and replays without a single compile
    # (the PR 4 restart lint, extended to the epilogue)
    from llm_np_cp_tpu.ops.pallas import support as _support

    eng = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
        num_blocks=32, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32,
    )
    assert eng.epilogue_impl == "fused", (
        f"self-check expects the fused epilogue here, got "
        f"{eng.epilogue_impl}"
    )
    epi_prompts = [rng.integers(1, 200, size=n) for n in (21, 5, 12)]
    eng.warmup([int(p.size) for p in epi_prompts], max_new_tokens=6)
    warm = dict(eng.compile_counts())
    with CompileCounter().watch() as counter:
        for rep in range(2):
            for i, p in enumerate(epi_prompts):
                eng.submit(p, 3 + i)
            eng.run_until_complete()
    assert counter.count == 0, (
        f"fused-epilogue composition churn compiled: {counter.events}"
    )
    assert eng.compile_counts() == warm
    assert eng.clone_fresh()._mixed_step is eng._mixed_step, (
        "clone_fresh did not share the fused-epilogue mixed step"
    )
    try:
        assert eng._degrade_mixed("self-check: forced epilogue degrade")
        assert eng.epilogue_impl == "xla"
        with CompileCounter().watch() as counter:
            for p in epi_prompts:
                eng.submit(p, 4)
            eng.run_until_complete()
        degraded_warm = dict(eng.compile_counts())
        # the degrade-to-XLA retry discipline: a rebuilt engine in the
        # SAME (degraded) process shares the XLA-tail step — recovery
        # replay after the degrade compiles nothing
        live = [eng.submit(p, 5) for p in epi_prompts]
        eng.step()
        rebuilt_epi = eng.clone_fresh()
        assert rebuilt_epi.epilogue_impl == "xla"  # ledger is process-wide
        assert rebuilt_epi._mixed_step is eng._mixed_step, (
            "degraded clone_fresh did not share the XLA-tail step"
        )
        with CompileCounter().watch() as counter:
            for r in live:
                rebuilt_epi.recover(
                    r.prompt, r.max_new_tokens, request_id=r.req_id,
                    seed=r.seed, generated=list(r.generated),
                )
            rebuilt_epi.run_until_complete()
        assert counter.count == 0, (
            f"post-degrade restart + replay compiled: {counter.events}"
        )
        assert rebuilt_epi.compile_counts() == degraded_warm
    finally:
        # the degrade ledger is process-wide by design; the remaining
        # sections need their kernels back
        _support._RUNTIME_DISABLED.clear()
    print(f"compile counts OK (fused epilogue): {warm} fused / "
          f"{degraded_warm} degraded")

    # the MESH-sharded engine (ServeEngine mesh_plan): the static-shape
    # contract extends to placement — params TP-sharded, pool slabs
    # kv-head-partitioned, per-tick operands committed replicated — so
    # ticks must trigger ZERO compiles under the mesh once the buckets
    # are warm, whatever the composition, and a replica restart via
    # clone_fresh must SHARE the compiled sharded steps (restart never
    # recompiles, even across a mesh)
    if jax.device_count() >= 2:
        from llm_np_cp_tpu.parallel.sharding import MeshPlan

        mesh_cfg = tiny_config(
            "llama", num_attention_heads=8, num_key_value_heads=4,
            head_dim=8, hidden_size=64,
        )
        mesh_params = init_params(
            jax.random.PRNGKey(7), mesh_cfg, dtype=jnp.float32
        )
        eng = ServeEngine(
            mesh_params, mesh_cfg, sampler=Sampler(kind="greedy"),
            max_slots=2, num_blocks=32, block_size=8, max_seq_len=64,
            cache_dtype=jnp.float32,
            enable_prefix_cache=True, mesh_plan=MeshPlan(model=2),
        )
        mesh_prompts = [rng.integers(1, 200, size=n) for n in (26, 4, 17)]
        eng.warmup([int(p.size) for p in mesh_prompts], max_new_tokens=8)
        warm = dict(eng.compile_counts())
        with CompileCounter().watch() as counter:
            for rep in range(2):  # round 2 hits the prefix cache
                for i, p in enumerate(mesh_prompts):
                    eng.submit(p, 3 + i)
                eng.run_until_complete()
        assert counter.count == 0, (
            f"sharded unified-tick churn compiled: {counter.events}"
        )
        assert_serve_compiles_bounded(eng)
        # replica restart: clone_fresh + teacher-forced recovery on the
        # SAME mesh slice must not compile anything
        live = [eng.submit(p, 6) for p in mesh_prompts]
        for _ in range(2):
            eng.step()
        rebuilt_mesh = eng.clone_fresh()
        with CompileCounter().watch() as counter:
            for r in live:
                rebuilt_mesh.recover(
                    r.prompt, r.max_new_tokens, request_id=r.req_id,
                    seed=r.seed, generated=list(r.generated),
                )
            rebuilt_mesh.run_until_complete()
        assert counter.count == 0, (
            f"sharded replica restart recompiled: {counter.events}"
        )
        assert rebuilt_mesh.compile_counts() == warm
        held = rebuilt_mesh.pool.stats()["request_held"]
        assert held == 0, f"sharded restart leaked {held} blocks"
        print(f"compile counts OK (mesh tp=2): {warm}")
    else:
        print("compile counts: mesh section SKIPPED (1 device)")

    # tracing is host-side only: attaching a recorder mid-life and
    # replaying more traffic must not compile anything new (the step
    # jaxprs cannot see the tracer), and the hot-path hooks must all be
    # is-None-guarded (the tracing-off zero-overhead lint)
    assert_tracing_hooks_guarded()
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    warm = dict(rebuilt.compile_counts())
    rebuilt.tracer = TraceRecorder(ring=10_000)
    for p in prompts:
        rebuilt.submit(p, 6)
    rebuilt.run_until_complete()
    assert rebuilt.compile_counts() == warm, (
        f"tracing recompiled: {warm} -> {rebuilt.compile_counts()}"
    )
    assert len(rebuilt.tracer) > 0, "tracer attached but recorded nothing"
    rebuilt.tracer = None
    print(f"compile counts OK (traced): {rebuilt.compile_counts()}")

    # journaling is host-side only (serve/journal.py): admissions,
    # per-tick delivery watermarks, and terminals are enqueued to the
    # writer THREAD — the step jaxprs cannot see the journal, so
    # attaching one and replaying traffic must compile NOTHING new
    import tempfile

    from llm_np_cp_tpu.serve.journal import RequestJournal, scan_journal

    with tempfile.TemporaryDirectory() as td:
        jpath = os.path.join(td, "serve.journal")
        journal = RequestJournal(jpath)
        rebuilt.journal = journal
        warm = dict(rebuilt.compile_counts())
        with CompileCounter().watch() as counter:
            for p in prompts:
                rebuilt.submit(p, 6)
            rebuilt.run_until_complete()
        assert counter.count == 0, (
            f"journaling compiled: {counter.events}"
        )
        assert rebuilt.compile_counts() == warm
        assert journal.flush(10.0)
        assert journal.stats()["records"] > 0, "journal recorded nothing"
        live, _, _ = scan_journal(jpath)
        assert live == {}, f"finished traffic left a replay set: {live}"
        journal.close()
        rebuilt.journal = None
    print(f"compile counts OK (journaled): {rebuilt.compile_counts()}")

    # roofline telemetry + cost attribution + OTLP export are host-side
    # only (serve/telemetry.py analytic byte model = numpy arithmetic,
    # attribution = Request field adds, serve/otel.py = a writer thread
    # hung off the recorder): attaching ALL of them and churning the
    # prefill:decode composition must compile NOTHING after the warmed
    # ladder, and a clone_fresh rebuild still shares the compiled step
    from llm_np_cp_tpu.serve.otel import OtlpExporter
    from llm_np_cp_tpu.serve.telemetry import TelemetryModel

    eng = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
        num_blocks=32, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32,
        telemetry=TelemetryModel(cfg, params),
        tracer=TraceRecorder(ring=50_000),
    )
    # a dead collector endpoint on purpose: export failures must stay a
    # dropped-batch counter, never a compile or a crash
    exporter = OtlpExporter(
        "http://127.0.0.1:9/v1/traces", timeout_s=0.2,
    ).attach(eng.tracer)
    tel_prompts = [rng.integers(1, 200, size=n) for n in (21, 5, 12)]
    eng.warmup([int(p.size) for p in tel_prompts], max_new_tokens=8)
    warm = dict(eng.compile_counts())
    with CompileCounter().watch() as counter:
        for i, p in enumerate(tel_prompts):
            eng.submit(p, 4 + i)
        eng.run_until_complete()
    assert counter.count == 0, (
        f"telemetry+otel churn compiled: {counter.events}"
    )
    assert eng.compile_counts() == warm
    snap = eng.metrics.snapshot()
    assert snap.get("roofline_ticks", 0) > 0, "telemetry graded nothing"
    assert all(
        r.device_time_s > 0 for r in eng.scheduler.finished
    ), "cost attribution left a request unbilled"
    rebuilt = eng.clone_fresh()
    assert rebuilt._mixed_step is eng._mixed_step, (
        "telemetry-attached clone_fresh did not share the compiled step"
    )
    exporter.close()
    print(f"compile counts OK (telemetry+otel): {eng.compile_counts()}")

    # rolling upgrade (serve/lifecycle + ReplicaSet.rolling_upgrade):
    # a same-shaped weight swap must compile NOTHING — params are jit
    # call arguments, every rolled replica adopts ONE shared step
    # callable (share_compiled_steps), and the drain re-prefills reuse
    # the warm shapes.  Mid-trace streams survive the roll.
    from llm_np_cp_tpu.serve.replica import ReplicaSet

    fleet = ReplicaSet([
        ServeEngine(
            params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
            num_blocks=32, block_size=8, max_seq_len=64,
            cache_dtype=jnp.float32,
        )
        for _ in range(3)
    ])
    for e in fleet.engines:
        e.warmup([5], max_new_tokens=6)
    for p in prompts:
        fleet.submit(p, 6)
    fleet.step()
    with CompileCounter().watch() as counter:
        fleet.rolling_upgrade(lambda: params, version=1,
                              steps_between=1)
        fleet.run_until_complete()
    assert counter.count == 0, (
        f"same-weights rolling upgrade compiled: {counter.events}"
    )
    shared = {id(e._mixed_step) for e in fleet.engines}
    assert len(shared) == 1, (
        "rolled replicas do not share one step callable — new weights "
        "would compile per replica, not per fleet"
    )
    assert all(e.weights_version == 1 for e in fleet.engines)
    print(f"compile counts OK (rolling upgrade): "
          f"{fleet.engines[0].compile_counts()}")

    # multi-tenant accounting (serve/tenants.py): the ledger is
    # host-side dict arithmetic fed at terminals, the fairness reorder
    # is a host-side sort feeding plan_tick, and throttling raises
    # before anything touches the device — so tenant churn (many
    # tenants, fairness on, per-tenant caps rejecting admissions)
    # must compile NOTHING after the warmed ladder, and clone_fresh
    # must CARRY the ledger (a supervised restart is the same replica,
    # so its bill keeps accumulating) while sharing the compiled step
    from llm_np_cp_tpu.serve.scheduler import TenantThrottled
    from llm_np_cp_tpu.serve.slo import SLOPolicy
    from llm_np_cp_tpu.serve.tenants import TenantLedger

    ledger = TenantLedger(
        fairness=True, max_inflight=2,
        policy=SLOPolicy(ttft_s=60.0, tpot_s=60.0),
    )
    eng = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
        num_blocks=32, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32, tenants=ledger,
    )
    ten_prompts = [rng.integers(1, 200, size=n) for n in (19, 7, 11)]
    eng.warmup([int(p.size) for p in ten_prompts], max_new_tokens=8)
    warm = dict(eng.compile_counts())
    throttled = 0
    with CompileCounter().watch() as counter:
        for rep in range(3):
            for i, p in enumerate(ten_prompts):
                for tenant in (f"team-{i}", f"team-{i}", "burst"):
                    try:
                        eng.submit(p, 4 + i, tenant=tenant)
                    except TenantThrottled:
                        throttled += 1  # the cap's 429 path, on purpose
            eng.run_until_complete()
    assert counter.count == 0, (
        f"tenant churn + throttling compiled: {counter.events}"
    )
    assert eng.compile_counts() == warm
    tsnap = ledger.snapshot()
    assert tsnap["n_tenants"] >= 3, "tenant churn metered nothing"
    assert throttled > 0 or any(
        e["throttled"] for e in tsnap["tenants"].values()
    ), "the per-tenant cap never bit — bad self-check workload"
    live = [eng.submit(p, 6, tenant="survivor") for p in ten_prompts[:2]]
    eng.step()
    rebuilt = eng.clone_fresh()
    assert rebuilt.tenants is ledger, "clone_fresh dropped the ledger"
    assert rebuilt._mixed_step is eng._mixed_step
    with CompileCounter().watch() as counter:
        for r in live:
            rebuilt.recover(
                r.prompt, r.max_new_tokens, request_id=r.req_id,
                seed=r.seed, generated=list(r.generated),
                tenant=r.tenant,
            )
        rebuilt.run_until_complete()
    assert counter.count == 0, (
        f"tenant-billed restart + recovery replay compiled: "
        f"{counter.events}"
    )
    surv = ledger.snapshot()["tenants"].get("survivor")
    assert surv and surv["requests"] == len(live), (
        "recovered requests lost their tenant across the rebuild"
    )
    print(f"compile counts OK (tenants): {tsnap['n_tenants']} tenants, "
          f"{throttled} throttled, {eng.compile_counts()}")


if __name__ == "__main__":
    _self_check()
