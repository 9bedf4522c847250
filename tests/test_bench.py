"""bench.py harness invariants (offline: children inherit JAX_PLATFORMS=cpu).

The bench artifact is the round's headline evidence; a harness regression
(e.g. a helper accidentally spliced into _spawn's success path, caught in
round 3) silently destroys it.  These tests pin the parent-side machinery
without a TPU: child spawn round-trip, timeout diagnosis, summary
emission, and the PRIORITY/config-dict sync assert.
"""

import json
import sys

import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
import bench


# Children run on the CPU because tests/conftest.py exports
# JAX_PLATFORMS=cpu into the environment every child inherits — the
# platform rule of the repo (utils/runtime.py): the environment decides.


def test_spawn_success_roundtrip():
    """A successful child returns its parsed result dict — the exact path
    that silently returned None in an early round-3 edit."""
    res = bench._spawn("smoke_tiny", 300)
    assert res is not None and res.get("ok") is True, res
    assert res["config"] == "smoke_tiny"
    assert res["decode_tok_s_chip"] > 0
    assert "compile_s" in res


def test_spawn_timeout_carries_diagnosis():
    res = bench._spawn("smoke_tiny", 1)
    assert res["ok"] is False
    assert "timeout" in res["error"]
    assert "diagnosis" in res


def test_diagnose_timeout_phases():
    mk = lambda phase, t: "bench-phase " + json.dumps(
        {"config": "x", "phase": phase, "t": t}
    )
    assert "backend init" in bench._diagnose_timeout([], 600)
    assert "prefill compile" in bench._diagnose_timeout(
        [mk("params_built", 5.0)], 600
    )
    assert "decode-loop compile" in bench._diagnose_timeout(
        [mk("warmup:prefill_done", 50.0)], 600
    )
    assert "execution" in bench._diagnose_timeout(
        [mk("rep1:decode_done", 400.0)], 600
    )


def test_emit_summary_always_parseable(capsys):
    detail = {
        "llama1b_bs8": {"config": "llama1b_bs8", "ok": True,
                        "decode_tok_s_chip": 2000.0, "per_seq_tok_s": 250.0},
        "gemma2_2b_bs1": {"config": "gemma2_2b_bs1", "ok": False,
                          "error": "timeout after 540s"},
    }
    bench._emit_summary(detail, {"ok": True, "backend": "tpu"},
                        error=bench._failed_error(detail))
    line = capsys.readouterr().out.strip().splitlines()[-1]
    d = json.loads(line)
    assert d["value"] == 2000.0
    assert d["vs_baseline"] == 2.0
    assert d["platform"] == "tpu" and d["rehearsal"] is False
    assert "gemma2_2b_bs1" in d["error"]
    # the same results from a CPU probe: no number under the device
    # metric's name
    bench._emit_summary(detail, {"ok": True, "backend": "cpu"}, error=None)
    d = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert d["value"] == 0.0 and d["rehearsal"] is True


def test_no_chip_run_exits_nonzero_with_nothing_on_stdout():
    """The device path fails without a chip: anything but an explicit
    list of smoke configs ends non-zero with no JSON on stdout (here: a
    device cell asked for on a CPU)."""
    import subprocess

    cell = subprocess.run(
        [sys.executable, bench.__file__, "--configs", "llama1b_bs8",
         "smoke_tiny"],
        capture_output=True, text=True, timeout=300)
    assert cell.returncode == 3 and cell.stdout == ""
    assert "no TPU" in cell.stderr


def test_kernels_child_covers_every_kernel_name():
    from llm_np_cp_tpu.ops.pallas import support

    res = bench.run_kernels()
    assert res["ok"] is True
    assert set(support.KERNELS) <= set(res)


def test_failed_error_ignores_warm():
    detail = {
        "warm": {"config": "warm", "ok": False, "error": "timeout"},
        "llama1b_bs8": {"config": "llama1b_bs8", "ok": True},
    }
    assert bench._failed_error(detail) is None


def test_priority_matches_config_dicts():
    """Import-time assert is live: every non-smoke config is prioritized."""
    non_smoke = {
        n
        for n in list(bench.DECODE_CONFIGS) + list(bench.SPEC_CONFIGS)
        + list(bench.PREFILL_CONFIGS) + list(bench.RAGGED_CONFIGS)
        + list(bench.SERVE_HTTP_CONFIGS)
        + list(bench.SERVE_CHAOS_CONFIGS) + list(bench.SERVE_MIXED_CONFIGS)
        + list(bench.SERVE_SPEC_CONFIGS) + list(bench.SERVE_SHARDED_CONFIGS)
        + list(bench.SERVE_RESTART_CONFIGS)
        + list(bench.SERVE_ROLLING_CONFIGS)
        + list(bench.SERVE_TIER_CONFIGS)
        + list(bench.SERVE_TENANT_CONFIGS)
        if not n.startswith("smoke")
    }
    assert set(bench.PRIORITY) == non_smoke | bench.EXTRA_CHILDREN


def test_warm_smoke_offline():
    """The warm child AOT-compiles all configs from abstract shapes on the
    CPU backend without error (cache-priming path the matrix runs first)."""
    res = bench._spawn("warm", 600)
    assert res.get("ok") is True, res
    assert set(res["warmed"]) == {n for n in bench.PRIORITY
                                 if n not in bench.SPEC_CONFIGS
                                 and n not in bench.EXTRA_CHILDREN
                                 and n not in bench.SERVE_HTTP_CONFIGS
                                 and n not in bench.SERVE_CHAOS_CONFIGS
                                 and n not in bench.SERVE_MIXED_CONFIGS
                                 and n not in bench.SERVE_SPEC_CONFIGS
                                 and n not in bench.SERVE_SHARDED_CONFIGS
                                 and n not in bench.SERVE_RESTART_CONFIGS
                                 and n not in bench.SERVE_ROLLING_CONFIGS
                                 and n not in bench.SERVE_TIER_CONFIGS
                                 and n not in bench.SERVE_TENANT_CONFIGS}


def test_warm_limit_covers_top_priority_only():
    """BENCH_WARM_LIMIT=N (tight-deadline mode) warms exactly the first N
    warmable priority configs and skips the ragged block."""
    res = bench._spawn("warm", 600, env={"BENCH_WARM_LIMIT": "3"})
    assert res.get("ok") is True, res
    warmable = [n for n in bench.PRIORITY
                if n not in bench.SPEC_CONFIGS
                and n not in bench.EXTRA_CHILDREN
                and n not in bench.RAGGED_CONFIGS
                and n not in bench.SERVE_HTTP_CONFIGS
                and n not in bench.SERVE_CHAOS_CONFIGS
                and n not in bench.SERVE_MIXED_CONFIGS]
    assert res["warmed"] == warmable[:3]


def test_ragged_smoke_offline():
    """The ragged decode child (mixed prompt lengths, marginal pair
    measurement) runs end-to-end on CPU with the tiny model."""
    res = bench._spawn("smoke_ragged", 600)
    assert res.get("ok") is True, res
    assert res["decode_tok_s_chip_e2e"] > 0
    assert res["prompt_lens"] == [24, 16, 9, 4]
    assert res["cache_capacity"] % 128 == 0


def test_serve_mixed_smoke_offline():
    """The tick-tail child: the same long-prefill-heavy trace through
    the fused-epilogue and XLA-tail engines — token parity between the
    legs, at most one dispatch per tick, one mixed_step compile per
    packed-width bucket, and the tick-tail fusion observables: the
    fused leg resolves epilogue=fused, makes exactly ONE device fetch
    per tick (trace-verified host_sync column), and the Δhost_sync/
    Δroofline_util pair is reported for slo_gate."""
    res = bench._spawn("smoke_serve_mixed", 600)
    assert res.get("ok") is True, res
    assert 0 < res["dispatches_per_tick"] <= 1.0
    legs = res["legs"]
    assert set(legs) == {"mixed", "mixed_xla_tail"}
    assert legs["mixed"]["mixed_prefill_tokens"] > 0
    assert legs["mixed"]["mixed_decode_tokens"] > 0
    assert set(legs["mixed"]["compile_counts"]) == {"mixed_step"}
    assert (legs["mixed"]["compile_counts"]["mixed_step"]
            <= len(legs["mixed"]["buckets"]))
    assert res["ragged_kernel_probe"] == "ok"  # interpret mode on CPU
    # the fused-vs-unfused pair (tick-tail fusion acceptance): token
    # parity at identical arrivals, the one-fetch ceiling on BOTH
    # unified legs, no extra compiles on the fused path, and the delta
    # fields slo_gate consumes present
    assert res["token_parity_fused_vs_xla_tail"] is True
    assert legs["mixed"]["epilogue"] == "fused"  # interpret-mode probe
    assert legs["mixed_xla_tail"]["epilogue"] == "xla"
    assert legs["mixed"]["host_fetches_max"] == 1
    assert legs["mixed_xla_tail"]["host_fetches_max"] == 1
    assert legs["mixed"]["host_sync_us_p99"] > 0
    assert 0.0 <= legs["mixed"]["host_sync_share"] <= 1.0
    assert legs["mixed"]["dispatches_per_tick"] <= 1.0
    assert set(legs["mixed_xla_tail"]["compile_counts"]) == {"mixed_step"}
    assert (legs["mixed"]["compile_counts"]["mixed_step"]
            == legs["mixed_xla_tail"]["compile_counts"]["mixed_step"])
    assert "host_sync_p99_delta_us" in res
    assert "roofline_util_delta" in res


def test_serve_spec_smoke_offline():
    """The speculative-serving child: one repetitive-prompt Poisson
    trace through plain and spec-enabled unified-tick engines — token
    parity between the legs (deterministic verify keys), a reported
    acceptance rate with real drafts, ~1 dispatch per tick on the spec
    leg (drafting is host-side), and slo_gate-compatible leg fields."""
    res = bench._spawn("smoke_serve_spec", 600)
    assert res.get("ok") is True, res
    assert res["token_parity_spec_vs_plain"] is True
    legs = res["legs"]
    assert legs["spec"]["spec_drafted_tokens"] > 0
    assert 0.0 <= res["acceptance_rate"] <= 1.0
    # drafting never adds dispatches: verify lanes ride the ONE mixed
    # dispatch per tick
    assert res["dispatches_per_tick"] <= 1.0
    # the repetitive workload is the draft's win case: the spec leg must
    # actually accept drafts and finish in fewer ticks
    assert legs["spec"]["spec_accepted_tokens"] > 0
    assert legs["spec"]["ticks"] < legs["plain"]["ticks"]
    # slo_gate-compatible summary fields on both legs
    for leg in legs.values():
        assert "goodput_tok_s" in leg and "slo_attainment" in leg
    assert set(legs["spec"]["compile_counts"]) == {"mixed_step"}


def test_serve_tier_smoke_offline():
    """The tiered-KV child: one capacity-stressed shared-prompt trace
    (prefix working set past pool capacity, distinct prompts cycled so
    every repeat outlives its cached blocks) through tier-off and
    tier-on engines — the ISSUE's acceptance bar: strictly higher
    prefix hit-rate AND strictly fewer prefill tokens dispatched on the
    tier leg, real restores with a reported latency p99, token parity
    (restored K/V is bit-identical to recompute), and zero compiles
    added by the tier (one warmed restore/slice program each)."""
    res = bench._spawn("smoke_serve_prefix_tiered", 600)
    assert res.get("ok") is True, res
    assert res["token_parity_tier_vs_off"] is True
    assert res["prefix_hit_rate"] > res["prefix_hit_rate_off"]
    assert res["prefill_tokens"] < res["prefill_tokens_off"]
    assert res["restored_blocks"] > 0
    assert res["restore_s_p99"] > 0
    assert res["compiles_added_by_tier"] == 0
    # the workload actually stressed capacity (the whole point): the
    # shareable working set exceeds the pool and the tier-off leg
    # visibly evicted
    assert res["working_set_over_capacity"] > 1.0
    legs = res["legs"]
    assert legs["tier_off"]["prefix_evicted_blocks"] > 0
    assert legs["tier_on"]["tier_spilled_blocks"] > 0
    # the tier's two programs compile exactly once each; mixed_step
    # stays at its warmed bucket count
    assert legs["tier_on"]["compile_counts"]["restore_block"] == 1
    assert legs["tier_on"]["compile_counts"]["slice_block"] == 1
    # slo_gate-compatible summary fields on both legs
    for leg in legs.values():
        assert "goodput_tok_s" in leg and "slo_attainment" in leg


def test_serve_tenant_smoke_offline():
    """The multi-tenant fairness child: three skewed-rate per-tenant
    Poisson processes merged into one arrival schedule, replayed
    fairness-off vs fairness-on — per-tenant attainment/goodput/cost
    share from the TenantLedger on both legs, token parity (fairness
    reorders prefill scheduling, never content), and zero compiles
    added by either leg (ordering is host-side)."""
    res = bench._spawn("smoke_serve_tenant", 600)
    assert res.get("ok") is True, res
    assert res["token_parity_fair_vs_off"] is True
    assert res["compiles_added_by_fairness"] == 0
    legs = res["legs"]
    mix = res["tenant_mix"]
    assert set(mix) == {"chat", "complete", "batch"}
    for leg in legs.values():
        assert leg["compiles_added_by_trace"] == 0
        tenants = leg["tenants"]
        # every configured tenant accounted, request counts conserved
        assert set(tenants) == set(mix)
        for t, d in tenants.items():
            assert d["requests"] == mix[t]["requests"]
            assert d["tokens"] > 0
            assert 0.0 <= d["cost_share"] <= 1.0
            # the slo_gate --min-tenant-attainment inputs are present
            assert d["slo_attainment"] is not None
            assert d["goodput_tok_s"] >= 0
        assert abs(sum(d["cost_share"] for d in tenants.values())
                   - 1.0) < 1e-3
    # the headline pair slo_gate reads
    assert res["worst_tenant_attainment"] is not None
    assert res["worst_tenant_attainment_off"] is not None


def test_serve_sharded_smoke_offline():
    """The mesh-sharded serving child: one shared-prompt trace over
    single-chip / TP=2 / DP=2xTP=2 legs on the 8-virtual-device CPU
    backend — token parity across every topology and routed
    shared-prompt traffic with zero spills."""
    res = bench._spawn("smoke_serve_sharded", 600, env={
        "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    })
    assert res.get("ok") is True, res
    assert res["token_parity_across_legs"] is True
    legs = res["legs"]
    assert "skipped" not in legs["tp"] and "skipped" not in legs["dp_tp"]
    assert "kv-sharded" in legs["tp"]["mesh"]
    assert legs["dp_tp"]["router_spilled"] == 0
    assert legs["dp_tp"]["router_routed"] == res["requests"]
    for leg in legs.values():
        assert leg["tok_s_per_chip"] > 0
        assert leg["prefix_hit_rate"] > 0
    assert res["platform"] == "cpu"  # every child stamps its device


@pytest.mark.http
def test_serve_http_smoke_offline():
    """The HTTP loadgen child: the same trace through direct engine calls
    and the in-process HTTP server (ephemeral loopback port), with token
    parity between the legs and the overhead delta recorded."""
    res = bench._spawn("smoke_serve_http", 600)
    assert res.get("ok") is True, res
    assert res["token_parity_http_vs_direct"] is True
    # both legs timed a first token; which is the slower is a device
    # number, and six test workers sharing a CPU do not decide it
    assert res["ttft_s_p50_http"] > 0 and res["ttft_s_p50_direct"] > 0
    assert res["metrics_scrape_ok"] is True
    # the default engine, the tick that is served: one program, at most
    # one compile a packed-width bucket across all three legs
    assert set(res["compile_counts"]) == {"mixed_step"}
    assert 1 <= res["compile_counts"]["mixed_step"] <= len(res["buckets"])


@pytest.mark.http
@pytest.mark.chaos
def test_serve_chaos_smoke_offline():
    """The chaos child: clean leg vs seeded-fault leg (tick crash +
    decode fault + transient 429s) on CPU with the tiny model — every
    request completes, recovery is token-identical, the restart and
    recovery latency are recorded, and the decode step never
    recompiles."""
    res = bench._spawn("smoke_serve_chaos", 600)
    assert res.get("ok") is True, res
    assert res["token_parity_chaos_vs_clean"] is True
    assert res["restarts"] >= 1
    assert res["faults_injected"]["injected_tick_crash"] == 1
    assert res["recovery_latency_s_max"] > 0
    assert res["client_retries_total"] >= 2  # the injected 429s
    # the served tick, restarted and degraded to its XLA twins: still
    # one program, compiled at most once a bucket by the final engine
    assert res["decode_impl_final"] == "xla"
    assert set(res["compile_counts"]) == {"mixed_step"}
    assert 1 <= res["compile_counts"]["mixed_step"] <= len(res["buckets"])


@pytest.mark.http
@pytest.mark.proc
def test_serve_restart_smoke_offline():
    """The kill -9 durability child: plain / journaled / SIGKILL+respawn
    server subprocesses on one trace — token parity across the kill,
    at least one client resumed via Last-Event-ID, the journal overhead
    pair recorded (with the off-thread fsync p99), and a clean final
    drain leaving an empty replay set."""
    res = bench._spawn("smoke_serve_restart", 600)
    # (a string: pytest cuts a dict's repr short, and ``ok`` is eleven
    # conditions)
    assert res.get("ok") is True, json.dumps(res)
    assert res["token_parity_journaled_vs_plain"] is True
    assert res["token_parity_across_kill"] is True
    assert res["streams_resumed"] >= 1
    # None is legal when every cut landed after a stream's final token
    # (the resume then replays only the parked finish)
    lat = res["restart_to_first_resumed_token_s"]
    assert lat is None or lat > 0
    assert res["journal_fsync_p99_s"] is not None
    assert res["journal_replayed_total"] >= 1
    assert res["journal_resumed_total"] >= 1
    # counts, not a tokens/s floor: every journaled token arrived, and
    # the resumed streams delivered theirs across the kill
    assert res["journal_overhead_ok"] is True
    assert res["tokens_resumed"] >= res["streams_resumed"] >= 1
    assert res["drain_left_unterminated"] == 0


def test_serve_rolling_smoke_offline(tmp_path):
    """The rolling-upgrade child: ONE trace over a 3-replica fleet,
    steady vs rolling legs — zero dropped streams, token parity across
    the full roll, zero compiles for the same-shaped swap, and the
    degradation pair — then the slo_gate CLI consumes the capture with
    ``--max-p99-ttft-degradation`` (pass at a generous bound, fail at
    an impossible one: the gate must be able to bite)."""
    res = bench._spawn("smoke_serve_rolling", 600)
    assert res.get("ok") is True, res
    assert res["dropped_streams"] == 0
    assert res["token_parity_across_roll"] is True
    assert res["rolled"] == [0, 1, 2]
    assert res["compiles_added_by_roll"] == 0
    assert res["weights_versions"] == [1, 1, 1]
    assert res["lifecycle_actions"].get("upgrade_replica") == 3
    assert res["ttft_p99_degradation"] > 0
    capture = tmp_path / "rolling.json"
    capture.write_text(json.dumps(res))
    from tools.slo_gate import main as gate_main

    # CPU tick jitter makes the ratio noisy; the smoke pins the WIRING
    # (gate reads the capture, passes a loose bound, fails a sub-1.0
    # one — a roll can't beat steady-state p99)
    assert gate_main([str(capture),
                      "--max-p99-ttft-degradation", "1000"]) == 0
    assert gate_main([str(capture),
                      "--max-p99-ttft-degradation", "0.001"]) == 1


def test_decomp_smoke_offline():
    """The decomp diagnostic child (fixed-vs-per-layer split) runs
    end-to-end on CPU with the tiny model: rate sources are recorded, and
    the per-layer/fixed split only appears when both depths were
    transport-cancelled (never from mixed marginal/e2e rates)."""
    res = bench._spawn(
        "decomp", 600,
        env={"DECOMP_MODEL": "tiny"},
    )
    assert res.get("ok") is True, res
    for mode in ("bf16", "int8", "int8_a8"):
        block = res[mode]
        assert block["step_ms"] > 0
        assert set(block["rate_sources"]) <= {"marginal", "e2e"}
        if block["rate_sources"] != ["marginal", "marginal"]:
            assert "per_layer_ms" not in block
            assert "skipped" in block["decomposition"]
    assert "lm_head_ms" in res
