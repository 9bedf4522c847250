"""Tiered KV prefix cache (serve/host_tier.py + the engine/fleet wiring).

The tier's acceptance bar is OUTPUT INVISIBILITY plus the capacity win:
restored blocks must be bit-identical to what spilled (so every stream
is token-identical to the tier-off engine on the same arrivals), the
tier-on engine must dispatch strictly fewer prefill tokens once the
working set outgrows the pool, restores must land as ordinary pool
blocks through ONE compiled program (zero recompiles across churn,
clone_fresh carries the tier), the restore-vs-recompute breakeven is
measured and a forced below-breakeven case falls back to re-prefill,
and the fleet's drain/re-home paths ship prefix blocks through the
shared tier so the destination replica serves a re-homed prefix with
zero re-prefilled prefix tokens.

CPU backend; restores exercise the real jax.device_put staging path.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import ServeEngine
from llm_np_cp_tpu.serve.block_pool import FreeList
from llm_np_cp_tpu.serve.host_tier import HostBlock, HostTier
from llm_np_cp_tpu.serve.prefix_cache import PrefixCache
from tools.compile_counter import (
    CompileCounter,
    assert_serve_compiles_bounded,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, tier=None, *, num_blocks=12, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        num_blocks=num_blocks, enable_prefix_cache=True, host_tier=tier,
        **kw,
    )


def _churn_prompts(rng, n=6, size=24):
    """Distinct random prompts whose combined shareable prefix blocks
    exceed the 12-block test pool — the capacity-stress workload."""
    return [rng.integers(1, 50, size=size).astype(np.int32)
            for _ in range(n)]


def _run_rounds(eng, prompts, rounds=2, max_new=4):
    for _ in range(rounds):
        for p in prompts:
            eng.submit(p, max_new)
            eng.run_until_complete()
            if eng.host_tier is not None:
                # join the spill writer before the next request: a block
                # its admission looks up is in the tier or it is not,
                # never "still being copied" (a miss that re-prefills,
                # one run in three on a loaded machine)
                assert eng.host_tier.drain()


def _tokens(eng):
    return {r.req_id: list(r.generated) for r in eng.scheduler.finished}


# ---------------------------------------------------------------------------
# HostTier units
# ---------------------------------------------------------------------------

def test_host_tier_roundtrip_bit_identical():
    tier = HostTier(1 << 20)
    rng = np.random.default_rng(0)
    blocks = {
        bytes([i]) * 4: (
            rng.standard_normal((2, 8, 1, 4)).astype(np.float32),
            rng.standard_normal((2, 8, 1, 4)).astype(np.float32),
        )
        for i in range(4)
    }
    for key, (k, v) in blocks.items():
        tier.enqueue_spill(key, jnp.asarray(k), jnp.asarray(v))
    assert tier.drain()
    assert len(tier) == 4
    assert tier.match(list(blocks)) == 4
    for i, (key, (k, v)) in enumerate(blocks.items()):
        ticket = tier.enqueue_restore(key, block_id=i + 1)
        (res,) = tier.take_restored([ticket])
        assert res is not None
        blk_id, staged, dt = res
        assert blk_id == i + 1 and dt >= 0.0
        np.testing.assert_array_equal(np.asarray(staged.k), k)
        np.testing.assert_array_equal(np.asarray(staged.v), v)
    st = tier.stats()
    assert st["spilled_blocks"] == 4 and st["restored_blocks"] == 4
    assert st["restored_bytes"] == st["spilled_bytes"]
    assert st["restore_s_p99"] > 0.0
    tier.close()


def test_host_tier_lru_capacity_eviction_and_miss():
    one = np.zeros((2, 8, 1, 4), np.float32)  # 256 B per array
    tier = HostTier(one.nbytes * 2 * 3 + 1)  # room for 3 blocks
    keys = [bytes([i]) * 4 for i in range(5)]
    for i, key in enumerate(keys):
        tier.enqueue_spill(key, jnp.asarray(one + i), jnp.asarray(one - i))
    tier.drain()
    # LRU: the two oldest dropped to stay under capacity
    assert len(tier) == 3
    assert tier.match(keys[2:]) == 3 and not tier.contains(keys[0])
    assert tier.stats()["dropped_blocks"] == 2
    assert tier.resident_bytes <= tier.capacity_bytes
    # a restore of a dropped key is a MISS, not an error
    ticket = tier.enqueue_restore(keys[0], block_id=7)
    (res,) = tier.take_restored([ticket])
    assert res is None
    assert tier.stats()["restore_misses"] == 1
    # a duplicate spill of a resident key is a no-op touch
    tier.enqueue_spill(keys[2], jnp.asarray(one), jnp.asarray(one))
    tier.drain()
    assert tier.stats()["spilled_blocks"] == 5 and len(tier) == 3
    tier.close()


def test_host_tier_breakeven_policy():
    tier = HostTier(1 << 20)
    # unmeasured: optimistic default (restores are bit-identical, so
    # the default is correctness-neutral)
    assert tier.breakeven_ratio(8) is None
    assert tier.should_restore(2, 8)
    # measured: restoring one block much cheaper than re-prefilling it
    tier.set_measured(restore_s_per_block=1e-4, prefill_tok_s=100.0)
    assert tier.breakeven_ratio(8) == pytest.approx(800.0)
    assert tier.should_restore(2, 8)
    # measured the other way: re-prefill wins, restore declined
    tier.set_measured(restore_s_per_block=10.0, prefill_tok_s=1e9)
    assert tier.breakeven_ratio(8) < 1.0
    assert not tier.should_restore(2, 8)
    # operator/test overrides beat the measurement
    tier.policy = "always"
    assert tier.should_restore(2, 8)
    tier.policy = "never"
    assert not tier.should_restore(2, 8)
    # the EWMA refines, never jumps
    tier.policy = "auto"
    tier.note_prefill_rate(1e9)
    tier.note_prefill_rate(1.0)
    assert tier.prefill_tok_s < 1e9
    tier.close()


def test_host_tier_validation_and_engine_gate(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="capacity_bytes"):
        HostTier(0)
    tier = HostTier(1 << 20)
    with pytest.raises(ValueError, match="prefix_cache"):
        ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                    max_slots=2, num_blocks=12, block_size=8,
                    max_seq_len=64, cache_dtype=jnp.float32, host_tier=tier)
    tier.close()


# ---------------------------------------------------------------------------
# Reclaim visibility (tier off — the previously-silent eviction)
# ---------------------------------------------------------------------------

def test_prefix_eviction_counted_without_tier(tiny):
    cfg, params = tiny
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    tracer = TraceRecorder()
    eng = _engine(cfg, params, tracer=tracer)
    rng = np.random.default_rng(3)
    _run_rounds(eng, _churn_prompts(rng), rounds=2)
    snap = eng.metrics.snapshot()
    assert snap["prefix_evicted_blocks"] > 0
    assert snap["prefix_evicted_bytes"] > 0
    # tier-off: evictions are NOT spills, and no tier series appears
    assert "tier_spilled_blocks" not in snap
    text = eng.metrics.prometheus()
    assert "llm_serve_prefix_evicted_total" in text
    assert "llm_serve_kv_tier_blocks_total" not in text
    evicts = [e for e in tracer.events()
              if e.get("name") == "prefix-evict"]
    assert evicts, "LRU reclaim left no trace instant"
    args = evicts[0]["args"]
    assert args["blocks"] == 1 and args["bytes"] > 0
    assert args["spilled"] is False


# ---------------------------------------------------------------------------
# Engine spill/restore: parity, fewer prefill tokens, ledgers
# ---------------------------------------------------------------------------

def test_tier_restore_parity_and_fewer_prefill_tokens(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(0)
    prompts = _churn_prompts(rng)
    tier = HostTier(64 << 20)
    on = _engine(cfg, params, tier)
    _run_rounds(on, prompts)
    off = _engine(cfg, params, None)
    _run_rounds(off, prompts)
    assert _tokens(on) == _tokens(off), "tier changed tokens"
    s_on, s_off = on.metrics.snapshot(), off.metrics.snapshot()
    # round 2 restored instead of re-prefilling: strictly fewer prefill
    # tokens and a strictly higher hit rate on identical arrivals
    assert s_on["mixed_prefill_tokens"] < s_off["mixed_prefill_tokens"]
    assert (s_on.get("prefix_hit_rate", 0.0)
            > s_off.get("prefix_hit_rate", 0.0))
    st = tier.stats()
    assert st["restored_blocks"] > 0 and st["restore_misses"] == 0
    # the metrics ledgers mirror the tier's own accounting
    assert s_on["tier_restored_blocks"] == st["restored_blocks"]
    assert s_on["tier_restored_bytes"] == st["restored_bytes"]
    # the spill LEDGER counts blocks actually enqueued (a re-eviction
    # of an already-resident key moves no bytes), so it tracks the
    # tier's own accounting and never exceeds the eviction count
    assert s_on["tier_spilled_blocks"] == st["spilled_blocks"]
    assert 0 < s_on["tier_spilled_blocks"] <= s_on["prefix_evicted_blocks"]
    assert s_on["tier_restore_s_p99"] > 0.0
    assert s_on["tier_breakeven_ratio"] > 0.0
    text = on.metrics.prometheus()
    assert 'llm_serve_kv_tier_blocks_total{op="restore"}' in text
    assert "llm_serve_kv_tier_breakeven_ratio" in text
    assert "kv tier:" in on.metrics.format()
    tier.close()


def test_tier_below_breakeven_falls_back_to_reprefill(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(1)
    prompts = _churn_prompts(rng)
    tier = HostTier(64 << 20)
    on = _engine(cfg, params, tier)
    # pin the measurement to "re-prefilling is much cheaper" AFTER the
    # engine build (the build's startup probe measures the real
    # restore side): every host hit must now decline and re-prefill.
    # The tick-measured prefill rates keep refining the EWMA, but the
    # pinned restore_s_per_block keeps the ratio far below 1.
    tier.set_measured(restore_s_per_block=100.0, prefill_tok_s=1e9)
    _run_rounds(on, prompts)
    off = _engine(cfg, params, None)
    _run_rounds(off, prompts)
    assert _tokens(on) == _tokens(off)
    st = tier.stats()
    assert st["restored_blocks"] == 0, "below-breakeven span restored"
    assert st["skipped_blocks"] > 0, "no host hit ever declined"
    # identical prefill work to the tier-less engine: the fallback IS
    # drop-and-recompute
    assert (on.metrics.snapshot()["mixed_prefill_tokens"]
            == off.metrics.snapshot()["mixed_prefill_tokens"])
    tier.close()


def test_tier_zero_recompiles_and_clone_fresh_carries(tiny):
    cfg, params = tiny
    rng = np.random.default_rng(4)
    prompts = _churn_prompts(rng)
    tier = HostTier(64 << 20)
    eng = _engine(cfg, params, tier)
    eng.warmup([int(p.size) for p in prompts], max_new_tokens=4)
    warm = dict(eng.compile_counts())
    assert warm["restore_block"] == 1 and warm["slice_block"] == 1
    with CompileCounter().watch() as counter:
        _run_rounds(eng, prompts, rounds=3)
    assert counter.count == 0, (
        f"tier-on churn compiled: {counter.events}"
    )
    assert eng.compile_counts() == warm
    assert tier.stats()["restored_blocks"] > 0
    assert_serve_compiles_bounded(eng)

    # clone_fresh carries the tier and shares every compiled program;
    # the rebuilt engine's ZEROED pool restores from host RAM — the
    # host entries outlive the crash.  The crashed requests are still
    # queued (no tokens yet): their teacher-forced re-admission keeps
    # the original left-pad, so the spilled chains match exactly
    live = [eng.submit(p, 4) for p in prompts[:2]]
    rebuilt = eng.clone_fresh()
    assert rebuilt.host_tier is tier
    assert rebuilt._restore_block is eng._restore_block
    assert rebuilt._slice_block is eng._slice_block
    restored_before = tier.stats()["restored_blocks"]
    with CompileCounter().watch() as counter:
        for r in live:
            rebuilt.recover(r.prompt, r.max_new_tokens,
                            request_id=r.req_id, seed=r.seed,
                            generated=list(r.generated))
        rebuilt.run_until_complete()
    assert counter.count == 0, (
        f"tiered restart replay compiled: {counter.events}"
    )
    assert tier.stats()["restored_blocks"] > restored_before, (
        "the rebuilt engine's zeroed pool never restored from host"
    )
    tier.close()


def test_tier_eviction_requeue_interplay(tiny):
    """Preemption churn (evict-requeue) on a starved pool with the tier
    on: requeued re-prefills may themselves restore, and every stream
    stays token-identical to the tier-off twin."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompts = _churn_prompts(rng, n=4, size=20)
    legs = {}
    for name, tier in (("on", HostTier(64 << 20)), ("off", None)):
        # 8 allocatable blocks, two concurrent requests growing to 5
        # blocks each: decode growth MUST preempt the youngest
        eng = _engine(cfg, params, tier, num_blocks=9)
        for rnd in range(2):
            for p in prompts:
                eng.submit(p, 16)
            eng.run_until_complete()
        if tier is not None:
            tier.drain()
        legs[name] = (eng, tier)
    on, tier = legs["on"]
    off, _ = legs["off"]
    assert _tokens(on) == _tokens(off)
    assert on.metrics.snapshot()["preemptions"] > 0, (
        "workload never preempted — the interplay was not exercised"
    )
    assert tier.stats()["restored_blocks"] > 0
    held = on.pool.stats()["request_held"]
    assert held == 0, f"tier churn leaked {held} blocks"
    tier.close()


# ---------------------------------------------------------------------------
# Observability: trace instants, tick args, summarize_trace section
# ---------------------------------------------------------------------------

def test_tier_trace_instants_tick_args_and_summary(tiny):
    cfg, params = tiny
    from llm_np_cp_tpu.serve.tracing import TraceRecorder
    from tools.summarize_trace import format_summary, kv_tier

    tracer = TraceRecorder()
    tier = HostTier(64 << 20)
    eng = _engine(cfg, params, tier, tracer=tracer)
    rng = np.random.default_rng(6)
    _run_rounds(eng, _churn_prompts(rng))
    events = tracer.events()
    evicts = [e for e in events if e.get("name") == "prefix-evict"]
    assert evicts and evicts[0]["args"]["spilled"] is True
    restores = [e for e in events if e.get("name") == "kv-restore"]
    assert restores, "no restore instant traced"
    assert restores[0]["args"]["bytes"] > 0
    assert restores[0]["args"]["restore_us"] > 0
    ticks = [e for e in events
             if e.get("ph") == "X" and e.get("cat") == "tick"]
    assert all("tier_spill_bytes" in (t.get("args") or {}) for t in ticks)
    assert sum(t["args"]["tier_restore_bytes"] for t in ticks) > 0
    sec = kv_tier(events)
    assert sec is not None
    assert sec["restore_bytes"] > 0 and sec["spill_bytes"] > 0
    assert sec["restore_us_p99"] > 0
    assert "== kv_tier ==" in format_summary(events)
    tier.close()


# ---------------------------------------------------------------------------
# Churn stress: 2000 steps of claims / decrefs / spill / restore
# ---------------------------------------------------------------------------

def test_tier_churn_stress_2000_steps():
    """Host-level stress over the real FreeList + PrefixCache +
    HostTier trio (the allocator math the engine runs, minus the
    model): 2000 random steps mixing registration, claims (sharers),
    decrefs, LRU reclaim-with-spill, and restores into freshly claimed
    blocks.  Invariants at every step: a restore never targets a
    free-listed block (jobs are enqueued only for blocks the claimant
    owns), the free list and the allocated set stay disjoint, and every
    restored payload is bit-identical to what spilled."""
    rng = np.random.default_rng(7)
    fl = FreeList(24)
    pc = PrefixCache(fl)
    tier = HostTier(48 * 2 * 64 * 4)  # ~48 two-array blocks of 64 floats
    truth: dict[bytes, np.ndarray] = {}

    def on_reclaim(key, blk):
        arr = truth[key]
        tier.enqueue_spill(key, jnp.asarray(arr), jnp.asarray(arr + 1))

    pc.on_reclaim = on_reclaim
    next_key = 0
    claims: list[int] = []  # extra references we hold (sharers)

    def check_invariants():
        free = set(fl._free)
        assert free.isdisjoint(fl._ref), "free list overlaps allocated"
        assert 0 not in free, "scratch block leaked into the free list"

    for step in range(2000):
        op = rng.integers(0, 5)
        if op == 0:  # register fresh content
            ids = fl.alloc(1) or (pc.release(1) and fl.alloc(1))
            if ids:
                key = next_key.to_bytes(8, "little")
                next_key += 1
                truth[key] = rng.standard_normal(64).astype(np.float32)
                pc.register([key], ids)
                fl.free(ids)  # the "request" finishes; cache ref remains
        elif op == 1 and len(pc):  # a sharer claims, holds
            key = list(pc._entries)[int(rng.integers(0, len(pc)))]
            got = pc.claim([key])
            claims.extend(got)
        elif op == 2 and claims:  # a sharer finishes (decref)
            fl.free([claims.pop(int(rng.integers(0, len(claims))))])
        elif op == 3:  # pool pressure: LRU reclaim spills
            pc.release(int(rng.integers(1, 3)))
        elif op == 4 and len(tier):  # restore into a claimed block
            keys = list(tier._wentries)
            key = keys[int(rng.integers(0, len(keys)))]
            ids = fl.alloc(1)
            if ids is None:
                pc.release(1)
                ids = fl.alloc(1)
            if ids:
                ticket = tier.enqueue_restore(key, ids[0])
                (res,) = tier.take_restored([ticket])
                # the target is OURS: never free-listed while staged
                assert ids[0] not in fl._free
                if res is not None:
                    blk_id, staged, _ = res
                    assert blk_id == ids[0]
                    np.testing.assert_array_equal(
                        np.asarray(staged.k), truth[key])
                    np.testing.assert_array_equal(
                        np.asarray(staged.v), truth[key] + 1)
                fl.free(ids)
        if step % 50 == 0:
            tier.drain()
            check_invariants()
    tier.drain()
    check_invariants()
    st = tier.stats()
    assert st["spilled_blocks"] > 50, "stress never spilled — bad mix"
    assert st["restored_blocks"] > 50, "stress never restored — bad mix"
    for blk in claims:
        fl.free([blk])
    tier.close()


# ---------------------------------------------------------------------------
# Fleet: drain/re-home and router-spill ship blocks through the tier
# ---------------------------------------------------------------------------

def test_fleet_rehome_ships_blocks_zero_prefix_reprefill(tiny):
    """remove_replica re-homes the prefix; the destination must serve
    it with ZERO re-prefilled prefix tokens — only the never-shareable
    last chunk dispatches (the prefill-token ledger is the proof)."""
    cfg, params = tiny
    from llm_np_cp_tpu.serve.replica import ReplicaSet

    tier = HostTier(64 << 20)
    fleet = ReplicaSet([
        _engine(cfg, params, tier, num_blocks=24),
        _engine(cfg, params, tier, num_blocks=24),
    ])
    rng = np.random.default_rng(8)
    prompt = rng.integers(1, 50, size=24).astype(np.int32)
    first = fleet.submit(prompt, 4)
    src = first.extra["replica"]
    fleet.run_until_complete()
    fleet.remove_replica(src)
    tier.drain()
    assert tier.stats()["spilled_blocks"] > 0, "drain shipped nothing"

    dst = 1 - src
    pf0 = fleet.engines[dst].metrics.snapshot()["mixed_prefill_tokens"]
    again = fleet.submit(prompt, 4)
    assert again.extra["replica"] == dst, "prefix did not re-home"
    fleet.run_until_complete()
    snap = fleet.engines[dst].metrics.snapshot()
    # the whole shareable prefix restored: prefill dispatched ONLY the
    # last chunk (prefill_chunk == block_size here)
    chunk = fleet.engines[dst].prefill_chunk
    shareable = again.n_shared_blocks * fleet.engines[dst].block_size
    assert shareable > 0
    assert snap["mixed_prefill_tokens"] - pf0 == prompt.size - shareable
    assert snap["mixed_prefill_tokens"] - pf0 <= chunk
    assert snap["tier_restored_blocks"] > 0
    assert again.generated == first.generated, "re-homed stream diverged"
    tier.close()


def test_fleet_router_spill_ships_chain(tiny):
    """A spill verdict lands a request OFF its affine replica; the
    affine replica ships the chain host-side so the spill target
    restores instead of re-prefilling."""
    cfg, params = tiny
    from llm_np_cp_tpu.serve.replica import ReplicaSet

    tier = HostTier(64 << 20)
    fleet = ReplicaSet(
        [_engine(cfg, params, tier, num_blocks=24),
         _engine(cfg, params, tier, num_blocks=24)],
        spill_queue_depth=1,
    )
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, 50, size=24).astype(np.int32)
    first = fleet.submit(prompt, 4)
    src = first.extra["replica"]
    fleet.run_until_complete()
    # pile un-stepped queue depth onto the affine replica, then submit
    # the same prefix: the router spills it to the idle peer
    blockers = [fleet.submit(rng.integers(1, 50, size=20), 4,
                             replica=src) for _ in range(3)]
    spilled = fleet.submit(prompt, 4)
    assert spilled.extra.get("spilled") is True
    dst = spilled.extra["replica"]
    assert dst != src
    tier.drain()
    fleet.run_until_complete()
    assert fleet.engines[dst].metrics.snapshot().get(
        "tier_restored_blocks", 0) > 0, (
        "spill target re-prefilled a chain the affine replica held"
    )
    assert spilled.generated == first.generated
    assert all(b.state.value == "finished" for b in blockers)
    tier.close()
