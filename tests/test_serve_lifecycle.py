"""Zero-downtime fleet lifecycle (serve/lifecycle.py + replica.py).

The contract being pinned: the fleet survives OPERATORS, not just
crashes.  A rolling checkpoint upgrade drains one replica at a time to
its peers (16+ live streams complete token-identically across a full
3-replica roll, zero dropped/duplicated tokens), requests are served
end-to-end under ONE weight version (journal admission records and
request-log lines carry ``weights_version``), a same-weights roll adds
ZERO compiles and a new-weights roll re-jits once per FLEET (the rolled
replicas share one step callable).  A checkpoint failure mid-roll
aborts cleanly — the replica stays live on old weights, the fleet never
drops below N-1.  Elastic DP: ``remove_replica`` under load completes
every in-flight stream on peers; ``add_replica`` joins warm and takes
traffic first-sight.  Auto-actions: an injected sustained host_sync
regression sheds prefill budget, a burn spike flips admission to
503-first shedding — both counted, traced, and REVERSIBLE.
"""

import asyncio
import json
import sys

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
    ActionPolicy,
    Autoscaler,
    FaultInjector,
    LifecycleController,
    ReplicaRunner,
    ReplicaSet,
    RequestJournal,
    RequestLog,
    ServeEngine,
    SLOPolicy,
    SLOTracker,
    TickSentinel,
    TraceRecorder,
    UpgradeAborted,
    read_request_log,
    scan_journal,
)
from llm_np_cp_tpu.serve.faults import install
from llm_np_cp_tpu.serve.http.client import (
    astream_completion,
    http_get,
    http_post,
)
from llm_np_cp_tpu.serve.http.server import HttpServer
from llm_np_cp_tpu.serve.journal import iter_records


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(
        "llama", num_attention_heads=8, num_key_value_heads=4,
        head_dim=8, hidden_size=64,
    )
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


@pytest.fixture(autouse=True)
def _clean_chaos_globals():
    yield
    install(None)


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    # "on" (not "auto"): the unified tick is the path plan_tick budget
    # shedding acts on, and forcing it keeps the compile-count pins
    # deterministic on CPU (XLA ragged fallback)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"), **kw)


def _offline(cfg, params, prompt, max_tokens):
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                    cache_dtype=jnp.float32)
    res = gen.generate_ragged([np.asarray(prompt, np.int32)], max_tokens)
    return [int(t) for t in np.asarray(res.tokens)[0][:max_tokens]]


def _streams(fleet):
    return [list(r.generated) for r in fleet.finished]


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def now(self) -> float:
        return self.t


class FakeTracker:
    """A burn-rate stub for policy-level tests (the real SLOTracker path
    is covered by the engine-integrated burn e2e below)."""

    def __init__(self, burn: float) -> None:
        self.burn = burn

    def burn_rate(self, window: str) -> float:
        return self.burn


# ---------------------------------------------------------------------------
# ActionPolicy / Autoscaler policy units (no engines)
# ---------------------------------------------------------------------------

def test_action_policy_shed_prefill_engage_and_release():
    clock = FakeClock()
    p = ActionPolicy(engage_streak=3, release_clean=4,
                     min_flip_interval_s=0.0, clock=clock.now)
    anom = [{"phase": "host_sync"}]
    assert p.on_tick(anom, None) == []
    assert p.on_tick(anom, None) == []
    assert p.on_tick(anom, None) == ["shed_prefill_on"]
    assert p.plan_budget(100, 8) == 8 + int(92 * 0.5)
    # an anomaly on another phase does not extend the streak — for
    # host_sync it is a clean tick like any other
    assert p.on_tick([{"phase": "deliver"}], None) == []
    for _ in range(2):
        assert p.on_tick([], None) == []
    assert p.on_tick([], None) == ["shed_prefill_off"]  # 4th clean tick
    assert p.plan_budget(100, 8) == 100
    assert p.snapshot()["actions_total"] == {
        "shed_prefill_on": 1, "shed_prefill_off": 1,
    }


def test_action_policy_shed_load_hysteresis_and_retry_after():
    clock = FakeClock()
    p = ActionPolicy(burn_threshold=2.0, burn_clear_frac=0.5,
                     min_flip_interval_s=0.0, clock=clock.now)
    assert p.on_tick([], FakeTracker(1.5)) == []
    assert not p.shedding
    assert p.on_tick([], FakeTracker(10.0)) == ["shed_load_on"]
    assert p.shedding
    assert p.retry_after() == 5.0  # burn / threshold, bounded [1, 30]
    # hovering between clear and engage thresholds: no flap
    assert p.on_tick([], FakeTracker(1.5)) == []
    assert p.shedding
    assert p.on_tick([], FakeTracker(0.9)) == ["shed_load_off"]
    assert not p.shedding


def test_action_policy_rate_limits_flips():
    clock = FakeClock()
    p = ActionPolicy(burn_threshold=2.0, min_flip_interval_s=5.0,
                     clock=clock.now)
    assert p.on_tick([], FakeTracker(10.0)) == ["shed_load_on"]
    # the signal cleared instantly, but the flip is rate-limited
    assert p.on_tick([], FakeTracker(0.0)) == []
    assert p.shedding
    clock.t += 6.0
    assert p.on_tick([], FakeTracker(0.0)) == ["shed_load_off"]


def test_action_policy_spawn_is_share_nothing():
    p = ActionPolicy(burn_threshold=3.0, engage_streak=7)
    q = p.spawn()
    assert q is not p
    assert q.burn_threshold == 3.0 and q.engage_streak == 7
    q.on_tick([], FakeTracker(10.0))
    assert q.shedding and not p.shedding


def test_autoscaler_verdicts_and_cooldown():
    clock = FakeClock()
    a = Autoscaler(min_replicas=1, max_replicas=3,
                   scale_up_queue_depth=4.0, scale_up_burn=2.0,
                   scale_down_queue_depth=0.5, cooldown_s=10.0,
                   clock=clock.now)
    assert a.verdict(n_replicas=1, queue_depth_per_replica=8.0) == 1
    # cooldown: the next verdict waits for the last one to take effect
    assert a.verdict(n_replicas=2, queue_depth_per_replica=8.0) == 0
    clock.t += 11.0
    # burn alone also scales up
    assert a.verdict(n_replicas=2, queue_depth_per_replica=0.0,
                     burn_5m=5.0) == 1
    clock.t += 11.0
    # scale-down needs BOTH quiet
    assert a.verdict(n_replicas=3, queue_depth_per_replica=0.0,
                     burn_5m=5.0) == 0
    assert a.verdict(n_replicas=3, queue_depth_per_replica=0.0,
                     burn_5m=0.0) == -1
    clock.t += 11.0
    # floors/ceilings
    assert a.verdict(n_replicas=1, queue_depth_per_replica=0.0) == 0
    assert a.verdict(n_replicas=3, queue_depth_per_replica=9.0) == 0


# ---------------------------------------------------------------------------
# Rolling upgrade: the acceptance e2e
# ---------------------------------------------------------------------------

def test_rolling_upgrade_e2e_16_streams(tiny, tmp_path):
    """16 live streams across a full 3-replica roll: zero dropped or
    duplicated tokens (byte parity vs an unrolled fleet), every
    request-log line reports the single weights_version that admitted
    it, the same-weights roll adds ZERO compiles, and the rolled fleet
    shares ONE step callable (compiled once per fleet)."""
    cfg, params = tiny
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(rng.integers(3, 14)))
               for _ in range(16)]

    def build(request_log=None):
        fleet = ReplicaSet([
            _engine(cfg, params, request_log=request_log)
            for _ in range(3)
        ])
        for e in fleet.engines:
            e.warmup([3], max_new_tokens=6)
        return fleet

    control = build()
    for i, p in enumerate(prompts):
        control.submit(p, 6, seed=i)
    control.run_until_complete()
    want = _streams(control)

    log = RequestLog(str(tmp_path / "req.log"))
    fleet = build(request_log=log)
    for i, p in enumerate(prompts):
        fleet.submit(p, 6, seed=i)
    for _ in range(2):
        fleet.step()  # streams live on every replica when the roll starts
    assert any(e._requests for e in fleet.engines)
    counts0 = dict(fleet.engines[0].compile_counts())
    out = fleet.rolling_upgrade(lambda: params, version=1,
                                steps_between=1)
    assert out["rolled"] == [0, 1, 2] and out["version"] == 1
    assert out["drained"] > 0
    fleet.run_until_complete()

    # zero dropped/duplicated tokens: byte parity with the unrolled run
    assert len(fleet.finished) == 16
    assert _streams(fleet) == want
    assert all(e.weights_version == 1 for e in fleet.engines)

    # compiled once per FLEET: the same-weights swap reused every warm
    # compile (params are jit call arguments)...
    assert dict(fleet.engines[0].compile_counts()) == counts0
    # ...because every rolled replica shares ONE step callable
    assert len({id(e._mixed_step) for e in fleet.engines}) == 1

    # one weights_version per request-log line — all admitted pre-roll,
    # so all report version 0, drains and all
    log.flush(5.0)
    log.close()
    lines = read_request_log(str(tmp_path / "req.log"))
    assert len(lines) == 16
    assert all(line["weights_version"] == 0 for line in lines)
    assert any(line["drains"] >= 1 for line in lines)
    # the roll itself is counted
    agg = {}
    for e in fleet.engines:
        for k, v in e.metrics.snapshot().get(
                "lifecycle_actions", {}).items():
            agg[k] = agg.get(k, 0) + v
    assert agg.get("upgrade_replica") == 3
    # post-roll traffic is admitted (and logged) under the new version
    fleet2 = fleet
    req = fleet2.submit(prompts[0], 2, seed=99)
    assert req.extra["weights_version"] == 1
    fleet2.run_until_complete()


def test_new_weights_roll_compiles_once_per_fleet(tiny):
    """A roll onto genuinely different param avals (bf16 copy of the
    f32 weights) re-traces the shared step ONCE for the whole fleet:
    replica 0's post-roll traffic compiles the new variant, replicas 1
    and 2 reuse it (identical callable, zero further compiles)."""
    cfg, params = tiny
    bf16 = jax.tree.map(
        lambda x: x.astype(jnp.bfloat16)
        if hasattr(x, "astype") else x, params,
    )
    fleet = ReplicaSet([_engine(cfg, params) for _ in range(3)])
    for e in fleet.engines:
        e.warmup([3], max_new_tokens=4)
    fleet.rolling_upgrade(lambda: bf16, version=2, steps_between=0)
    shared = fleet.engines[0]._mixed_step
    assert all(e._mixed_step is shared for e in fleet.engines)

    def counts():
        return fleet.engines[0].compile_counts()["mixed_step"]

    prompt = np.arange(1, 8, dtype=np.int32)
    size0 = counts()
    fleet.submit(prompt, 4, seed=0, replica=0)
    fleet.run_until_complete()
    size1 = counts()
    assert size1 > size0  # the new avals really did re-trace...
    outs = {0: _streams(fleet)[-1]}
    for i in (1, 2):
        fleet.submit(prompt, 4, seed=0, replica=i)
        fleet.run_until_complete()
        outs[i] = _streams(fleet)[-1]
    # ...exactly once per fleet: the other replicas reused the compile
    assert counts() == size1
    # and the rolled fleet is weight-consistent: same prompt+seed →
    # same stream on every replica
    assert outs[0] == outs[1] == outs[2]


def test_rolling_upgrade_fleet_of_one_replays_in_place(tiny):
    """A single-replica fleet has no peer to drain to: the roll replays
    the in-flight streams in place on the rebuilt engine (teacher-
    forced) instead of stranding the fleet at zero alive replicas."""
    cfg, params = tiny
    rng = np.random.default_rng(41)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(rng.integers(4, 12)))
               for _ in range(4)]
    control = ReplicaSet([_engine(cfg, params)])
    for i, p in enumerate(prompts):
        control.submit(p, 6, seed=i)
    control.run_until_complete()
    want = _streams(control)

    fleet = ReplicaSet([_engine(cfg, params)])
    for i, p in enumerate(prompts):
        fleet.submit(p, 6, seed=i)
    fleet.step()
    assert fleet.engines[0]._requests
    out = fleet.rolling_upgrade(lambda: params, version=1,
                                steps_between=0)
    assert out["rolled"] == [0] and fleet.alive == [True]
    fleet.run_until_complete()
    assert _streams(fleet) == want
    assert fleet.engines[0].weights_version == 1


def test_checkpoint_loaded_once_per_roll(tiny):
    """An N-replica roll reads the checkpoint ONCE — the in-process
    replicas share one host, so N full reads of the same weights would
    be pure wasted roll wall-time."""
    cfg, params = tiny
    fleet = ReplicaSet([_engine(cfg, params) for _ in range(3)])
    calls = []

    def loader():
        calls.append(1)
        return params

    fleet.rolling_upgrade(loader, version=1, steps_between=0)
    assert len(calls) == 1
    assert all(e.weights_version == 1 for e in fleet.engines)


@pytest.mark.http
def test_removed_replica_stuck_shed_does_not_shed_fleet(tiny):
    """A shed_load verdict frozen on a removed (or crashed) replica
    must not 503 the whole fleet forever: only SERVING replicas'
    policies vote on admission."""
    cfg, params = tiny
    engines = [
        _engine(cfg, params,
                actions=ActionPolicy(min_flip_interval_s=0.0))
        for _ in range(2)
    ]
    runner = ReplicaRunner(engines, spill_queue_depth=None)

    async def main():
        srv = HttpServer(engines[0], model_id="tiny", drain_timeout=10.0,
                         runner=runner)
        await srv.start("127.0.0.1", 0)
        loop = asyncio.get_running_loop()
        # wedge replica 1's policy into shedding, then remove it — its
        # tick thread can never release the flag
        engines[1].actions.on_tick([], FakeTracker(100.0))
        assert engines[1].actions.shedding
        await loop.run_in_executor(None, runner.remove_replica, 1)
        assert srv._shed_retry_after() is None
        res = await astream_completion(
            srv.host, srv.port,
            {"prompt": [5] * 5, "max_tokens": 3, "stream": True},
            timeout=30)
        assert res["status"] == 200, res
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


def test_upgrade_ckpt_chaos_aborts_cleanly(tiny):
    """The checkpoint read fails while rolling replica 1 (chaos
    ``upgrade_ckpt``): the roll aborts with UpgradeAborted, replica 1
    is untouched on its old weights, replica 0 keeps the new ones, the
    fleet never went below N-1 capacity, and every in-flight stream
    still completes token-identically."""
    cfg, params = tiny
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(rng.integers(4, 12)))
               for _ in range(8)]

    def build(injector=None):
        return ReplicaSet([
            _engine(cfg, params, fault_injector=injector)
            for _ in range(3)
        ])

    control = build()
    for i, p in enumerate(prompts):
        control.submit(p, 5, seed=i)
    control.run_until_complete()
    want = _streams(control)

    # the site is tripped once per replica roll: hit 2 = replica 1
    injector = FaultInjector("upgrade_ckpt@2")
    fleet = build(injector)
    for i, p in enumerate(prompts):
        fleet.submit(p, 5, seed=i)
    fleet.step()
    with pytest.raises(UpgradeAborted) as err:
        fleet.rolling_upgrade(lambda: params, version=1)
    assert err.value.rolled == [0]
    # capacity: every replica is alive and serving right now
    assert fleet.alive == [True, True, True]
    assert [e.weights_version for e in fleet.engines] == [1, 0, 0]
    fleet.run_until_complete()
    assert _streams(fleet) == want
    agg = sum(
        e.metrics.snapshot().get("lifecycle_actions", {})
        .get("upgrade_aborted", 0)
        for e in fleet.engines
    )
    assert agg == 1


def test_weights_version_journal_roundtrip(tiny, tmp_path):
    """Admission records journal the serving weight version; it
    survives ``_apply``, compaction, and the runner's replay — a
    post-restart request-log line still reports the version that
    actually served the stream."""
    cfg, params = tiny
    path = str(tmp_path / "j")
    j = RequestJournal(path, compact_bytes=1)  # compact every batch
    engine = _engine(cfg, params, journal=j, weights_version=3)
    req = engine.submit([7] * 6, 8, seed=1)
    assert req.extra["weights_version"] == 3
    for _ in range(3):
        engine.step()
    assert j.flush(5.0)
    recs = [r for r in iter_records(path) if r.get("t") == "adm"]
    assert recs and all(r.get("wv") == 3 for r in recs)
    j.close()

    state, _, _ = scan_journal(path)
    assert state[req.req_id]["wv"] == 3

    # the runner replay path re-stamps the ORIGINAL version even though
    # the rebuilt engine runs a newer one
    j2 = RequestJournal(path)
    engine2 = _engine(cfg, params, journal=j2, weights_version=5)
    srv = HttpServer(engine2, model_id="tiny")
    assert srv.runner.journal_replayed == 1
    replayed = engine2._requests[req.req_id]
    assert replayed.extra["weights_version"] == 3
    engine2.run_until_complete()
    j2.close()


def test_direct_drain_terminates_source_journal(tiny, tmp_path):
    """Direct-mode drains (remove_replica / rolling_upgrade via
    ``_drain_to_peers``) must write a ``drained`` terminal into the
    SOURCE replica's journal segment — the peer's ``recover`` re-admits
    the stream into the peer's segment, so an unterminated admission
    left behind would make a restart scanning both segments replay the
    stream twice.  Same rule the HTTP fleet's ``_drain_dead`` pins."""
    cfg, params = tiny
    paths = [str(tmp_path / f"j.{i}") for i in range(2)]
    js = [RequestJournal(p) for p in paths]
    fleet = ReplicaSet(
        [_engine(cfg, params, journal=js[i]) for i in range(2)]
    )
    for i in range(6):
        fleet.submit([5 + i] * 6, 6, seed=i)
    for _ in range(2):
        fleet.step()
    victim = next(
        i for i, e in enumerate(fleet.engines) if e._requests
    )
    drained = fleet.remove_replica(victim)
    assert drained  # it really had in-flight streams to move
    fleet.run_until_complete()
    assert len(fleet.finished) == 6
    for j in js:
        assert j.flush(5.0)
        j.close()
    # the victim's segment: every drained stream is terminated (the
    # pre-fix bug left them unterminated → double replay on restart)
    state_v, _, _ = scan_journal(paths[victim])
    assert state_v == {}
    state_p, _, _ = scan_journal(paths[1 - victim])
    assert state_p == {}


def test_http_drain_prefers_same_version_peer(tiny):
    """A mid-roll HTTP-fleet drain adopts streams onto a peer still on
    the draining replica's weight version when one exists (the
    one-version-end-to-end rule ``_drain_to_peers`` pins for direct
    mode), and falls back to any live peer when none is left."""
    cfg, params = tiny
    fleet = ReplicaRunner([_engine(cfg, params) for _ in range(3)])
    fleet.replicas[0].engine.weights_version = 1  # already rolled
    rec = {"rid": 1, "prompt": [7] * 6, "tokens": [3], "max_tokens": 6,
           "seed": 0}
    adopted = fleet._drain_dead(1, [dict(rec)], prefer_version=0)
    assert adopted == {1}
    assert fleet._owner[1] == 2  # the v0 peer, never rolled replica 0
    # no same-version peer left (the last old-version replica rolling):
    # any live peer adopts — the stream is never dropped
    fleet._dead.discard(1)
    fleet.replicas[2].engine.weights_version = 1
    rec2 = dict(rec, rid=2)
    adopted = fleet._drain_dead(1, [rec2], prefer_version=0)
    assert adopted == {2}
    assert fleet._owner[2] in (0, 2)


# ---------------------------------------------------------------------------
# Elastic DP under load
# ---------------------------------------------------------------------------

def test_elastic_scale_down_under_load(tiny):
    """``remove_replica`` with in-flight streams: every stream the
    removed replica held completes token-identically on a peer, the
    survivors keep serving, and the removed slot never takes traffic
    again."""
    cfg, params = tiny
    rng = np.random.default_rng(23)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(rng.integers(4, 14)))
               for _ in range(12)]

    def build():
        return ReplicaSet([_engine(cfg, params) for _ in range(3)])

    control = build()
    for i, p in enumerate(prompts):
        control.submit(p, 6, seed=i)
    control.run_until_complete()
    want = _streams(control)

    fleet = build()
    for i, p in enumerate(prompts):
        fleet.submit(p, 6, seed=i)
    for _ in range(2):
        fleet.step()
    victim = next(
        i for i, e in enumerate(fleet.engines) if e._requests
    )
    drained = fleet.remove_replica(victim)
    assert drained  # it really had in-flight streams
    assert fleet.alive[victim] is False
    fleet.run_until_complete()
    assert len(fleet.finished) == 12
    assert _streams(fleet) == want
    # new traffic never lands on the removed slot
    req = fleet.submit(prompts[0], 2, seed=50)
    assert req.extra["replica"] != victim
    fleet.run_until_complete()
    snap = fleet.snapshot()
    assert snap["alive_replicas"] == 2
    assert snap["finished"] == 13


@pytest.mark.parametrize("how", ["remove", "roll", "kill-restart"])
def test_direct_mode_moves_hand_every_token_to_the_callback_once(tiny, how):
    """In direct mode ``req.generated`` is the replay ledger, and the
    unified tick holds a tick's accepted tokens until the next dispatch:
    a drain, a roll or a restart first hands out what the replica still
    owes (``publish_owed``), so the adopting engine — which teacher-
    forces ``generated`` and re-emits none of it — starts exactly where
    the callbacks stopped.  Every stream reaches its callback whole,
    each token once, in order, the terminal last."""
    cfg, params = tiny
    rng = np.random.default_rng(35)
    prompts = [rng.integers(1, cfg.vocab_size, size=int(rng.integers(4, 14)))
               for _ in range(8)]
    control = ReplicaSet([_engine(cfg, params) for _ in range(2)])
    for i, p in enumerate(prompts):
        control.submit(p, 7, seed=i)
    control.run_until_complete()
    want = {r.req_id: list(r.generated) for r in control.finished}

    fleet = ReplicaSet([_engine(cfg, params) for _ in range(2)])
    log: dict[int, list] = {}
    for i, p in enumerate(prompts):
        req = fleet.submit(
            p, 7, seed=i,
            callback=lambda r, t, d: log[r.req_id].append(t),
            on_event=lambda r, e: log[r.req_id].append(e))
        log[req.req_id] = []
    for _ in range(3):
        fleet.step()
    victim = next(i for i, e in enumerate(fleet.engines) if e._requests)
    assert fleet.engines[victim]._owed, "nothing owed: the move proves nothing"
    if how == "remove":
        assert fleet.remove_replica(victim)
    elif how == "roll":
        fleet.rolling_upgrade(lambda: params, version=1, steps_between=1)
    else:
        assert fleet.kill_replica(victim)
        assert not fleet.engines[victim]._owed
        fleet.step()  # the peer keeps serving meanwhile
        fleet.restart_replica(victim)
    fleet.run_until_complete()
    assert {r.req_id: list(r.generated) for r in fleet.finished} == want
    for rid, tokens in want.items():
        assert log[rid] == tokens + ["length"], rid
    assert not any(e._owed for e in fleet.engines)


def test_spills_recover_after_add_replica(tiny):
    """A two-replica fleet spilling under hot-prefix pressure stops
    spilling once ``add_replica`` grows it: the warmed clone (shared
    compiled steps — joining compiles nothing) takes first-sight
    traffic immediately."""
    cfg, params = tiny
    fleet = ReplicaSet(
        [_engine(cfg, params, enable_prefix_cache=True)
         for _ in range(2)],
        spill_queue_depth=2,
    )
    for e in fleet.engines:
        e.warmup([3], max_new_tokens=4)
    hot = np.arange(1, 25, dtype=np.int32)
    for j in range(10):
        fleet.submit(hot, 4, seed=0)
    fleet.run_until_complete()
    assert fleet.router.spilled > 0

    counts_before = dict(fleet.engines[0].compile_counts())
    idx = fleet.add_replica()
    assert idx == 2 and fleet.alive == [True, True, True]
    # the clone shares the warm compiled steps — zero new compiles
    assert fleet.engines[idx]._mixed_step is fleet.engines[0]._mixed_step
    # a fresh prefix routes to the newcomer by least-loaded first-sight
    # (submit a few distinct prompts — the rotating tiebreak guarantees
    # the new replica is among the first assignments)
    rng = np.random.default_rng(3)
    homes = set()
    for i in range(6):
        p = rng.integers(1, cfg.vocab_size, size=9)
        homes.add(fleet.submit(p, 3, seed=i).extra["replica"])
    fleet.run_until_complete()
    assert idx in homes
    assert dict(fleet.engines[0].compile_counts()) == counts_before


def test_lifecycle_controller_autoscales(tiny):
    """The closed loop: deep queues scale the fleet up, a quiet fleet
    scales back down (cooldown-gated), and removal drains through the
    peer path."""
    cfg, params = tiny
    clock = FakeClock()
    fleet = ReplicaSet([_engine(cfg, params)])
    ctl = LifecycleController(fleet, autoscaler=Autoscaler(
        min_replicas=1, max_replicas=2, scale_up_queue_depth=3.0,
        scale_down_queue_depth=0.5, cooldown_s=5.0, clock=clock.now,
    ))
    prompt = np.arange(1, 10, dtype=np.int32)
    for i in range(8):
        fleet.submit(prompt, 3, seed=i)
    assert ctl.autoscale_tick() == 1
    assert len(fleet.engines) == 2 and fleet.alive == [True, True]
    # cooldown holds the next verdict even though queues are still deep
    assert ctl.autoscale_tick() == 0
    fleet.run_until_complete()
    clock.t += 6.0
    assert ctl.autoscale_tick() == -1
    assert sum(fleet.alive) == 1
    clock.t += 6.0
    # at the floor: no further shrink
    assert ctl.autoscale_tick() == 0


def test_lifecycle_controller_serializes_rolls(tiny):
    cfg, params = tiny
    fleet = ReplicaSet([_engine(cfg, params) for _ in range(2)])
    ctl = LifecycleController(fleet)

    def reentrant():
        # a params_fn that tries to start a second roll mid-roll
        with pytest.raises(RuntimeError, match="already in progress"):
            ctl.rolling_upgrade(lambda: params)
        return params

    out = ctl.rolling_upgrade(reentrant, version=1, steps_between=0)
    assert out["version"] == 1
    assert ctl.roll_history == [out]
    assert not ctl.roll_active


# ---------------------------------------------------------------------------
# Auto-actions: the acceptance e2e
# ---------------------------------------------------------------------------

def test_auto_action_host_sync_shed_and_revert(tiny):
    """Injected SUSTAINED host_sync regression (chaos ``host_sync``
    sleeps inside the host_sync phase window): the sentinel attributes
    it, the ActionPolicy engages shed-prefill after the streak, the
    tick budget shrinks (decode floor intact), and when the injected
    regression clears the action REVERTS — all visible as counters and
    trace instants."""
    cfg, params = tiny
    tracer = TraceRecorder()
    # the injected regression must stand out from THIS host's tick: the
    # sentinel flags mean + 3 * max(dev, 10% of mean), and a CPU running
    # the Pallas interpreter spends tens of ms in host_sync per tick — a
    # 20 ms sleep (the value this test shipped with) is inside that band
    # on a slow box and the action never engages
    injector = FaultInjector("host_sync@8:14=0.2")
    engine = _engine(
        cfg, params, fault_injector=injector, tracer=tracer,
        sentinel=TickSentinel(threshold=3.0, warmup_ticks=4),
        actions=ActionPolicy(engage_streak=3, release_clean=8,
                             min_flip_interval_s=0.0),
    )
    full = engine.tick_token_budget
    shed_budgets = []

    def watch(req, tok, delta):
        shed_budgets.append(engine._tick_budget())

    engine.submit([5] * 6, 48, seed=0, callback=watch)
    engine.run_until_complete()
    snap = engine.metrics.snapshot()
    acts = snap["lifecycle_actions"]
    assert acts.get("shed_prefill_on") == 1
    assert acts.get("shed_prefill_off") == 1  # reverted after the clear
    assert snap["anomaly_ticks"].get("host_sync", 0) >= 3
    assert not engine.actions.snapshot()["shed_prefill"]
    # while engaged, the planner budget really shrank (never below the
    # decode floor), and it recovered after the release
    assert min(shed_budgets) < full
    assert min(shed_budgets) >= engine.scheduler.max_slots
    assert shed_budgets[-1] == full
    names = [e.get("name") for e in tracer.to_dict()["traceEvents"]]
    assert names.count("lifecycle-action") == 2
    assert "anomaly" in names


def test_auto_action_burn_spike_sheds_load_and_reverts(tiny):
    """A burn spike (every request missing a tight TTFT target) flips
    503-first load shedding with a burn-scaled Retry-After; once the
    burn window drains the action reverts and admission reopens."""
    cfg, params = tiny
    clock = FakeClock()
    engine = _engine(
        cfg, params, clock=clock.now,
        actions=ActionPolicy(burn_threshold=2.0,
                             min_flip_interval_s=0.0, clock=clock.now),
    )
    engine.metrics.slo = SLOTracker(
        SLOPolicy(ttft_s=0.05, target=0.99), clock=clock.now,
    )
    srv = HttpServer(engine, model_id="tiny")  # runner built, not started
    assert srv._shed_retry_after() is None

    # five misses: a second of fake wall time passes between submit and
    # the first token
    for i in range(5):
        engine.submit([3] * 4, 2, seed=i)
        clock.t += 1.0
        engine.run_until_complete()
    assert engine.actions.shedding
    retry = srv._shed_retry_after()
    assert retry is not None and retry >= 1.0
    snap = engine.metrics.snapshot()
    assert snap["lifecycle_actions"].get("shed_load_on") == 1
    assert snap["slo_burn_rate_5m"] > 2.0

    # the signal clears: the miss window ages out, fresh traffic meets
    # the target, the action reverts, admission reopens
    clock.t += 400.0
    for i in range(3):
        engine.submit([3] * 4, 2, seed=10 + i)
        engine.run_until_complete()
    assert not engine.actions.shedding
    assert srv._shed_retry_after() is None
    acts = engine.metrics.snapshot()["lifecycle_actions"]
    assert acts.get("shed_load_off") == 1


def test_idle_runner_releases_shed_load(tiny):
    """Shed_load blocks exactly the fresh work whose ticks would
    release it — so the runner's IDLE loop passes must poll the
    ActionPolicy too, or a drained-idle server 503s new completions
    forever after the burn window has long cleared."""
    import time as _time

    from llm_np_cp_tpu.serve.http.server import EngineRunner

    cfg, params = tiny
    engine = _engine(
        cfg, params,
        actions=ActionPolicy(burn_threshold=2.0,
                             min_flip_interval_s=0.0),
    )
    engine.metrics.slo = FakeTracker(10.0)  # burning hot
    engine._actions_tick([])
    assert engine.actions.shedding
    engine.metrics.slo = FakeTracker(0.0)  # the signal clears
    runner = EngineRunner(engine)
    runner.start()
    try:
        deadline = _time.monotonic() + 5.0
        while engine.actions.shedding and _time.monotonic() < deadline:
            _time.sleep(0.02)
        # no work was ever submitted: only the idle poll can release
        assert not engine.actions.shedding
    finally:
        runner.stop(timeout=5.0)


@pytest.mark.http
def test_http_503_first_load_shedding(tiny):
    """The HTTP spelling of shed_load: fresh completions get 503 +
    Retry-After while the policy sheds, resumes still pass, and
    admission reopens when the policy releases."""
    cfg, params = tiny
    engine = _engine(cfg, params,
                     actions=ActionPolicy(min_flip_interval_s=0.0))

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        loop = asyncio.get_running_loop()
        ok = await astream_completion(
            srv.host, srv.port,
            {"prompt": [4] * 5, "max_tokens": 3, "stream": True},
            timeout=30)
        assert ok["status"] == 200

        # flip the policy (the engine-integrated path is covered above)
        engine.actions.on_tick([], FakeTracker(10.0))
        shed = await astream_completion(
            srv.host, srv.port,
            {"prompt": [4] * 5, "max_tokens": 3, "stream": True},
            timeout=30)
        assert shed["status"] == 503, shed
        st, _ = await loop.run_in_executor(
            None, http_get, srv.host, srv.port, "/healthz")
        assert st == 200  # shedding is admission control, not sickness

        engine.actions.on_tick([], FakeTracker(0.0))
        again = await astream_completion(
            srv.host, srv.port,
            {"prompt": [4] * 5, "max_tokens": 3, "stream": True},
            timeout=30)
        assert again["status"] == 200
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


# ---------------------------------------------------------------------------
# HTTP admin plane
# ---------------------------------------------------------------------------

@pytest.mark.http
def test_http_admin_upgrade_fleet_e2e(tiny):
    """``POST /admin/upgrade`` on a live 2-replica fleet with streams
    in flight: the roll drains each replica to its peer, every stream
    completes with offline-parity tokens, /healthz and /metrics report
    the new weights version, and a concurrent roll is refused."""
    cfg, params = tiny
    engines = [_engine(cfg, params) for _ in range(2)]
    runner = ReplicaRunner(engines, spill_queue_depth=None)
    rng = np.random.default_rng(31)
    prompts = [list(map(int, rng.integers(1, cfg.vocab_size, size=n)))
               for n in (5, 9, 7, 12, 4, 10)]

    async def main():
        srv = HttpServer(engines[0], model_id="tiny", drain_timeout=10.0,
                         runner=runner,
                         upgrade_loader=lambda body: params)
        await srv.start("127.0.0.1", 0)
        loop = asyncio.get_running_loop()
        tasks = [
            asyncio.create_task(astream_completion(
                srv.host, srv.port,
                {"prompt": p, "max_tokens": 32, "stream": True},
                timeout=60))
            for p in prompts
        ]
        while runner.inflight < len(prompts):
            await asyncio.sleep(0.002)
        st, body = await loop.run_in_executor(
            None, http_post, srv.host, srv.port, "/admin/upgrade", {})
        assert st == 200, body
        assert body["rolled"] == [0, 1] and body["version"] == 1

        results = await asyncio.gather(*tasks)
        for p, res in zip(prompts, results):
            assert res["status"] == 200 and res["finish_reason"] == "length"
            assert res["token_ids"] == _offline(cfg, params, p, 32)

        st, hz = await loop.run_in_executor(
            None, http_get, srv.host, srv.port, "/healthz")
        payload = json.loads(hz)
        assert st == 200
        assert [r["weights_version"] for r in payload["replicas"]] \
            == [1, 1]
        st, scrape = await loop.run_in_executor(
            None, http_get, srv.host, srv.port, "/metrics")
        text = scrape.decode()
        assert 'version="1"' in text
        assert "llm_serve_weights_version" in text
        assert 'llm_serve_lifecycle_actions_total{' \
            'action="upgrade_replica"' in text
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=180))


@pytest.mark.http
def test_http_admin_upgrade_guards(tiny):
    """The admin surface fails safe: no loader → 404 with a hint, a
    loader that raises → 500 UpgradeAborted and the fleet keeps
    serving on its old weights."""
    cfg, params = tiny
    engine = _engine(cfg, params)

    def bad_loader(body):
        raise OSError("checkpoint shard vanished")

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=10.0)
        await srv.start("127.0.0.1", 0)
        loop = asyncio.get_running_loop()
        st, body = await loop.run_in_executor(
            None, http_post, srv.host, srv.port, "/admin/upgrade", {})
        assert st == 404
        srv.upgrade_loader = bad_loader
        st, body = await loop.run_in_executor(
            None, http_post, srv.host, srv.port, "/admin/upgrade", {})
        assert st == 500 and "checkpoint load failed" in body["error"]
        # still serving, still on the old weights
        res = await astream_completion(
            srv.host, srv.port,
            {"prompt": [6] * 5, "max_tokens": 3, "stream": True},
            timeout=30)
        assert res["status"] == 200
        assert engine.weights_version == 0
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))


@pytest.mark.http
def test_http_admin_scale_elastic_fleet(tiny):
    """``POST /admin/scale``: grow the HTTP fleet by one warmed clone,
    serve through it, shrink back with a drain — indices stay stable
    and the removed replica leaves routing."""
    cfg, params = tiny
    engines = [_engine(cfg, params) for _ in range(2)]
    runner = ReplicaRunner(engines, spill_queue_depth=None)

    async def main():
        srv = HttpServer(engines[0], model_id="tiny", drain_timeout=10.0,
                         runner=runner)
        await srv.start("127.0.0.1", 0)
        loop = asyncio.get_running_loop()
        st, body = await loop.run_in_executor(
            None, http_post, srv.host, srv.port, "/admin/scale",
            {"replicas": 3})
        assert st == 200, body
        assert body["replicas"] == 3 and body["added"] == [2]
        res = await astream_completion(
            srv.host, srv.port,
            {"prompt": [8] * 6, "max_tokens": 3, "stream": True},
            timeout=30)
        assert res["status"] == 200
        st, body = await loop.run_in_executor(
            None, http_post, srv.host, srv.port, "/admin/scale",
            {"replicas": 2})
        assert st == 200, body
        assert body["replicas"] == 2 and body["removed"] == [2]
        states = {r["replica"]: r["state"] for r in body["states"]}
        assert states[2] == "removed"
        res = await astream_completion(
            srv.host, srv.port,
            {"prompt": [9] * 6, "max_tokens": 3, "stream": True},
            timeout=30)
        assert res["status"] == 200
        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))
