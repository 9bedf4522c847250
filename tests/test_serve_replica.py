"""DP engine replicas + prefix-affinity routing (serve/replica.py).

Three layers under test: the pure ``PrefixRouter`` policy (affinity,
spill, rebalance on death), the direct-mode ``ReplicaSet`` (token
parity vs a single engine on a 32-request trace, 100% block-local
routing on the shared-prompt workload, one replica's supervised
recovery while its peers keep serving, DP x TP composition on the
8-device mesh), and the HTTP-mode ``ReplicaRunner`` behind the real
server (per-replica supervision, replica-labeled Prometheus series,
router counters, fleet /healthz).
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
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.parallel.sharding import MeshPlan
from llm_np_cp_tpu.serve import (
    PrefixRouter,
    ReplicaRunner,
    ReplicaSet,
    ServeEngine,
    poisson_trace,
    prefix_block_keys,
)

pytestmark = pytest.mark.mesh


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config(
        "llama", num_attention_heads=8, num_key_value_heads=4,
        head_dim=8, hidden_size=64,
    )
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, plan=None, devices=None, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                       mesh_plan=plan, mesh_devices=devices, **kw)


def _streams(engines_or_set):
    if isinstance(engines_or_set, ReplicaSet):
        return [r.generated for r in engines_or_set.finished]
    return [
        r.generated
        for r in sorted(engines_or_set.scheduler.finished,
                        key=lambda r: r.req_id)
    ]


# ---------------------------------------------------------------------------
# PrefixRouter policy units (no engines)
# ---------------------------------------------------------------------------

def test_router_affinity_matches_prefix_cache_hash():
    """The routing key IS the prefix cache's chained block key: same
    prompt → same key; a prompt differing only in its last (partial,
    unshareable) block → same key; different first block → different
    key.  Pinned against prefix_block_keys directly."""
    r = PrefixRouter(4, block_size=8, prefill_chunk=8)
    long = np.arange(1, 25, dtype=np.int32)  # 24 tokens, 3 blocks
    k1 = r.affinity_key(long)
    k2 = r.affinity_key(long.copy())
    assert k1 == k2
    # the deepest shareable key (width 24 → 2 shareable blocks)
    want = prefix_block_keys(long, 0, 8, 2)[-1]
    assert k1 == want
    # suffix past the shareable span doesn't change the route
    tail = long.copy()
    tail[-1] += 1
    assert r.affinity_key(tail) == k1
    # different leading content does
    head = long.copy()
    head[0] += 1
    assert r.affinity_key(head) != k1
    # too short to share any block → whole-prompt hash, still sticky
    short = np.asarray([5, 6, 7], np.int32)
    assert r.affinity_key(short) == r.affinity_key(short.copy())
    assert r.affinity_key(short) != r.affinity_key(
        np.asarray([5, 6, 8], np.int32))


def test_router_sticky_and_least_loaded():
    r = PrefixRouter(3, block_size=8, prefill_chunk=8,
                     spill_queue_depth=None)
    ka, kb = b"a" * 32, b"b" * 32
    idx_a, sp = r.route(ka, loads=[0, 0, 0])
    assert not sp
    # same key sticks regardless of load
    for loads in ([5, 0, 0], [9, 9, 9]):
        idx, sp = r.route(ka, loads=loads)
        assert idx == idx_a and not sp
    # a new key goes least-loaded
    loads = [0, 0, 0]
    loads[idx_a] = 4
    idx_b, _ = r.route(kb, loads=loads)
    assert idx_b != idx_a
    assert r.routed == 4 and r.spilled == 0


def test_router_spill_on_queue_pressure():
    r = PrefixRouter(2, block_size=8, prefill_chunk=8,
                     spill_queue_depth=3)
    key = b"k" * 32
    idx, _ = r.route(key, loads=[0, 0])
    other = 1 - idx
    # pressure below threshold: stick
    qd = [0, 0]
    qd[idx] = 2
    assert r.route(key, loads=qd, queue_depths=qd)[0] == idx
    # at threshold with a shallower peer: spill, stickiness unmoved
    qd[idx] = 3
    got, spilled = r.route(key, loads=qd, queue_depths=qd)
    assert got == other and spilled
    assert r.spilled == 1
    # peer equally deep: no point spilling
    qd[other] = 3
    got, spilled = r.route(key, loads=qd, queue_depths=qd)
    assert got == idx and not spilled


def test_router_rebalance_on_replica_death():
    r = PrefixRouter(2, block_size=8, prefill_chunk=8)
    key = b"d" * 32
    idx, _ = r.route(key, loads=[0, 0])
    alive = [True, True]
    alive[idx] = False
    got, _ = r.route(key, loads=[0, 0], alive=alive)
    assert got != idx  # re-homed
    # and the new home sticks once the dead replica returns
    assert r.route(key, loads=[0, 0])[0] == got
    with pytest.raises(RuntimeError, match="no alive replica"):
        r.route(b"x" * 32, loads=[0, 0], alive=[False, False])


def test_router_forget_replica():
    r = PrefixRouter(2, block_size=8, prefill_chunk=8)
    keys = [bytes([i]) * 32 for i in range(6)]
    homes = {k: r.route(k, loads=[0, 0])[0] for k in keys}
    dropped = r.forget_replica(0)
    assert dropped == sum(1 for v in homes.values() if v == 0)


# ---------------------------------------------------------------------------
# ReplicaSet: the DP acceptance criteria
# ---------------------------------------------------------------------------

def test_dp_trace_parity_32_requests(tiny):
    """4 DP replicas reproduce the single engine's token streams on a
    32-request Poisson trace — per-request streams depend only on
    (params, prompt, seed), never on placement."""
    cfg, params = tiny
    rng = np.random.default_rng(0)
    trace = poisson_trace(rng, 32, rate_rps=40.0, prompt_len_range=(3, 14),
                          max_new_tokens=6, vocab_size=cfg.vocab_size)
    single = _engine(cfg, params)
    snap1 = single.replay_trace(trace)
    assert snap1["finished"] == 32

    fleet = ReplicaSet([_engine(cfg, params) for _ in range(4)])
    snap = fleet.replay_trace(trace)
    assert snap["finished"] == 32
    assert _streams(fleet) == _streams(single)
    assert snap["router_routed"] + snap["router_spilled"] == 32
    assert snap["total_generated_tokens"] == snap1["total_generated_tokens"]


def test_shared_prompt_trace_100pct_block_local(tiny):
    """The serve_prefix_shared-style workload (32 requests, 8 distinct
    prompts) routes 100% block-locally: zero spills, every repeat of a
    prompt lands on the replica that already registered its blocks, and
    the fleet's prefix hit count equals the single engine's — sharing
    lost nothing to placement."""
    cfg, params = tiny
    rng = np.random.default_rng(3)
    trace = poisson_trace(
        rng, 32, rate_rps=30.0, prompt_len_range=(18, 30),
        max_new_tokens=5, vocab_size=cfg.vocab_size, distinct_prompts=8,
    )
    single = _engine(cfg, params, enable_prefix_cache=True, num_blocks=96)
    snap1 = single.replay_trace(trace)
    assert snap1["prefix_blocks_hit"] > 0

    fleet = ReplicaSet(
        [_engine(cfg, params, enable_prefix_cache=True, num_blocks=96)
         for _ in range(4)],
        spill_queue_depth=None,  # isolate affinity from load shedding
    )
    snap = fleet.replay_trace(trace)
    assert snap["finished"] == 32
    assert snap["router_spilled"] == 0
    # block-locality: each distinct prompt served by exactly one replica
    owners: dict[bytes, set] = {}
    for i, e in enumerate(fleet.engines):
        for r in e.scheduler.finished:
            owners.setdefault(r.prompt.tobytes(), set()).add(i)
    assert len(owners) == 8
    assert all(len(v) == 1 for v in owners.values())
    assert snap["prefix_blocks_hit"] == snap1["prefix_blocks_hit"]
    assert _streams(fleet) == _streams(single)


def test_spill_relieves_queue_pressure(tiny):
    """With a hot prefix hammering one replica, the spill policy moves
    overflow to idle peers instead of queueing behind affinity."""
    cfg, params = tiny
    prompt = np.arange(1, 25, dtype=np.int32)
    fleet = ReplicaSet(
        [_engine(cfg, params, enable_prefix_cache=True)
         for _ in range(2)],
        spill_queue_depth=2,
    )
    for j in range(10):  # 2 slots/replica: queues build fast
        fleet.submit(prompt, 4, seed=0)
    fleet.run_until_complete()
    assert fleet.router.spilled > 0
    assert len(fleet.finished) == 10
    # spilled requests really ran on the non-affine replica
    assert all(e.scheduler.finished for e in fleet.engines)


def test_replica_recovery_while_peers_serve(tiny):
    """Kill one replica mid-trace, let the peers keep ticking, then
    restart it via clone_fresh + teacher-forced recovery: every stream
    completes token-identically to an undisturbed fleet, and the
    router re-homes the dead replica's prefixes in between."""
    cfg, params = tiny
    rng = np.random.default_rng(7)
    trace = poisson_trace(
        rng, 16, rate_rps=200.0, prompt_len_range=(18, 30),
        max_new_tokens=6, vocab_size=cfg.vocab_size, distinct_prompts=4,
    )

    def build():
        return ReplicaSet(
            [_engine(cfg, params, enable_prefix_cache=True)
             for _ in range(2)],
            spill_queue_depth=None,
        )

    undisturbed = build()
    for t in trace:
        undisturbed.submit(t["prompt"], t["max_new_tokens"],
                           seed=t.get("seed", 0))
    undisturbed.run_until_complete()
    want = _streams(undisturbed)

    fleet = build()
    for t in trace:
        fleet.submit(t["prompt"], t["max_new_tokens"], seed=t.get("seed", 0))
    for _ in range(3):
        fleet.step()
    inflight = fleet.kill_replica(0)
    assert inflight, "bad setup: replica 0 had nothing in flight"
    peer_done_before = len(fleet.engines[1].scheduler.finished)
    for _ in range(3):
        fleet.step()  # peers keep serving while 0 is down
    assert len(fleet.engines[1].scheduler.finished) >= peer_done_before
    # new traffic for a dead replica's prefix re-homes to the survivor
    re_homed = fleet.submit(trace[0]["prompt"], 2,
                            seed=trace[0].get("seed", 0))
    assert fleet.alive[re_homed.extra["replica"]]
    fleet.abort(re_homed.req_id)  # keep the parity set undisturbed
    fleet.restart_replica(0)
    fleet.run_until_complete()
    assert _streams(fleet) == want


def test_dp_x_tp_composition(tiny):
    """2 replicas x TP=2 over 4 devices: each replica TP-shards its
    params and pool on its OWN mesh slice; token parity holds and the
    slices are disjoint."""
    cfg, params = tiny
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs 4 devices")
    rng = np.random.default_rng(5)
    trace = poisson_trace(rng, 12, rate_rps=40.0, prompt_len_range=(3, 14),
                          max_new_tokens=5, vocab_size=cfg.vocab_size)
    single = _engine(cfg, params)
    single.replay_trace(trace)

    fleet = ReplicaSet([
        _engine(cfg, params, MeshPlan(model=2), devs[0:2]),
        _engine(cfg, params, MeshPlan(model=2), devs[2:4]),
    ])
    snap = fleet.replay_trace(trace)
    assert snap["finished"] == 12
    assert _streams(fleet) == _streams(single)
    slices = [
        {d.id for d in e.pool.pages.k.sharding.device_set}
        for e in fleet.engines
    ]
    assert slices[0].isdisjoint(slices[1])


def test_replica_set_rejects_mismatched_geometry(tiny):
    cfg, params = tiny
    with pytest.raises(ValueError, match="geometry"):
        ReplicaSet([
            _engine(cfg, params),
            _engine(cfg, params, block_size=16),
        ])


# ---------------------------------------------------------------------------
# HTTP mode: ReplicaRunner behind the real server
# ---------------------------------------------------------------------------

@pytest.mark.http
def test_http_replica_fleet_e2e(tiny):
    """2 replicas behind HttpServer: 8 concurrent streams complete with
    offline-parity tokens, /healthz lists per-replica states, and the
    scrape carries replica-labeled series plus the router counters."""
    from llm_np_cp_tpu.generate import Generator
    from llm_np_cp_tpu.serve.http.client import (
        astream_completion,
        http_get,
    )
    from llm_np_cp_tpu.serve.http.server import HttpServer

    cfg, params = tiny
    engines = [_engine(cfg, params) for _ in range(2)]
    runner = ReplicaRunner(engines, spill_queue_depth=None)
    rng = np.random.default_rng(21)
    prompts = [list(map(int, rng.integers(1, cfg.vocab_size, size=n)))
               for n in (5, 9, 5, 12, 7, 9, 4, 11)]

    async def main():
        srv = HttpServer(engines[0], model_id="tiny", drain_timeout=10.0,
                         runner=runner)
        await srv.start("127.0.0.1", 0)
        host, port = srv.host, srv.port
        loop = asyncio.get_running_loop()

        st, body = await loop.run_in_executor(
            None, http_get, host, port, "/healthz")
        payload = json.loads(body)
        assert st == 200 and payload["status"] == "ok"
        assert [r["replica"] for r in payload["replicas"]] == [0, 1]

        results = await asyncio.gather(*[
            astream_completion(host, port, {
                "prompt": p, "max_tokens": 4, "stream": True,
            })
            for p in prompts
        ])
        gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                        cache_dtype=jnp.float32)
        for p, res in zip(prompts, results):
            assert res["finish_reason"] == "length"
            want = [int(t) for t in np.asarray(gen.generate_ragged(
                [np.asarray(p, np.int32)], 4).tokens)[0][:4]]
            assert res["token_ids"] == want

        st, scrape = await loop.run_in_executor(
            None, http_get, host, port, "/metrics")
        text = scrape.decode()
        assert st == 200
        assert 'llm_serve_requests_finished_total{replica="0"}' in text
        assert 'llm_serve_requests_finished_total{replica="1"}' in text
        assert 'llm_serve_ttft_seconds_bucket{le="+Inf",replica="0"}' \
            in text
        routed = int(next(
            line.split()[-1] for line in text.splitlines()
            if line.startswith("llm_serve_router_routed_total")
        ))
        assert routed == len(prompts)
        # both replicas actually served traffic (rotating tiebreak)
        fin = {
            line.split()[-1] for line in text.splitlines()
            if line.startswith("llm_serve_requests_finished_total")
        }
        assert fin and fin != {"0"}

        srv.begin_drain()
        await srv.serve_until_shutdown()

    asyncio.run(asyncio.wait_for(main(), timeout=120))
    total = sum(len(e.scheduler.aborted) + runner.replicas[i].inflight
                for i, e in enumerate(engines))
    assert total == 0
