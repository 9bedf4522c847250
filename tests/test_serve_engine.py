"""ServeEngine end-to-end: continuous batching must be output-invisible.

The whole serving layer (queueing, paged pool, packed decode, eviction)
is legitimate only if a request cannot tell it shared the machine: every
request's greedy tokens must equal ``Generator.generate_ragged`` run
offline on the same prompt (the acceptance criterion for the serve/
subsystem), whether its KV lived in contiguous slabs or scattered
blocks, bf16/f32 or int8, interrupted by preemption or not.

CPU backend, tiny fixture; the compile-counter assertions ride along so
the parity traffic doubles as the jit-stability evidence.
"""

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
from llm_np_cp_tpu.serve import ServeEngine, poisson_trace
from tools.compile_counter import assert_serve_compiles_bounded


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


# the parity oracle, one a (weights, cache dtype) of this module: its
# programs compile once a prompt length and budget
_ORACLES: dict = {}


def _offline_tokens(cfg, params, cache_dtype, req) -> list[int]:
    key = (id(params), jnp.dtype(cache_dtype).name)
    if key not in _ORACLES:
        # (``params`` is kept: its id stays this tree's)
        _ORACLES[key] = (Generator(
            params, cfg, sampler=Sampler(kind="greedy"),
            cache_dtype=cache_dtype), params)
    res = _ORACLES[key][0].generate_ragged(
        [req.prompt], req.max_new_tokens, seed=req.seed)
    return [int(t) for t in np.asarray(res.tokens)[0][: req.max_new_tokens]]


def _assert_parity(engine: ServeEngine, cfg, params, cache_dtype) -> None:
    assert engine.scheduler.finished, "nothing finished — bad test setup"
    for req in engine.scheduler.finished:
        assert req.generated == _offline_tokens(cfg, params, cache_dtype, req), (
            f"request {req.req_id} (preempted {req.n_preemptions}x) diverged "
            "from the offline run"
        )


def test_trace_parity_32_requests_and_bounded_compiles(tiny):
    """The acceptance criterion: a 32-request Poisson trace through the
    engine produces per-request greedy tokens identical to offline
    ``generate_ragged``, and the jitted step compiles once per packed
    width bucket — never per tick."""
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=4, num_blocks=48, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32,
    )
    assert engine.mixed
    rng = np.random.default_rng(0)
    trace = poisson_trace(
        rng, 32, rate_rps=40.0, prompt_len_range=(3, 14),
        max_new_tokens=6, vocab_size=cfg.vocab_size,
    )
    snap = engine.replay_trace(trace)
    assert snap["finished"] == 32
    _assert_parity(engine, cfg, params, jnp.float32)

    assert engine.scheduler.n_preemptions == 0
    assert_serve_compiles_bounded(engine)
    counts = engine.compile_counts()
    assert set(counts) == {"mixed_step"}
    assert 1 <= counts["mixed_step"] <= len(engine.mixed_buckets)
    assert snap["ticks"] > counts["mixed_step"]


def test_eviction_requeue_parity(tiny):
    """A pool too small for the running set forces evict→requeue; the
    re-prefilled (teacher-forced) request must still produce the exact
    uninterrupted token sequence."""
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=2, num_blocks=6, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32,
    )
    rng = np.random.default_rng(7)
    for n in (4, 5, 3):
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), 20)
    engine.run_until_complete()
    assert engine.scheduler.n_preemptions > 0, (
        "pool was not tight enough to exercise eviction"
    )
    assert len(engine.scheduler.finished) == 3
    _assert_parity(engine, cfg, params, jnp.float32)
    # preempted blocks all returned
    assert engine.pool.free_list.num_allocated == 0


def test_int8_block_pool_parity(tiny):
    """int8 pool blocks (quantize on write, dequantize on read — the
    quant.quantize_kv discipline) must decode exactly like the
    contiguous int8 ``KVCache``: same greedy tokens on the tiny
    fixture."""
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=3, num_blocks=16, block_size=8, max_seq_len=64,
        cache_dtype=jnp.int8,
    )
    assert engine.pool.pages.quantized
    rng = np.random.default_rng(11)
    for n in (6, 11, 4):
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), 5)
    engine.run_until_complete()
    assert len(engine.scheduler.finished) == 3
    _assert_parity(engine, cfg, params, jnp.int8)


def test_streaming_callbacks_per_request(tiny):
    """Each generated token reaches the request's callback in order, and
    detokenized deltas concatenate to the full decoded text."""
    cfg, params = tiny

    class Tok:
        def decode(self, ids, skip_special_tokens=True):
            return "".join(chr(97 + (int(i) % 26)) for i in ids)

    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=2, num_blocks=16, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32, tokenizer=Tok(),
    )
    got: dict[int, list] = {}
    text: dict[int, str] = {}

    def cb(req, token, delta):
        got.setdefault(req.req_id, []).append(token)
        if delta:
            text[req.req_id] = text.get(req.req_id, "") + delta

    rng = np.random.default_rng(2)
    reqs = [
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), 4, callback=cb)
        for n in (3, 7)
    ]
    engine.run_until_complete()
    for req in reqs:
        assert got[req.req_id] == req.generated
        assert text[req.req_id] == Tok().decode(req.generated)


def test_submit_rejects_impossible_requests(tiny):
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, max_slots=1, num_blocks=4, block_size=8, max_seq_len=24,
        cache_dtype=jnp.float32,
    )
    with pytest.raises(ValueError, match="max_seq_len"):
        engine.submit(np.arange(1, 20, dtype=np.int32), 30)
    with pytest.raises(ValueError, match="empty prompt"):
        engine.submit(np.zeros(0, np.int32), 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        engine.submit(np.asarray([5], np.int32), 0)


def test_submit_rejects_unadmittable_request(tiny):
    """The submit check must mirror the scheduler's admission rule
    (prefill need + decode reserve): with prefill_chunk=100 over 64-slot
    blocks, a 150-token prompt fits max_seq_len and the raw pool, but
    its 200-wide prefill needs 4 blocks + 1 reserve > 4 allocatable —
    it would starve the FIFO head forever if accepted."""
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, max_slots=1, num_blocks=5, block_size=64,
        max_seq_len=256, prefill_chunk=100, cache_dtype=jnp.float32,
    )
    with pytest.raises(ValueError, match="pool capacity"):
        engine.submit(np.arange(1, 151, dtype=np.int32), 1)
    # a request whose worst-case admission leaves the reserve free is in
    engine.submit(np.arange(1, 11, dtype=np.int32), 2)


# ---------------------------------------------------------------------------
# ``step()``'s ONE contract: what it returns, and when a token reaches its
# callback.
# ---------------------------------------------------------------------------

class _Stream:
    """Logging callbacks of one engine: per request the tokens handed
    out, and every event in arrival order."""

    def __init__(self):
        self.tokens: dict[int, list[int]] = {}
        self.events: list[tuple] = []

    def callback(self, req, token, delta):
        self.tokens.setdefault(req.req_id, []).append(token)
        self.events.append((req.req_id, "token", token))

    def on_event(self, req, event):
        self.events.append((req.req_id, event))

    def of(self, req):
        return self.tokens.get(req.req_id, [])


def _contract_engine(cfg, params, spec, **kw):
    return ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=2,
        num_blocks=24, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32, spec_k=3 if spec else 0, **kw)


def _step_held_to_contract(engine, stream, reqs):
    """One ``step()``, held to its contract for every request of
    ``reqs``; returns what it returned."""
    before = {r.req_id: list(r.generated) for r in reqs}
    more = engine.step()
    for r in reqs:
        handed = stream.of(r)
        # what was accepted before this step has been handed out by now
        # (one tick late, never two), in order, each token once
        if r.finish_reason != "aborted":
            assert handed[:len(before[r.req_id])] == before[r.req_id]
        # nothing is handed out that was not accepted
        assert r.generated[:len(handed)] == handed
        # ...and ``generated`` is at most this tick's tokens ahead
        assert len(r.generated) - len(handed) <= engine.spec_k + 1
    if not more:
        # False: no work is left and nothing is owed
        assert not engine._owed and engine.publish_owed() == 0
        assert all(stream.of(r) == r.generated for r in reqs)
    return more


@pytest.mark.parametrize("spec", [False, True], ids=["spec-off", "spec-on"])
@pytest.mark.parametrize("scenario", [
    "dispatching", "idle", "abort-empties-the-engine", "recovery-replay"])
def test_step_has_one_contract(tiny, scenario, spec):
    """``step()`` returns True while work remains or a token is owed;
    the tokens and terminals a tick accepted reach the callbacks during
    the NEXT ``step()``, per request in order and exactly once, or before
    this one returns when it dispatched nothing or leaves no work."""
    cfg, params = tiny
    stream = _Stream()
    engine = _contract_engine(cfg, params, spec)
    # a repeating prompt: a spec engine's drafts are taken
    prompt = np.resize(np.asarray([5, 9, 3], np.int32), 11)
    submit = dict(callback=stream.callback, on_event=stream.on_event,
                  speculative=spec)

    if scenario == "idle":
        # nothing to do: False, no dispatch, no event — however often
        assert not engine.step() and not engine.step()
        assert engine.n_dispatches == 0 and not stream.events
        # one token: the tick that accepts it leaves no work, so it is
        # handed out before that ``step()`` returns
        req = engine.submit(prompt[:5], 1, **submit)  # (one chunk)
        assert not _step_held_to_contract(engine, stream, [req])
        assert stream.events == [
            (req.req_id, "token", req.generated[0]), (req.req_id, "length")]
        n = engine.n_dispatches
        assert not engine.step() and engine.n_dispatches == n == 1
        return

    if scenario == "dispatching":
        # (one length and one budget: the offline run compiles once)
        reqs = [engine.submit(prompt, 8, **submit),
                engine.submit(prompt[::-1].copy(), 8, **submit)]
        ahead = 0
        while _step_held_to_contract(engine, stream, reqs):
            ahead += any(len(r.generated) > len(stream.of(r)) for r in reqs)
        assert ahead, "no token was ever accepted a tick before its callback"
        for r in reqs:
            mine = [e for e in stream.events if e[0] == r.req_id]
            assert mine[-1] == (r.req_id, "length")  # the terminal, last
            assert [e[2] for e in mine[:-1]] == r.generated
        _assert_parity(engine, cfg, params, jnp.float32)
        if spec:
            assert engine.metrics.snapshot()["spec_accepted_tokens"] > 0
        return

    if scenario == "abort-empties-the-engine":
        # between ticks: what the request is owed goes out first, then its
        # ``aborted``; no tick follows and ``step()`` says so
        req = engine.submit(prompt, 20, **submit)
        for _ in range(3):
            assert _step_held_to_contract(engine, stream, [req])
        assert engine._owed and len(req.generated) > len(stream.of(req))
        assert engine.abort(req.req_id)
        assert stream.of(req) == req.generated and not engine._owed
        assert stream.events[-1] == (req.req_id, "aborted")
        assert not engine.step()
        # from inside a token callback: the tokens of that tick not yet
        # handed out are dropped, and ``aborted`` follows the token whose
        # callback asked for it
        other = _Stream()

        def cut(r, token, delta):
            other.callback(r, token, delta)
            if len(other.of(r)) == 2:
                engine.abort(r.req_id)

        late = engine.submit(prompt, 20, callback=cut,
                             on_event=other.on_event, speculative=spec)
        while engine.step():
            pass
        assert other.of(late) == late.generated and len(late.generated) == 2
        assert other.events[-2:] == [
            (late.req_id, "token", late.generated[1]),
            (late.req_id, "aborted")]
        assert engine.pool.stats()["request_held"] == 0
        return

    assert scenario == "recovery-replay"
    req = engine.submit(prompt, 10, request_id=7, seed=3, **submit)
    for _ in range(3):
        assert _step_held_to_contract(engine, stream, [req])
    # what a supervisor reads between ticks as delivered: the gap closed
    assert engine.publish_owed() > 0 and stream.of(req) == req.generated
    delivered = list(req.generated)
    assert 0 < len(delivered) < 10
    rebuilt, replay = engine.clone_fresh(), _Stream()
    again = rebuilt.recover(
        prompt, 10, request_id=7, seed=3, generated=delivered,
        callback=replay.callback, on_event=replay.on_event,
        speculative=spec)
    while True:
        before = list(again.generated)
        more = rebuilt.step()
        handed = delivered + replay.of(again)
        assert handed[:len(before)] == before
        assert again.generated[:len(handed)] == handed
        if not more:
            break
    # the replayed tokens are not handed out again; the stream goes on
    assert delivered + replay.of(again) == again.generated
    assert replay.events[-1] == (7, "length") and not rebuilt._owed
    _assert_parity(rebuilt, cfg, params, jnp.float32)


def test_prefix_sharing_eviction_stress_parity(tiny):
    """Interleave evict-on-OOM with shared prefixes on a pool too small
    for the running set: refcounted eviction must never free a block a
    live request still references (FreeList would raise on the resulting
    double free) and every request must still match the offline run."""
    cfg, params = tiny
    rng = np.random.default_rng(9)
    prompts = [rng.integers(1, cfg.vocab_size, size=n) for n in (9, 9, 5)]
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=2, num_blocks=8, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32, enable_prefix_cache=True,
    )
    for rep in range(3):
        for j, p in enumerate(prompts):
            engine.submit(p, 12, seed=j)
    engine.run_until_complete()
    assert len(engine.scheduler.finished) == 9
    assert engine.scheduler.n_preemptions > 0, (
        "pool was not tight enough to exercise eviction"
    )
    _assert_parity(engine, cfg, params, jnp.float32)
    fl = engine.pool.free_list
    assert fl.num_free + fl.num_allocated == fl.capacity
    assert fl.num_allocated == len(engine.pool.prefix_cache)


def test_metrics_snapshot_shape(tiny):
    cfg, params = tiny
    engine = ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"),
        max_slots=2, num_blocks=16, block_size=8, max_seq_len=64,
        cache_dtype=jnp.float32,
    )
    rng = np.random.default_rng(4)
    trace = poisson_trace(
        rng, 5, rate_rps=100.0, prompt_len_range=(2, 10),
        max_new_tokens=3, vocab_size=cfg.vocab_size,
    )
    snap = engine.replay_trace(trace)
    assert snap["submitted"] == snap["finished"] == 5
    assert snap["total_generated_tokens"] == 15
    assert snap["throughput_tok_s"] > 0
    assert snap["ttft_s_p50"] > 0
    assert 0 <= snap["occupancy_p99"] <= 1
    assert "tok/s" in engine.metrics.format()
