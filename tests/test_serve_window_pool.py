"""MiMo-V2 on the SERVED path: prefill in slices, then decode through a pool
of two page classes whose window blocks recycle, against the benchmark's
independent float32 reference of the whole sequence (logits, not tokens);
what two page classes refuse at start-up; a supervised restart; the window
class's allocator under a seeded schedule.  The model, the kernel and the
cost file are tests/test_mimo_v2.py."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import llm_np_cp_tpu.serve.engine as engine_mod
from llm_np_cp_tpu.config import ModelConfig, tiny_config
from llm_np_cp_tpu.models import forward, init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import ServeEngine
from llm_np_cp_tpu.serve.block_pool import WindowRings, window_blocks_per_slot
from llm_np_cp_tpu.utils.synthetic import hf_config_dict

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_mimo_v2 as ref  # noqa: E402

# the served path against float32 at the highest matmul precision: the
# kernel's online softmax on AMLA's grid and the summation order differ
TOL_SERVED = 5e-5
# a bf16 program against float32 of the same (bf16-rounded) weights:
# every matmul rounds to 8 bits of mantissa, about a hundredth of the
# spread over five layers; a wrong equation moves a logit by a tenth
TOL_BF16 = 0.08


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("mimo_v2")
    hf = hf_config_dict(cfg)
    assert ModelConfig.from_hf_dict(hf) == cfg
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, hf, params


def _gap(got, want) -> float:
    """Largest logit difference as a share of the reference's spread."""
    want = np.asarray(want)
    spread = float((want.max(-1) - want.mean(-1)).mean())
    return float(np.abs(np.asarray(got, np.float32) - want).max()) / spread


def _ids(n, seed=1):
    return np.random.default_rng(seed).integers(1, 256, n)


# ----------------------------------------------------------------------
# the served path: two page classes, window blocks that recycle
# ----------------------------------------------------------------------

# the logits the tick samples from, tick by tick: ``final_logits`` (the XLA
# tail, ``sample_epilogue="off"``) wrapped with a callback, ONCE for the
# module, so that engines of one geometry share their compiled programs
TICKS: list[np.ndarray] = []
_BUILT: dict[tuple, ServeEngine] = {}


@pytest.fixture(scope="module", autouse=True)
def probe():
    patch = pytest.MonkeyPatch()
    real = engine_mod.final_logits

    def probed(params, x, config, **kw):
        logits = real(params, x, config, **kw)
        jax.debug.callback(lambda a: TICKS.append(np.asarray(a)), logits)
        return logits

    patch.setattr(engine_mod, "final_logits", probed)
    yield
    patch.undo()
    _BUILT.clear()


def _engine(cfg, params, attn="pallas", **kw):
    # (two slots and a budget of 18: three programs to compile, not five;
    # a ring of 5 blocks: the window's 7 slots + a budget-wide slice + 1)
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_blocks", 10)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("prefill_chunk", 16)
    kw.setdefault("tick_token_budget", 18)
    kw.setdefault("cache_dtype", jnp.float32)
    engine = ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                         sample_epilogue="off", **kw)
    assert engine.mixed and engine.ragged_attn_impl == "pallas"
    if attn == "xla":  # the kernel's twin: what a failed probe falls back to
        engine.ragged_attn_impl = "xla"
        engine._mixed_step = engine._make_mixed_step()
    # (the first engine of a geometry compiles for the rest)
    key = (attn, jax.tree.leaves(params)[0].dtype.name,
           tuple(sorted((k, str(v)) for k, v in kw.items())))
    first = _BUILT.setdefault(key, engine)
    if first is not engine:
        engine.share_compiled_steps(first)
    return engine


def _serve(engine, reqs):
    """Run to completion; per request the logits each of its tokens was
    sampled from."""
    got = {r.req_id: [] for r in reqs}
    more = True
    while more:
        before = {r.req_id: len(r.generated) for r in reqs}
        more = engine.step()
        jax.effects_barrier()
        for r in reqs:
            if len(r.generated) > before[r.req_id]:
                slot = r.slot if r.slot >= 0 else r.extra["_slot"]
                got[r.req_id].append(TICKS[-1][slot, 0])
            if r.slot >= 0:
                r.extra["_slot"] = r.slot
    return got


# every sequence goes to the reference at ONE length: its plain jax.numpy
# compiles each operation anew for each length (4 s a length here), and a
# causal model's logits at a position do not depend on what follows it
REF_LEN = 48


def _worst_gap(params, hf, reqs, got, **kw) -> float:
    worst = 0.0
    for r in reqs:
        seq = list(r.prompt) + list(r.generated)
        assert len(seq) <= REF_LEN
        want = np.asarray(ref.forward(
            params, hf, seq + [1] * (REF_LEN - len(seq)), **kw))
        p = len(r.prompt)
        have = np.stack(got[r.req_id])
        assert have.shape[0] == len(r.generated)
        worst = max(worst, _gap(have, want[p - 1:p - 1 + len(r.generated)]))
    return worst


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


SERVE_CASES = {
    # a prompt in slices of 16 beside a short one that decodes while it
    # prefills: contexts three to six windows long, blocks recycle
    "pallas": dict(lengths=[21, 5], new=3, attn="pallas"),
    "xla": dict(lengths=[37, 5], new=5, attn="xla"),
    # a global class too small for all three: one is evicted, its ring let
    # go, and re-prefilled into another
    "evict_requeue": dict(lengths=[20, 22, 18], new=10, attn="xla",
                          engine={}),
}


@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_served_logits_match_the_references_full_forward(tiny, case):
    cfg, hf, params = tiny
    spec = SERVE_CASES[case]
    engine = _engine(cfg, params, spec["attn"], **spec.get("engine", {}))
    reqs = [engine.submit(p, max_new_tokens=spec["new"], seed=i)
            for i, p in enumerate(_prompts(spec["lengths"], seed=11))]
    got = _serve(engine, reqs)
    assert all(len(r.generated) == spec["new"] for r in reqs)
    if case == "evict_requeue":
        assert engine.scheduler.n_preemptions > 0, "pool not tight enough"
    assert _worst_gap(params, hf, reqs, got) < TOL_SERVED
    stats = engine.pool.stats()
    # both classes back to empty; window blocks went round their rings
    assert stats["allocated"] == 0 and stats["window_blocks_in_use"] == 0
    assert stats["window_blocks_recycled_total"] >= 2
    assert stats["window_blocks_per_slot"] == engine.window_blocks == 5


def test_a_bf16_program_is_within_its_tolerance_and_a_bf16_pool_of_float32_is_not(
        tiny, monkeypatch):
    cfg, hf, params = tiny
    prompts = _prompts([21], seed=5)
    half = jax.tree.map(
        lambda a: a.astype(jnp.bfloat16) if a.dtype == jnp.float32 and a.ndim > 1
        and a.shape[-1] != cfg.num_attention_heads else a, params)
    engine = _engine(cfg, half, "xla", cache_dtype=jnp.bfloat16)
    reqs = [engine.submit(p, max_new_tokens=4, seed=i) for i, p in enumerate(prompts)]
    gap = _worst_gap(half, hf, reqs, _serve(engine, reqs))
    assert 10 * TOL_SERVED < gap < TOL_BF16, gap
    control = _engine(cfg, params, "xla", cache_dtype=jnp.bfloat16)
    reqs = [control.submit(p, max_new_tokens=4, seed=i) for i, p in enumerate(prompts)]
    gap = _worst_gap(params, hf, reqs, _serve(control, reqs))
    assert gap > 10 * TOL_SERVED, gap


def test_a_window_block_recycled_a_tick_early_fails(tiny, monkeypatch):
    """The control of the allocator's rule: a ring that lets a block go
    while this tick's first query still sees it serves other logits."""
    cfg, hf, params = tiny
    real = WindowRings.advance

    def early(self, slot, start, n):
        got = real(self, slot, start, n)
        first = np.maximum(np.asarray(start) + n - self.window + 1, 0) // self.block_size
        self.first[slot] = np.minimum(np.maximum(first, self.first[slot]),
                                      self.end[slot] - 1)
        return got

    monkeypatch.setattr(WindowRings, "advance", early)
    engine = _engine(cfg, params, "xla")
    reqs = [engine.submit(p, max_new_tokens=3, seed=i)
            for i, p in enumerate(_prompts([37], seed=11))]
    assert _worst_gap(params, hf, reqs, _serve(engine, reqs)) > 10 * TOL_SERVED


def test_the_pool_has_two_page_classes_and_one_manager(tiny):
    cfg, _, params = tiny
    engine = _engine(cfg, params)
    pages, rings = engine.pool.pages, engine.pool.window
    # 2 global layers of 1 kv head (K 24 / V 16 wide), 3 window layers of
    # 2 (K 48 / V 32), both stored merged; 2 slots x a ring of 5 + scratch
    assert pages.k.shape == (2, 10, 8, 24) and pages.v.shape == (2, 10, 8, 16)
    assert [a.shape for a in pages.window] == [(3, 11, 8, 48), (3, 11, 8, 32)]
    assert pages.merged and pages.kv_heads == 1 and pages.head_dim == 24
    assert len(pages.all_arrays()) == 4 and rings.num_blocks == 11
    # window 8, a slice of the budget's 18, blocks of 8: 25 slots span 4
    # blocks, + 1 (a slice of one chunk of 16 wanted 4)
    assert engine.window_blocks == window_blocks_per_slot(8, 18, 8) == 5
    assert window_blocks_per_slot(8, 16, 8) == 4
    # the published widths: window 128 and the cell's budget of 576
    assert window_blocks_per_slot(128, 576, 64) == 12
    assert window_blocks_per_slot(128, 128, 64) == 5
    # the second table is one more section of the ONE operand
    layout, size = engine._mixed_layouts[engine.mixed_buckets[-1]]
    assert layout["wtables"][1] == (2, 5) and layout["wfirst"][1] == (2,)
    plain_cfg = tiny_config("qwen2")
    plain = ServeEngine(
        init_params(jax.random.PRNGKey(0), plain_cfg, dtype=jnp.float32),
        plain_cfg, max_slots=2, num_blocks=10, block_size=8, max_seq_len=96,
        prefill_chunk=16, tick_token_budget=18, cache_dtype=jnp.float32)
    plain_layout, plain_size = plain._mixed_layouts[plain.mixed_buckets[-1]]
    assert "wtables" not in plain_layout and plain_size == size - 2 * 5 - 2
    gauges = engine.pool_form_gauges()
    assert gauges["kv_global_block_bytes"] == 8 * 2 * (24 + 16) * 4
    assert gauges["kv_window_block_bytes"] == 8 * 3 * 2 * (24 + 16) * 4
    assert "kv_window_blocks_in_use" not in plain.pool_form_gauges()


@pytest.mark.parametrize("kw, match", [
    (dict(enable_prefix_cache=True), "--prefix-cache"),
    (dict(spec_k=2), "--spec-k"),
    (dict(cache_dtype=jnp.int8), "--cache-dtype int8"),
])
def test_what_two_page_classes_cannot_do_yet_is_refused_by_flag(tiny, kw, match):
    cfg, _, params = tiny
    with pytest.raises(ValueError, match=match):
        ServeEngine(params, cfg, max_slots=2, num_blocks=16, block_size=8,
                    max_seq_len=64, **kw)


def test_without_the_kernel_both_classes_take_the_xla_twin(
        tiny, monkeypatch, caplog):
    """A failed ragged probe: the engine is still the unified tick with
    both page classes, over ``ragged_paged_attention_xla``, and says so."""
    from llm_np_cp_tpu.ops.pallas import support

    cfg, _, params = tiny
    monkeypatch.setattr(support, "_FORCE_FAIL", True)
    support._probe.cache_clear()  # conftest clears it again afterwards
    with caplog.at_level("WARNING", logger="llm_np_cp_tpu"):
        engine = ServeEngine(params, cfg, max_slots=2, num_blocks=10,
                             block_size=8, max_seq_len=96, prefill_chunk=16,
                             tick_token_budget=18, cache_dtype=jnp.float32)
    assert engine.mixed and engine.ragged_attn_impl == "xla"
    assert engine.window_blocks and engine.pool.window is not None
    assert set(engine.compile_counts()) == {"mixed_step"}
    assert any("ragged_paged_attention_xla" in r.getMessage()
               for r in caplog.records)


def test_the_offline_cache_refuses_two_page_classes(tiny):
    from llm_np_cp_tpu.cache import KVCache

    cfg, _, params = tiny
    cache = KVCache.init(cfg, 1, 32, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="one kind of K/V page"):
        forward(params, jnp.asarray(_ids(4))[None], cfg, cache)


def test_a_supervised_restart_starts_both_classes_empty(tiny):
    cfg, _, params = tiny
    engine = _engine(cfg, params, "xla")
    reqs = [engine.submit(p, 6, seed=i)
            for i, p in enumerate(_prompts([20, 9], seed=3))]
    for _ in range(4):
        engine.step()
    assert engine.pool.window.in_use > 0
    snap = {r.req_id: list(r.generated) for r in reqs}
    rebuilt = engine.clone_fresh()
    stats = rebuilt.pool.stats()
    assert stats["allocated"] == 0 and stats["window_blocks_in_use"] == 0
    assert rebuilt.window_blocks == engine.window_blocks
    done: dict[int, list[int]] = {r.req_id: [] for r in reqs}
    for r in reqs:
        rebuilt.recover(
            r.prompt, r.max_new_tokens, request_id=r.req_id, seed=r.seed,
            generated=snap[r.req_id],
            callback=lambda req, tok, delta: done[req.req_id].append(tok))
    rebuilt.run_until_complete()
    engine.run_until_complete()
    for r in reqs:  # the replay continues the stream the first engine made
        assert snap[r.req_id] + done[r.req_id] == list(r.generated)
    stats = rebuilt.pool.stats()
    assert stats["allocated"] == 0 and stats["window_blocks_in_use"] == 0


# ----------------------------------------------------------------------
# the window class's allocator, under a seeded schedule
# ----------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 1, 2])
def test_the_allocators_invariants_under_a_seeded_schedule(seed):
    """Requests of random lengths come and go over 3 slots; each tick a
    row prefills a slice or decodes a token.  No block in two chains; a
    chain's blocks are distinct; every position a live query sees is in a
    live block; a recycled block is outside every later window; all
    rings empty after the drain."""
    rng = np.random.default_rng(seed)
    bs, window, chunk, slots = 8, 20, 16, 3
    per = window_blocks_per_slot(window, chunk, bs)
    rings = WindowRings(slots, per, bs, window)
    rows: dict[int, list[int]] = {}  # slot -> [cache slots done, target]
    recycled = 0
    for _ in range(600):
        for s in range(slots):
            if s not in rows and rng.random() < 0.3:
                rows[s] = [0, int(rng.integers(1, 120))]
        for s, (done, target) in list(rows.items()):
            if rng.random() < 0.05:  # preempted / aborted mid-way
                rings.release(s)
                del rows[s]
                continue
            n = int(min(rng.integers(1, chunk + 1), target - done))
            recycled += rings.advance(s, done, n)
            chain = rings.chain(s)
            assert len(set(chain)) == len(chain) <= per
            assert all(1 + s * per <= b <= (s + 1) * per for b in chain)
            lo = max(done - window + 1, 0)
            assert rings.first[s] <= lo // bs
            assert rings.end[s] == (done + n - 1) // bs + 1
            table = rings.table(s)
            for pos in (lo, done, done + n - 1):
                assert table[pos // bs - rings.first[s]] == rings.block(s, pos // bs)
            rows[s][0] = done + n
            if rows[s][0] >= target:
                rings.release(s)
                del rows[s]
        chains = [set(rings.chain(s)) for s in range(slots)]
        assert sum(map(len, chains)) == len(set().union(*chains)) == rings.in_use
    for s in list(rows):
        rings.release(s)
    assert rings.in_use == 0 and recycled == rings.recycled_total > 50
    with pytest.raises(AssertionError, match="ring holds"):
        rings.advance(0, 0, per * bs + 1)


def test_the_scheduler_lets_a_slots_ring_go_with_the_slot():
    from llm_np_cp_tpu.serve.block_pool import FreeList
    from llm_np_cp_tpu.serve.scheduler import Request, Scheduler

    released: list[int] = []
    sched = Scheduler(FreeList(8), max_slots=2, block_size=8,
                      on_slot_release=released.append)
    reqs = [Request(i, np.arange(1, 9, dtype=np.int32), 4) for i in range(3)]
    for r in reqs:
        sched.add(r)
    a, b = sched.admit()
    slots = (a.slot, b.slot)
    sched.finish(a)
    sched.abort(b)
    assert released == list(slots)
    (c,) = sched.admit()
    slot = c.slot
    sched._preempt(c)
    assert released[-1] == slot and c.slot == -1


