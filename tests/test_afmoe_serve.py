"""AFMoE (Trinity) on the SERVED path: prefill in slices, then decode through
a pool whose window and global layers hold pages of ONE shape in two
classes, against the benchmark's independent float32 reference of the whole
sequence (logits, not tokens) with contexts past three windows, so that the
ring turns over; the bounded class never grows; the controls of the
allocator's rule and of the global layers' missing RoPE; what the tick
reports.  The model, the share and the cost file are tests/test_afmoe.py."""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import llm_np_cp_tpu.serve.engine as engine_mod
from llm_np_cp_tpu.config import ModelConfig, tiny_config
from llm_np_cp_tpu.models import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import ServeEngine
from llm_np_cp_tpu.serve.block_pool import WindowRings, window_blocks_per_slot
from llm_np_cp_tpu.serve.tracing import TraceRecorder
from llm_np_cp_tpu.utils.synthetic import hf_config_dict

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))
import reference_afmoe as ref  # noqa: E402

# the served path against float32 at the highest matmul precision: the
# kernel's online softmax on AMLA's grid and the summation order differ
TOL_SERVED = 5e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("afmoe")
    hf = hf_config_dict(cfg)
    assert ModelConfig.from_hf_dict(hf) == cfg
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, hf, params


def _gap(got, want) -> float:
    """Largest logit difference as a share of the reference's spread."""
    want = np.asarray(want)
    spread = float((want.max(-1) - want.mean(-1)).mean())
    return float(np.abs(np.asarray(got, np.float32) - want).max()) / spread


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


def _engine(cfg, params, attn="pallas", wide=0, **kw):
    # (two slots and a budget of 18: three programs to compile, not five;
    # a ring of 5 blocks: the window's 7 slots + a budget-wide slice + 1)
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_blocks", 12)
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
    key = (attn, wide, cfg.head_dim, tuple(sorted(
        (k, str(v)) for k, v in kw.items() if k != "tracer")))
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
# compiles each operation anew for each length, and a causal model's logits
# at a position do not depend on what follows it
REF_LEN = 64


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
    # prefills: contexts past six windows of 8, the ring of 5 blocks
    # turns over more than once
    "pallas": dict(lengths=[53, 5], new=5, attn="pallas"),
    "xla": dict(lengths=[53, 5], new=5, attn="xla"),
    # heads of 128, the published width: both classes stored merged, a head
    # one whole row of lanes of a page (``_lane_pack`` 1), behind a table
    # that starts past position 0
    "pallas_d128": dict(lengths=[53], new=3, attn="pallas", head_dim=128),
    # WIDE query tiles (in small: 16 lanes where the cell's are 64): a
    # slice of 17 or 18 tokens is one wide tile and a tile of 8 lanes, the
    # row that decodes beside it keeps its one-token tile; the window (8)
    # starts and the causal edge lies inside every wide tile
    "pallas_wide": dict(lengths=[53, 5], new=5, attn="pallas", wide=16),
    "pallas_wide_d128": dict(lengths=[53], new=3, attn="pallas",
                             head_dim=128, wide=16),
}


@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_served_logits_match_the_references_full_forward(
        tiny, case, monkeypatch):
    import llm_np_cp_tpu.ops.pallas.decode_attention as da

    cfg, hf, params = tiny
    spec = SERVE_CASES[case]
    # (toy float32 pages would have the widest tile, 64 lanes, more than
    # the toy budget: without ``wide`` the engine has none)
    monkeypatch.setattr(
        da, "ragged_wide_tile", lambda *a: spec.get("wide", 0))
    if "head_dim" in spec:
        cfg = tiny_config("afmoe", head_dim=spec["head_dim"])
        hf = hf_config_dict(cfg)
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    engine = _engine(cfg, params, spec["attn"], wide=spec.get("wide", 0))
    assert engine._wide_tile == spec.get("wide", 0)
    reqs = [engine.submit(p, max_new_tokens=spec["new"], seed=i)
            for i, p in enumerate(_prompts(spec["lengths"], seed=11))]
    grown = []
    real = engine.pool.window.advance

    def watched(*a):
        out = real(*a)
        grown.append(engine.pool.window.in_use)
        return out

    engine.pool.window.advance = watched
    got = _serve(engine, reqs)
    assert all(len(r.generated) == spec["new"] for r in reqs)
    assert _worst_gap(params, hf, reqs, got) < TOL_SERVED
    # a prefill tile held 8 tokens at most, or a wide tile's 16
    snap = engine.metrics.snapshot()
    assert snap["attn_prefill_tiles_packed"] > 0
    assert (snap["attn_prefill_tile_tokens"] > 8) == bool(spec.get("wide"))
    stats = engine.pool.stats()
    # both classes back to empty; the bounded one never held more than its
    # rings while the contexts grew past them, and its blocks went round
    assert stats["allocated"] == 0 and stats["window_blocks_in_use"] == 0
    assert stats["window_blocks_per_slot"] == engine.window_blocks == 5
    assert max(grown) <= len(reqs) * 5
    assert -(-(len(reqs[0].prompt) + spec["new"]) // 8) > 5  # > a ring
    assert stats["window_blocks_recycled_total"] >= 3


def test_the_packer_lays_a_chunks_whole_wide_tiles_first(tiny, monkeypatch):
    """A tick of a program with wide tiles (in small: 16 lanes, the rung
    past two one-tile rows): a prompt slice's whole wide tiles lie first
    on the tiled axis, each named by the first of its two tile entries,
    what is left of the slice and the decoding row's one-token tile behind
    them; the two index maps tie every token to its lane and back; the
    program is the one the totals pick without wide tiles."""
    import llm_np_cp_tpu.ops.pallas.decode_attention as da
    from llm_np_cp_tpu.serve.engine import split_mixed_operands

    cfg, _, params = tiny
    monkeypatch.setattr(da, "ragged_wide_tile", lambda *a: 16)
    engine = _engine(cfg, params, wide=16)
    assert {t: engine._wide_program(t) for t, _ in engine.mixed_buckets} == {
        8: 0, 16: 0, 32: 16}
    packed = []
    real = engine._pack_mixed

    def watched(decode_rows, prefill_segs):
        out = real(decode_rows, prefill_segs)
        packed.append((out[0].copy(), out[1],
                       [1 + r.draft_len for r in decode_rows],
                       [n for _, n in prefill_segs]))
        return out

    engine._pack_mixed = watched
    engine.submit(_prompts([5], seed=3)[0], max_new_tokens=6)
    engine.step()
    engine.submit(_prompts([53], seed=2)[0], max_new_tokens=2)
    engine.run_until_complete()
    wide_ticks = 0
    for ops, program, dec, pre in packed:
        sec = split_mixed_operands(ops, engine._mixed_layouts[program][0])
        sizes = dec + pre
        assert program == engine._pick_bucket(
            sum(-(-n // 8) * 8 for n in sizes), sum(sizes))
        qlen = sec["tile_qlen"]
        n_tok = sum(sizes)
        # every token has its lane, and its lane names it
        lanes = sec["tok_lane"][:n_tok]
        assert len(set(lanes.tolist())) == n_tok
        assert (sec["lane_tok"][lanes] == np.arange(n_tok)).all()
        assert qlen.sum() == n_tok
        wide = engine._wide_program(program[0])
        full = [n // 16 * 16 for n in pre] if wide else []
        n_wide = sum(full) // 16
        # the wide tiles: first, at every second entry, the one after dead
        assert (qlen[0:2 * n_wide:2] == 16).all()
        assert (qlen[1:2 * n_wide:2] == 0).all()
        assert (qlen[2 * n_wide:] <= 8).all()
        if n_wide:
            wide_ticks += 1
            at = len(dec)  # (dense order: decode rows, then the slices)
            tok0 = sum(dec)
            for n, w in zip(pre, full):
                # a slice's tokens: its wide part on consecutive lanes from
                # a multiple of 16 on, then what is left of it
                lane = sec["tok_lane"][tok0:tok0 + n]
                assert lane[0] % 16 == 0 and (np.diff(lane[:w]) == 1).all()
                assert (lane[:w] < 16 * n_wide).all()
                assert (lane[w:] >= 16 * n_wide).all()
                ti = lane[0] // 8
                assert sec["tile_qpos0"][ti] == sec["tok_slot"][tok0]
                tok0 += n
                at += 1
            # ... and a decode row's one token in a tile of its own behind
            for i in range(len(dec)):
                assert sec["tok_lane"][i] >= 16 * n_wide
                assert qlen[sec["tok_lane"][i] // 8] == 1
    assert wide_ticks >= 2 and any(d and any(
        n >= 16 for n in p) for _, _, d, p in packed)


def test_one_shape_lands_in_two_classes(tiny):
    cfg, _, params = tiny
    assert cfg.kv_token_shapes("window") == cfg.kv_token_shapes("global")
    assert cfg.two_page_classes and cfg.swa_num_key_value_heads is None
    engine = _engine(cfg, params)
    pages, rings = engine.pool.pages, engine.pool.window
    # 1 global layer and 4 window layers of 2 kv heads of 16: toy heads are
    # stored merged in both classes; 2 slots x a ring of 5 + scratch
    assert pages.k.shape == (1, 12, 8, 32) and pages.v.shape == (1, 12, 8, 32)
    assert [a.shape for a in pages.window] == [(4, 11, 8, 32)] * 2
    assert pages.merged and rings.num_blocks == 11
    # the published widths: window 4,096, the cell's budget of 800 (a
    # slice of one chunk of 128 wanted 67), blocks of 64
    assert window_blocks_per_slot(4096, 800, 64) == 78
    assert window_blocks_per_slot(4096, 128, 64) == 67
    gauges = engine.pool_form_gauges()
    assert gauges["kv_global_block_bytes"] == 8 * 1 * 2 * 2 * 16 * 4
    assert gauges["kv_window_block_bytes"] == 8 * 4 * 2 * 2 * 16 * 4
    # heads of whole rows of lanes are stored merged too, in BOTH classes
    # (one class of them would stay [BS, K, D]: merges_pages)
    wide = tiny_config("afmoe", head_dim=128)
    from llm_np_cp_tpu.serve.block_pool import BlockPool, merges_pages

    pool = BlockPool(wide, 4, 8, dtype=jnp.float32, state_slots=2,
                     window_blocks=4)
    assert pool.pages.k.shape == (1, 4, 8, 256) and pool.pages.merged
    assert [a.shape for a in pool.pages.window] == [(4, 9, 8, 256)] * 2
    assert not merges_pages(2, 128, False, 2)
    # the published heads are merged by the rule itself, in one class or
    # two (8 x 8 x 6 > 256: the heads-in-rows sheet the compiler refuses);
    # Qwen's 2 heads and a group of 4 over 8 heads are not
    assert merges_pages(8, 128, False, 6) and not merges_pages(8, 128, False, 4)
    assert not merges_pages(2, 128, False, 6) and not merges_pages(8, 128, True, 6)


@pytest.mark.parametrize("control", ["recycled_early", "rope_in_global"])
def test_a_broken_served_path_fails(tiny, monkeypatch, control):
    """The controls of the served path: a ring that lets a block go while
    this tick's first query still sees it, and global layers that rotate
    like the window ones, serve other logits."""
    cfg, hf, params = tiny
    if control == "recycled_early":
        real = WindowRings.advance

        def early(self, slot, start, n):
            got = real(self, slot, start, n)
            first = np.maximum(
                np.asarray(start) + n - self.window + 1, 0) // self.block_size
            self.first[slot] = np.minimum(
                np.maximum(first, self.first[slot]), self.end[slot] - 1)
            return got

        monkeypatch.setattr(WindowRings, "advance", early)
        engine = _engine(cfg, params, "xla")
    else:
        import dataclasses

        engine = _engine(dataclasses.replace(cfg, global_rope=True), params,
                         "xla", num_blocks=13)  # (a geometry of its own)
    reqs = [engine.submit(p, max_new_tokens=3, seed=i)
            for i, p in enumerate(_prompts([37], seed=11))]
    assert _worst_gap(params, hf, reqs, _serve(engine, reqs)) > 10 * TOL_SERVED


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


def test_the_tick_reports_both_classes_and_the_experts(tiny):
    cfg, _, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, "xla", tracer=tracer)
    engine.submit(_prompts([37], seed=2)[0], max_new_tokens=3)
    engine.submit(_prompts([5], seed=3)[0], max_new_tokens=4)
    engine.run_until_complete()
    events = tracer.events()
    build = next(e for e in events if e["name"] == "engine_build")["args"]
    assert build["window_blocks_per_slot"] == 5
    assert build["window_class_bytes"] == 2 * 4 * 11 * 8 * 32 * 4
    assert build["global_class_bytes"] == 2 * 1 * 12 * 8 * 32 * 4
    ticks = [e["args"] for e in events
             if e["name"] == "tick" and "attn_pages_window" in e["args"]]
    assert ticks
    for a in ticks:
        assert a["attn_prefill_tiles"] == (
            a["attn_live_tiles"] - a["attn_decode_tiles"]) >= 0
        assert 0 < a["pairs_held"] == a["pairs_routed"]  # all experts held
        assert a["experts_touched"] > 0 and a["expert_load_max"] >= 1
    # a window layer never streams more than the window's blocks + 1 a
    # tile; a global layer's pages grow with the context
    assert max(a["attn_pages_window"] / a["attn_live_tiles"] for a in ticks) <= 3
    assert max(a["attn_pages_global"] for a in ticks) > max(
        a["attn_pages_window"] for a in ticks)
    assert any(a["attn_prefill_tiles"] for a in ticks)
    snap = engine.metrics.snapshot()
    assert snap["page_class_ticks"] == len(ticks)
    assert snap["attn_pages_window"] == sum(a["attn_pages_window"] for a in ticks)
    assert snap["attn_prefill_tiles"] == sum(a["attn_prefill_tiles"] for a in ticks)
    assert snap["window_blocks_recycled"] == sum(
        a["window_blocks_recycled"] for a in ticks
    ) == engine.pool.window.recycled_total
    text = engine.metrics.prometheus()
    assert "llm_serve_window_ring_recycled_blocks_total" in text
    assert 'llm_serve_attn_pages_streamed_total{kind="window"}' in text
