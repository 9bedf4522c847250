"""The tick accounts for itself (PR 24): the unified tick's host phases
cut at pack / h2d / dispatch / deliver / account, the dispatch's rows,
context and bucket as tick args, the step's named scopes read back as an
op map, the request track down to the socket, set-up and compiles as
spans — and none of it costs anything with tracing off.
"""

import asyncio
import json
import sys
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models.transformer import (
    HYBRID_SCOPES,
    STEP_SCOPES,
    init_params,
)
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import ServeEngine, TraceRecorder, opmap
from llm_np_cp_tpu.serve.tracing import MIXED_TICK_PHASES
from tools.compile_counter import assert_tracing_hooks_guarded
from tools.summarize_trace import (
    MIXED_TICK_PHASES as TOOL_PHASES,
    format_summary,
    request_table,
    setup_spans,
    stray_compiles,
    tick_account,
)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("llama")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"), **kw)


def _ticks_with_phases(events):
    """[(tick event, [its phase events])] — tick() appends them together."""
    out = []
    for i, ev in enumerate(events):
        if ev.get("cat") == "tick" and ev.get("ph") == "X":
            out.append((ev, events[i + 1:i + 1 + len(MIXED_TICK_PHASES)]))
    return out


# workloads: (prompt lengths, new tokens) — decode only after one short
# prefill; prefill chunks beside decode rows; more requests than slots
WORKLOADS = {
    "decode": ([5], 9),
    "mixed": ([5, 21, 11], 6),
    "queued": ([7, 19, 4, 13, 9], 5),
}


@pytest.fixture(scope="module", params=sorted(WORKLOADS))
def traced(request, tiny):
    """A traced run whose every dispatch is hand-counted at pack time."""
    cfg, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    hand = []
    pack = engine._pack_mixed
    bs = engine.block_size

    def counting_pack(decode_rows, prefill_segs):
        sizes = [1 + r.draft_len for r in decode_rows] + \
                [n for _, n in prefill_segs]
        aligned = sum(-(-n // engine._q_tile) * engine._q_tile for n in sizes)
        # the program: the first, by (packed, dense) width, that holds
        # the aligned lanes and the tokens
        width, dense = min(
            p for p in engine.mixed_buckets
            if p[0] >= aligned and p[1] >= sum(sizes))
        hand.append(dict(
            rows=len(sizes), tokens=sum(sizes),
            # a decode row attends its prompt and everything generated
            # so far; a prefill row what it has prefilled with this chunk
            context=sum(r.prompt.size + len(r.generated) for r in decode_rows)
            + sum(r.prefill_done + n for r, n in prefill_segs),
            width=width, dense=dense,
            # one layer's attention call: per query tile, the blocks from
            # the row's left pad to the tile's last token
            pages=sum(
                (r.pad + r.prompt.size + len(r.generated) - 1) // bs
                - r.pad // bs + 1 for r in decode_rows)
            + sum(
                (r.pad + r.prefill_done + min(i + engine._q_tile, n) - 1)
                // bs - r.pad // bs + 1
                for r, n in prefill_segs
                for i in range(0, n, engine._q_tile)),
            # its live query tiles, and those that hold ONE token: a
            # decode row without drafts, a segment of 8 n + 1 tokens' tail
            live_tiles=sum(-(-n // engine._q_tile) for n in sizes),
            decode_tiles=sum(n % engine._q_tile == 1 for n in sizes),
        ))
        packed = pack(decode_rows, prefill_segs)
        hand[-1]["bytes"] = packed[0].nbytes
        # ...as the packed operand itself says them
        off, shape = engine._mixed_layouts[packed[1]][0]["tile_qlen"]
        qlen = packed[0][off:off + shape[0]]
        assert hand[-1]["live_tiles"] == int((qlen > 0).sum())
        assert hand[-1]["decode_tiles"] == int((qlen == 1).sum())
        hand[-1]["array_rows"] = sum(not r.draft_len for r in decode_rows)
        return packed

    engine._pack_mixed = counting_pack
    lens, new = WORKLOADS[request.param]
    rng = np.random.default_rng(7)
    for n in lens:
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), new)
    engine.run_until_complete()
    return engine, tracer, hand


def test_cut_phases_are_consecutive_and_sum_to_the_tick(traced):
    _, tracer, _ = traced
    ticks = _ticks_with_phases(tracer.events())
    assert ticks
    for tick, phases in ticks:
        assert [p["name"] for p in phases] == list(MIXED_TICK_PHASES)
        assert phases[0]["ts"] == tick["ts"]
        for a, b in zip(phases, phases[1:]):
            assert a["ts"] + a["dur"] == pytest.approx(b["ts"], abs=1e-6)
        end = phases[-1]["ts"] + phases[-1]["dur"]
        assert end <= tick["ts"] + tick["dur"] + 1e-6
        # the tick span closes a clock read after its last phase
        assert sum(p["dur"] for p in phases) == pytest.approx(
            end - tick["ts"], abs=1e-3)
    assert TOOL_PHASES == MIXED_TICK_PHASES


def test_tick_args_equal_a_hand_count_of_the_planned_rows(traced):
    engine, tracer, hand = traced
    # the counter anyone can scrape: dense lanes dispatched, beside the
    # tokens in them
    snap = engine.metrics.snapshot()
    assert snap["mixed_dense_lanes"] == sum(h["dense"] for h in hand)
    assert (snap["mixed_prefill_tokens"] + snap["mixed_decode_tokens"]
            == sum(h["tokens"] for h in hand) <= snap["mixed_dense_lanes"])
    assert (f"llm_serve_mixed_dense_lanes_total {snap['mixed_dense_lanes']}"
            in engine.metrics.prometheus().splitlines())
    ticks = [(t, p) for t, p in _ticks_with_phases(tracer.events())
             if t["args"]["packed_width"]]
    assert len(ticks) == len(hand) > 0
    for (tick, phases), want in zip(ticks, hand):
        args = tick["args"]
        assert args["active_slots"] == want["rows"]
        assert args["context_tokens"] == want["context"]
        assert args["packed_width"] == want["width"]
        assert args["dense_width"] == want["dense"]
        assert args["thread_cpu_us"] >= 0.0
        # the attention call: pages asked for, and the kv grid it takes
        # (tiles x groups of P pages; this table is one group wide)
        assert args["attn_pages"] == want["pages"]
        assert args["attn_pages_per_step"] == engine.max_blocks_per_seq == 8
        assert args["attn_grid_steps"] == want["width"] // engine._q_tile
        # the tiles that take the kernel's one-token branch
        assert args["attn_live_tiles"] == want["live_tiles"]
        assert args["attn_decode_tiles"] == want["decode_tiles"]
        h2d = next(p for p in phases if p["name"] == "h2d")
        assert args["pack_array_rows"] == want["array_rows"]
        # ONE transfer a tick: the packed operand
        assert h2d["args"] == {"count": 1, "bytes": want["bytes"]}


def test_idle_tick_has_empty_dispatch_phases(tiny):
    cfg, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    engine.step()  # nothing submitted: plans nothing, dispatches nothing
    (tick, phases), = _ticks_with_phases(tracer.events())
    by = {p["name"]: p for p in phases}
    for name in ("pack", "h2d", "mixed_dispatch", "host_sync"):
        assert by[name]["dur"] == 0.0
    assert tick["args"]["active_slots"] == tick["args"]["packed_width"] == 0
    assert tick["args"]["dense_width"] == 0
    assert by["h2d"]["args"] == {"count": 0, "bytes": 0}


def test_every_phase_runs_under_its_annotation_and_dispatch_ends_with_it(
        tiny, monkeypatch):
    """``serve.<phase>`` annotations bracket the recorder's phases; the
    ``serve.mixed_dispatch`` annotation (what the benchmark aligns the
    clocks on) closes exactly one clock read before the phase does."""
    cfg, params = tiny
    reads = [0]

    def clock():  # every read is one step: order is exact
        reads[0] += 1
        return float(reads[0])

    log = []

    class FakeAnnotation:
        def __init__(self, name, **meta):
            self.name = name

        def __enter__(self):
            log.append(("enter", self.name, reads[0]))
            return self

        def __exit__(self, *exc):
            log.append(("exit", self.name, reads[0]))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    tracer = TraceRecorder(clock=clock)
    engine = _engine(cfg, params, tracer=tracer)
    engine.submit(np.arange(1, 8), 4)
    engine.run_until_complete()
    ticks = [(t, p) for t, p in _ticks_with_phases(tracer.events())
             if t["args"]["packed_width"]]
    assert ticks
    spans = {}
    for kind, name, at in log:
        if kind == "enter":
            spans.setdefault(name, []).append([at, None])
        else:
            spans[name][-1][1] = at
    assert set(spans) == {"serve." + p for p in MIXED_TICK_PHASES}
    us = lambda reading: (reading - tracer._t0) * 1e6  # noqa: E731
    dispatches = [s for s in spans["serve.mixed_dispatch"]]
    assert len(dispatches) == len(ticks)
    for (tick, phases), (a0, a1) in zip(ticks, dispatches):
        ph = next(p for p in phases if p["name"] == "mixed_dispatch")
        # the annotation's scope lies inside the phase and ends with it:
        # no clock read between its exit and the phase's end stamp
        assert us(a0) >= ph["ts"] - 1e-6
        assert ph["ts"] + ph["dur"] == pytest.approx(us(a1 + 1), abs=1e-6)
    # the other phases: entered right after their start stamp, left
    # right before their end stamp
    every = {p["name"]: [] for p in ticks[0][1]}
    for _, phases in _ticks_with_phases(tracer.events()):
        for p in phases:
            every[p["name"]].append(p)
    for name in MIXED_TICK_PHASES:
        if name == "mixed_dispatch":
            continue
        got = spans["serve." + name]
        ticks_of = [p for p in every[name]
                    if name in ("admission", "draft", "grow", "plan", "pack",
                                "account") or p["dur"] > 0]
        assert len(got) == len(ticks_of), name
        for p, (a0, a1) in zip(ticks_of, got):
            assert us(a0) == pytest.approx(p["ts"], abs=1e-6), name
            assert us(a1 + 1) == pytest.approx(p["ts"] + p["dur"], abs=1e-6)


@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8],
                         ids=["f32", "int8"])
def test_op_map_names_every_scope_and_marks_the_pool(tiny, cache_dtype):
    cfg, params = tiny
    engine = _engine(cfg, params, cache_dtype=cache_dtype)
    engine.warmup([12], max_new_tokens=3)
    warm = engine._mixed_step._cache_size()
    op_map = engine.device_op_map()
    # keyed the way a profile names an operation, one answer or None each
    assert op_map and all(key.startswith("%") and " " in key for key in op_map)
    known = [v for v in op_map.values() if v is not None]
    assert all(len(v) == 2 for v in known)
    # (a stack of one kind of layer enters none of the hybrid scopes)
    assert {scope for scope, _ in known} == (
        set(STEP_SCOPES) - set(HYBRID_SCOPES)) | {""}
    # the layer loop carries the pool and writes it in place: nothing the
    # step computes has the shape of one layer's slab
    assert {kind for _, kind in known} == {"pool", ""}
    k = engine.pool.pages.k
    whole = opmap.hlo_shape(k.dtype.name, k.shape)
    flat = opmap.hlo_shape(
        k.dtype.name, (k.shape[0] * k.shape[1],) + k.shape[2:])
    writes = [key for key, val in op_map.items()
              if val is not None and val[0] == "kv_write" and val[1]]
    assert writes and all(key.split(" ", 1)[1] != whole for key in writes)
    for key, val in op_map.items():
        if val is not None and key.split(" ", 1)[1] in (whole, flat):
            assert val[1] == "pool"
    # the map is read from the warm step itself: no second jit of it,
    # no executable more in the step's cache
    assert engine._mixed_step._cache_size() == warm == len(engine.mixed_buckets)


HLO = """HloModule jit_step, entry_computation_layout={()->f32[4]}

%fused_computation.1 (p0: bf16[6,2,8]) -> bf16[6,2,8] {
  %p0 = bf16[6,2,8]{2,1,0} parameter(0)
  ROOT %inner.7 = bf16[6,2,8]{2,1,0} negate(%p0), metadata={op_name="jit(step)/mlp/neg"}
}

%region_0.2 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0)
  %b = f32[] parameter(1)
  ROOT %add.9 = f32[] add(%a, %b)
}

%body.3 (arg: (s32[], bf16[3,6,2,8])) -> (s32[], bf16[3,6,2,8]) {
  %arg = (s32[], bf16[3,6,2,8]{3,2,1,0}) parameter(0)
  %gte.1 = bf16[3,6,2,8]{3,2,1,0} get-tuple-element(%arg), index=1
  %slice_fusion.4 = bf16[6,2,8]{2,1,0:T(2,128)(2,1)} fusion(%gte.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/squeeze"}
  %fusion.5 = bf16[6,2,8]{2,1,0} fusion(%slice_fusion.4), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/closed_call/kv_write/scatter"}
  %fusion.6 = bf16[4,16]{1,0} fusion(%fusion.5), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/closed_call/mlp/bsh,ho->bso/dot_general"}
  %attn_kernel.2 = bf16[4,2,8]{2,1,0} custom-call(%fusion.6), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/while/body/closed_call/attn/jit(ragged)/pallas_call"}
  %dus_fusion.8 = bf16[3,6,2,8]{3,2,1,0} fusion(%gte.1, %fusion.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/dynamic_update_slice"}
  ROOT %tuple.9 = (s32[], bf16[3,6,2,8]{3,2,1,0}) tuple(%gte.1, %dus_fusion.8)
}

%cond.4 (arg.1: (s32[], bf16[3,6,2,8])) -> pred[] {
  %arg.1 = (s32[], bf16[3,6,2,8]{3,2,1,0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%arg.1, %arg.1), direction=LT, metadata={op_name="jit(step)/while/cond/lt"}
}

ENTRY %main.10 (pages: bf16[3,6,2,8]) -> f32[4] {
  %pages = bf16[3,6,2,8]{3,2,1,0} parameter(0)
  %embed_fusion = f32[4,16]{1,0} fusion(%pages), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/embed/gather"}
  %while.12 = (s32[], bf16[3,6,2,8]{3,2,1,0}) while(%pages), condition=%cond.4, body=%body.3, metadata={op_name="jit(step)/while"}
  %copy.88 = bf16[3,6,2,8]{3,2,1,0} copy(%pages)
  %reduce.3 = f32[4]{0} reduce(%embed_fusion, %embed_fusion), dimensions={1}, to_apply=%region_0.2, metadata={op_name="jit(step)/tail/reduce_sum"}
  ROOT %tail.1 = f32[4]{0} negate(%reduce.3), metadata={op_name="jit(step)/tail/neg"}
}
"""
POOL = opmap.pool_shapes([("bfloat16", (3, 6, 2, 8))])


def test_pool_shapes_know_the_flat_pool():
    """The unified step carries the pool flat over (layer, block); a
    one-layer pool's flat shape is its slab's, and counts as the pool."""
    assert POOL == {"bf16[3,6,2,8]": "pool", "bf16[18,2,8]": "pool",
                    "bf16[6,2,8]": "slab", "bf16[1,6,2,8]": "slab"}
    one = opmap.pool_shapes(iter([("int8", (1, 6, 2, 8)), ("float32", (1, 6, 2))]))
    assert one["s8[6,2,8]"] == one["f32[6,2]"] == "pool"
    assert one["s8[1,6,2,8]"] == "pool"
PARSED = opmap.op_map_from_hlo(HLO, STEP_SCOPES, POOL)


@pytest.mark.parametrize("name,want", [
    # the entry computation and the loop's body and condition
    ("embed_fusion", ["embed", "f32[4,16]", ""]),
    ("reduce.3", ["tail", "f32[4]", ""]),
    ("fusion.6", ["mlp", "bf16[4,16]", ""]),
    ("attn_kernel.2", ["attn", "bf16[4,2,8]", ""]),
    ("lt.1", ["", "pred[]", ""]),
    # pool movement: told by the result's shape, with a scope or without
    ("copy.88", ["", "bf16[3,6,2,8]", "pool"]),
    ("dus_fusion.8", ["", "bf16[3,6,2,8]", "pool"]),
    ("slice_fusion.4", ["", "bf16[6,2,8]", "slab"]),
    ("fusion.5", ["kv_write", "bf16[6,2,8]", "slab"]),
    # a loop's whole carry is not pool-shaped: not every array in it is
    ("while.12", ["", "(s32[], bf16[3,6,2,8])", ""]),
    # bodies of fusions and reducers are part of the op that calls them;
    # parameters, tuples and get-tuple-elements never run on their own
    ("inner.7", None), ("add.9", None), ("pages", None), ("gte.1", None),
    ("tuple.9", None),
])
def test_op_map_parser(name, want):
    assert PARSED.get(name) == want


def test_op_map_merge_is_by_name_and_shape_and_refuses_ambiguity():
    buckets = [
        {"fusion.1": ["mlp", "bf16[8,16]", ""],
         "kernel.2": ["attn", "bf16[8,2,8]", ""],
         "fusion.3": ["qkv", "f32[4]", ""]},
        # another bucket reuses the names: told apart by shape where the
        # shape differs, ambiguous where it does not
        {"fusion.1": ["qkv", "bf16[16,16]", ""],
         "kernel.2": ["attn", "bf16[16,2,8]", ""],
         "fusion.3": ["tail", "f32[4]", ""]},
    ]
    table = opmap.merge(buckets)
    assert table["%fusion.1 bf16[8,16]"] == ["mlp", ""]
    assert table["%fusion.1 bf16[16,16]"] == ["qkv", ""]
    assert table["%kernel.2 bf16[16,2,8]"] == ["attn", ""]
    assert table["%fusion.3 f32[4]"] is None
    long = "(" + ", ".join(["bf16[1,512,1536]"] * 6) + ")"
    assert opmap.trace_key("while.1", long) == "%while.1 " + long[:57] + "..."


def test_scopes_change_no_program(tiny):
    """Named scopes are metadata: the step lowered with them is, debug
    information stripped, the text it is without them."""
    import re

    from llm_np_cp_tpu.models import transformer

    cfg, params = tiny

    def lowered():
        engine = _engine(cfg, params)
        text = engine._make_mixed_step().lower(
            engine.params, engine.pool.pages,
            engine._put(engine._dead_mixed_operands(8, 8))).as_text()
        return re.sub(r"loc\(.*?\)$|^#loc.*$", "", text, flags=re.M)

    with_scopes = lowered()
    real = jax.named_scope
    try:
        import contextlib

        jax.named_scope = lambda name: contextlib.nullcontext()
        without = lowered()
    finally:
        jax.named_scope = real
    assert transformer.SCOPE_MLP in STEP_SCOPES
    assert with_scopes == without


def test_setup_spans_cover_build_and_each_warmup_bucket(tiny):
    cfg, params = tiny
    tracer = TraceRecorder()
    tracer.watch_compiles()
    engine = _engine(cfg, params, tracer=tracer)
    engine.warmup([12], max_new_tokens=3)
    events = tracer.to_dict()["traceEvents"]
    assert {ev["cat"] for ev in events if ev["ph"] != "M"} == {
        "setup", "compile"}, "the dummy request leaves no tick, no track"
    setup = [ev for ev in events if ev.get("cat") == "setup"]
    by = {}
    for ev in setup:
        by.setdefault(ev["name"], []).append(ev)
    # exactly these, each once but the buckets: nothing else leaks in
    assert {name: len(evs) for name, evs in by.items()} == {
        "probe.ragged_attn": 1, "pool_alloc": 1,
        "probe.sample_epilogue": 1, "engine_build": 1, "warmup.request": 1,
        "warmup.bucket": len(engine.mixed_buckets), "warmup": 1, "op_map": 1,
    }

    def inside(child, parent):
        return (parent["ts"] <= child["ts"] and child["ts"] + child["dur"]
                <= parent["ts"] + parent["dur"] + 1e-6)

    build, = by["engine_build"]
    assert all(inside(ev, build) for name in ("pool_alloc", "probe.ragged_attn")
               for ev in by[name])
    assert by["pool_alloc"][0]["args"]["bytes"] == sum(
        a.nbytes for a in engine.pool.pages if a is not None)
    warm, = by["warmup"]
    assert [(ev["args"]["width"], ev["args"]["dense"])
            for ev in by["warmup.bucket"]] == list(engine.mixed_buckets)
    assert build["args"]["buckets"] == len(engine.mixed_buckets)
    assert all(inside(ev, warm) for ev in by["warmup.bucket"] + by["warmup.request"])
    assert not inside(by["op_map"][0], warm)  # tracing's own cost, apart
    # every compile the process made meanwhile is a span that names where
    # it fell; a bucket says whether one ran that the cache did not serve
    compiles = [ev for ev in events if ev.get("cat") == "compile"]
    assert compiles and all("within" in ev["args"] for ev in compiles)
    for ev in by["warmup.bucket"]:
        missed = [c for c in compiles if not c["args"]["cache_hit"]
                  and inside(c, ev)]
        assert ev["args"]["compiled"] == bool(missed)
    assert tracer.get_other("op_map") == engine.device_op_map()
    rows = setup_spans(events)
    assert [r["name"] for r in rows] == [
        ev["name"] for ev in sorted(setup, key=lambda e: e["ts"])]
    # the warm-up's programs, each with its seconds, on one line
    line = next(ln for ln in format_summary(events).splitlines()
                if ln.lstrip().startswith("warm-up:"))
    assert f"warm-up: {len(engine.mixed_buckets)} programs" in line
    assert all(f"{t_w}x{d_w}" in line for t_w, d_w in engine.mixed_buckets)


def test_compile_span_names_the_set_up_span_it_fell_in():
    from llm_np_cp_tpu.serve import tracing

    tracer = TraceRecorder()
    tracer.watch_compiles()
    t0 = tracer.now_us()
    jax.jit(lambda x: x * 3 + 1)(jnp.arange(5)).block_until_ready()
    tracer.complete("engine_build", t0, cat="setup")
    jax.jit(lambda x: x * 5 - 1)(jnp.arange(5)).block_until_ready()
    spans = [ev for ev in tracer.to_dict()["traceEvents"]
             if ev.get("cat") == "compile"]
    assert len(spans) >= 2 and tracer.n_compiles == len(spans)
    assert spans[0]["args"]["within"] == "engine_build"
    assert spans[-1]["args"]["within"] is None
    # the operator's summary names the compiles that fell outside set-up
    events = tracer.to_dict()["traceEvents"]
    stray = stray_compiles(events)
    assert set(stray) == {"between spans"} and stray["between spans"] >= 1
    assert (f"compiles outside set-up: {stray['between spans']} in between "
            "spans") in format_summary(events)
    phase = {"name": "deliver", "cat": "phase", "ph": "X", "pid": 1,
             "tid": spans[-1]["tid"], "ts": spans[-1]["ts"] - 1.0,
             "dur": spans[-1]["dur"] + 2.0}
    named = tracing._name_sites(tracer.events() + [phase])
    assert stray_compiles(named)["deliver"] == 1
    assert all(ev["dur"] > 0 and ev["name"] == "backend_compile" for ev in spans)
    # a recorder that is gone stops being fed
    n = tracer.n_compiles
    tracing._compile_watchers.discard(tracer)
    jax.jit(lambda x: x * 7)(jnp.arange(5)).block_until_ready()
    assert tracer.n_compiles == n


def test_tracing_on_adds_no_compile_and_off_runs_no_tracing_code(tiny):
    """Same workload traced and untraced: the step compiles the same
    number of times (once per bucket touched), and with tracing off the
    tick never reaches a phase mark, an annotation or the CPU clock."""
    cfg, params = tiny

    def drive(tracer):
        engine = _engine(cfg, params, tracer=tracer)
        marks = []
        engine._phase_mark = lambda name, **meta: marks.append(name) or 0.0
        rng = np.random.default_rng(5)
        for n in (5, 17, 9):
            engine.submit(rng.integers(1, cfg.vocab_size, size=n), 6)
        engine.run_until_complete()
        return engine._mixed_step._cache_size(), marks, [
            list(r.generated) for r in engine.scheduler.finished]

    off_compiles, off_marks, off_tokens = drive(None)
    on_compiles, on_marks, on_tokens = drive(TraceRecorder())
    assert off_compiles == on_compiles > 0
    assert off_marks == [] and len(on_marks) > 0
    assert sorted(off_tokens) == sorted(on_tokens)
    assert_tracing_hooks_guarded()


@pytest.mark.parametrize("snippet", [
    "t0 = self._phase_mark('serve.admission')",
    "cpu = time.thread_time_ns()",
    "ann = jax.profiler.TraceAnnotation('serve.pack')",
    "t = self.tracer.now_us() if self.tracer is not None "
    "else self._phase_mark(None)",
])
def test_hook_lint_bites_tracing_only_calls_outside_the_guard(tmp_path, snippet):
    bad = tmp_path / "bad.py"
    bad.write_text(
        "class E:\n    def step(self):\n        " + snippet + "\n")
    with pytest.raises(AssertionError, match="tracer is not None"):
        assert_tracing_hooks_guarded((str(bad),))
    good = tmp_path / "good.py"
    good.write_text(
        "class E:\n    def step(self):\n"
        "        if self.tracer is not None:\n            " + snippet + "\n")
    if "else self._phase_mark" not in snippet:
        assert_tracing_hooks_guarded((str(good),))


@pytest.mark.http
def test_request_track_runs_down_to_the_socket(tiny):
    """A streamed request: ``first_write`` after the first token's emit,
    ``stream_end`` with lag stats over as many frames as tokens; a
    response that streams nothing leaves neither."""
    from llm_np_cp_tpu.serve.http.client import (
        astream_completion,
        post_completion,
    )
    from llm_np_cp_tpu.serve.http.server import HttpServer

    cfg, params = tiny
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    engine.warmup([8], max_new_tokens=3)
    n_tokens = 7

    async def main():
        srv = HttpServer(engine, model_id="tiny", drain_timeout=30.0)
        await srv.start("127.0.0.1", 0)
        streamed = await astream_completion(
            srv.host, srv.port,
            {"prompt": [3, 5, 7, 9], "max_tokens": n_tokens, "stream": True},
            timeout=120)
        loop = asyncio.get_running_loop()
        unary = await loop.run_in_executor(
            None, lambda: post_completion(
                srv.host, srv.port,
                {"prompt": [3, 5, 7, 9], "max_tokens": 3}))
        srv.begin_drain()
        await asyncio.wait_for(srv.serve_until_shutdown(), timeout=60)
        return streamed, unary

    streamed, unary = asyncio.run(asyncio.wait_for(main(), timeout=180))
    events = tracer.events()
    tracks = {}
    for ev in events:
        if ev.get("cat") == "request":
            tracks.setdefault(ev["id"], []).append(ev)
    by_stream = {rid: evs for rid, evs in tracks.items()
                 if any(e["name"] == "http" and e["ph"] == "b"
                        and e["args"]["stream"] for e in evs)}
    (rid, evs), = by_stream.items()
    named = {(e["name"], e["ph"]): e for e in evs}
    first_write, end = named[("first_write", "n")], named[("stream_end", "n")]
    assert first_write["args"]["lag_us"] >= 0.0
    # after the first token was emitted (the prefill span holds the emit)
    assert first_write["ts"] >= named[("prefill", "b")]["ts"]
    assert first_write["ts"] <= named[("http", "e")]["ts"]
    assert end["args"]["frames"] == n_tokens
    assert 0.0 <= end["args"]["lag_mean_us"] <= end["args"]["lag_max_us"]
    assert end["ts"] <= named[("http", "e")]["ts"]
    assert named[("finish", "n")]["ts"] <= end["ts"]
    # the other request streamed nothing: no socket instants, no state left
    other = [e["name"] for r, es in tracks.items() if r != rid for e in es]
    assert "http" in other and "first_write" not in other
    assert "stream_end" not in other and tracer._streams == {}
    # the operator's table carries both lags for the streamed request
    row = request_table(events)[rid]
    assert row["first_write_lag_us"] == first_write["args"]["lag_us"]
    assert row["write_lag_us"] == end["args"]["lag_mean_us"]
    assert row["write_lag_max_us"] == end["args"]["lag_max_us"]
    assert row["frames"] == n_tokens
    out = format_summary(events)
    assert "fw_lag_ms" in out and "wr_max_ms" in out and "frames" in out
    line = next(ln for ln in out.splitlines()
                if ln.split()[:1] == [str(rid)])
    assert f"{end['args']['lag_max_us'] / 1e3:.2f}" in line
    assert str(n_tokens) in line.split()


def test_summarize_tick_account_and_device_scopes(traced, tmp_path):
    engine, tracer, hand = traced
    events = tracer.events()
    acct = tick_account(events)
    assert acct["ticks"] == len(hand)
    assert acct["h2d_count"] == 1
    assert acct["context_tokens"] == pytest.approx(
        sum(h["context"] for h in hand) / len(hand))
    parts = sum(acct[p + "_us"] for p in MIXED_TICK_PHASES)
    assert parts == pytest.approx(acct["tick_us"], rel=0.05)
    assert acct["host_wait_us"] <= acct["tick_us"]
    assert sum(acct["packed_widths"].values()) == len(hand)
    assert set(acct["packed_widths"]) == {h["width"] for h in hand}
    assert sum(acct["programs"].values()) == len(hand)
    assert set(acct["programs"]) == {
        f"{h['width']}x{h['dense']}" for h in hand}
    assert acct["dense_occupancy"] == pytest.approx(
        sum(h["tokens"] for h in hand) / sum(h["dense"] for h in hand))
    assert 0.0 < acct["dense_occupancy"] <= 1.0
    # ... and of the tile-aligned axis inside attention (a decode row is
    # one token of a tile's eight lanes): at most the dense axis' share
    assert acct["tile_lane_occupancy"] == pytest.approx(
        sum(h["tokens"] for h in hand) / sum(h["width"] for h in hand))
    assert 0.0 < acct["tile_lane_occupancy"] <= acct["dense_occupancy"]
    assert acct["pack_array_rows"] == pytest.approx(
        sum(h["array_rows"] for h in hand) / len(hand))
    assert acct["attn_pages"] == pytest.approx(
        sum(h["pages"] for h in hand) / len(hand))
    assert acct["attn_slot_share"] == pytest.approx(
        sum(h["pages"] for h in hand)
        / sum(h["width"] // engine._q_tile * 8 for h in hand))
    assert acct["attn_decode_tile_share"] == pytest.approx(
        sum(h["decode_tiles"] for h in hand)
        / sum(h["live_tiles"] for h in hand))
    assert 0.0 < acct["attn_decode_tile_share"] <= 1.0
    out = format_summary(events)
    assert (f"attention streams {acct['attn_pages']:.0f} pages a layer in "
            f"{acct['attn_grid_steps']:.0f} kv grid steps") in out
    assert (f"{acct['attn_decode_tiles']:.1f} of "
            f"{acct['attn_live_tiles']:.1f} live query tiles a dispatch "
            f"({acct['attn_decode_tile_share']:.0%}) hold one token; "
            f"{acct['tile_lane_occupancy']:.0%} of the tiled axis' lanes "
            "held a token") in out
    assert "== tick account" in out and "pack " in out
    assert (f"h2d 1 transfers, {acct['h2d_bytes']:.0f} bytes; pack wrote "
            f"{acct['pack_array_rows']:.1f} of {acct['rows']:.1f} rows as "
            "arrays") in out
    assert "packed width " + " ".join(
        f"{w}x{n}" for w, n in acct["packed_widths"].items()
    ) + "; programs (packed x dense width: ticks) " + " ".join(
        f"{p}:{n}" for p, n in acct["programs"].items()) in out
    assert tick_account([]) is None


def test_publish_counter_follows_the_order_of_the_tick(traced):
    """``deliver`` comes BEFORE ``host_sync`` and hands out the previous
    tick's items: its tick args say how many and whether a dispatch was
    in flight; the run's last tick hands its own out at its end
    (``publish_drained_rows``); ``/metrics`` counts both kinds; and the
    tool prints the share of dispatching ticks that hid their publish."""
    engine, tracer, hand = traced
    ticks = _ticks_with_phases(tracer.events())
    order = [p["name"] for p in ticks[0][1]]
    assert (order.index("mixed_dispatch") + 1 == order.index("deliver")
            == order.index("host_sync") - 1 == order.index("accept") - 2)
    dispatching = [t["args"] for t, _ in ticks if t["args"]["packed_width"]]
    assert len(dispatching) == len(hand)
    # the first dispatching tick has nothing to hand out; every later one
    # hands out what the tick before it accepted, behind its dispatch
    assert dispatching[0]["publish_rows"] == 0
    assert dispatching[0]["publish_overlapped"] == 0
    for prev, args in zip(dispatching, dispatching[1:]):
        assert args["publish_overlapped"] == 1 and args["publish_rows"] >= 1
        assert args["host_fetches"] == 1  # the one-fetch contract holds
        assert prev["publish_drained_rows"] == 0
    # the last tick leaves no work: its own tokens and terminals go out
    # on the spot, and nothing stays owed
    assert dispatching[-1]["publish_drained_rows"] >= 2
    assert not engine._owed
    handed = sum(a["publish_rows"] + a["publish_drained_rows"]
                 for a in dispatching)
    snap = engine.metrics.snapshot()
    # every token and every terminal is one item, handed out once
    assert handed == snap["total_generated_tokens"] + snap["finished"]
    assert snap["publish_overlapped_ticks"] == len(dispatching) - 1
    assert snap["publish_immediate_ticks"] == 1
    prom = engine.metrics.prometheus().splitlines()
    assert (f"llm_serve_publish_overlapped_ticks_total "
            f"{len(dispatching) - 1}") in prom
    assert "llm_serve_publish_immediate_ticks_total 1" in prom
    acct = tick_account(tracer.events())
    assert acct["publish_ticks"] == len(dispatching) - 1
    assert acct["publish_overlapped_share"] == 1.0
    assert acct["accept_us"] > 0.0 and acct["deliver_us"] > 0.0
    out = format_summary(tracer.events())
    assert (f"publish: 100.0% of {len(dispatching) - 1} dispatching ticks "
            "handed the previous tick's") in out
    # the tick's sentinel sees the same phases, in the same order
    from llm_np_cp_tpu.serve.slo import TickSentinel

    seen = []
    sentinel = TickSentinel()
    observe = sentinel.observe
    sentinel.observe = lambda phases: (seen.append(
        [p[0] for p in phases]), observe(phases))[1]
    watched = _engine(engine.config, engine.params, tracer=TraceRecorder(),
                      sentinel=sentinel)
    watched.submit(np.arange(1, 6), 3)
    watched.run_until_complete()
    assert seen and all(names == list(MIXED_TICK_PHASES) for names in seen)


def test_emit_stamps_keep_the_item_shape_and_order():
    """The recorder pairs emits and writes in order, per request, across
    threads; what the HTTP layer hands over keeps its shape."""
    tracer = TraceRecorder()
    done = threading.Event()

    def tick_thread():
        for _ in range(5):
            tracer.stamp_emit(1)
            tracer.stamp_emit(2)
        done.set()

    t = threading.Thread(target=tick_thread)
    t.start()
    done.wait(5)
    t.join()
    for _ in range(5):
        tracer.frame_written(1)
    tracer.frame_written(3)  # never emitted under the tracer: nothing
    tracer.stream_end(1)
    tracer.stream_end(2)     # emitted, nothing written: no instant
    tracer.stream_end(3)
    names = [(e["name"], e["id"]) for e in tracer.events()
             if e.get("cat") == "request"]
    assert names == [("first_write", 1), ("stream_end", 1)]
    end = [e for e in tracer.events() if e["name"] == "stream_end"][0]
    assert end["args"]["frames"] == 5 and tracer._streams == {}
    json.dumps(tracer.to_dict())
