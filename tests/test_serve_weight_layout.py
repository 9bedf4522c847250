"""No weight is re-laid-out inside a tick (PR 54): the engine asks the
compiler which layout the steady decode program reads each weight in and
puts the weights there ONCE, when it is built.

What only a TPU's compiler can say - which leaves it wants turned - is
tests/test_kernel_lowering.py's business (a described v5e, the cells'
widths).  Here, on the CPU: the mechanism.  A CPU keeps its arrays
untiled and its compiler asks for nothing, so these tests hand the engine
the answer a v5e gives (the projections whose result is split into heads,
contracting axis minor: ``major_to_minor=(0, 2, 1)``) and hold it to what
the re-put must be - invisible to everything but the clock.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.layout import Format, Layout

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import ServeEngine, TraceRecorder, opmap

# the stacks whose projections a v5e turns, and the leaves it turns
# there (the chip: q_proj and k_proj of mimo's 7 layers; q_proj, kv_a_proj
# and kv_b_proj of kanana's 24)
TURNED = {
    "mimo_v2": ("q_proj", "k_proj"),
    "deepseek_v3": ("q_proj", "kv_a_proj", "kv_b_proj"),
}
PROMPTS = ((5, 7), (21, 6), (11, 8))


def _weights(arch, seed=0):
    cfg = tiny_config(arch)
    return cfg, init_params(jax.random.PRNGKey(seed), cfg, dtype=jnp.float32)


def _engine(cfg, params, **kw):
    return ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=3,
        num_blocks=48, block_size=8, max_seq_len=64, prefill_chunk=16,
        cache_dtype=jnp.float32, **kw)


def _turned_formats(params, names):
    """What ``step_weight_formats`` answers on a v5e, made by hand: every
    leaf's own format, the named 3-d leaves with their last two axes
    swapped."""
    def fmt(path, leaf):
        if path[-1].key in names and leaf.ndim == 3:
            return Format(Layout(major_to_minor=(0, 2, 1)), leaf.sharding)
        return leaf.format
    return jax.tree_util.tree_map_with_path(fmt, params)


def _decides(monkeypatch, names):
    monkeypatch.setattr(
        ServeEngine, "_decide_weight_formats",
        lambda self: _turned_formats(self.params, names))


def _stream(engine, cfg, seed=5):
    rng = np.random.default_rng(seed)
    for n, new in PROMPTS:
        engine.submit(rng.integers(1, cfg.vocab_size, size=n), new)
    engine.run_until_complete()
    return sorted(list(r.generated) for r in engine.scheduler.finished)


def _n_turned(params, names):
    return sum(path[-1].key in names and leaf.ndim == 3 for path, leaf
               in jax.tree_util.tree_flatten_with_path(params)[0])


@pytest.mark.parametrize("arch", sorted(TURNED))
def test_the_build_lays_the_weights_out_and_nobody_else_can_tell(
        arch, monkeypatch):
    """The caller's pytree is what it was (structure, the very leaves,
    their values and layouts); the engine holds the turned leaves in the
    asked layout and every other leaf as the SAME buffer; the served
    tokens are those of an engine that turned nothing."""
    cfg, params = _weights(arch)
    names = TURNED[arch]
    before = jax.tree.map(np.asarray, params)
    held = jax.tree.leaves(params)
    want = _stream(_engine(cfg, params), cfg)

    _decides(monkeypatch, names)
    engine = _engine(cfg, params)
    n = _n_turned(params, names)
    assert n > 0 and engine.weights_reput == (n, sum(
        leaf.nbytes for path, leaf
        in jax.tree_util.tree_flatten_with_path(params)[0]
        if path[-1].key in names and leaf.ndim == 3))

    # the caller's pytree: untouched
    assert jax.tree.structure(params) == jax.tree.structure(before)
    for leaf, was, old in zip(jax.tree.leaves(params), held,
                              jax.tree.leaves(before)):
        assert leaf is was
        assert leaf.format.layout.major_to_minor == tuple(range(leaf.ndim))
        np.testing.assert_array_equal(np.asarray(leaf), old)
    # the engine's: turned where asked, the same buffers elsewhere
    for (path, leaf), was in zip(
            jax.tree_util.tree_flatten_with_path(engine.params)[0], held):
        if path[-1].key in names and leaf.ndim == 3:
            assert leaf.format.layout.major_to_minor == (0, 2, 1)
            np.testing.assert_array_equal(np.asarray(leaf), np.asarray(was))
        else:
            assert leaf is was
    assert _stream(engine, cfg) == want
    # the step compiled for the weights as they lie: the layout the
    # program's own parameters have
    text = engine._mixed_step.lower(
        engine.params, engine.pool.pages,
        engine._put(engine._dead_mixed_operands(*engine.mixed_buckets[0])),
    ).compile().as_text()
    assert re_count(text, r"\{1,2,0\} parameter\(") == n


def re_count(text, pattern):
    import re

    entry = text[text.index("\nENTRY"):]
    return len(re.findall(pattern, entry))


def test_the_compiler_is_asked_and_a_cpu_asks_for_nothing():
    """``step_weight_formats`` runs on any backend (the steady decode
    program, weights' layouts left open): a format a leaf the step reads,
    and on the CPU each is the layout the leaf has - so the build, which
    does not put the question to a device that keeps its arrays untiled,
    loses nothing by it."""
    cfg, params = _weights("llama")
    engine = _engine(cfg, params)
    assert engine._weight_formats is None and engine.weights_reput == (0, 0)
    formats = engine.step_weight_formats(engine.params, engine.pool.pages)
    assert jax.tree.structure(formats) == jax.tree.structure(params)
    for leaf, fmt in zip(jax.tree.leaves(params), jax.tree.leaves(formats)):
        assert fmt.layout is None or fmt.layout == leaf.format.layout
    # the steady program is the one a full batch of decode rows picks
    assert engine._mixed_step._cache_size() == 0  # asked of another jit


def test_a_clone_puts_fresh_weights_where_the_shared_step_reads_them(
        monkeypatch):
    """``clone_fresh(params=...)`` (a rolling upgrade) and
    ``share_compiled_steps`` hand an engine a step compiled for weights
    in certain layouts: the engine that takes the step puts ITS weights
    there, so the warm programs serve them - nothing compiles again, and
    the tokens are the fresh weights' own."""
    arch, names = "mimo_v2", TURNED["mimo_v2"]
    cfg, params = _weights(arch)
    _, fresh = _weights(arch, seed=1)
    want = _stream(_engine(cfg, fresh), cfg)

    _decides(monkeypatch, names)
    first = _engine(cfg, params)
    _stream(first, cfg)
    warm = first._mixed_step._cache_size()
    assert warm > 0

    # the question is the builder's: whoever takes a step takes its answer
    monkeypatch.setattr(ServeEngine, "_decide_weight_formats",
                        lambda self: pytest.fail("asked again"))
    rolled = first.clone_fresh(params=fresh, weights_version=1)
    assert rolled._mixed_step is first._mixed_step
    assert rolled._weight_formats is first._weight_formats
    n = _n_turned(fresh, names)
    assert rolled.weights_reput[0] == n
    for (path, leaf), was in zip(
            jax.tree_util.tree_flatten_with_path(rolled.params)[0],
            jax.tree.leaves(fresh)):
        if path[-1].key in names and leaf.ndim == 3:
            assert leaf.format.layout.major_to_minor == (0, 2, 1)
        else:
            assert leaf is was
    assert _stream(rolled, cfg) == want
    assert first._mixed_step._cache_size() == warm  # the warm programs

    # a peer built on its own (its step, its weights as they came) that
    # adopts the fleet's step
    monkeypatch.setattr(ServeEngine, "_decide_weight_formats",
                        lambda self: None)
    peer = _engine(cfg, fresh)
    assert peer.weights_reput == (0, 0)
    peer.share_compiled_steps(rolled)
    assert peer._mixed_step is first._mixed_step
    assert peer.weights_reput[0] == n
    assert _stream(peer, cfg) == want
    assert first._mixed_step._cache_size() == warm


# ----------------------------------------------------------------------
# the counter: what a compiled program still re-lays out of its weights
# ----------------------------------------------------------------------

# the ENTRY computation of a v5e's decode program (mimo, the parent of PR
# 54), cut to what the parser has to tell apart
ENTRY = '''
HloModule jit_mixed_step, is_scheduled=true

%bitcast_fusion.13 (bitcast_input.13: bf16[64,192,4096]) -> bf16[64,192,4096] {
  %bitcast_input.13 = bf16[64,192,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %bitcast.1 = bf16[64,192,4096]{2,1,0:T(8,128)(2,1)} bitcast(%bitcast_input.13)
}

%fused_computation.92 (param_0.587: bf16[64,192,4096], param_1.1: bf16[64,4096]) -> bf16[64,64,192] {
  %param_0.587 = bf16[64,192,4096]{2,1,0:T(8,128)(2,1)} parameter(0)
  %param_1.1 = bf16[64,4096]{1,0:T(8,128)(2,1)} parameter(1)
  %fusion.521 = bf16[64,192,4096]{2,1,0:T(8,128)(2,1)} fusion(%param_0.587), kind=kLoop, calls=%bitcast_fusion.13
  ROOT %convolution.1 = bf16[64,64,192]{2,1,0:T(8,128)(2,1)} convolution(%param_1.1, %fusion.521), dim_labels=bf_oi->bf
}

ENTRY %main.75 (params__layers___0___q_proj__.1: bf16[1,4096,12288], params__layers___0___k_proj__.1: bf16[1,4096,768], params__layers___0___gate_proj__.1: bf16[1,4096,16384], params__lm_head__.1: bf16[4096,2048], pages__k__.1: bf16[2,1026,64,768], ops.1: s32[4242]) -> (s32[64,3], bf16[2,1026,64,768]) {
  %params__layers___0___q_proj__.1 = bf16[1,4096,12288]{2,1,0:T(8,128)(2,1)} parameter(0), sharding={replicated}, metadata={op_name="params[\\'layers\\'][0][\\'q_proj\\']"}
  %params__layers___0___k_proj__.1 = bf16[1,4096,768]{2,1,0:T(8,128)(2,1)} parameter(1), sharding={replicated}, metadata={op_name="params[\\'layers\\'][0][\\'k_proj\\']"}
  %params__layers___0___gate_proj__.1 = bf16[1,4096,16384]{2,1,0:T(8,128)(2,1)} parameter(2), sharding={replicated}, metadata={op_name="params[\\'layers\\'][0][\\'gate_proj\\']"}
  %params__lm_head__.1 = bf16[4096,2048]{1,0:T(8,128)(2,1)} parameter(3), sharding={replicated}, metadata={op_name="params[\\'lm_head\\']"}
  %pages__k__.1 = bf16[2,1026,64,768]{3,2,1,0:T(8,128)(2,1)} parameter(4), sharding={replicated}, metadata={op_name="pages.k"}
  %ops.1 = s32[4242]{0:T(1024)} parameter(5), sharding={replicated}, metadata={op_name="ops"}
  %copy-start.19 = (bf16[1,4096,768]{2,1,0:T(8,128)(2,1)S(1)}, bf16[1,4096,768]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%params__layers___0___k_proj__.1)
  %copy-done.19 = bf16[1,4096,768]{2,1,0:T(8,128)(2,1)S(1)} copy-done(%copy-start.19)
  %copy.201 = bf16[1,4096,768]{1,2,0:T(8,128)(2,1)S(1)} copy(%copy-done.19), sharding={replicated}, metadata={op_name="params[\\'layers\\'][0][\\'k_proj\\']"}
  %copy.198 = bf16[1,4096,12288]{1,2,0:T(8,128)(2,1)} copy(%params__layers___0___q_proj__.1), sharding={replicated}, metadata={op_name="params[\\'layers\\'][0][\\'q_proj\\']"}
  %bitcast.729 = bf16[64,192,4096]{2,1,0:T(8,128)(2,1)} bitcast(%copy.198)
  %fusion.1 = bf16[64,4096]{1,0:T(8,128)(2,1)} fusion(%ops.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(mixed_step)/embed/gather"}
  %fusion.92 = bf16[64,64,192]{2,1,0:T(8,128)(2,1)} fusion(%bitcast.729, %fusion.1), kind=kOutput, calls=%fused_computation.92, metadata={op_name="jit(mixed_step)/qkv/bsh,ho->bso/dot_general"}
  %fusion.122 = bf16[64,16384]{1,0:T(8,128)(2,1)} fusion(%params__layers___0___gate_proj__.1, %fusion.1), kind=kOutput, calls=%fused_computation.93, metadata={op_name="jit(mixed_step)/mlp/bsh,ho->bso/dot_general"}
  %bitcast.800 = bf16[2048,4096]{0,1:T(8,128)(2,1)} bitcast(%params__lm_head__.1)
  %fusion.300 = bf16[2048,4096]{1,0:T(8,128)(2,1)} fusion(%bitcast.800), kind=kLoop, calls=%fused_computation.300, metadata={op_name="jit(mixed_step)/tail/transpose"}
  %copy.900 = bf16[2,1026,64,768]{3,2,1,0:T(8,128)(2,1)} copy(%pages__k__.1), metadata={op_name="jit(mixed_step)/kv_write/scatter"}
  %fusion.400 = bf16[1024,4096]{1,0:T(8,128)(2,1)} fusion(%fusion.1, %params__lm_head__.1), kind=kLoop, calls=%fused_computation.400, metadata={op_name="jit(mixed_step)/tail/mul"}
  ROOT %tuple.1 = (s32[64,3]{1,0}, bf16[2,1026,64,768]{3,2,1,0}) tuple(%fusion.1, %copy.900)
}
'''


def test_the_parser_counts_a_weights_relayout_by_its_operand():
    """A ``copy(%params...)``, a ``copy(%copy-done)`` of a prefetched
    weight and a loop fusion over a ``bitcast`` of one count, with the
    bytes they write; what does NOT: the prefetch itself, a matmul's
    fusion (``kOutput``) that reads a weight, the ``%bitcast_fusion``
    NESTED in it, a copy of the pool, a loop fusion that reads a weight
    and writes something of another size (an activation), anything
    under the floor."""
    found = opmap.weight_relayouts(ENTRY)
    assert found == [
        ("copy.201", "bf16[1,4096,768]", 4096 * 768 * 2,
         "params['layers'][0]['k_proj']"),
        ("copy.198", "bf16[1,4096,12288]", 4096 * 12288 * 2,
         "params['layers'][0]['q_proj']"),
        ("fusion.300", "bf16[2048,4096]", 2048 * 4096 * 2,
         "params['lm_head']"),
    ]
    # the floor is the caller's: under it a norm's scale, above it nothing
    assert [f[0] for f in opmap.weight_relayouts(
        ENTRY, min_elements=1 << 23)] == ["copy.198", "fusion.300"]
    assert opmap.weight_relayouts(ENTRY, argument="pages") == [
        ("copy.900", "bf16[2,1026,64,768]", 2 * 1026 * 64 * 768 * 2,
         "pages.k")]
    assert opmap.weight_relayouts("HloModule m\n") == []


def test_the_recorder_publishes_the_counter_and_the_build_says_what_it_moved(
        monkeypatch):
    """With a recorder: ``otherData["weight_relayout_bytes"]`` has a
    reading a program (0 on the CPU, whose programs read the weights as
    they lie), ``/metrics`` the gauge ``llm_serve_step_weight_relayout_
    bytes{program=}``, and the ``engine_build`` span how many leaves and
    bytes the build re-put.  One HELP line a gauge, however many
    programs."""
    cfg, params = _weights("mimo_v2")
    _decides(monkeypatch, TURNED["mimo_v2"])
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    assert "step_weight_relayout_bytes" not in "".join(
        engine.weight_layout_gauges())  # nobody has read the programs yet
    engine.warmup([8], max_new_tokens=2)
    build, = (e for e in tracer.events() if e.get("name") == "engine_build")
    assert (build["args"]["weights_reput"],
            build["args"]["weights_reput_bytes"]) == engine.weights_reput
    assert build["args"]["weights_reput"] == _n_turned(
        params, TURNED["mimo_v2"])
    programs = {f"{t}x{d}" for t, d in engine.mixed_buckets}
    assert tracer.get_other("weight_relayout_bytes") == dict.fromkeys(
        programs, 0)
    text = engine.metrics.prometheus(
        extra_gauges=engine.weight_layout_gauges())
    for program in programs:
        assert (f'llm_serve_step_weight_relayout_bytes{{program="{program}"}}'
                " 0") in text
    assert text.count("# HELP llm_serve_step_weight_relayout_bytes ") == 1
    assert f"llm_serve_weights_reput {engine.weights_reput[0]}" in text


def test_the_reput_neither_reads_nor_writes_the_persistent_compile_cache():
    """jax 0.9.0 hands an executable whose RESULT has a non-default layout
    back from the persistent cache with the layout's label lost (the bytes
    transposed, the array saying it is plain): the second process to
    ``device_put`` a weight into a layout served garbage on the chip.  So
    the re-put compiles outside the cache (``compile_cache_bypassed``) -
    and only it: the next compile asks the cache again."""
    from llm_np_cp_tpu.utils.runtime import compile_cache_bypassed

    asked, listening = [], [True]
    jax.monitoring.register_event_listener(
        lambda event, **kw: asked.append(event)
        if listening[0] and "compile_requests_use_cache" in event else None)
    try:
        w = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 24), jnp.float32)
        turned = Format(Layout(major_to_minor=(0, 2, 1)), w.sharding)
        for _ in range(2):  # the second: a hit, were the cache asked
            jax.clear_caches()
            asked.clear()
            with compile_cache_bypassed():
                put = jax.device_put(w, turned)
            assert not asked
            assert put.format.layout.major_to_minor == (0, 2, 1)
            np.testing.assert_array_equal(np.asarray(put), np.asarray(w))
        jax.jit(lambda a: a * 3 + 1)(w).block_until_ready()
        assert asked  # the cache is back for everything else
    finally:
        listening[0] = False
