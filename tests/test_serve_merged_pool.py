"""A pool of MERGED pages under every program that touches it.

A float pool whose ``head_dim`` is short of a row of lanes (64) and whose
kv heads side by side fill whole rows is allocated ``[L, NB, BS, K * D]``
(serve/block_pool.py ``merges_pages``): the order a TPU keeps and the
ragged kernel reads.  The benchmark's hybrid stacks run such a pool through
the tick alone (a recurrent state refuses the rest, engine.py); the
dense ``head_dim``-64 models (Llama-3.2-1B, Qwen2.5-0.5B) run it through
everything else too: a prefix-cache hit read in place, a host-tier spill
and restore.  Each path here serves a tiny dense model whose pool comes out merged
(``K * D`` = 128) and must emit, token for token, what ``models.forward``
does on the same weights.

CPU backend: the ragged kernel in interpret mode.
"""

import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, __file__.rsplit("/tests/", 1)[0])
from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models import forward
from llm_np_cp_tpu.models.transformer import init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import ServeEngine
from llm_np_cp_tpu.serve.block_pool import BlockPool, merges_pages
from llm_np_cp_tpu.serve.host_tier import HostTier

MAX_NEW, WIDTH = 5, 64


@pytest.fixture(scope="module")
def served():
    """(config, params, greedy continuation by ``models.forward``)."""
    cfg = tiny_config("llama", num_key_value_heads=2, head_dim=64,
                      num_attention_heads=4, num_hidden_layers=2)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    # causal attention: a token's logits do not see what follows it, so one
    # program over a fixed width serves every length
    logits = jax.jit(lambda ids: forward(params, ids, cfg)[0])

    def want(prompt, n):
        ids = [int(t) for t in prompt]
        for _ in range(n):
            padded = np.zeros((1, WIDTH), np.int32)
            padded[0, :len(ids)] = ids
            ids.append(int(jnp.argmax(logits(padded)[0, len(ids) - 1])))
        return ids[len(prompt):]

    return cfg, params, want


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 3)
    kw.setdefault("num_blocks", 40)
    return ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), block_size=8,
        max_seq_len=WIDTH, cache_dtype=jnp.float32, **kw)


def _prompts(rng, cfg, sizes):
    return [rng.integers(1, cfg.vocab_size, size=n).astype(np.int32)
            for n in sizes]


def _serve_together(engine, prompts):
    for j, p in enumerate(prompts):
        engine.submit(p, MAX_NEW, seed=j)
    engine.run_until_complete()


def _serve_in_turn(engine, prompts, rounds=1):
    for _ in range(rounds):
        for p in prompts:
            engine.submit(p, MAX_NEW)
            engine.run_until_complete()
            if engine.host_tier is not None:
                engine.host_tier.drain()  # (the spill writer, joined)


# path: (engine arguments, prompt lengths, how they are served, what the
# metrics must show of the mechanism the path is named for)
_PATHS = {
    # the tick: scatter of [tokens, K * D] rows into the flat pool, the
    # ragged kernel over merged pages
    "unified-tick": ({}, (13, 5, 22), "together", None),
    # the same tick over the XLA oracle attention (a failed kernel probe)
    "unified-tick-xla-attention": ({}, (13, 5, 22), "together", "xla"),
    # a prefix-cache hit, read in place
    "prefix-hit-unified": (
        dict(enable_prefix_cache=True), (24, 24), "twice", "prefix"),
    # blocks spilled to the host tier (slice_block) and restored
    # (restore_block) once the working set has outgrown the pool
    "host-tier-restore": (
        dict(enable_prefix_cache=True, num_blocks=12, max_slots=2),
        (24,) * 6, "twice", "tier"),
}


@pytest.mark.parametrize("path", list(_PATHS))
def test_merged_pool_token_parity_with_forward(served, path, monkeypatch):
    cfg, params, want = served
    kw, sizes, how, shows = _PATHS[path]
    if shows == "xla":
        from llm_np_cp_tpu.ops.pallas import support

        monkeypatch.setattr(support, "_FORCE_FAIL", True)
        support._probe.cache_clear()
    tier = HostTier(64 << 20) if shows == "tier" else None
    engine = _engine(cfg, params, host_tier=tier, **kw)
    pages = engine.pool.pages
    assert pages.merged and pages.k.shape[2:] == (8, 128)
    assert (pages.kv_heads, pages.head_dim, pages.token_shape) == (
        2, 64, (128,))
    assert engine.pool_page_shape == "8x128"
    assert engine.pool_carried
    assert engine.ragged_attn_impl == ("xla" if shows == "xla" else "pallas")
    prompts = _prompts(np.random.default_rng(len(path)), cfg, sizes)
    if how == "together":
        _serve_together(engine, prompts)
    else:
        _serve_in_turn(engine, prompts, rounds=2)
    finished = engine.scheduler.finished
    assert len(finished) == len(prompts) * (1 if how == "together" else 2)
    for req in finished:
        assert list(req.generated) == want(req.prompt, MAX_NEW), (
            path, req.req_id)
    snap = engine.metrics.snapshot()
    if shows == "prefix":
        assert snap["prefix_blocks_hit"] > 0
    if shows == "tier":
        assert tier.stats()["restored_blocks"] > 0
        assert snap["tier_restored_blocks"] == tier.stats()["restored_blocks"]
        tier.close()
    # the pool came back in its own form from every donated program
    assert engine.pool.pages.merged
    assert engine.pool.pages.k.shape == pages.k.shape


@pytest.mark.parametrize("kh,d,dtype,merged", [
    (8, 64, jnp.bfloat16, True),    # LFM2, Llama-3.2-1B
    (2, 64, jnp.float32, True),     # Qwen2.5-0.5B; float32 is permuted too
    (4, 32, jnp.bfloat16, True),
    (2, 128, jnp.bfloat16, False),  # the Qwen cells: row-major as they are
    (4, 128, jnp.bfloat16, False),  # Falcon-H1
    (4, 256, jnp.bfloat16, False),  # Gemma-2
    (2, 16, jnp.float32, False),    # K * D = 32: no whole row of lanes
    (1, 64, jnp.bfloat16, False),
    (8, 64, jnp.int8, False),       # int8 pages stay beside their scales
], ids=["bf16-8x64", "f32-2x64", "bf16-4x32", "bf16-2x128", "bf16-4x128",
        "bf16-4x256", "f32-2x16", "bf16-1x64", "int8-8x64"])
def test_page_form_follows_from_shapes_and_dtype(kh, d, dtype, merged):
    quantized = jnp.dtype(dtype) == jnp.int8
    assert merges_pages(kh, d, quantized) is merged
    cfg = tiny_config("llama", num_key_value_heads=kh, head_dim=d,
                      num_attention_heads=kh * 2)
    pages = BlockPool(cfg, 4, 8, dtype=dtype).pages
    assert pages.merged is merged
    assert pages.k.shape == (cfg.num_hidden_layers, 4, 8) + (
        (kh * d,) if merged else (kh, d))
    assert (pages.kv_heads, pages.head_dim) == (kh, d)
    assert pages.token_shape == pages.k.shape[3:]
    assert pages.quantized is quantized
    # the form is part of the tree's structure, not a leaf of it
    assert len(jax.tree.leaves(pages)) == (4 if quantized else 2)


def test_merged_pool_is_cut_over_model_in_whole_heads():
    """``paged_kv_specs``: the merged axis is kv-major, so a shard of it is
    whole heads, and the specs' tree matches the pool's (the constraint a
    step pins on its result is a tree map over both)."""
    from jax.sharding import PartitionSpec as P

    from llm_np_cp_tpu.parallel.sharding import MeshPlan, paged_kv_specs

    cfg = tiny_config("llama", num_key_value_heads=4, head_dim=64,
                      num_attention_heads=8)
    specs = paged_kv_specs(cfg, MeshPlan(model=2))
    assert specs.k == specs.v == P(None, None, None, "model")
    pages = BlockPool(cfg, 4, 8, dtype=jnp.bfloat16).pages
    assert jax.tree.structure(specs._replace(k=0, v=0)) == jax.tree.structure(
        pages._replace(k=0, v=0))
    assert pages.k.shape[-1] // 2 % pages.head_dim == 0
    # an unmerged pool's specs are what they were
    plain = paged_kv_specs(tiny_config("llama"), MeshPlan(model=2))
    assert plain.k == P(None, None, None, "model") and plain.form is None


# ---------------------------------------------------------------------------
# The two ways a hybrid stack's layer loop holds the pool.  Flat over (layer,
# block) like the dense scan wherever the device keeps the pool row-major
# (every float pool since PR 38; always on the CPU), or WHOLE ``[L, NB, ..]``,
# written at [layer, block, slot] and attended by its slab — what is left of
# ``paged_hooks(layer=)``, for a hybrid stack over an int8 pool on a TPU
# (pages and scale pages permuted).  The device's answer is forced each way
# here; the tokens are the same.
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cache", ["f32", "int8"])
@pytest.mark.parametrize("model_type", ["lfm2_moe", "falcon_h1"])
def test_hybrid_stack_serves_the_same_tokens_in_both_pool_forms(
        monkeypatch, model_type, cache):
    import llm_np_cp_tpu.serve.engine as engine_mod

    cfg = tiny_config(model_type)
    params = init_params(jax.random.PRNGKey(2), cfg, dtype=jnp.float32)
    prompts = _prompts(np.random.default_rng(7), cfg, (19, 4, 11))
    tokens = {}
    for carried in (True, False):
        monkeypatch.setattr(
            engine_mod, "_pool_is_row_major", lambda pages, c=carried: c)
        engine = ServeEngine(
            params, cfg, sampler=Sampler(kind="greedy"), max_slots=3,
            num_blocks=40, block_size=8, max_seq_len=WIDTH, prefill_chunk=8,
            cache_dtype=jnp.int8 if cache == "int8" else jnp.float32)
        assert engine.mixed and engine.pool_carried is carried
        _serve_together(engine, prompts)
        tokens[carried] = {
            r.req_id: list(r.generated) for r in engine.scheduler.finished}
        assert len(tokens[carried]) == len(prompts)
    assert tokens[True] == tokens[False]


@pytest.mark.parametrize("heads,shape", [((2, 64), "8x128"), ((2, 16), "8x2x16")],
                         ids=["merged", "plain"])
def test_what_says_how_the_tick_holds_the_pool(heads, shape):
    """The ``engine_build`` set-up span, ``/metrics`` and the trace summary
    say whether the layer loop carries the pool (flat, written in place) and
    what a page of it is."""
    from llm_np_cp_tpu.serve.tracing import TraceRecorder
    from tools.summarize_trace import format_summary

    kh, d = heads
    cfg = tiny_config("llama", num_key_value_heads=kh, head_dim=d,
                      num_attention_heads=4)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    tracer = TraceRecorder()
    engine = _engine(cfg, params, tracer=tracer)
    events = tracer.to_dict()["traceEvents"]
    build, = (e for e in events if e.get("name") == "engine_build")
    assert build["args"]["pool_carried"] == 1
    assert build["args"]["pool_page_shape"] == shape == engine.pool_page_shape
    text = engine.metrics.prometheus(extra_gauges=engine.pool_form_gauges())
    assert "\nllm_serve_pool_carried 1\n" in text
    assert f'\nllm_serve_pool_page_shape{{shape="{shape}"}} 1\n' in text
    assert "# TYPE llm_serve_pool_page_shape gauge" in text
    line, = (ln for ln in format_summary(events).splitlines()
             if ln.lstrip().startswith("pool:"))
    assert f"pages {shape}" in line and "written in place" in line
    assert engine.pool_form_gauges()["pool_carried"] == 1.0
