"""The work-conserving prefill lane on the SERVED path: the same prompts
through an engine whose budget leaves room for several chunks of ONE row
(the planner's leftover pass hands them to the oldest prompt:
``Scheduler.plan_tick``) and through one whose budget holds a single
chunk (every segment is at most a chunk, the pace before the leftover
pass) give the same greedy streams — in a dense GQA stack, the two
window-class stacks (whose ring is sized for a budget-wide slice and
never refuses one), the latent one and a recurrent one.  The policy table
is tests/test_serve_scheduler.py."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models import forward, init_params
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.serve import ServeEngine
from llm_np_cp_tpu.serve.block_pool import window_blocks_per_slot

SLOTS, BLOCK, CHUNK, SEQ = 2, 8, 16, 96
NARROW, WIDE = CHUNK, 3 * CHUNK + SLOTS
# past a window (8) + the wide budget, so that every ring turns over
# inside one prompt; a prompt of under a chunk behind it; one that waits
# for a slot
PROMPTS, NEW = (75, 11, 40), 6
# a divergence passes only where the plain forward itself ranks both
# tokens within this share of its logit spread of its maximum (float32 on
# one backend: the streams are expected identical)
NEAR_TIE = 1e-4

ARCHS = ["llama", "afmoe", "mimo_v2", "deepseek_v3", "falcon_h1"]


def _engine(cfg, params, budget):
    return ServeEngine(
        params, cfg, sampler=Sampler(kind="greedy"), max_slots=SLOTS,
        num_blocks=2 * SEQ // BLOCK + 2, block_size=BLOCK, max_seq_len=SEQ,
        prefill_chunk=CHUNK, tick_token_budget=budget,
        cache_dtype=jnp.float32)


def _serve(engine, prompts):
    """Run to completion: every request's tokens, and the widest prefill
    segment any tick planned."""
    plan, widest = engine.scheduler.plan_tick, [0]

    def watched(*a, **kw):
        decode, prefill = plan(*a, **kw)
        widest[0] = max([widest[0]] + [n for _, n in prefill])
        rows = [r.req_id for r, _ in prefill]
        assert len(rows) == len(set(rows)), "one segment a row"
        return decode, prefill

    engine.scheduler.plan_tick = watched
    # the first prompt has one tick to itself: a lone row and no decode
    # row, the widest slice a tick can write
    reqs = [engine.submit(prompts[0], max_new_tokens=NEW, seed=0)]
    engine.step()
    reqs += [engine.submit(p, max_new_tokens=NEW, seed=0)
             for p in prompts[1:]]
    while engine.step():
        pass
    assert all(r.finish_reason == "length" for r in reqs)
    return [list(r.generated) for r in reqs], widest[0]


def _near_tie(cfg, params, prompt, a, b) -> bool:
    """The repo's rule for two greedy streams of one prompt (chip_smoke
    ``same_or_near_tie``): identical, or first apart at a position where
    the plain forward of the common prefix holds both tokens at its top."""
    div = next((i for i, (x, y) in enumerate(zip(a, b)) if x != y), None)
    if div is None:
        return len(a) == len(b)
    seq = jnp.asarray([list(prompt) + a[:div]], jnp.int32)
    logits = np.asarray(forward(params, seq, cfg)[0][0, -1], np.float32)
    spread = float(logits.max() - logits.mean())
    return bool(max(logits.max() - logits[a[div]],
                    logits.max() - logits[b[div]]) <= NEAR_TIE * spread)


@pytest.mark.parametrize("arch", ARCHS)
def test_budget_wide_slices_serve_the_one_chunk_lanes_tokens(arch):
    cfg = tiny_config(arch)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    rng = np.random.default_rng(52)
    prompts = [rng.integers(1, cfg.vocab_size, n).astype(np.int32)
               for n in PROMPTS]
    narrow, wide = _engine(cfg, params, NARROW), _engine(cfg, params, WIDE)
    for engine in (narrow, wide):
        # the window class's ring, by the engine's rule: the window and
        # the widest slice a tick writes into a row — the budget
        rings = engine.pool.window
        assert (rings is not None) == cfg.two_page_classes
        if rings is not None:
            per = window_blocks_per_slot(
                cfg.sliding_window, engine.tick_token_budget, BLOCK)
            assert engine.window_blocks == rings.per_slot == per
            assert rings.num_blocks == 1 + SLOTS * per
            assert all(a.shape[1] == rings.num_blocks
                       for a in engine.pool.pages.window)
    # (a ring that a budget-wide slice passed would raise in
    # ``WindowRings.advance`` here, not serve wrong tokens)
    want, widest = _serve(narrow, prompts)
    assert widest == CHUNK
    got, widest = _serve(wide, prompts)
    # the lone first prompt took the whole budget: several chunks in a tick
    assert widest == WIDE > 3 * CHUNK
    # the counters behind ``sched.prefill_tokens_per_row_tick``: the same
    # prompt tokens in fewer, longer segments
    was, now = narrow.metrics.snapshot(), wide.metrics.snapshot()
    assert (was["mixed_prefill_tokens"] == now["mixed_prefill_tokens"]
            == sum(PROMPTS))
    assert was["prefill_segments"] > now["prefill_segments"] > 0
    for prompt, a, b in zip(prompts, want, got):
        assert len(a) == len(b) == NEW
        assert _near_tie(cfg, params, prompt, a, b), (a, b)
