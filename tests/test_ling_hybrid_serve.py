"""Ling-3.0's language model (``model_type: ling_hybrid``) through the SERVED
engine on the CPU at a tiny size, seeded random float32 weights: prefill
chunks beside decode rows, decode through the matrix state and the latent
pool, a slot reused, a re-prefill — each against
``benchmark/reference_ling_v3.py``'s full forward on LOGITS; both forms of the
state update; the engine's start-up refusals by flag; and the scopes, tick
arguments and counters the per-layer metrics read.  The declaration, the plain
forward and the router are tests/test_ling_hybrid.py, the recurrence
tests/test_kda.py.
"""

import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "benchmark"))

import reference_ling_v3 as ref  # noqa: E402

import llm_np_cp_tpu.serve.engine as engine_mod  # noqa: E402
from llm_np_cp_tpu.config import ModelConfig, tiny_config  # noqa: E402
from llm_np_cp_tpu.models.transformer import (  # noqa: E402
    STEP_SCOPES,
    init_params,
)
from llm_np_cp_tpu.ops import kda  # noqa: E402
from llm_np_cp_tpu.ops.sampling import Sampler  # noqa: E402
from llm_np_cp_tpu.parallel.sharding import MeshPlan  # noqa: E402
from llm_np_cp_tpu.serve import ServeEngine  # noqa: E402
from llm_np_cp_tpu.utils.synthetic import hf_config_dict  # noqa: E402

# largest logit difference as a share of the reference's spread: float32
# against float32, sums in another order
TOL = 2e-5


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("ling_hybrid")
    hf = hf_config_dict(cfg)
    assert cfg == ModelConfig.from_hf_dict(hf)
    return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32), hf


# the preset with heads 128 wide: the narrowest the state-update kernel takes
@pytest.fixture(scope="module")
def wide():
    cfg = tiny_config("ling_hybrid", kda_head_dim=128, num_attention_heads=2,
                      num_key_value_heads=2, num_hidden_layers=3)
    return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)


def _gap(got: np.ndarray, want: np.ndarray) -> float:
    spread = float((want.max(-1) - want.mean(-1)).mean())
    return float(np.abs(got - want).max()) / spread


_REF: dict = {}


def _reference(params, hf, seq) -> np.ndarray:
    """The reference's logits for ``seq``, computed on the sequence padded
    to a multiple of 32 tokens (causal: what follows a position cannot
    change it), so that a few compiled programs serve every length."""
    n = -(-len(seq) // 32) * 32
    if n not in _REF:
        _REF[n] = jax.jit(lambda p, ids: ref.forward(p, hf, ids))
    ids = np.zeros((n,), np.int32)
    ids[:len(seq)] = seq
    return np.asarray(_REF[n](params, ids))[:len(seq)]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


# ----------------------------------------------------------------------
# the served path against the reference's full forward
# ----------------------------------------------------------------------

class Probe:
    """The logits every tick's XLA tail samples from, kept per tick."""

    def __init__(self, mp):
        self.ticks: list[np.ndarray] = []
        real = engine_mod.final_logits

        def probed(p, x, cfg, **kw):
            logits = real(p, x, cfg, **kw)
            jax.debug.callback(lambda a: self.ticks.append(np.asarray(a)), logits)
            return logits

        mp.setattr(engine_mod, "final_logits", probed)


@pytest.fixture(scope="module")
def probe():
    with pytest.MonkeyPatch.context() as mp:
        yield Probe(mp)


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("num_blocks", 24)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                       sample_epilogue="off", **kw)


@pytest.fixture(scope="module")
def shared(tiny, probe):
    """ONE engine for the serve cases: a tick program is compiled once for
    the file (an idle engine is as good as a new one, which is what the
    cases show: every request starts from a zero state in whatever slot)."""
    return _engine(*tiny[:2])


def _serve(engine, probe, reqs):
    got = {r.req_id: [] for r in reqs}
    while True:
        n_before = {r.req_id: len(r.generated) for r in reqs}
        more = engine.step()
        jax.effects_barrier()
        for r in reqs:
            if len(r.generated) > n_before[r.req_id]:
                slot = r.slot if r.slot is not None and r.slot >= 0 else r.extra["_slot"]
                got[r.req_id].append(probe.ticks[-1][slot, 0])
            if r.slot is not None and r.slot >= 0:
                r.extra["_slot"] = r.slot
        if not more:
            return got


SERVE_CASES = {
    # a 21-token prompt in chunks of 8 (state handed from tick to tick) and a
    # short one that decodes beside it: mixed ticks
    "mixed_ticks": dict(lengths=[21, 3], new=6),
    # three requests over two slots: the third starts in a slot another left
    "a_slot_reused": dict(lengths=[5, 9, 12], new=5),
    # a pool too small for both: one is evicted, requeued and prefilled
    # again from its first token (its state rebuilt from zero)
    "re_prefill": dict(lengths=[4, 5], new=20, engine=dict(num_blocks=5)),
}


@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_served_logits_match_reference(tiny, probe, shared, case):
    cfg, params, hf = tiny
    spec = SERVE_CASES[case]
    engine = (_engine(cfg, params, **spec["engine"]) if "engine" in spec
              else shared)
    reqs = [engine.submit(p, max_new_tokens=spec["new"], seed=i)
            for i, p in enumerate(_prompts(spec["lengths"], seed=len(case)))]
    preempted = engine.metrics.snapshot().get("preemptions", 0)
    got = _serve(engine, probe, reqs)
    if case == "re_prefill":
        assert engine.metrics.snapshot()["preemptions"] > preempted
    for r in reqs:
        seq = list(r.prompt) + list(r.generated)
        want = _reference(params, hf, seq)
        p, have = len(r.prompt), np.stack(got[r.req_id])
        assert have.shape[0] == len(r.generated) == spec["new"]
        assert _gap(have, want[p - 1:p - 1 + len(r.generated)]) <= TOL, case


def test_pool_holds_one_latent_layer_a_group_and_the_state_beside_it(shared):
    pages = shared.pool.pages
    assert pages.latent and pages.v is None and pages.k.shape[0] == 2
    assert set(pages.state) == {"conv", "kda"}
    assert pages.state["kda"].shape == (4, 2, 4, 16, 16)
    assert pages.state["kda"].dtype == jnp.float32
    assert shared.kda_state_impl == "xla" and shared.ssm_state_impl is None


@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_both_forms_of_the_state_update_serve_the_same_tokens(wide, form):
    """Heads 128 wide take the kernel (here in the interpreter, as a TPU's
    probe would answer): tick argument ``kda_state_impl``, gauge
    ``kda_state_kernel`` and the ``probe.kda_state_update`` set-up span say
    which form ran; the tokens are the same."""
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    cfg, params = wide
    tracer = TraceRecorder()
    real = kda.state_update_impl
    with pytest.MonkeyPatch.context() as mp:
        if form == "pallas":
            mp.setattr(kda, "state_update_impl",
                       lambda state, interpret=None: real(state, True))
        engine = ServeEngine(params, cfg, max_slots=2, num_blocks=24,
                             block_size=8, max_seq_len=64, prefill_chunk=8,
                             cache_dtype=jnp.float32, tracer=tracer)
        assert engine.kda_state_impl == form
        reqs = [engine.submit(p, max_new_tokens=5, seed=i)
                for i, p in enumerate(_prompts([9, 12], seed=2))]
        engine.run_until_complete()
    span, = [e for e in tracer.events() if e.get("name") == "probe.kda_state_update"]
    assert span["args"]["ok"] is (form == "pallas")
    ticks = [e["args"] for e in tracer.events()
             if e.get("name") == "tick" and "kda_state_rows" in e["args"]]
    assert ticks and all(a["kda_state_impl"] == form for a in ticks)
    assert f"kda_state_kernel {int(form == 'pallas')}" in engine.metrics.prometheus()
    _TOKENS.setdefault("served", [r.generated for r in reqs])
    assert [r.generated for r in reqs] == _TOKENS["served"]


_TOKENS: dict = {}


# ----------------------------------------------------------------------
# start-up refusals, spans and counters
# ----------------------------------------------------------------------

class _Tier:
    pass


@pytest.mark.parametrize("kw, pattern", [
    (dict(enable_prefix_cache=True), "delta-rule layers.*refused: --prefix-cache"),
    (dict(host_tier=_Tier()), "host_tier"),
    (dict(spec_k=2), "delta-rule layers.*refused: --spec-k"),
    (dict(mesh_plan=MeshPlan(model=2)), r"delta-rule layers.*refused: --mesh model\>1"),
    (dict(cache_dtype=jnp.int8), "latent.*refused: --cache-dtype int8"),
], ids=["prefix-cache", "tier", "spec-k", "mesh", "int8"])
def test_start_up_refusals_name_the_flag(tiny, kw, pattern):
    cfg, params, _ = tiny
    kw.setdefault("cache_dtype", jnp.float32)
    with pytest.raises(ValueError, match=pattern):
        ServeEngine(params, cfg, max_slots=2, num_blocks=16, block_size=8,
                    max_seq_len=32, **kw)


def test_tick_arguments_counters_and_scopes(tiny):
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    cfg, params, _ = tiny
    tracer = TraceRecorder()
    engine = ServeEngine(params, cfg, max_slots=2, num_blocks=24, block_size=8,
                         max_seq_len=64, prefill_chunk=8,
                         cache_dtype=jnp.float32, tracer=tracer)
    for i, p in enumerate(_prompts([9, 12], seed=2)):
        engine.submit(p, max_new_tokens=5, seed=i)
    engine.run_until_complete()
    ticks = [e["args"] for e in tracer.events()
             if e.get("name") == "tick" and "kda_state_rows" in e["args"]]
    assert ticks and any(a["decode_tokens"] for a in ticks)
    rows = tokens = 0
    for a in ticks:
        assert 1 <= a["kda_state_rows"] <= 2 and a["kda_state_impl"] == "xla"
        assert a["kda_scan_tokens"] == a["prefill_tokens"] + a["decode_tokens"]
        assert {"pairs_held", "experts_touched", "expert_load_max"} <= set(a)
        rows, tokens = rows + a["kda_state_rows"], tokens + a["kda_scan_tokens"]
    assert tokens == 9 + 12 + 2 * 4  # every prompt token once, 4 decode steps each
    text = engine.metrics.prometheus()
    assert f"kda_state_rows_total {rows}" in text
    assert f"kda_scan_tokens_total {tokens}" in text
    assert "kda_ticks_total" in text and "kda_state_kernel 0" in text
    assert "moe_ticks_total" in text and "ssm_ticks_total" not in text
    assert {"kda_proj", "kda_scan"} <= set(STEP_SCOPES)
    table = engine.device_op_map()
    scopes = {v[0] for v in table.values() if v}
    assert {"kda_proj", "kda_scan", "moe_route", "moe_experts", "moe_shared",
            "attn", "qkv", "mlp"} <= scopes
    moves = [v for k, v in table.items() if "f32[4,2,4,16,16]" in k]
    assert moves and any(v and v[0] == "kda_scan" for v in moves), moves
    from tools.summarize_trace import format_summary, tick_account

    assert tick_account(tracer.events())["kda_state_rows"] == rows / len(ticks)
    assert "delta-rule recurrence" in format_summary(tracer.events(), top=0)


