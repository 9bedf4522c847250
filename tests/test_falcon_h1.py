"""Falcon-H1 (``model_type: falcon_h1``): a Mamba-2 state-space mixer in
parallel with GQA attention in every layer - a float32 recurrent state a
slot and layer beside the paged K/V pool, one chunked scan for
``models.forward`` and the serving tick, muP multipliers.

Everything is compared with the plain reference
(``benchmark/reference_falcon_h1.py``: float32, no cache, the recurrence
token by token) on seeded random weights of the TINY preset
(``tiny_config("falcon_h1")``: 3 layers, 2 groups of 2 state-space heads,
``d_state`` 16, every multiplier different from 1) - logits, never sampled
tokens.

Tolerances, and why:

- ``TOL`` = 1e-4 of the logits' (max - mean) spread, float32 against
  float32: the program and the reference do the same sums in another order
  (a chunked scan against a token loop, a packed axis against a sequence),
  which on these sizes differ by 1e-6 of the spread; 1e-4 leaves two orders
  of room.
- a recurrent state KEPT in bf16 (rounded once a token) moves logits by
  5e-3 of the spread and a state zeroed between ticks by far more: both
  must FAIL ``TOL``.  They only can because the preset draws ``in_proj``
  at 0.2 (``init_ssm_in_proj_std``): at 0.02 the state's share of the
  mixer's output is a thousandth of the skip ``D x`` and a bf16 state moved
  logits by 1.5e-5 (measured, PERF.md section 6, PR 34).
- ``SCAN_TOL`` = 1e-5 relative to the largest ``y``: the chunked form
  against the token-by-token recurrence, both float32 at the highest matmul
  precision, differ by sums in another order (measured 1e-6).
"""

import contextlib
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "benchmark"))

import reference_falcon_h1 as ref  # noqa: E402

import llm_np_cp_tpu.serve.engine as engine_mod  # noqa: E402
from llm_np_cp_tpu.cache import KVCache  # noqa: E402
from llm_np_cp_tpu.config import ModelConfig, tiny_config  # noqa: E402
from llm_np_cp_tpu.models.transformer import (  # noqa: E402
    forward,
    init_params,
    param_shapes,
)
from llm_np_cp_tpu.ops import ssm  # noqa: E402
from llm_np_cp_tpu.ops.sampling import Sampler  # noqa: E402
from llm_np_cp_tpu.parallel.sharding import MeshPlan  # noqa: E402
from llm_np_cp_tpu.serve import ServeEngine  # noqa: E402

TOL = 1e-4
SCAN_TOL = 1e-5

# the tiny preset as a published config.json would state it (what the plain
# reference and ``from_hf_dict`` read)
TINY_HF = {
    "model_type": "falcon_h1", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 128, "num_hidden_layers": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "max_position_embeddings": 512, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "attention_bias": False, "mlp_bias": False, "mamba_proj_bias": False,
    "projectors_bias": False, "mamba_rms_norm": True,
    "mamba_norm_before_gate": False, "mamba_use_mlp": True,
    "attn_layer_indices": None,
    "mamba_d_ssm": 64, "mamba_n_heads": 4, "mamba_d_head": 16,
    "mamba_d_state": 16, "mamba_n_groups": 2, "mamba_d_conv": 4,
    "mamba_conv_bias": True, "mamba_chunk_size": 8, "mamba_expand": 2,
    "embedding_multiplier": 5.5, "lm_head_multiplier": 0.125,
    "key_multiplier": 0.7, "attention_in_multiplier": 1.25,
    "attention_out_multiplier": 0.6, "ssm_in_multiplier": 1.5,
    "ssm_out_multiplier": 0.8, "mlp_multipliers": [0.9, 0.45],
    "ssm_multipliers": [0.85, 1.2, 1.4, 1.1, 0.75],
    "init_ssm_in_proj_std": 0.2,
}

# the published configuration (the catalog row's keys that size a layer)
PUBLISHED = dict(
    TINY_HF, vocab_size=261120, hidden_size=5120, intermediate_size=21504,
    num_hidden_layers=6, num_attention_heads=20, num_key_value_heads=4,
    head_dim=128, mamba_d_ssm=4096, mamba_n_heads=32, mamba_d_head=128,
    mamba_d_state=256, mamba_chunk_size=128, rope_theta=100000000000)


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config("falcon_h1")
    assert cfg == ModelConfig.from_hf_dict(TINY_HF)
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    return cfg, params


# the preset with a state 128 wide: the narrowest the state-update kernel
# takes (``ops/ssm.state_update_heads``; the preset's own 16 go through
# ``ssm_chunk`` whoever asks)
WIDE_HF = dict(TINY_HF, mamba_d_state=128)


@pytest.fixture(scope="module")
def wide():
    cfg = ModelConfig.from_hf_dict(WIDE_HF)
    return cfg, init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)


@contextlib.contextmanager
def _state_kernel(form):
    """``"pallas"``: whoever asks which form advances a state (the engine
    when it is built, ``ssm_packed`` whenever a program is traced) is told
    the kernel, run in the interpreter — what a TPU's probe answers, on a
    CPU.  ``"xla"``: nothing is patched."""
    real = ssm.state_update_heads
    with pytest.MonkeyPatch.context() as mp:
        if form == "pallas":
            mp.setattr(ssm, "state_update_heads",
                       lambda state, groups, interpret=None: real(state, groups, True))
        yield


def _spread(logits: np.ndarray) -> float:
    return float((logits.max(-1) - logits.mean(-1)).mean())


def _gap(got: np.ndarray, want: np.ndarray) -> float:
    """Largest logit difference as a share of the reference's spread."""
    return float(np.abs(got - want).max()) / _spread(want)


_REF: dict = {}


def _reference(params, seq, hf=TINY_HF, **kw) -> np.ndarray:
    """The reference's logits for ``seq``, computed on the sequence padded
    to a multiple of 32 tokens (causal: what follows a position cannot
    change it), so that a few compiled programs serve every length."""
    n = -(-len(seq) // 32) * 32
    key = (n, hf["mamba_d_state"], tuple(sorted(kw.items())))
    if key not in _REF:
        _REF[key] = jax.jit(lambda p, ids: ref.forward(p, hf, ids, **kw))
    ids = np.zeros((n,), np.int32)
    ids[:len(seq)] = seq
    return np.asarray(_REF[key](params, ids))[:len(seq)]


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


# ----------------------------------------------------------------------
# the model: forward against the reference
# ----------------------------------------------------------------------

def test_stack_is_one_run_of_layers_that_hold_both_kv_and_a_state(tiny):
    cfg, params = tiny
    assert cfg.layer_groups() == (("attn_ssm", "dense", 0, 3),)
    assert cfg.attn_layers == cfg.ssm_layers == (0, 1, 2)
    assert cfg.conv_layers == () and cfg.carries_state
    (stack,) = params["layers"]
    assert stack["ssm_in_proj"].shape == (3, 64, 64 + 128 + 4)  # z | x B C | dt
    assert stack["ssm_conv"].shape == (3, 128, 4)
    # the recurrence's own scalars are float32 whatever is served
    served = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16)
    for name in ("ssm_A_log", "ssm_dt_bias", "ssm_D"):
        assert served["layers"][0][name].dtype == jnp.float32
    a = np.exp(np.asarray(stack["ssm_A_log"]))
    step = np.log1p(np.exp(np.asarray(stack["ssm_dt_bias"])))
    assert 1 <= a.min() and a.max() <= 16
    assert 1e-3 <= step.min() and step.max() <= 1e-1 * (1 + 1e-5)
    assert np.all(np.asarray(stack["ssm_D"]) == 1)
    assert not tiny_config("llama").carries_state


def test_a_layer_at_the_published_widths_has_430_120_032_parameters():
    cfg = ModelConfig.from_hf_dict(PUBLISHED)
    (stack,) = param_shapes(cfg)["layers"]
    assert sum(int(np.prod(s[1:])) for s in stack.values()) == 430_120_032
    assert stack["ssm_in_proj"][1:] == (5120, 9248)
    shapes = cfg.state_shapes(64, "bfloat16")
    assert shapes["ssm"] == ((6, 64, 32, 128, 256), "float32")  # 4 MiB a row
    assert shapes["conv"] == ((6, 64, 3, 5120), "bfloat16")
    top = param_shapes(cfg)
    assert top["embed_tokens"] == (261120, 5120) and top["lm_head"] == (5120, 261120)


def test_forward_matches_reference(tiny):
    cfg, params = tiny
    ids = np.asarray(_prompts([45], seed=3))  # 5 chunks of 8 and 5 more
    logits, _ = forward(params, ids, cfg)
    parts: dict = {}
    want = ref.forward(params, TINY_HF, ids[0], parts=parts)
    assert _gap(np.asarray(logits[0]), np.asarray(want)) < TOL
    # the state's share of the mixer's output is of the skip's order, so
    # that a comparison of logits can see a broken state (module docstring)
    for from_state, skip in zip(parts["from_state_rms"], parts["skip_rms"]):
        assert from_state > 0.5 * skip


def test_cache_prefill_then_decode_matches_full_forward(tiny):
    """The offline path: ``KVCache`` holds K/V of every layer and, beside
    them, the convolution's history and the float32 recurrent state."""
    cfg, params = tiny
    ids = np.asarray(_prompts([20], seed=4))
    want = _reference(params, ids[0])
    cache = KVCache.init(cfg, 1, 32, dtype=jnp.bfloat16)
    assert cache.ssm.dtype == jnp.float32 and cache.conv.dtype == jnp.bfloat16
    cache = KVCache.init(cfg, 1, 32, dtype=jnp.float32)
    assert cache.k.shape[0] == 3 and cache.conv.shape == (3, 1, 3, 128)
    assert cache.ssm.shape == (3, 1, 4, 16, 16)
    parts = []
    for lo, hi in ((0, 7), (7, 8), (8, 9), (9, 20)):  # chunks and single steps
        out, cache = forward(params, ids[:, lo:hi], cfg, cache)
        parts.append(np.asarray(out[0]))
    assert _gap(np.concatenate(parts), want) < TOL


def test_left_padded_ragged_batch_matches_reference(tiny):
    """Padding before a sequence's start is not part of it: it neither
    enters the convolution nor moves the state."""
    cfg, params = tiny
    a, b = _prompts([5, 9], seed=5)
    ids = np.zeros((2, 9), np.int32)
    ids[0, 4:], ids[1] = a, b
    mask = ids > 0
    cache = KVCache.init(cfg, 2, 16, dtype=jnp.float32)
    out, _ = forward(params, ids, cfg, cache, attn_mask=jnp.asarray(mask),
                     pad_offsets=jnp.asarray([4, 0], jnp.int32))
    assert _gap(np.asarray(out[0, 4:]), _reference(params, a)) < TOL
    assert _gap(np.asarray(out[1]), _reference(params, b)) < TOL


# ----------------------------------------------------------------------
# the scan: chunked against token by token
# ----------------------------------------------------------------------

def _scan_inputs(seed, s, rows=1, nh=4, p=8, ng=2, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        x=jax.random.normal(ks[0], (rows, s, nh, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (rows, s, nh)) - 1.0),
        a=-jnp.exp(jax.random.normal(ks[2], (nh,))),
        b=jax.random.normal(ks[3], (rows, s, ng, n)),
        c=jax.random.normal(ks[4], (rows, s, ng, n)),
        d_skip=jax.random.normal(ks[5], (nh,)),
        h0=jax.random.normal(ks[6], (rows, nh, p, n)))


@jax.jit
def _recurrence(*args):
    with jax.default_matmul_precision("highest"):
        return ref.recurrence(*args)


def _token_by_token(i, row, h0):
    return _recurrence(i["x"][row], i["dt"][row], i["a"], i["b"][row],
                       i["c"][row], i["d_skip"], h0)


@pytest.mark.parametrize("chunk", [1, 4, 5, 16, 64])
@pytest.mark.parametrize("start", ["zero", "nonzero"])
def test_chunked_scan_is_the_token_by_token_recurrence(chunk, start):
    """Chunk sizes that do (1, 4, 16) and do not (5) divide the 16 tokens,
    and one longer than the sequence; from nothing and from a state."""
    i = _scan_inputs(1, 16, rows=2)
    h0 = i["h0"] if start == "nonzero" else jnp.zeros_like(i["h0"])
    y, h = jax.jit(ssm.ssm_scan, static_argnames="chunk")(
        h0, i["x"], i["dt"], i["a"], i["b"], i["c"], i["d_skip"], chunk=chunk)
    for row in range(2):
        want_y, want_h = _token_by_token(i, row, h0[row])
        scale = float(jnp.abs(want_y).max())
        assert float(jnp.abs(y[row] - want_y).max()) < SCAN_TOL * scale
        assert float(jnp.abs(h[row] - want_h).max()) < SCAN_TOL * float(
            jnp.abs(want_h).max())


@pytest.mark.parametrize("form", ["xla", "pallas"])
@pytest.mark.parametrize("chunk", [4, 5])
def test_packed_scan_resets_at_segment_boundaries_and_moves_no_other_row(
        chunk, form):
    """The tick's form: rows of 1, 11, 0, 6 and 3 tokens, consecutive on
    one packed axis of 24 lanes (3 of them dead).  Row 1 continues its
    state, rows 0, 3 and 4 start here (a slot's old state is not read),
    row 2 is not in the tick and keeps its state bit for bit.  Six rows
    have more tokens than one: each advances by itself, row 1 in three
    passes or two.  Through both forms of the first pass: ``ssm_chunk``
    over every row, and the state-update kernel (in the interpreter, on a
    state 128 wide) over the rows with a token."""
    counts, fresh = [1, 11, 0, 6, 3, 2, 2, 2], [True, False, False, True, True,
                                                 False, True, False]
    rows = len(counts)
    i = _scan_inputs(2, max(counts), rows=rows, n=128 if form == "pallas" else 16)
    assert (ssm.state_update_heads(i["h0"][None], 2, True) is None) is (form == "xla")
    state = jnp.stack([i["h0"] * 0.5, i["h0"]])  # two layers; layer 1 advances
    tok_row, start, packed = [], [], {k: [] for k in "x dt b c".split()}
    for r, n in enumerate(counts):
        start.append(len(tok_row))
        tok_row += [r] * n
        for k in packed:
            packed[k].append(i[k][r, :n])
    dead = 3  # lanes that hold no token: they belong to no row's count
    t = len(tok_row) + dead
    pad = lambda v: jnp.concatenate(  # noqa: E731
        [v, jnp.ones((dead,) + v.shape[1:], v.dtype)])
    y, new = jax.jit(ssm.ssm_packed, static_argnames=("chunk", "interpret"))(
        state, jnp.int32(1), *(pad(jnp.concatenate(packed[k])) for k in ("x", "dt")),
        i["a"], *(pad(jnp.concatenate(packed[k])) for k in ("b", "c")),
        i["d_skip"], tok_row=jnp.asarray(tok_row + [0] * dead, jnp.int32),
        start=jnp.asarray(start, jnp.int32), count=jnp.asarray(counts, jnp.int32),
        fresh=jnp.asarray(fresh), chunk=chunk,
        interpret=True if form == "pallas" else None)
    assert y.shape[0] == t
    assert np.array_equal(np.asarray(new[0]), np.asarray(state[0]))
    assert np.array_equal(np.asarray(new[1, 2]), np.asarray(state[1, 2]))
    for r, n in enumerate(counts):
        if not n:
            continue
        h0 = jnp.zeros_like(i["h0"][r]) if fresh[r] else i["h0"][r]
        cut = {k: (v[:, :n] if k in ("x", "dt", "b", "c") else v)
               for k, v in i.items()}
        want_y, want_h = _token_by_token(cut, r, h0)
        got = y[start[r]:start[r] + n]
        assert float(jnp.abs(got - want_y).max()) < SCAN_TOL * float(
            jnp.abs(want_y).max()), r
        assert float(jnp.abs(new[1, r] - want_h).max()) < SCAN_TOL * float(
            jnp.abs(want_h).max()), r


# ----------------------------------------------------------------------
# the served path: logits of the unified tick against the reference
# ----------------------------------------------------------------------

class Probe:
    """The logits the tick samples from, tick by tick: ``final_logits``
    (the XLA tail, ``sample_epilogue="off"``) wrapped with a callback."""

    def __init__(self, monkeypatch):
        self.ticks: list[np.ndarray] = []
        real = engine_mod.final_logits

        def probed(params, x, config, **kw):
            logits = real(params, x, config, **kw)
            jax.debug.callback(lambda a: self.ticks.append(np.asarray(a)), logits)
            return logits

        monkeypatch.setattr(engine_mod, "final_logits", probed)


@pytest.fixture(scope="module")
def probe():
    """One probe for the file: every engine built here is traced with it."""
    with pytest.MonkeyPatch.context() as mp:
        yield Probe(mp)


@pytest.fixture(scope="module")
def shared(tiny, probe):
    """ONE engine for the cases that need nothing special of it: a tick
    program is compiled once for the file, not once a case (an idle engine
    is as good as a new one: every slot's state starts from zero)."""
    return _engine(*tiny)


def _engine(cfg, params, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("num_blocks", 48)
    kw.setdefault("block_size", 8)
    kw.setdefault("max_seq_len", 64)
    kw.setdefault("prefill_chunk", 8)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(params, cfg, sampler=Sampler(kind="greedy"),
                       sample_epilogue="off", **kw)


def _serve(engine, probe, reqs, between=None):
    """Run an idle engine to completion; per request the logits each of its tokens was
    sampled from (a requeued request's tokens are teacher-forced back, so
    every position is sampled once)."""
    got = {r.req_id: [] for r in reqs}
    tick = 0
    while True:
        n_before = {r.req_id: len(r.generated) for r in reqs}
        more = engine.step()
        jax.effects_barrier()
        for r in reqs:
            if len(r.generated) > n_before[r.req_id]:
                # an emitting row still holds its slot when step() returns,
                # unless it finished: then the slot it had is in the record
                slot = r.slot if r.slot is not None and r.slot >= 0 else r.extra["_slot"]
                got[r.req_id].append(probe.ticks[-1][slot, 0])
            if r.slot is not None and r.slot >= 0:
                r.extra["_slot"] = r.slot
        tick += 1
        if between is not None:
            between(tick)
        if not more:
            return got


def _worst_gap(params, reqs, got, hf=TINY_HF) -> float:
    worst = 0.0
    for r in reqs:
        seq = list(r.prompt) + list(r.generated)
        want = _reference(params, seq, hf)
        p = len(r.prompt)
        have = np.stack(got[r.req_id])
        assert have.shape[0] == len(r.generated)
        worst = max(worst, _gap(have, want[p - 1:p - 1 + len(r.generated)]))
    return worst


SERVE_CASES = {
    # a 21-token prompt in chunks of 8: two chunk boundaries inside it,
    # the state handed from tick to tick
    "prompt_over_three_ticks": dict(lengths=[21], new=5),
    # two sequences' chunks packed in one tick (budget 4 + 2 x 8 tokens)
    "two_prefills_one_tick": dict(lengths=[7, 6], new=4),
    # a short prompt decodes while a long one is still being prefilled
    "decode_beside_prefill": dict(lengths=[3, 30], new=8),
    # a pool too small for all three: one is evicted, requeued and
    # prefilled again from its first token
    "evict_requeue": dict(lengths=[4, 5, 3], new=20,
                          engine=dict(max_slots=2, num_blocks=6)),
}


@pytest.mark.parametrize("case", sorted(SERVE_CASES))
def test_served_logits_match_reference(tiny, probe, shared, case):
    cfg, params = tiny
    spec = SERVE_CASES[case]
    engine = _engine(cfg, params, **spec["engine"]) if "engine" in spec else shared
    assert engine.mixed and engine.ragged_attn_impl == "pallas"
    reqs = [engine.submit(p, max_new_tokens=spec["new"], seed=i)
            for i, p in enumerate(_prompts(spec["lengths"], seed=11))]
    seen = {"beside": 0}

    def between(_tick):
        running = engine.scheduler.running
        seen["beside"] += (any(r.prefilled for r in running)
                           and any(not r.prefilled for r in running))

    got = _serve(engine, probe, reqs, between)
    assert all(len(r.generated) == spec["new"] for r in reqs)
    if case == "decode_beside_prefill":
        assert seen["beside"] > 0, "no tick held a decode row beside a prefill"
    if case == "evict_requeue":
        assert engine.scheduler.n_preemptions > 0, "pool not tight enough"
    assert _worst_gap(params, reqs, got) < TOL
    assert engine.pool.free_list.num_allocated == 0


def test_a_slot_reused_by_a_new_request_starts_from_zero(tiny, probe, shared):
    """The second request runs in the slot the first one left, whose rows
    of the state still hold the first one's last values."""
    cfg, params = tiny
    slots = []
    for i, p in enumerate(_prompts([9, 11], seed=12)):
        req = shared.submit(p, max_new_tokens=4, seed=i)
        got = _serve(shared, probe, [req])
        slots.append(req.extra["_slot"])
        assert _worst_gap(params, [req], got) < TOL
        assert float(jnp.abs(shared.pool.pages.state["ssm"][:, slots[-1]]).max()) > 0
    assert slots[0] == slots[1]


def test_pool_holds_every_layers_pages_and_the_state_pytree_beside_them(tiny):
    cfg, params = tiny
    engine = _engine(cfg, params)
    pages = engine.pool.pages
    assert pages.k.shape == (3, 48, 8, 2, 16)  # every layer has K/V
    assert {k: (v.shape, v.dtype.name) for k, v in pages.state.items()} == {
        "conv": ((3, 4, 3, 128), "float32"),  # layers, slots, taps - 1, [x B C]
        "ssm": ((3, 4, 4, 16, 16), "float32")}  # layers, slots, heads, P, N
    assert len(pages.pool_arrays()) == 2
    # the recurrent state is float32 whatever is served
    served = ServeEngine(
        init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.bfloat16), cfg,
        max_slots=4, num_blocks=48, block_size=8, max_seq_len=64,
        prefill_chunk=8, cache_dtype=jnp.bfloat16)
    assert served.pool.pages.state["ssm"].dtype == jnp.float32
    assert served.pool.pages.state["conv"].dtype == jnp.bfloat16
    # the bucket set is the one a stack of attention layers alone gets
    plain = ServeEngine(
        init_params(jax.random.PRNGKey(0), tiny_config("qwen2"), dtype=jnp.float32),
        tiny_config("qwen2"), max_slots=4, num_blocks=48, block_size=8,
        max_seq_len=64, prefill_chunk=8, cache_dtype=jnp.float32)
    assert engine.mixed_buckets == plain.mixed_buckets
    assert plain.pool.pages.state is None


def test_a_sequences_logits_do_not_depend_on_the_rest_of_the_tick(
        tiny, probe, shared):
    cfg, params = tiny
    prompts = _prompts([10, 14, 5, 9], seed=21)
    r0 = shared.submit(prompts[0], max_new_tokens=6, seed=0)
    got_alone = _serve(shared, probe, [r0])[r0.req_id]
    reqs = [shared.submit(p, max_new_tokens=6, seed=i) for i, p in enumerate(prompts)]
    got_crowd = _serve(shared, probe, reqs)[reqs[0].req_id]
    assert reqs[0].generated == r0.generated
    # the same sums at another packed width: float32 rounding only
    assert _gap(np.stack(got_crowd), np.stack(got_alone)) < TOL


# ----------------------------------------------------------------------
# what the tolerance must refuse
# ----------------------------------------------------------------------

@pytest.mark.parametrize("form", ["xla", "pallas"])
@pytest.mark.parametrize("broken", ["float32", "bf16_state", "zeroed_every_tick"])
def test_a_state_kept_in_bf16_or_zeroed_between_ticks_fails_the_tolerance(
        tiny, wide, probe, shared, monkeypatch, broken, form):
    """The control of every comparison in this file: the SAME requests with
    the state as the program keeps it pass ``TOL``; kept in bf16, or zeroed
    after every tick, they do not.  ``pallas``: the preset with a state 128
    wide served through the state-update kernel (a bf16 state the kernel
    does not take: that engine says ``xla`` and fails all the same)."""
    with _state_kernel(form):
        _state_controls(tiny, wide, probe, shared, monkeypatch, broken, form)


def _state_controls(tiny, wide, probe, shared, monkeypatch, broken, form):
    cfg, params = tiny if form == "xla" else wide
    hf = TINY_HF if form == "xla" else WIDE_HF
    engine = shared if form == "xla" else None
    if broken == "bf16_state":
        real = ModelConfig.state_shapes

        def lower(self, slots, dtype):
            out = real(self, slots, dtype)
            out["ssm"] = (out["ssm"][0], "bfloat16")
            return out

        monkeypatch.setattr(ModelConfig, "state_shapes", lower)
        engine = None
    engine = engine or _engine(cfg, params)
    assert engine.pool.pages.state["ssm"].dtype == (
        jnp.bfloat16 if broken == "bf16_state" else jnp.float32)
    assert engine.ssm_state_impl == (
        "pallas" if form == "pallas" and broken != "bf16_state" else "xla")
    reqs = [engine.submit(p, max_new_tokens=10, seed=i)
            for i, p in enumerate(_prompts([21, 6], seed=13))]

    def between(_tick):
        if broken == "zeroed_every_tick":
            pages = engine.pool.pages
            engine.pool.pages = pages._replace(state=dict(
                pages.state, ssm=jnp.zeros_like(pages.state["ssm"])))

    worst = _worst_gap(params, reqs, _serve(engine, probe, reqs, between), hf)
    if broken == "float32":
        assert worst < TOL
    else:
        assert worst > 10 * TOL, worst


def test_reference_with_a_bf16_state_fails_the_tolerance(tiny):
    """The same control on the reference's side: what the comparison calls
    wrong does not depend on which side keeps the state lower."""
    cfg, params = tiny
    ids = _prompts([45], seed=3)[0]
    want = _reference(params, ids)
    lower = _reference(params, ids, state_dtype=jnp.bfloat16)
    assert _gap(lower, want) > 10 * TOL


# ----------------------------------------------------------------------
# what is refused, by the flag that asked for it
# ----------------------------------------------------------------------

class _Tier:
    """Stands for a host tier: the refusal comes before anything reads it."""


@pytest.mark.parametrize("kw, flag", [
    (dict(enable_prefix_cache=True), "--prefix-cache"),
    (dict(enable_prefix_cache=True, host_tier=_Tier()), "--prefix-cache"),
    (dict(host_tier=_Tier()), "host_tier"),
    (dict(spec_k=2), "--spec-k"),
    (dict(mesh_plan=MeshPlan(model=2)), "--mesh model>1"),
], ids=["prefix-cache", "prefix-cache+tier", "tier", "spec-k", "mesh"])
def test_start_up_refusals_name_the_flag(tiny, kw, flag):
    cfg, params = tiny
    pattern = ("host_tier" if flag == "host_tier" else
               "state-space layers.*refused: " + flag.replace(">", r"\>"))
    with pytest.raises(ValueError, match=pattern):
        ServeEngine(params, cfg, max_slots=2, num_blocks=16, block_size=8,
                    max_seq_len=32, cache_dtype=jnp.float32, **kw)


def test_unknown_model_type_and_unimplemented_variants_are_refused():
    with pytest.raises(ValueError, match="unknown model_type 'falcon_h2'"):
        ModelConfig.from_hf_dict(dict(TINY_HF, model_type="falcon_h2"))
    with pytest.raises(ValueError, match="mamba_proj_bias"):
        ModelConfig.from_hf_dict(dict(TINY_HF, mamba_proj_bias=True))
    with pytest.raises(ValueError, match="mamba_norm_before_gate"):
        ModelConfig.from_hf_dict(dict(TINY_HF, mamba_norm_before_gate=True))
    with pytest.raises(ValueError, match="mamba_n_heads"):
        ModelConfig.from_hf_dict(dict(TINY_HF, mamba_d_ssm=48))


# ----------------------------------------------------------------------
# spans and counters
# ----------------------------------------------------------------------

@pytest.mark.parametrize("form", ["xla", "pallas"])
def test_the_tick_says_which_form_advanced_the_state(wide, form):
    """Tick argument ``ssm_state_impl`` on every dispatching tick, gauge
    ``ssm_state_kernel`` and the ``probe.ssm_state_update`` set-up span:
    a fallback shows in the trace and on ``/metrics``, not only in a log
    line.  Both forms serve the same tokens."""
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    cfg, params = wide
    tracer = TraceRecorder()
    with _state_kernel(form):
        engine = ServeEngine(params, cfg, max_slots=4, num_blocks=48,
                             block_size=8, max_seq_len=64, prefill_chunk=8,
                             cache_dtype=jnp.float32, tracer=tracer)
        assert engine.ssm_state_impl == form
        reqs = [engine.submit(p, max_new_tokens=5, seed=i)
                for i, p in enumerate(_prompts([9, 12], seed=2))]
        engine.run_until_complete()
    span, = [e for e in tracer.events() if e.get("name") == "probe.ssm_state_update"]
    assert span["args"]["ok"] is (form == "pallas")
    ticks = [e["args"] for e in tracer.events()
             if e.get("name") == "tick" and "ssm_state_rows" in e["args"]]
    assert ticks and all(a["ssm_state_impl"] == form for a in ticks)
    assert f"ssm_state_kernel {int(form == 'pallas')}" in engine.metrics.prometheus()
    from tools.summarize_trace import format_summary, tick_account

    assert tick_account(tracer.events())["ssm_kernel_share"] == (form == "pallas")
    assert ("the state-update kernel in "
            f"{'100.0' if form == 'pallas' else '0.0'}% of those ticks"
            in format_summary(tracer.events(), top=0))
    _TOKENS.setdefault("served", [r.generated for r in reqs])
    assert [r.generated for r in reqs] == _TOKENS["served"]


_TOKENS: dict = {}


def test_tick_arguments_counters_and_scopes(tiny):
    from llm_np_cp_tpu.models.transformer import STEP_SCOPES
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    cfg, params = tiny
    tracer = TraceRecorder()
    engine = ServeEngine(params, cfg, max_slots=4, num_blocks=48, block_size=8,
                         max_seq_len=64, prefill_chunk=8,
                         cache_dtype=jnp.float32, tracer=tracer)
    assert engine.epilogue_impl == "fused"
    build = next(e for e in tracer.events() if e.get("name") == "engine_build")
    assert build["args"]["state_bytes"] == 3 * 4 * (4 * 16 * 16 + 3 * 128) * 4
    for i, p in enumerate(_prompts([9, 12], seed=2)):
        engine.submit(p, max_new_tokens=5, seed=i)
    fetches = engine.n_host_fetches
    engine.run_until_complete()
    ticks = [e for e in tracer.events()
             if e.get("name") == "tick" and "ssm_state_rows" in e["args"]]
    assert ticks and any(e["args"]["decode_tokens"] for e in ticks)
    rows = tokens = 0
    for ev in ticks:
        a = ev["args"]
        assert 1 <= a["ssm_state_rows"] <= 2 and a["host_fetches"] == 1
        assert a["ssm_state_impl"] == "xla"  # no TPU, and a state 16 wide
        assert a["ssm_scan_tokens"] == a["prefill_tokens"] + a["decode_tokens"]
        assert a["state_slots_live"] <= 2
        rows, tokens = rows + a["ssm_state_rows"], tokens + a["ssm_scan_tokens"]
    assert tokens == 9 + 12 + 2 * 4  # every prompt token once, 4 decode steps each
    assert engine.n_host_fetches - fetches == engine.n_dispatches
    text = engine.metrics.prometheus()
    assert f"ssm_state_rows_total {rows}" in text
    assert f"ssm_scan_tokens_total {tokens}" in text
    assert "ssm_ticks_total" in text and "ssm_state_slots_live" in text
    assert "ssm_state_kernel 0" in text
    assert "moe_ticks_total" not in text
    # the op map knows the two scopes; the state's update is the scan's
    # own time, not a pool move (`pool.move_share` reads the K/V pool alone)
    assert {"ssm_proj", "ssm_scan"} <= set(STEP_SCOPES)
    table = engine.device_op_map()
    scopes = {v[0] for v in table.values() if v}
    assert {"ssm_proj", "ssm_scan", "mlp", "attn", "qkv"} <= scopes
    moves = [v for k, v in table.items() if "f32[3,4,4,16,16]" in k]
    assert moves, "no operation moves the recurrent state"
    assert any(v and v[0] == "ssm_scan" for v in moves), moves
    assert not any(v and v[1] for v in moves), moves


def test_fused_epilogue_draws_the_xla_tails_token(tiny):
    """The head's multiplier reaches the fused tail's operands: the kernel's
    greedy draw is the argmax of ``final_logits`` (an untied head, its
    multiplier on the float32 logits)."""
    from llm_np_cp_tpu.models.transformer import final_logits, sample_epilogue_tail

    cfg, params = tiny
    x = jax.random.normal(jax.random.PRNGKey(5), (6, cfg.hidden_size))
    want = jnp.argmax(final_logits(params, x[None], cfg)[0], axis=-1)
    assert np.array_equal(np.asarray(sample_epilogue_tail(params, x, cfg)),
                          np.asarray(want))


def test_a_stack_without_state_space_layers_reports_none_of_it():
    from llm_np_cp_tpu.serve.tracing import TraceRecorder

    cfg = tiny_config("qwen2")
    tracer = TraceRecorder()
    engine = ServeEngine(
        init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32), cfg,
        max_slots=2, num_blocks=16, block_size=8, max_seq_len=32,
        prefill_chunk=8, cache_dtype=jnp.float32, tracer=tracer)
    engine.submit(_prompts([5])[0], max_new_tokens=3)
    engine.run_until_complete()
    ticks = [e for e in tracer.events() if e.get("name") == "tick"]
    assert ticks and not any(
        k.startswith("ssm_") for e in ticks for k in e["args"])
    assert "ssm_" not in engine.metrics.prometheus()
    assert engine.ssm_state_impl is None
    assert not [e for e in tracer.events()
                if e.get("name") == "probe.ssm_state_update"]
    build = next(e for e in tracer.events() if e.get("name") == "engine_build")
    assert build["args"]["state_bytes"] == 0


# ----------------------------------------------------------------------
# checkpoints: the published names, there and back
# ----------------------------------------------------------------------

def test_hf_key_map_round_trip(tiny, tmp_path):
    from llm_np_cp_tpu.utils.loading import load_params
    from llm_np_cp_tpu.utils.synthetic import (
        hf_config_dict,
        hf_state_dict,
        hf_tensor_shapes,
        write_hf_checkpoint,
    )

    cfg, params = tiny
    assert ModelConfig.from_hf_dict(hf_config_dict(cfg)) == cfg
    host = jax.tree.map(np.asarray, params)
    tensors = hf_state_dict(host, cfg)
    assert {k: v.shape for k, v in tensors.items()} == hf_tensor_shapes(cfg)
    # the published names, stored as the published code stores them
    assert tensors["model.layers.0.mamba.in_proj.weight"].shape == (196, 64)
    assert tensors["model.layers.1.mamba.conv1d.weight"].shape == (128, 1, 4)
    assert tensors["model.layers.1.mamba.conv1d.bias"].shape == (128,)
    assert tensors["model.layers.2.mamba.out_proj.weight"].shape == (64, 64)
    assert tensors["model.layers.2.mamba.norm.weight"].shape == (64,)
    for name in ("A_log", "D", "dt_bias"):
        assert tensors[f"model.layers.0.mamba.{name}"].shape == (4,)
        assert tensors[f"model.layers.0.mamba.{name}"].dtype == np.float32
    assert tensors["model.layers.0.self_attn.o_proj.weight"].shape == (64, 64)
    assert tensors["model.layers.0.feed_forward.gate_proj.weight"].shape == (128, 64)
    assert "model.layers.2.pre_ff_layernorm.weight" in tensors
    assert "model.layers.2.input_layernorm.weight" in tensors
    assert "model.final_layernorm.weight" in tensors
    assert tensors["lm_head.weight"].shape == (256, 64)
    write_hf_checkpoint(tmp_path, cfg, tensors)
    for use_native in (False, True):
        loaded, cfg2 = load_params(tmp_path, dtype=jnp.float32,
                                   use_native=use_native, on_host=True)
        assert cfg2 == cfg
        flat_a, tree_a = jax.tree.flatten(host)
        flat_b, tree_b = jax.tree.flatten(loaded)
        assert tree_a == tree_b
        for a, b in zip(flat_a, flat_b):
            np.testing.assert_array_equal(a, b)
    # served in bf16, the recurrence's scalars are still float32
    loaded, _ = load_params(tmp_path, dtype=jnp.bfloat16, on_host=True)
    assert loaded["layers"][0]["ssm_A_log"].dtype == np.float32
    # one tensor short is an error that names it
    del tensors["model.layers.1.mamba.dt_bias"]
    write_hf_checkpoint(tmp_path, cfg, tensors)
    with pytest.raises(ValueError, match="layers.1.mamba.dt_bias"):
        load_params(tmp_path, dtype=jnp.float32, on_host=True)


def test_offline_generator_runs_the_stack(tiny):
    """``Generator`` (chunked ragged prefill, fused decode scan) carries
    both states in its ``KVCache``: greedy tokens are the reference's argmax."""
    from llm_np_cp_tpu.generate import Generator

    cfg, params = tiny
    gen = Generator(params, cfg, sampler=Sampler(kind="greedy"),
                    cache_dtype=jnp.float32)
    prompts = [np.asarray(p) for p in _prompts([5, 11], seed=8)]
    tokens = np.asarray(gen.generate_ragged(prompts, 5, seed=0).tokens)
    for p, got in zip(prompts, tokens):
        seq = list(p)
        for t in got:
            logits = _reference(params, seq)[-1]
            # the reference's argmax, or within rounding of it
            assert logits.max() - logits[t] < TOL * _spread(logits[None]), (seq, t)
            seq.append(int(t))
