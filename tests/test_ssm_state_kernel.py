"""The state-update kernel (ops/pallas/ssm_state_update.py) in the
interpreter, against ``ssm_chunk`` at ``q = 1`` — the one statement of the
recurrence — and ``ssm_packed`` through both of its forms.

What a CPU can say: the arithmetic, which rows are visited, what a fresh
row reads, who decides which form runs.  That Mosaic takes the kernel at
the served shapes is tests/test_kernel_lowering.py's; what it costs is the
chip's (PERF.md section 6, PR 45).

``TOL`` = 2e-6 relative to the largest value compared: both sides are
float32 and differ by the order of a 128- or 256-term sum and by whether a
multiply-add is contracted (measured 5e-7).
"""

import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.ops import ssm
from llm_np_cp_tpu.ops.pallas import ssm_state_update as ssu
from llm_np_cp_tpu.ops.pallas import support

TOL = 2e-6


def _tick(seed, *, layers=2, rows=5, nh=4, ng=2, p=8, n=128):
    """A layer's rows and one token's operands for each."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    return dict(
        state=jax.random.normal(ks[0], (layers, rows, nh, p, n)),
        x=jax.random.normal(ks[1], (rows, nh, p)),
        dt=jax.nn.softplus(jax.random.normal(ks[2], (rows, nh)) - 1.0),
        a=-jnp.exp(jax.random.normal(ks[3], (nh,))),
        b=jax.random.normal(ks[4], (rows, ng, n)),
        c=jax.random.normal(ks[5], (rows, ng, n)),
        d_skip=jax.random.normal(ks[6], (nh,)))


def _chunk(t, layer, count, fresh):
    """``ssm_chunk`` at ``q = 1`` over the layer's rows as ``ssm_packed``
    hands them to it: ``(y [R, nh, P], state after [R, nh, P, N])``."""
    h = jnp.where(fresh[:, None, None, None], 0.0, t["state"][layer])
    live = jnp.where(count > 0, 1.0, 0.0)[:, None, None]
    y, h = ssm.ssm_chunk(
        h, t["x"][:, None], live * t["dt"][:, None], t["a"], t["b"][:, None],
        t["c"][:, None], t["d_skip"])
    return y[:, 0], h


def _kernel(t, layer, count, fresh, heads):
    return ssm._first_tokens(
        t["state"], jnp.int32(layer), t["x"], t["dt"], t["a"], t["b"], t["c"],
        t["d_skip"], count, fresh, heads, True)


def _close(got, want):
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) <= TOL * scale


@pytest.mark.parametrize("nh,ng,heads", [
    (4, 1, 4), (4, 1, 2), (4, 1, 1),  # one group: a row, half a row, a head
    (4, 2, 4), (4, 2, 2), (4, 2, 1),  # two: both in a block, one, half of one
    (8, 2, 4), (8, 4, 8),
], ids=lambda v: str(v))
def test_kernel_is_the_recurrences_first_token(nh, ng, heads):
    """Head blocks that equal ``nh``, that hold whole groups, one group, or
    part of one; rows with a token, without, and fresh ones."""
    t = _tick(1, rows=6, nh=nh, ng=ng, n=256 if nh == 8 else 128)
    count = jnp.asarray([1, 0, 3, 1, 0, 2], jnp.int32)
    fresh = jnp.asarray([True, False, False, False, False, True])
    want_y, want_h = _chunk(t, 1, count, fresh)
    y, state = _kernel(t, 1, count, fresh, heads)
    there = np.asarray(count) > 0
    _close(y[there], want_y[there])
    _close(state[1][there], want_h[there])
    # (a row of no token: nothing of the state in its y, which nobody reads)
    assert np.isfinite(np.asarray(y)).all()
    assert np.array_equal(np.asarray(state[0]), np.asarray(t["state"][0]))


@pytest.mark.parametrize("counts", [[2, 0, 0, 1, 0], [0, 0, 0, 0, 0]],
                         ids=["some", "none"])
def test_a_row_of_no_token_is_not_visited_and_keeps_its_bits(counts):
    """The operands of a row that is not in the tick are whatever token the
    packed axis holds at its clipped ``start`` — here a real step ``dt >
    0``, never masked on the kernel's way: had the kernel visited the row,
    its state would have moved.  With no row in the tick at all the one
    grid step names row 0 and leaves it as it was, negative zeros and
    NaNs included."""
    t = _tick(2)
    odd = t["state"].at[1, 0, 0, 0, :4].set(
        jnp.asarray([-0.0, jnp.nan, jnp.inf, 1e-42]))
    t = dict(t, state=odd)
    count = jnp.asarray(counts, jnp.int32)
    _, state = _kernel(t, 1, count, jnp.zeros((5,), jnp.bool_), 4)
    before = np.asarray(t["state"]).view(np.uint32)
    after = np.asarray(state).view(np.uint32)
    there = np.asarray(counts) > 0
    assert np.array_equal(after[0], before[0])
    assert np.array_equal(after[1][~there], before[1][~there])
    assert all((after[1, r] != before[1, r]).any() for r in np.flatnonzero(there))


def test_a_fresh_row_starts_from_zero_whatever_the_slot_held():
    t = _tick(3)
    held = t["state"].at[1, 2].set(jnp.nan).at[1, 3].set(1e30)
    count = jnp.asarray([1, 1, 1, 1, 0], jnp.int32)
    fresh = jnp.asarray([False, False, True, True, False])
    y, state = _kernel(dict(t, state=held), 1, count, fresh, 2)
    zero = dict(t, state=t["state"].at[1, 2:4].set(0.0))
    want_y, want_h = _chunk(zero, 1, count, jnp.zeros((5,), jnp.bool_))
    assert np.isfinite(np.asarray(y[:4])).all()
    _close(y[:4], want_y[:4])
    _close(state[1, :4], want_h[:4])
    # nothing of the state enters a fresh row's y: the token's own terms
    dtx = t["dt"][2, :, None] * t["x"][2]
    own = (jnp.sum(t["c"][2] * t["b"][2], -1).repeat(2)[:, None] * dtx
           + t["d_skip"][:, None] * t["x"][2])
    _close(y[2], own)


def _packed_tick(n=128, dead=2):
    """Decode rows 0, 2, 4 (row 2 fresh), row 1 with a prefill chunk of 7
    tokens in passes of 4, row 3 not in the tick; ``dead`` lanes hold no
    token."""
    counts = [1, 7, 1, 0, 1]
    t = _tick(4, n=n)
    ks = jax.random.split(jax.random.PRNGKey(5), 4)
    lanes = sum(counts) + dead
    tok_row, start = [], []
    for r, k in enumerate(counts):
        start.append(len(tok_row))
        tok_row += [r] * k
    packed = dict(
        x=jax.random.normal(ks[0], (lanes, 4, 8)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (lanes, 4)) - 1.0),
        b=jax.random.normal(ks[2], (lanes, 2, n)),
        c=jax.random.normal(ks[3], (lanes, 2, n)))
    rows = dict(
        tok_row=jnp.asarray(tok_row + [0] * dead, jnp.int32),
        start=jnp.asarray(start, jnp.int32),
        count=jnp.asarray(counts, jnp.int32),
        fresh=jnp.asarray([False, False, True, False, False]))
    return t, packed, rows, len(tok_row)


def _run_packed(t, packed, rows, interpret):
    return jax.jit(ssm.ssm_packed, static_argnames=("chunk", "interpret"))(
        t["state"], jnp.int32(1), packed["x"], packed["dt"], t["a"],
        packed["b"], packed["c"], t["d_skip"], chunk=4, interpret=interpret,
        **rows)


def test_packed_tick_is_the_same_through_both_forms():
    """The kernel's pass followed by the ``more`` loop over the chunk's own
    row, against ``ssm_chunk``'s pass followed by the same loop."""
    t, packed, rows, live = _packed_tick()
    y_k, state_k = _run_packed(t, packed, rows, True)
    y_x, state_x = _run_packed(t, packed, rows, None)
    _close(y_k[:live], y_x[:live])
    _close(state_k, state_x)
    assert np.array_equal(np.asarray(state_k[1, 3]), np.asarray(t["state"][1, 3]))
    assert np.array_equal(np.asarray(state_k[0]), np.asarray(t["state"][0]))
    assert not np.array_equal(np.asarray(state_k[1, 1]), np.asarray(t["state"][1, 1]))


def test_a_state_the_kernel_does_not_take_goes_through_ssm_chunk():
    """Asked for the kernel (``interpret=True``) with a state 16 wide, or
    kept in bf16: the gate refuses from shape and dtype, and the tick is
    the compiler's — the same numbers as unasked."""
    t, packed, rows, live = _packed_tick(n=16)
    assert ssm.state_update_heads(t["state"], 2, True) is None
    y_k, state_k = _run_packed(t, packed, rows, True)
    y_x, state_x = _run_packed(t, packed, rows, None)
    assert np.array_equal(np.asarray(y_k[:live]), np.asarray(y_x[:live]))
    assert np.array_equal(np.asarray(state_k), np.asarray(state_x))
    wide = _tick(4)["state"]
    assert ssm.state_update_heads(wide, 2, True) == 4
    assert ssm.state_update_heads(wide.astype(jnp.bfloat16), 2, True) is None


@pytest.mark.parametrize("shape,want", [
    ((32, 2, 128, 256), 32),   # Falcon-H1-34B: a row is 4 MiB, whole
    ((64, 2, 128, 256), 32),   # 8 MiB a row: halved, one group a block
    ((128, 1, 64, 128), 128),  # 4 MiB
    ((128, 8, 128, 256), 32),  # 16 MiB: two groups of 16 a block
    ((4, 2, 8, 128), 4),
    ((4, 2, 16, 16), None),    # the tiny preset: N is no row of lanes
    ((4, 2, 12, 128), None),   # P is not whole sublanes
    ((256, 8, 64, 128), None),  # the heads do not fit one row of lanes
    ((6, 4, 8, 128), None),    # heads that groups do not divide
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else None)
def test_head_block_by_hand(shape, want):
    assert ssu.head_block(*shape) == want


def test_the_gate_asks_backend_then_probe_and_warns_once(monkeypatch, caplog):
    state = jax.ShapeDtypeStruct((2, 5, 4, 8, 128), jnp.float32)
    # this backend is no TPU: ssm_chunk, silently
    with caplog.at_level(logging.WARNING, logger="llm_np_cp_tpu"):
        assert ssm.state_update_heads(state, 2) is None
    assert not caplog.records
    # a TPU whose Mosaic refuses the kernel: ssm_chunk, one warning that
    # names it, however many layers and programs ask
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(support, "_FORCE_FAIL", True)
    monkeypatch.setattr(support, "_WARNED", set())
    support._probe.cache_clear()
    with caplog.at_level(logging.WARNING, logger="llm_np_cp_tpu"):
        assert ssm.state_update_heads(state, 2) is None
        assert ssm.state_update_heads(state, 2) is None
    said = [r.getMessage() for r in caplog.records]
    assert len(said) == 1 and "ssm_state_update" in said[0]
    assert "falling back to ssm_chunk" in said[0]
    # ... and one that takes it
    monkeypatch.setattr(support, "_FORCE_FAIL", False)
    monkeypatch.setattr(support, "kernel_error", lambda kernel: None)
    assert ssm.state_update_heads(state, 2) == 4


def test_probe_case_holds_every_kind_of_row():
    """What a server compiles and runs at start-up on a TPU: rows with a
    token, without, fresh ones; ``N`` two rows of lanes, two groups, the
    block the served shape takes (a whole row)."""
    assert "ssm_state_update" in support.KERNELS
    shape = support.STATE_PROBE_SHAPE
    _, rows, nh, ng, p, n = shape.state
    assert n == 256 and ng == 2 and ssu.head_block(nh, ng, p, n) == nh
    make_args, _, _ = support.kernel_case("ssm_state_update", shape, interpret=True)
    *_, count, fresh = make_args()
    count, fresh = np.asarray(count), np.asarray(fresh)
    assert (count == 0).any() and (count > 0).any()
    assert fresh.any() and (~fresh & (count > 0)).any() and not (fresh & (count == 0)).any()
    cases = [(k, s.name) for k, s, _ in support.kernel_cases()
             if k == "ssm_state_update" or s.state is not None]
    assert cases == [("ssm_state_update", "probe/state"),
                     ("ssm_state_update", "falcon-h1-34b-6l")]
    (rec,) = support.kernel_matrix((shape,), interpret=True)
    assert rec["ok"] and rec["max_err"] < 1e-5, rec
