"""The delta-rule recurrence (ops/kda.py) and its state-update kernel
(ops/pallas/kda_state_update.py) on the CPU: the chunk form and the tick's
packed form against the token-by-token statement of the equations, the
extreme the chunk's exponent bound allows, a state kept in bf16 (which must
FAIL the tolerance the float32 one meets), and the Pallas kernel in the
interpreter against its twin.  The layer around it is tests/test_ling_hybrid.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.ops import kda
from llm_np_cp_tpu.ops.pallas import kda_state_update as ksu
from llm_np_cp_tpu.ops.pallas import support

LOW = -5.0
# float32 sums in another order: outputs are of order 0.05
TOL = 2e-6


def _inputs(seed, rows, s, heads=2, d=16, pinned=False, state=True):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)

    shape = (rows, s, heads, d)
    q = unit(jax.random.normal(ks[0], shape)) * d ** -0.5
    k = unit(jax.random.normal(ks[1], shape))
    v = jax.random.normal(ks[2], shape)
    # log-decays from a few thousandths to a few units a token
    g = LOW * jax.nn.sigmoid(3.0 * jax.random.normal(ks[3], shape) - 3.0)
    if pinned:
        g = jnp.full(shape, LOW)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], shape[:3]))
    s0 = (jax.random.normal(ks[5], (rows, heads, d, d)) * 0.1 if state
          else jnp.zeros((rows, heads, d, d)))
    return s0, q, k, v, g, beta


def _close(a, b, tol=TOL):
    return float(jnp.max(jnp.abs(a - b))) <= tol


@pytest.mark.parametrize("chunk", [1, 4, 5, 16, 32])
@pytest.mark.parametrize("start", ["zero", "nonzero"])
def test_chunked_scan_is_the_token_by_token_recurrence(chunk, start):
    args = _inputs(chunk, rows=3, s=37, state=start == "nonzero")
    want_o, want_s = kda.kda_recurrent(*args)
    got_o, got_s = jax.jit(lambda *a: kda.kda_scan(
        *a, chunk=chunk, lower_bound=LOW))(*args)
    assert _close(got_o, want_o) and _close(got_s, want_s)


def test_a_gate_pinned_at_the_bound_for_a_whole_chunk_stays_in_float32():
    """Every channel decays by e^-5 a token for 16 tokens: the factors of
    the chunk form span e^+-40 about the chunk's middle.  Finite, and within
    the tolerance of the recurrence.  What the bound allows is refused
    beyond it, at trace time."""
    args = _inputs(7, rows=2, s=32, d=128, pinned=True)
    want_o, want_s = kda.kda_recurrent(*args)
    got_o, got_s = kda.kda_scan(*args, chunk=16, lower_bound=LOW)
    assert bool(jnp.isfinite(got_o).all() and jnp.isfinite(got_s).all())
    assert _close(got_o, want_o) and _close(got_s, want_s)
    # the largest chunk the bound allows is finite too (its rounding is
    # coarser: e^+-80)
    wide, _ = kda.kda_scan(*args, chunk=32, lower_bound=LOW)
    assert bool(jnp.isfinite(wide).all()) and _close(wide, want_o, 1e-3)
    assert kda.max_chunk(LOW, 128) == 33
    assert kda.max_chunk(LOW, 128) >= 2 * 16  # the program's chunk, twice
    with pytest.raises(ValueError, match="leaves float32"):
        kda.kda_chunk(*_inputs(7, rows=1, s=64, d=128), lower_bound=LOW)


def _tick(form, state, layer, tokens, segments, rows, fresh, chunk=4):
    """One packed tick: ``segments`` = ``[(row, n tokens)]`` laid end to
    end on the packed axis, dead lanes after them."""
    q, k, v, g, beta = tokens
    t = q.shape[0]
    tok_row = np.zeros((t,), np.int32)
    start = np.zeros((rows,), np.int32)
    count = np.zeros((rows,), np.int32)
    at = 0
    for row, n in segments:
        tok_row[at:at + n] = row
        start[row], count[row] = at, n
        at += n
    return jax.jit(lambda st, *a: kda.kda_packed(
        st, jnp.int32(layer), *a, tok_row=jnp.asarray(tok_row),
        start=jnp.asarray(start), count=jnp.asarray(count),
        fresh=jnp.asarray(fresh), chunk=chunk, lower_bound=LOW,
        interpret=True if form == "pallas" else None))(state, q, k, v, g, beta)


@pytest.mark.parametrize("form", ["xla", "pallas"])
@pytest.mark.parametrize("segments, fresh", [
    # ragged prefill segments beside decode rows; row 3 starts here
    ([(2, 9), (0, 1), (3, 6), (4, 1)], [False, False, False, True, False]),
    # a decode-only tick
    ([(0, 1), (1, 1), (3, 1), (4, 1)], [False] * 5),
    # every row with a token is a new sequence
    ([(1, 5), (4, 1)], [False, True, False, False, True]),
], ids=["mixed", "decode_only", "all_fresh"])
def test_packed_form_is_the_recurrence_row_by_row(form, segments, fresh):
    """``kda_packed`` under ``ssm_packed``'s contract: a row's tokens
    continue ITS state (zero where the row is fresh: the slot's old state is
    not read), a row with no token keeps its bits, the other layer's rows
    are never touched."""
    rows, layers, d = 5, 2, 128 if form == "pallas" else 16
    n_tok = sum(n for _, n in segments)
    s0, q, k, v, g, beta = _inputs(11, rows=1, s=n_tok + 3, d=d)
    tokens = tuple(a[0] for a in (q, k, v, g, beta))  # [T, ..]
    state = jax.random.normal(
        jax.random.PRNGKey(3), (layers, rows) + s0.shape[1:]) * 0.1
    o, new = _tick(form, state, 1, tokens, segments, rows, fresh)
    at = 0
    touched = set()
    for row, n in segments:
        seg = tuple(a[None, at:at + n] for a in tokens)
        begin = (jnp.zeros_like(state[1, row]) if fresh[row]
                 else state[1, row])[None]
        want_o, want_s = kda.kda_recurrent(begin, *seg)
        assert _close(o[at:at + n], want_o[0]), (row, n)
        assert _close(new[1, row], want_s[0]), (row, n)
        touched.add(row)
        at += n
    for row in set(range(rows)) - touched:
        assert bool((new[1, row] == state[1, row]).all()), row
    assert bool((new[0] == state[0]).all())


def test_a_state_kept_in_bf16_fails_the_tolerance():
    """Forty decode ticks of one row: the float32 state stays within the
    tolerance of the recurrence, the same ticks over a bf16 state do not
    (what the tolerance is for)."""
    s0, q, k, v, g, beta = _inputs(5, rows=1, s=40, state=False)
    want_o, _ = kda.kda_recurrent(s0, q, k, v, g, beta)

    def run(dtype):
        state = jnp.zeros((1, 1) + s0.shape[1:], dtype)
        outs = []
        for i in range(40):
            tok = tuple(a[0, i:i + 1] for a in (q, k, v, g, beta))
            o, state = _tick("xla", state, 0, tok, [(0, 1)], 1, [i == 0])
            outs.append(o[0])
        return jnp.stack(outs)

    assert _close(run(jnp.float32), want_o[0])
    assert float(jnp.max(jnp.abs(run(jnp.bfloat16) - want_o[0]))) > 50 * TOL


@pytest.mark.parametrize("shape", [support.KDA_PROBE_SHAPE], ids=["probe"])
def test_state_update_kernel_in_the_interpreter_is_its_twin(shape):
    """The on-chip matrix's case for the kernel (rows of no token, rows that
    start from nothing), run in the interpreter: the output and the layer's
    state against the twin, and the rows of no token bit for bit."""
    make_args, run, reference = support.kernel_case(
        "kda_state_update", shape, interpret=True)
    args = make_args()
    got, want = np.asarray(run(*args)), np.asarray(reference(*args))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 1e-5
    state, count = np.asarray(args[0]), np.asarray(args[-2])
    layers, rows, nh, d = shape.kda_state
    new = got[-rows * nh * d * d:].reshape(rows, nh, d, d)
    idle = count == 0
    assert idle.any() and (new[idle] == state[-1][idle]).all()
    assert ksu.takes(32, 128, 128) and not ksu.takes(4, 16, 16)
    assert ksu.head_block(32, 128, 128) == 32  # a whole row: 2 MiB a block
