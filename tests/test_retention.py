"""The power-retention recurrence (ops/retention.py) and its state-update
kernel (ops/pallas/retention_state_update.py) on the CPU: the feature map's
inner product, the state form against the attention form that has NO feature
map, the chunk form and the tick's packed form against the token-by-token
statement of the equations, gates at both extremes, a query group reading one
kv head's state, a state kept in bf16 (which must FAIL the tolerance the
float32 one meets), and the Pallas kernel in the interpreter against its twin.
The layer around it is tests/test_brumby.py.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.ops import retention
from llm_np_cp_tpu.ops.pallas import retention_state_update as rsu
from llm_np_cp_tpu.ops.pallas import support

# float32 sums in another order: outputs are weighted means of values of
# order 1, the states sums of a few dozen terms of order 1
TOL = 2e-5


def _inputs(seed, rows, s, heads=4, kv_heads=2, d=16, state=True, gate=None,
            lean=1.0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    k = jax.random.normal(ks[1], (rows, s, kv_heads, d))
    # a query leans on its own token's key, so that no denominator is the
    # square of a chance near-zero inner product (``attention_form`` says
    # what such a token's conditioning is; its test has them)
    q = (0.5 * jnp.repeat(k, heads // kv_heads, axis=2) * lean
         + jax.random.normal(ks[0], (rows, s, heads, d)))
    v = jax.random.normal(ks[2], (rows, s, kv_heads, d))
    # gates from a few hundredths to nearly one
    log_g = jax.nn.log_sigmoid(2.0 * jax.random.normal(ks[3], (rows, s, kv_heads)))
    if gate is not None:
        log_g = jnp.full_like(log_g, gate)
    if state:
        keys = jax.random.normal(ks[4], (rows, kv_heads, 5, d))
        vals = jax.random.normal(ks[5], (rows, kv_heads, 5, d))
        s0 = jnp.einsum("rhjp,rhjv->rhpv", retention.phi(keys), vals)
        z0 = jnp.einsum("rhja,rhjb->rhab", keys, keys)
    else:
        s0 = jnp.zeros((rows, kv_heads, retention.phi_rows(d), d))
        z0 = jnp.zeros((rows, kv_heads, d, d))
    return s0, z0, q, k, v, log_g


def _close(a, b, tol=TOL):
    scale = max(float(jnp.max(jnp.abs(b))), 1.0)
    return float(jnp.max(jnp.abs(a - b))) <= tol * scale


def attention_form(q, k, v, log_g):
    """``A[t, j] = (q_t . k_j)^2 exp(sum_{s=j+1..t} log g_s)``, no feature
    map, no state: what benchmark/reference_brumby.py computes.  Returns
    ``(o, cond)``: the state form sums ``phi(q) . phi(k)`` over the
    monomials, terms of either sign whose absolute values sum to ``(sum_a
    |q_a k_a|)^2 <= |q|^2 |k|^2`` where the attention form squares ONE
    inner product, so a token whose query is nearly orthogonal to the keys
    it weighs is conditioned by ``cond = sum_j w_j |q|^2 |k_j|^2 / sum_j w_j
    (q . k_j)^2`` (the published normaliser's own property)."""
    group = q.shape[2] // k.shape[2]
    kk, vv = (jnp.repeat(a, group, axis=2) for a in (k, v))
    cs = jnp.repeat(jnp.cumsum(log_g, axis=1), group, axis=2)  # [R, S, H]
    n = q.shape[1]
    causal = jnp.tril(jnp.ones((n, n), jnp.bool_))[None, None]
    diff = jnp.moveaxis(cs, 2, 1)[..., :, None] - jnp.moveaxis(cs, 2, 1)[..., None, :]
    a = jnp.square(jnp.einsum("rthd,rjhd->rhtj", q, kk,
                              precision="highest"))
    a = jnp.where(causal, a * jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    den = jnp.sum(a, axis=-1)
    num = jnp.einsum("rhtj,rjhv->rthv", a, vv, precision="highest")
    norms = jnp.einsum("rth,rjh->rhtj", jnp.sum(q * q, -1), jnp.sum(kk * kk, -1))
    weight = jnp.where(causal, jnp.exp(jnp.where(causal, diff, 0.0)), 0.0)
    cond = jnp.sum(weight * norms, axis=-1) / jnp.where(den > 0, den, 1.0)
    return (num / jnp.moveaxis(jnp.where(den > 0, den, 1.0), 1, 2)[..., None],
            jnp.moveaxis(cond, 1, 2))


@pytest.mark.parametrize("d", [8, 16, 128])
def test_the_feature_map_squares_the_inner_product(d):
    u, w = jax.random.normal(jax.random.PRNGKey(d), (2, 7, d))
    got = jnp.sum(retention.phi(u) * retention.phi(w), axis=-1)
    want = jnp.square(jnp.sum(u * w, axis=-1))
    assert _close(got, want, 1e-5)
    # the layout: whole registers of 8 rows, the distinct monomials and a
    # twin of each off-diagonal pair INSIDE a diagonal block, no more
    nb = d // 8
    assert retention.phi_rows(d) == 64 * nb * (nb + 1) // 2
    assert retention.phi_rows(d) - rsu.distinct_monomials(d) == nb * 28
    a, b, f = rsu.phi_layout(d)
    assert _close(retention.phi(u), f * u[:, a] * u[:, b], 1e-6)
    assert (retention.phi_rows(128), rsu.distinct_monomials(128)) == (8704, 8256)


@pytest.mark.parametrize("gate", [None, 0.0, -20.0], ids=["mixed", "keep_all", "forget_all"])
def test_the_state_form_is_the_attention_form(gate):
    """From an empty state the recurrence IS the causal attention with
    squared scores and gated weights; a gate of one keeps every token, a
    gate of e^-20 leaves the last alone (o_t = v_t)."""
    s0, z0, q, k, v, log_g = _inputs(2, rows=2, s=23, gate=gate, state=False,
                                     lean=0.0)
    o, _, _ = retention.retention_recurrent(s0, z0, q, k, v, log_g)
    want, cond = attention_form(q, k, v, log_g)
    gap = jnp.max(jnp.abs(o - want), axis=-1)
    assert bool((gap <= 1e-6 * jnp.maximum(cond, 10.0)).all()), float(
        jnp.max(gap / jnp.maximum(cond, 10.0)))
    assert float(jnp.median(gap)) <= TOL
    if gate == -20.0:
        assert _close(want, jnp.repeat(v, 2, axis=2), 1e-3)


@pytest.mark.parametrize("chunk", [1, 4, 5, 16, 64])
@pytest.mark.parametrize("start", ["zero", "nonzero"])
def test_chunked_scan_is_the_token_by_token_recurrence(chunk, start):
    """A chunk boundary inside a sequence (37 tokens), a padded last
    chunk, a state some keys were summed into before."""
    args = _inputs(chunk, rows=3, s=37, state=start == "nonzero")
    want = retention.retention_recurrent(*args)
    got = jax.jit(lambda *a: retention.retention_scan(*a, chunk=chunk))(*args)
    for g, w in zip(got, want):
        assert _close(g, w)


def test_a_query_group_reads_its_kv_heads_state():
    """Five query heads on one kv head: head ``i`` reads the state of kv
    head ``i // 5`` and no other — the same queries against the other kv
    head's keys give another answer."""
    s0, z0, q, k, v, log_g = _inputs(9, rows=1, s=6, heads=10, kv_heads=2)
    o, s, z = retention.retention_recurrent(s0, z0, q, k, v, log_g)
    for head in range(10):
        one = retention.retention_recurrent(
            s0[:, head // 5:head // 5 + 1], z0[:, head // 5:head // 5 + 1],
            q[:, :, head:head + 1], k[:, :, head // 5:head // 5 + 1],
            v[:, :, head // 5:head // 5 + 1], log_g[:, :, head // 5:head // 5 + 1])
        assert _close(o[:, :, head], one[0][:, :, 0]), head
    assert s.shape == s0.shape and z.shape == z0.shape
    swapped, _, _ = retention.retention_recurrent(
        s0, z0, q, k[:, :, ::-1], v[:, :, ::-1], log_g[:, :, ::-1])
    assert not _close(swapped, o, 1e-2)


def _tick(form, state, layer, tokens, segments, rows, fresh, chunk=4):
    """One packed tick: ``segments`` = ``[(row, n tokens)]`` laid end to
    end on the packed axis, dead lanes after them."""
    q, k, v, log_g = tokens
    t = q.shape[0]
    tok_row = np.zeros((t,), np.int32)
    start = np.zeros((rows,), np.int32)
    count = np.zeros((rows,), np.int32)
    at = 0
    for row, n in segments:
        tok_row[at:at + n] = row
        start[row], count[row] = at, n
        at += n
    live = np.arange(t) < at
    k = jnp.where(live[:, None, None], k, 0.0)
    log_g = jnp.where(live[:, None], log_g, 0.0)
    return jax.jit(lambda s, z, *a: retention.retention_packed(
        s, z, jnp.int32(layer), *a, tok_row=jnp.asarray(tok_row),
        start=jnp.asarray(start), count=jnp.asarray(count),
        fresh=jnp.asarray(fresh), chunk=chunk,
        interpret=True if form == "pallas" else None))(*state, q, k, v, log_g)


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
    """``retention_packed`` under ``ssm_packed``'s contract: a row's tokens
    continue ITS state (zero where the row is fresh: the slot's old state is
    not read), a row with no token keeps its bits, the other layer's rows
    are never touched."""
    rows, d = 5, 16
    n_tok = sum(n for _, n in segments)
    s0, z0, q, k, v, log_g = _inputs(11, rows=rows, s=n_tok + 3, d=d)
    tokens = tuple(a[0] for a in (q, k, v, log_g))  # [T, ..]
    state = (jnp.stack([s0 * 0.5, s0]), jnp.stack([z0 * 0.5, z0]))
    o, s, z = _tick(form, state, 1, tokens, segments, rows, fresh)
    at = 0
    touched = set()
    for row, n in segments:
        seg = tuple(a[None, at:at + n] for a in tokens)
        begin = tuple((jnp.zeros_like(a[1, row]) if fresh[row]
                       else a[1, row])[None] for a in state)
        want_o, want_s, want_z = retention.retention_recurrent(*begin, *seg)
        assert _close(o[at:at + n], want_o[0]), (row, n)
        assert _close(s[1, row], want_s[0]) and _close(z[1, row], want_z[0])
        touched.add(row)
        at += n
    for row in set(range(rows)) - touched:
        assert bool((s[1, row] == state[0][1, row]).all()), row
        assert bool((z[1, row] == state[1][1, row]).all()), row
    assert bool((s[0] == state[0][0]).all() and (z[0] == state[1][0]).all())


def test_a_state_kept_in_bf16_fails_the_tolerance():
    """Forty decode ticks of one row under gates near one: the float32
    state stays within the tolerance of the recurrence, the same ticks over
    a bf16 state do not (what the tolerance is for)."""
    s0, z0, q, k, v, log_g = _inputs(5, rows=1, s=40, state=False, gate=-0.02)
    want_o, _, _ = retention.retention_recurrent(s0, z0, q, k, v, log_g)

    one = jnp.ones((1,), jnp.int32)
    tick = jax.jit(lambda s, z, fresh, *tok: retention.retention_packed(
        s, z, jnp.int32(0), *tok, tok_row=one * 0, start=one * 0, count=one,
        fresh=fresh, chunk=4))

    def run(dtype):
        state = (jnp.zeros((1,) + s0.shape, dtype), jnp.zeros((1,) + z0.shape, dtype))
        outs = []
        for i in range(40):
            tok = tuple(a[0, i:i + 1] for a in (q, k, v, log_g))
            o, *state = tick(*state, jnp.asarray([i == 0]), *tok)
            outs.append(o[0])
        return jnp.stack(outs)

    assert _close(run(jnp.float32), want_o[0])
    assert float(jnp.max(jnp.abs(run(jnp.bfloat16) - want_o[0]))) > 50 * TOL


@pytest.mark.parametrize("shape", [support.RETENTION_PROBE_SHAPE], ids=["probe"])
def test_state_update_kernel_in_the_interpreter_is_its_twin(shape):
    """The on-chip matrix's case for the kernel (rows of no token, rows that
    start from nothing), run in the interpreter: the output and the layer's
    state against the twin, and the rows of no token bit for bit."""
    make_args, run, reference = support.kernel_case(
        "retention_state_update", shape, interpret=True)
    args = make_args()
    got, want = np.asarray(run(*args)), np.asarray(reference(*args))
    assert np.isfinite(got).all()
    assert np.abs(got - want).max() <= 1e-4 * max(np.abs(want).max(), 1.0)
    s, count = np.asarray(args[0]), np.asarray(args[-2])
    layers, rows, nh, d = shape.retention_state
    new = got[-rows * nh * rsu.phi_rows(d) * d:].reshape(rows, nh, -1, d)
    idle = count == 0
    assert idle.any() and (new[idle] == s[-1][idle]).all()
    assert rsu.takes(8, 8704, 128) and not rsu.takes(2, 64, 8)
