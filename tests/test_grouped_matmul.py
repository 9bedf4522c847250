"""The routed experts' grouped matmul (ops/pallas/grouped_matmul.py, in the
interpreter here) against ``lax.ragged_dot`` on the same sorted rows and
counts; the layout it multiplies in; and ``ops/moe.moe_dropless`` through
it against the same call on ``ragged_dot`` — the rounding points are the
layer's, whatever implements it."""

import functools
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from llm_np_cp_tpu.ops import moe
from llm_np_cp_tpu.ops.pallas import grouped_matmul as gmm
from llm_np_cp_tpu.ops.pallas import support

# sizes of the groups, sorted rows in all (those past the groups are
# nobody's), row tile
# (most of one shape, so that they share a compile)
CASES = {
    "groups-of-0-1-15-16-17": ((0, 1, 15, 16, 17), 64, 16),
    "empty-first-and-last-rows-of-no-group": ((0, 5, 0, 20, 0), 64, 16),
    "one-row": ((0, 0, 0, 1, 0), 64, 16),
    "every-row-grouped": ((16, 16, 0, 31, 1), 64, 16),
    "one-expert": ((9,), 12, 16),
    "tile-32-groups-of-33-0-31-32": ((33, 0, 31, 32), 100, 32),
}
F32 = jnp.float32
_align = gmm.align_groups
_ragged_dot = jax.jit(functools.partial(gmm.ragged_dot, interpret=True),
                      static_argnames=("tm",))


def _operands(sizes, rows, k, n, dtype, weights=1, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 1 + weights)
    x = jax.random.normal(keys[0], (rows, k), F32).astype(dtype)
    ws = [(jax.random.normal(key, (len(sizes), k, n), F32)
           * k ** -0.5).astype(dtype) for key in keys[1:]]
    return x, ws, jnp.asarray(sizes, jnp.int32)


# ----------------------------------------------------------------------
# the layout
# ----------------------------------------------------------------------

@pytest.mark.parametrize("rows,experts,want", [
    (64 * 4, 32, 16),      # LFM2's steady tick: 8 rows an expert
    (320 * 4, 32, 64),     # its widest program: 40
    (96 * 6, 128, 16),     # Kanana-2's decode tick: 4.5
    (352 * 6, 128, 32),    # its widest program: 16.5
    (3584 * 4, 32, 128),   # the benchmark's check, LFM2
    (8704 * 6, 128, 128),  # ... and Kanana-2
    (4, 8, 16),
])
def test_row_tile_by_hand(rows, experts, want):
    assert gmm.row_tile(rows, experts) == want


@pytest.mark.parametrize("rows,held,tm,want", [
    (256, 32, 16, 46), (1280, 32, 64, 51), (52224, 16, 128, 423), (1, 3, 16, 2),
])
def test_tile_count_holds_every_split_of_the_rows(rows, held, tm, want):
    assert gmm.tile_count(rows, held, tm) == want
    # the worst split: as many groups as can be of one row past a tile
    sizes = np.zeros(held, np.int64)
    left = rows
    for e in range(held):
        sizes[e] = min(left, tm + 1 if left > tm else left)
        left -= sizes[e]
    sizes[-1] += left
    assert -(-sizes // tm).sum() <= want


@pytest.mark.parametrize("sizes,rows,tm", [
    *CASES.values(), ((0, 0, 0), 8, 16), ((0, 17, 1, 32, 0, 9), 70, 16),
], ids=[*CASES, "no-row-at-all", "support-case"])
def test_groups_are_laid_out_in_whole_tiles_of_one_expert(sizes, rows, tm):
    layout = _align(jnp.asarray(sizes, jnp.int32), rows, tm)
    tile_expert, live, src, dest = (np.asarray(a) for a in layout)
    tiles = gmm.tile_count(rows, len(sizes), tm)
    assert tile_expert.shape == (tiles,) and src.shape == (tiles * tm,)
    per = [-(-s // tm) for s in sizes]
    assert live.tolist() == [sum(per)]
    owners = [e for e, n in enumerate(per) for _ in range(n)]
    assert tile_expert[:live[0]].tolist() == owners
    # (a tile past the live ones is no step of the kernel's grid: any
    # expert that exists will do)
    assert set(tile_expert[live[0]:]) <= {len(sizes) - 1}
    grouped = sum(sizes)
    expert_of_row = np.repeat(np.arange(len(sizes)), sizes)
    # every sorted row of a group has a laid row of its own, in a tile of
    # its expert, and the laid row reads it
    assert len(set(dest[:grouped])) == grouped
    assert (src[dest[:grouped]] == np.arange(grouped)).all()
    assert (tile_expert[dest[:grouped] // tm] == expert_of_row).all()
    assert (dest[grouped:] == 0).all() and (dest < live[0] * tm).all() | (grouped == 0)


# ----------------------------------------------------------------------
# the kernel against lax.ragged_dot
# ----------------------------------------------------------------------

@pytest.mark.parametrize("orientation", ["E-H-I", "E-I-H"])
@pytest.mark.parametrize("case", CASES)
def test_kernel_is_ragged_dot_on_the_same_rows(case, orientation):
    """bf16 operands, the float32 accumulator compared as float32."""
    sizes, rows, tm = CASES[case]
    k, n = (128, 256) if orientation == "E-H-I" else (256, 128)
    x, (w,), counts = _operands(sizes, rows, k, n, jnp.bfloat16)
    got = _ragged_dot(x, w, counts, tm=tm)
    want = lax.ragged_dot(x, w, counts, preferred_element_type=F32)
    assert got.dtype == F32 and got.shape == (rows, n)
    grouped = sum(sizes)
    np.testing.assert_allclose(
        np.asarray(got[:grouped]), np.asarray(want[:grouped]),
        rtol=1e-5, atol=1e-5)


def test_tiles_past_the_live_ones_are_no_steps_of_the_grid():
    sizes, rows, tm = (3, 0, 18), 90, 16
    x, (w,), counts = _operands(sizes, rows, 128, 128, jnp.bfloat16)
    layout = _align(counts, rows, tm)
    assert int(layout.live[0]) == 3 and layout.tile_expert.shape == (8,)
    # NaN rows past the live tiles: read by no tile, so none comes back
    # among the live rows
    laid = jnp.where(jnp.arange(8 * tm)[:, None] < 3 * tm, x[layout.src], jnp.nan)
    out = gmm.grouped_matmul(laid, (w,), layout.tile_expert, layout.live,
                             tm=tm, out_dtype=F32, interpret=True)
    assert np.isfinite(np.asarray(out[:3 * tm])).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_gate_and_up_in_one_pass_round_where_the_layer_rounds(dtype):
    """``act(gate) * up`` of one call: each product accumulated in
    float32 and rounded to the rows' dtype BEFORE act and the product,
    the result in the rows' dtype — ``moe_dropless``'s own expression
    over ``lax.ragged_dot``."""
    sizes, rows, tm = (0, 17, 1, 32, 0, 9), 70, 16
    x, (w1, w3), counts = _operands(sizes, rows, 128, 256, dtype, weights=2)
    layout = _align(counts, rows, tm)
    got = gmm.grouped_matmul(
        x[layout.src], (w1, w3), layout.tile_expert, layout.live, tm=tm,
        act=jax.nn.silu, interpret=True)[layout.dest]
    assert got.dtype == dtype
    gate, up = (lax.ragged_dot(x, w, counts, preferred_element_type=F32)
                for w in (w1, w3))
    want = (jax.nn.silu(gate.astype(dtype)) * up.astype(dtype)).astype(dtype)
    grouped = sum(sizes)
    # float32: the dots' orders differ.  bf16: an ulp where a product
    # lands on the other side of a rounding, or act's two roundings in
    # bf16 against the kernel's one
    tol = 1e-5 if dtype == F32 else float(jnp.finfo(dtype).eps)
    np.testing.assert_allclose(
        np.asarray(got[:grouped], np.float32),
        np.asarray(want[:grouped], np.float32), rtol=2 * tol, atol=tol)


@pytest.mark.parametrize("fault", ["none", "rows-no-whole-tiles", "another-dtype",
                                   "half-a-lane", "two-weights-no-act"])
def test_kernel_refuses_operands_it_does_not_take(fault):
    x, (w, w3), counts = _operands((4, 4), 32, 128, 128, jnp.bfloat16, weights=2)
    layout = _align(counts, 32, 16)
    x, ws = x[layout.src], (w,)
    assert x.shape == (3 * 16, 128)
    if fault == "rows-no-whole-tiles":
        x = x[:40]
    elif fault == "another-dtype":
        ws = (w.astype(F32),)
    elif fault == "half-a-lane":
        x, ws = x[:, :64], (w[:, :64],)
    elif fault == "two-weights-no-act":
        ws = (w, w3)

    def call():
        return gmm.grouped_matmul(x, ws, layout.tile_expert, layout.live,
                                  tm=16, interpret=True)

    if fault == "none":
        assert call().shape == (48, 128)
    else:
        with pytest.raises(ValueError):
            call()


def test_column_block_by_hand():
    # LFM2: gate and up whole (2 x 2 x 7.3 MB), down whole; Kanana-2 whole
    assert gmm.column_block(2048, 1792, 2, 2) == 1792
    assert gmm.column_block(1792, 2048, 1, 2) == 2048
    assert gmm.column_block(2048, 768, 2, 2) == 768
    # float32 experts of LFM2's widths: two weights' buffers pass 48 MiB
    assert gmm.column_block(2048, 1792, 2, 4) == 896
    assert gmm.column_block(8192, 28672, 2, 2) == 512


# ----------------------------------------------------------------------
# the layer through the kernel
# ----------------------------------------------------------------------

def _layer(dtype, seed=0):
    t, h, inter, e, held = 24, 128, 256, 8, 4
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(keys[0], (t, h), F32)
    router = jax.random.normal(keys[1], (h, e), F32)
    w1, w3 = ((jax.random.normal(k, (held, h, inter), F32) * h ** -0.5).astype(dtype)
              for k in keys[2:4])
    w2 = (jax.random.normal(keys[4], (held, inter, h), F32) * inter ** -0.5).astype(dtype)
    return (x, router, None, w1, w3, w2), dict(
        act=jax.nn.silu, top_k=2, live=jnp.arange(t) < t - 5, first_expert=2,
        out_dtype=F32)


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5), (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_moe_dropless_through_the_kernel_is_the_layer_on_ragged_dot(dtype, atol):
    """Dead tokens and a window of the experts (4 held of 8, from the
    third on).  float32: the existing layer tests' tolerance (the dots'
    orders differ); bf16: two ulps of a rounded ``hidden`` of magnitude 1
    through ``w2`` — the float32 combine adds none."""
    args, kw = _layer(dtype)
    out, chosen, load = jax.jit(lambda *a: moe.moe_dropless(*a, **kw))(*args)
    k_out, k_chosen, k_load = jax.jit(
        lambda *a: moe.moe_dropless(*a, interpret=True, **kw))(*args)
    assert np.array_equal(np.asarray(chosen), np.asarray(k_chosen))
    assert np.array_equal(np.asarray(load), np.asarray(k_load))
    assert 0 < int(load.sum()) < 19 * 2 and k_out.dtype == F32
    np.testing.assert_allclose(np.asarray(k_out), np.asarray(out), atol=atol)
    assert float(jnp.abs(k_out[19:]).max()) == 0.0
    assert float(jnp.abs(k_out[:19]).max()) > 0.1


def test_moe_dropless_through_the_kernel_with_no_pair_held():
    args, kw = _layer(jnp.bfloat16)
    kw = dict(kw, live=jnp.zeros(24, bool))
    out, _, load = jax.jit(
        lambda *a: moe.moe_dropless(*a, interpret=True, **kw))(*args)
    assert int(load.sum()) == 0 and float(jnp.abs(out).max()) == 0.0


@pytest.mark.parametrize("what,tile", [
    ("float-whole-lanes", 16), ("on-the-cpu", None), ("int8", None),
    ("quant-tree", None), ("half-a-lane-wide", None), ("probe-refused", None),
])
def test_the_kernel_is_chosen_from_backend_dtype_and_shape(what, tile, monkeypatch):
    w = jax.ShapeDtypeStruct((4, 128, 256), jnp.bfloat16)
    backend = "tpu"
    if what == "on-the-cpu":
        backend = "cpu"
    elif what == "int8":
        w = jax.ShapeDtypeStruct((4, 128, 256), jnp.int8)
    elif what == "quant-tree":
        w = {"q": w, "s": w}
    elif what == "half-a-lane-wide":
        w = jax.ShapeDtypeStruct((4, 128, 192), jnp.bfloat16)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(
        support, "kernel_error",
        lambda kernel: "refused" if what == "probe-refused" else None)
    monkeypatch.setattr(support, "_WARNED", set())
    assert moe.expert_row_tile(w, 48, 8) == tile
    if what == "probe-refused":  # one warning a process, naming the fallback
        assert support._WARNED == {"grouped_matmul"}
    # asked for on purpose (a test's interpreter): backend and probe unasked
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    forced = moe.expert_row_tile(w, 48, 8, interpret=True)
    assert forced == (16 if what in ("float-whole-lanes", "on-the-cpu",
                                     "probe-refused") else None)


def test_kernel_is_a_probe_case_against_three_ragged_dots():
    """What ``chip_smoke.py --kernels`` and the start-up probe run on the
    chip: both calls against the layer's expression over ragged_dot."""
    assert "grouped_matmul" in support.KERNELS
    make_args, run, reference = support.kernel_case(
        "grouped_matmul", support.PROBE_SHAPE, interpret=True)
    args = make_args()
    got, want = jax.jit(run)(*args), jax.jit(reference)(*args)
    assert got.shape == (70, support.PROBE_SHAPE.hidden) and got.dtype == F32
    assert float(jnp.abs(want[:59]).max()) > 0.1
    assert float(jnp.abs(got - want).max()) <= support.KERNEL_TOLERANCE
    assert float(jnp.abs(got[59:]).max()) == 0.0


def test_the_probe_runs_where_a_traced_caller_asks(monkeypatch):
    """``moe_dropless`` asks for the kernel's verdict while the step is
    being traced; the probe's own compile and run must not land in that
    trace (it would read as a refusal and every expert layer would fall
    back in silence): it runs in a thread of its own."""
    monkeypatch.setattr(support, "kernel_case", functools.partial(
        support.kernel_case, interpret=True))  # no Mosaic on a CPU
    verdicts = []

    def traced(x):
        verdicts.append(support._probe.__wrapped__("grouped_matmul", "tpu"))
        # ... which in the caller's own thread is what fails
        verdicts.append(support._compile_and_run("grouped_matmul"))
        return x

    jax.jit(traced)(jnp.ones(3))
    assert verdicts[0] is None and "Tracer" in verdicts[1]


def test_engine_counts_row_tiles_at_the_tile_its_program_multiplies_in(monkeypatch):
    from llm_np_cp_tpu.serve.engine import ServeEngine

    w1 = jax.ShapeDtypeStruct((1, 32, 2048, 1792), jnp.bfloat16)
    def stub():
        return types.SimpleNamespace(
            params={"layers": [{"mlp_gate": None}, {"w1": w1}]},
            config=types.SimpleNamespace(num_experts_per_tok=4, num_experts=32),
            _expert_row_tiles={})

    assert ServeEngine._expert_row_tile(stub(), 64) is None  # ragged_dot here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(support, "kernel_error", lambda kernel: None)
    engine = stub()
    assert ServeEngine._expert_row_tile(engine, 64) == 16
    assert ServeEngine._expert_row_tile(engine, 320) == 64
    assert engine._expert_row_tiles == {64: 16, 320: 64}
