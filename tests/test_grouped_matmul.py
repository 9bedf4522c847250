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
    tile_expert, live, tile_rows, src, dest = (np.asarray(a) for a in layout)
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
    # a tile's real rows are its first ``tile_rows``: the group's whole
    # tiles and then what is left of it, none past the live tiles; a row
    # that pads reads sorted row 0
    want = [min(tm, n - i * tm) for n in sizes for i in range(-(-n // tm))]
    assert tile_rows.tolist() == want + [0] * (tiles - len(want))
    real = np.arange(tm)[None, :] < tile_rows[:, None]
    assert sorted(src.reshape(tiles, tm)[real]) == list(range(grouped))
    assert (src.reshape(tiles, tm)[~real] == 0).all()


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


def _laid(sizes, rows, tm, tokens, seed=0):
    """A layout with a token and a weight for every laid row, as
    ``moe_dropless`` makes them: the sorted rows' tokens drawn at random
    (the last three are nobody's), weight zero where a row pads."""
    layout = _align(jnp.asarray(sizes, jnp.int32), rows, tm)
    keys = jax.random.split(jax.random.PRNGKey(seed), 2)
    pads = (jnp.arange(tm)[None, :] >= layout.rows[:, None]).reshape(-1)
    token = jnp.where(
        pads, 0, jax.random.randint(keys[0], pads.shape, 0, tokens - 3))
    weight = jnp.where(pads, 0.0, jax.random.uniform(keys[1], pads.shape, F32))
    return layout, pads, token, weight


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", CASES)
def test_a_tile_gathers_its_tokens_rows_itself(case, dtype):
    """``gather``: the tokens' float32 rows held whole, a tile's real rows
    cut out by ``token`` and rounded to the weights' dtype — the result
    of XLA's ``x.astype(dtype)[token]`` through the same call, to the
    bit, on every real row."""
    sizes, rows, tm = CASES[case]
    tokens = 24
    layout, pads, token, _ = _laid(sizes, rows, tm, tokens)
    x, (w,), _ = _operands(sizes, tokens, 128, 256, F32)
    w = w.astype(dtype)
    tiles = dict(tile_expert=layout.tile_expert, live=layout.live, tm=tm,
                 out_dtype=F32, interpret=True)
    got = gmm.grouped_matmul(x, (w,), gather=True, tile_rows=layout.rows,
                             token=token, **tiles)
    want = gmm.grouped_matmul(x.astype(dtype)[token], (w,), **tiles)
    real = ~np.asarray(pads)
    assert got.shape == want.shape and real.sum() == sum(sizes)
    assert np.array_equal(np.asarray(got)[real], np.asarray(want)[real])


@pytest.mark.parametrize("tokens,blocks", [(24, 1), (21, 1), (16, 2)], ids=[
    "whole-sublanes", "and-5", "two-column-blocks"])
@pytest.mark.parametrize("case", CASES)
def test_a_tile_adds_its_weighted_rows_to_their_tokens(
        case, tokens, blocks, monkeypatch):
    """``weight``: the result by token, in float32 — the laid rows of
    the plain call weighted and summed by token over the REAL rows.  A row
    that pads, and every row past the live tiles, is NaN here: none is
    added, and a token no real row names reads exact zeros."""
    sizes, rows, tm = CASES[case]
    if blocks == 2:  # room for 128 of the 256 columns: the block is cut
        monkeypatch.setattr(
            gmm, "_BLOCK_VMEM_BYTES", 2 * 128 * 128 * 2 + 2 * tokens * 128 * 4)
    assert gmm.column_block(128, 256, 1, 2, kept_rows=tokens) == 256 // blocks
    layout, pads, token, weight = _laid(sizes, rows, tm, tokens, seed=1)
    x, (w,), _ = _operands(sizes, rows, 128, 256, jnp.bfloat16)
    laid = jnp.where(pads[:, None], jnp.nan, x[layout.src])
    tiles = dict(tile_expert=layout.tile_expert, live=layout.live, tm=tm,
                 interpret=True)
    got = gmm.grouped_matmul(laid, (w,), tile_rows=layout.rows, token=token,
                             weight=weight, tokens=tokens, **tiles)
    ys = gmm.grouped_matmul(x[layout.src], (w,), out_dtype=F32, **tiles)
    want = np.zeros((tokens, 256), np.float64)
    real = ~np.asarray(pads)
    np.add.at(want, np.asarray(token)[real],
              (np.asarray(ys, np.float64) * np.asarray(weight)[:, None])[real])
    assert got.shape == (tokens, 256) and got.dtype == F32
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-6, atol=1e-6)
    named = np.zeros(tokens, bool)
    named[np.asarray(token)[real]] = True
    assert (np.asarray(got)[~named] == 0).all() and not named[-3:].any()


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


# what the rows' way through the calls is held to: the same layer on the
# same kernel with XLA's gather of every laid row, un-sort and masked sum
# over ALL ``T * k`` pairs around it (``expert_rows_in_call`` False)
LAYER_CASES = {
    # live tokens of 24, experts held of 8 from ``first_expert``, top k
    "dead-lanes-first-expert-2": dict(live=19, held=4, first=2, top_k=2),
    "nothing-held": dict(live=0, held=4, first=2, top_k=2),
    "every-pair-held": dict(live=24, held=8, first=0, top_k=4),
    "one-expert-held-of-eight": dict(live=24, held=1, first=7, top_k=3),
    "rows-that-do-not-fit-fall-back": dict(
        live=19, held=4, first=2, top_k=2, scalars=16),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("case", LAYER_CASES)
def test_rows_moved_in_the_calls_are_the_all_pairs_combine(case, dtype, monkeypatch):
    """float32, to rounding: only the ORDER in which a token's terms are
    added differs (by expert, not by choice).  Dead lanes and tokens none
    of whose experts is held read exact zeros; the rows of another
    holder's pairs are not moved at all."""
    spec = LAYER_CASES[case]
    (x, router, bias, w1, w3, w2), kw = _layer(dtype)
    first, held = spec["first"], spec["held"]
    if held > 4:
        w1, w3, w2 = (jnp.concatenate([w, w * 0.5]) for w in (w1, w3, w2))
    w1, w3, w2 = w1[:held], w3[:held], w2[:held]
    kw = dict(kw, live=jnp.arange(24) < spec["live"], first_expert=first,
              top_k=spec["top_k"], interpret=True)
    if "scalars" in spec:
        monkeypatch.setattr(gmm, "_SCALAR_ROWS", spec["scalars"])
    layer = moe.moe_dropless.__wrapped__
    in_call = moe.expert_rows_in_call(w1, 24, spec["top_k"], 16)
    assert in_call == ("scalars" not in spec)
    got, chosen, load = jax.jit(lambda *a: layer(*a, **kw))(x, router, bias, w1, w3, w2)
    text = jax.jit(lambda *a: layer(*a, **kw)).lower(
        x, router, bias, w1, w3, w2).as_text()
    monkeypatch.setattr(moe, "expert_rows_in_call", lambda *a: False)
    want, w_chosen, w_load = jax.jit(lambda *a: layer(*a, **kw))(
        x, router, bias, w1, w3, w2)
    assert np.array_equal(chosen, w_chosen) and np.array_equal(load, w_load)
    here = (np.asarray(chosen) >= first) & (np.asarray(chosen) < first + held)
    here &= (np.arange(24) < spec["live"])[:, None]
    assert int(load.sum()) == here.sum()
    scale = max(float(jnp.abs(want).max()), 1e-6)
    assert float(jnp.abs(got - want).max()) <= 4e-7 * scale
    assert (np.asarray(got)[~here.any(-1)] == 0).all()
    if case == "dead-lanes-first-expert-2":
        # a token with two held experts, one with none, a group padded
        assert (here.sum(-1) == 2).any() and (~here.any(-1))[:19].any()
        assert (np.asarray(load) % 16 != 0).any()
    if case == "every-pair-held":
        assert here.all()
    # one sort of the pairs where the calls move the rows, two around XLA's
    assert text.count("stablehlo.sort") == (1 if in_call else 2)


@pytest.mark.parametrize("tokens,top_k,experts,held,h,inter,fits", [
    (352, 6, 128, 16, 2048, 768, True),     # Kanana-2's widest program
    (8, 6, 128, 16, 2048, 768, True),       # ... its narrowest
    (576, 8, 256, 16, 4096, 2048, True),    # MiMo-V2's widest
    (320, 4, 32, 32, 2048, 1792, True),     # LFM2's widest
    (64, 8, 512, 128, 2560, 768, True),     # Ling-3.0's steady tick
    (3584, 4, 32, 32, 2048, 1792, False),   # the benchmark's check, LFM2
    (8704, 6, 128, 16, 2048, 768, False),   # ... Kanana-2
    (8192, 8, 256, 16, 4096, 2048, False),  # ... MiMo-V2, two chunks' worth
])
def test_the_rows_are_moved_in_the_calls_where_they_fit(
        tokens, top_k, experts, held, h, inter, fits):
    w = jax.ShapeDtypeStruct((held, h, inter), jnp.bfloat16)
    tm = gmm.row_tile(tokens * top_k, experts)
    assert moe.expert_rows_in_call(w, tokens, top_k, tm) is fits
    assert moe.expert_rows_in_call(w, tokens, top_k, None) is False  # ragged_dot


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
    # laid rows in and out (70 sorted rows), then the 40 tokens' sums
    assert got.shape == (70 + 40, support.PROBE_SHAPE.hidden) and got.dtype == F32
    assert float(jnp.abs(want[:59]).max()) > 0.1
    assert float(jnp.abs(want[70:109]).max()) > 0.1
    assert float(jnp.abs(got - want).max()) <= support.KERNEL_TOLERANCE
    assert float(jnp.abs(got[59:70]).max()) == 0.0
    assert float(jnp.abs(got[109]).max()) == 0.0  # a token of no row


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
        engine = types.SimpleNamespace(
            params={"layers": [{"mlp_gate": None}, {"w1": w1}]},
            config=types.SimpleNamespace(num_experts_per_tok=4, num_experts=32),
            _expert_row_tiles={})
        engine._expert_weights = functools.partial(
            ServeEngine._expert_weights, engine)
        return engine

    assert ServeEngine._expert_row_tile(stub(), 64) is None  # ragged_dot here
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(support, "kernel_error", lambda kernel: None)
    engine = stub()
    assert ServeEngine._expert_row_tile(engine, 64) == 16
    assert ServeEngine._expert_row_tile(engine, 320) == 64
    assert engine._expert_row_tiles == {64: 16, 320: 64}
    # the tick's ``expert_rows_impl`` is ``moe_dropless``'s own question
    assert moe.expert_rows_in_call(engine._expert_weights(), 64, 4, 16)
    assert not moe.expert_rows_in_call(engine._expert_weights(), 64, 4, None)
