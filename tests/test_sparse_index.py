"""A sparse-attention indexer's operations (ops/sparse_index.py, its Pallas
forms ops/pallas/sparse_index.py in interpret mode): index scores and the
EXACT selection against NumPy, the tie rule, ``topk >= context`` as the
identity, and the three kernels against their XLA twins over one mixed tick."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.ops import sparse_index
from llm_np_cp_tpu.ops.pallas import sparse_index as kernels
from llm_np_cp_tpu.ops.pallas import support
from llm_np_cp_tpu.ops.pallas.latent_attention import latent_pages_per_step


def _np_select(scores, visible, k):
    """Each row's ``min(k, visible)`` visible positions of largest score, a
    stable sort settling ties for the lower position."""
    out = np.zeros(scores.shape, bool)
    for r in range(scores.shape[0]):
        pos = np.flatnonzero(visible[r])
        order = pos[np.argsort(-scores[r, pos], kind="stable")]
        out[r, order[:k]] = True
    return out


def test_index_scores_are_the_weighted_relu_of_the_head_products():
    rng = np.random.default_rng(0)
    q = rng.standard_normal((5, 3, 8)).astype(np.float32)
    w = rng.standard_normal((5, 3)).astype(np.float32)
    k = rng.standard_normal((11, 8)).astype(np.float32)
    want = np.einsum("th,ths->ts", w, np.maximum(np.einsum("thd,sd->ths", q, k), 0))
    with jax.default_matmul_precision("highest"):
        got = sparse_index.index_scores(jnp.asarray(q), jnp.asarray(w), jnp.asarray(k))
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("k", [1, 7, 16, 40, 64])
def test_the_selection_is_the_exact_top_k_of_what_is_visible(k):
    rng = np.random.default_rng(k)
    scores = rng.standard_normal((9, 40)).astype(np.float32) * 3
    scores[0] = np.abs(scores[0])          # every key positive
    scores[1] = -np.abs(scores[1])         # every key negative
    visible = np.arange(40)[None, :] <= np.array([39, 39, 5, 0, 20, 39, 12, 30, 39])[:, None]
    visible[8, :3] = False                 # a left pad
    got = np.asarray(sparse_index.select_topk(
        jnp.asarray(scores), jnp.asarray(visible), k))
    assert np.array_equal(got, _np_select(scores, visible, k))
    assert np.array_equal(got.sum(1), np.minimum(visible.sum(1), k))
    if k >= 40:  # topk >= context: the identity on what is visible
        assert np.array_equal(got, visible)


def test_ties_go_to_the_lower_position_and_the_two_zeros_are_one_score():
    scores = np.array([
        [1.0, 2.0, 1.0, 1.0, 3.0, 1.0, 0.5, 1.0],
        [0.0, -0.0, 0.0, -0.0, -1.0, 0.0, -0.0, 5.0],
        [7.0] * 8,
    ], np.float32)
    visible = np.ones((3, 8), bool)
    got = np.asarray(sparse_index.select_topk(
        jnp.asarray(scores), jnp.asarray(visible), 4))
    assert got.tolist() == [
        [True, True, True, False, True, False, False, False],
        [True, True, True, False, False, False, False, True],
        [True, True, True, True, False, False, False, False]]
    assert np.array_equal(got, _np_select(
        np.where(scores == 0, 0.0, scores), visible, 4))


def test_the_expanded_form_in_blocks_is_the_form_at_once():
    rng = jax.random.split(jax.random.PRNGKey(0), 6)
    b, s, h, dk, dv, ih, idim, topk = 2, 21, 3, 8, 5, 2, 8, 6
    q, k = (jax.random.normal(r, (b, s, h, dk)) for r in rng[:2])
    v = jax.random.normal(rng[2], (b, s, h, dv))
    qi = jax.random.normal(rng[3], (b, s, ih, idim))
    wi = jax.random.normal(rng[4], (b, s, ih))
    ki = jax.random.normal(rng[5], (b, s, idim))
    mask = jnp.tril(jnp.ones((s, s), bool))[None]
    with jax.default_matmul_precision("highest"):
        got = sparse_index.attend_selected_in_blocks(
            q, k, v, qi, wi, ki, mask, topk=topk, scale=0.3, block=8)
        sel = sparse_index.select_topk(
            sparse_index.index_scores(qi, wi, ki),
            jnp.broadcast_to(mask, (b, s, s)), topk)
        want = jnp.stack([sparse_index.sparse_latent_attention(
            q[i], k[i], v[i], sel[i], scale=0.3) for i in range(b)])
    assert np.array_equal(np.asarray(sel.sum(-1))[0], np.minimum(np.arange(s) + 1, topk))
    np.testing.assert_allclose(got, want, atol=1e-6)


# ----------------------------------------------------------------------
# the kernels (interpret mode) against their XLA twins
# ----------------------------------------------------------------------

TINY = dataclasses.replace(
    support.DSA_PROBE_SHAPE, name="tiny/dsa", heads=4, latent_rank=32,
    head_dim=8, index=(2, 16, 24))


def _tick(bs):
    """``kernel_case``'s mixed tick (a 2-tile chunk, a deep decode row behind
    a pad, a chunk across a block boundary, a dead tile) at toy widths."""
    make_args, run, reference = support.kernel_case(
        "sparse_latent_attention", TINY, bs, interpret=True)
    return make_args(), run, reference


@pytest.mark.parametrize("bs", [8, 64])
def test_score_select_attend_matches_its_twin(bs):
    args, run, reference = _tick(bs)
    got = np.asarray(jax.jit(run)(*args), np.float32)
    want = np.asarray(jax.jit(reference)(*args), np.float32)
    assert np.isfinite(got).all() and np.abs(want).max() > 0.5
    assert np.abs(got - want).max() < 2e-2  # bf16 operands, f32 sums


@pytest.mark.parametrize("bs", [8, 64])
def test_the_score_and_select_kernels_alone_match_their_twins(bs):
    (q, pool, tables, tile_row, tile_qpos0, tile_qlen, tile_tok, pads,
     q_idx, w_idx, keys), _, _ = _tick(bs)
    # float32 operands: the two forms then differ by the order of a sum
    q_idx, keys = q_idx.astype(jnp.float32), keys.astype(jnp.float32)
    tiles = (tables, tile_row, tile_qpos0, tile_qlen, tile_tok, pads)
    pages = latent_pages_per_step(tables.shape[1], bs, pool.shape[-1], pool.dtype)
    topk = TINY.index[2]
    with jax.default_matmul_precision("highest"):
        scores = kernels.ragged_index_scores(
            q_idx, w_idx, keys, *tiles, pages=pages, interpret=True)
        sel = np.asarray(kernels.select_topk_tiles(
            scores, *tiles, topk=topk, block_s=bs, interpret=True)) > 0.5
        qlen = np.asarray(tile_qlen)
        tok_row = np.repeat(np.asarray(tile_row), qlen)
        tok_slot = np.concatenate([p0 + np.arange(n) for p0, n in
                                   zip(np.asarray(tile_qpos0), qlen)])
        want_scores = np.asarray(kernels.ragged_index_scores_xla(
            q_idx[:len(tok_row)], w_idx[:len(tok_row)], keys, tables,
            jnp.asarray(tok_row)))
    scores = np.asarray(scores)
    start = np.asarray(pads)[np.asarray(tile_row)] // bs * bs
    t = 0
    checked = selecting = 0
    for ti, n in enumerate(qlen):
        for lane in range(n):
            lo, hi = int(np.asarray(pads)[tok_row[t]]), int(tok_slot[t])
            cols = np.arange(lo, hi + 1) - start[ti]
            np.testing.assert_allclose(
                scores[ti, lane, cols], want_scores[t, lo:hi + 1],
                rtol=1e-4, atol=1e-4)
            # the kernel's selection is the exact top-k of ITS scores
            vis = np.zeros(scores.shape[-1], bool)
            vis[cols] = True
            want = _np_select(scores[ti, lane][None], vis[None], topk)[0]
            assert np.array_equal(sel[ti, lane], want)
            selecting += len(cols) > topk
            checked += 1
            t += 1
        assert not sel[ti, n:].any()  # a dead lane attends nothing
    assert checked == qlen.sum() and selecting >= 1


def test_a_tile_of_ties_keeps_the_lowest_positions_in_the_kernel():
    nt, s, bs = 2, 128, 8
    scores = jnp.ones((nt, 8, s), jnp.float32).at[1, :, 30].set(2.0)
    tables = jnp.zeros((1, s // bs), jnp.int32)
    zeros = jnp.zeros((nt,), jnp.int32)
    sel = np.asarray(kernels.select_topk_tiles(
        scores, tables, zeros, jnp.asarray([100, 40], jnp.int32),
        jnp.asarray([8, 3], jnp.int32), zeros, jnp.zeros((1,), jnp.int32),
        topk=16, block_s=bs, interpret=True)) > 0.5
    for lane in range(8):
        assert np.flatnonzero(sel[0, lane]).tolist() == list(range(16))
    for lane in range(3):  # the one larger score, then the 15 lowest ties
        assert np.flatnonzero(sel[1, lane]).tolist() == list(range(15)) + [30]
    assert not sel[1, 3:].any()


def test_the_engine_gate_names_the_three_kernels_as_one():
    assert support.ragged_kernel_name(False, latent=True, indexer=True) == (
        "sparse_latent_attention")
    assert "sparse_latent_attention" in support.KERNELS
    cases = [c for c in support.kernel_cases()
             if c[0] == "sparse_latent_attention"]
    assert {(c[1].name, c[2]) for c in cases} == {("probe/dsa", 64), ("probe/dsa", 128)}
    # the latent kernel's own case is not run at the indexer's shape
    assert not any(c[0] == "ragged_latent_attention" and c[1].index
                   for c in support.kernel_cases())
