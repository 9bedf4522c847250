"""Latent attention (MLA) in its two forms and its kernel: the absorbed
form over the cached rows ``[c' | k_pe]`` is the expanded form (every
head its own K and V through ``kv_b_proj``); ``ragged_latent_attention``
(Pallas, interpret mode here) is its XLA twin over the same pages, chunk
tiles and decode tiles alike; RoPE over interleaved pairs is the pairwise
definition."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.models import init_params
from llm_np_cp_tpu.models.transformer import latent_attention_block
from llm_np_cp_tpu.ops.pallas import support
from llm_np_cp_tpu.ops.pallas.decode_attention import RAGGED_Q_TILE as QT
from llm_np_cp_tpu.ops.pallas.latent_attention import (
    latent_pages_per_step,
    ragged_latent_attention,
    ragged_latent_attention_xla,
)
from llm_np_cp_tpu.ops.rope import apply_rope, deinterleave, rope_cos_sin

# ----------------------------------------------------------------------
# RoPE over interleaved pairs
# ----------------------------------------------------------------------


def _rope_pairs(x, positions, theta):
    """The pairwise definition: pair ``(2i, 2i+1)`` of a head rotates by
    ``pos * theta^(-2i/d)``; the result in the checkpoint's own order."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ang = positions[:, None].astype(np.float64) * inv[None, :]
    cos, sin = np.cos(ang)[:, None, :], np.sin(ang)[:, None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return np.stack([a * cos - b * sin, b * cos + a * sin], -1).reshape(x.shape)


def test_interleaved_rope_is_the_pairwise_rotation_moved_apart():
    cfg = tiny_config("deepseek_v3")
    rng = np.random.default_rng(0)
    x = rng.standard_normal((1, 11, 3, 8)).astype(np.float32)
    pos = np.arange(5, 16)
    cos, sin = rope_cos_sin(jnp.asarray(pos)[None], cfg)
    got = np.asarray(apply_rope(jnp.asarray(x), cos, sin, interleave=True))
    want = np.asarray(deinterleave(jnp.asarray(_rope_pairs(x[0], pos, cfg.rope_theta))))
    assert np.abs(got[0] - want).max() < 1e-5
    # the order both sides share does not reach a dot product
    y = rng.standard_normal((1, 11, 3, 8)).astype(np.float32)
    dots = np.einsum("shd,thd->hst", _rope_pairs(x[0], pos, cfg.rope_theta),
                     _rope_pairs(y[0], pos, cfg.rope_theta))
    got_y = np.asarray(apply_rope(jnp.asarray(y), cos, sin, interleave=True))
    assert np.abs(np.einsum("shd,thd->hst", got[0], got_y[0]) - dots).max() < 1e-4
    # ... and half-split rotation of the same values is another function
    half = np.asarray(apply_rope(jnp.asarray(x), cos, sin))
    assert np.abs(half[0] - want).max() > 0.1
    assert np.array_equal(deinterleave(jnp.arange(8)), [0, 2, 4, 6, 1, 3, 5, 7])


# ----------------------------------------------------------------------
# absorbed == expanded, on the same latent rows
# ----------------------------------------------------------------------


def _pool_of(rows, block_s, width):
    """Rows ``[S, W']`` as pages ``[NB, BS, width]`` behind a one-row
    block table (block 0 left as the scratch block a pool keeps)."""
    s = rows.shape[0]
    nb = -(-s // block_s)
    padded = jnp.zeros((nb * block_s, width), rows.dtype).at[:s, :rows.shape[1]].set(rows)
    pool = jnp.concatenate(
        [jnp.zeros((1, block_s, width), rows.dtype),
         padded.reshape(nb, block_s, width)])
    return pool, jnp.arange(1, nb + 1, dtype=jnp.int32)[None]


@pytest.mark.parametrize("impl", ["xla", "kernel"])
def test_absorbed_attention_over_the_cached_rows_is_expanded_attention(impl):
    cfg = tiny_config("deepseek_v3")
    params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    w = {k: v[0] for k, v in params["layers"][0].items()}
    s, block_s, width = 21, 8, 128
    x = jax.random.normal(jax.random.PRNGKey(1), (1, s, cfg.hidden_size), jnp.float32)
    pos = jnp.arange(s, dtype=jnp.int32)
    cos, sin = rope_cos_sin(pos[None], cfg)
    mask = (pos[None, :] <= pos[:, None])[None]
    seen = {}

    def attn_fn(q_lat, rows):
        seen["rows"] = rows
        pool, tables = _pool_of(rows[0], block_s, width)
        pads = jnp.zeros((1,), jnp.int32)
        if impl == "xla":
            out = ragged_latent_attention_xla(
                q_lat[0], pool, tables, jnp.zeros((s,), jnp.int32), pos,
                jnp.ones((s,), bool), pads, scale=cfg.attn_scale,
                rank=cfg.kv_lora_rank)
            return out[None]
        n_tiles = -(-s // QT)
        q = jnp.zeros((s,) + q_lat.shape[2:3] + (width,), q_lat.dtype)
        q = q.at[:, :, :q_lat.shape[-1]].set(q_lat[0])
        first = jnp.arange(n_tiles, dtype=jnp.int32) * QT
        out = ragged_latent_attention(
            q, pool, tables, jnp.zeros((n_tiles,), jnp.int32), first,
            jnp.minimum(s - first, QT), first, pads, scale=cfg.attn_scale,
            rank=cfg.kv_lora_rank, interpret=True)
        return out[None]

    with jax.default_matmul_precision("highest"):
        expanded, rows = latent_attention_block(
            w, x, config=cfg, cos=cos, sin=sin, mask=mask)
        absorbed, _ = latent_attention_block(
            w, x, config=cfg, cos=cos, sin=sin, attn_fn=attn_fn)
    # one row a token: [c' | k_pe], the same in both forms
    assert rows.shape == (1, s, cfg.kv_lora_rank + cfg.qk_rope_head_dim)
    assert np.array_equal(seen["rows"], rows)
    scale = float(jnp.abs(expanded - x).max())
    assert scale > 1e-3
    assert float(jnp.abs(absorbed - expanded).max()) < 2e-5 * max(scale, 1.0)


# ----------------------------------------------------------------------
# the kernel against its XLA twin
# ----------------------------------------------------------------------


def _mixed_tick(h, rank, rope, width, block_s, dtype, mb=12, nbp=40):
    """A tick of six tiles over three rows: a two-tile chunk with a ragged
    tail behind a pad, a decode row deep in its tenth block, a chunk
    across a block boundary, a dead tile."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    live = jnp.arange(width) < rank + rope
    q = jnp.where(live, jax.random.normal(k1, (6 * QT, h, width), jnp.float32), 0)
    pool = jnp.where(live, jax.random.normal(k2, (nbp, block_s, width), jnp.float32), 0)
    tables = jnp.asarray((np.arange(3 * mb) * 7 % 37 + 1).reshape(3, mb), jnp.int32)
    tile_row = jnp.asarray([0, 0, 1, 2, 2, 0], jnp.int32)
    tile_qpos0 = jnp.asarray(
        [5, 5 + QT, 9 * block_s + 7, block_s - 4, block_s - 4 + QT, 0], jnp.int32)
    tile_qlen = jnp.asarray([QT, QT - 3, 1, QT, QT, 0], jnp.int32)
    pads = jnp.asarray([5, block_s + 2, 0], jnp.int32)
    return (q.astype(dtype), pool.astype(dtype), tables, tile_row, tile_qpos0,
            tile_qlen, pads)


@pytest.mark.parametrize("h, rank, rope, width, block_s, dtype, tol", [
    (4, 32, 8, 128, 8, jnp.float32, 2e-5),
    # bf16 operands, float32 accumulation: ``p`` is rounded to bf16 at
    # another octave than the twin's (the AMLA rescale), a few bf16 ulps
    # of outputs of magnitude <= 1 (support.KERNEL_TOLERANCE)
    (4, 32, 8, 128, 16, jnp.bfloat16, support.KERNEL_TOLERANCE),
], ids=["f32", "bf16"])
def test_kernel_matches_its_xla_twin_on_a_mixed_tick(h, rank, rope, width,
                                                     block_s, dtype, tol):
    q, pool, tables, tile_row, tile_qpos0, tile_qlen, pads = _mixed_tick(
        h, rank, rope, width, block_s, dtype)
    scale = (rank + rope) ** -0.5
    # (the token axis laid out in whole tiles: a tile's first token is
    # its first lane, and the lanes past ``tile_qlen`` belong to no tile)
    got = ragged_latent_attention(
        q, pool, tables, tile_row, tile_qpos0, tile_qlen,
        jnp.arange(6, dtype=jnp.int32) * QT, pads, scale=scale, rank=rank,
        interpret=True)
    assert got.shape == (6 * QT, h, rank) and got.dtype == dtype
    lane = jnp.arange(q.shape[0]) % QT
    live = lane < jnp.repeat(tile_qlen, QT)
    want = ragged_latent_attention_xla(
        q[..., :rank + rope], pool, tables, jnp.repeat(tile_row, QT),
        jnp.repeat(tile_qpos0, QT) + lane, live, pads, scale=scale, rank=rank)
    err = jnp.abs(jnp.where(live[:, None, None],
                            got.astype(jnp.float32) - want.astype(jnp.float32), 0))
    assert float(err.max()) < tol
    assert float(jnp.abs(want).max()) > 0.1
    # a decode tile's dead lanes and a dead tile come back finite
    assert bool(jnp.isfinite(got.astype(jnp.float32)).all())


def test_kernel_at_the_published_widths_is_a_probe_case():
    cases = [c for c in support.kernel_cases() if c[0] == "ragged_latent_attention"]
    assert [(s.heads, s.latent_rank, s.head_dim, bs) for _, s, bs in cases] == [
        (32, 512, 64, 64), (32, 512, 64, 128)]
    assert "ragged_latent_attention" in support.KERNELS
    assert support.ragged_kernel_name(False, latent=True) == "ragged_latent_attention"
    (rec,) = [r for r in support.kernel_matrix(
        (support.LATENT_PROBE_SHAPE,), interpret=True) if r["block_size"] == 64]
    assert rec["ok"], rec
    # no other kernel is asked to read a latent shape, nor this one another
    # (under an indexer the rows are read behind a selection: its own case)
    assert all((k in ("ragged_latent_attention", "sparse_latent_attention"))
               == (s.latent_rank is not None)
               for k, s, _ in support.kernel_cases())


def test_pages_a_step_cover_512_positions_within_the_table():
    assert latent_pages_per_step(36, 64, 640, jnp.bfloat16) == 8
    assert latent_pages_per_step(4, 64, 640, jnp.bfloat16) == 4
    assert latent_pages_per_step(8, 8, 128, jnp.float32) == 8
    one = jnp.zeros((1,), jnp.int32)
    with pytest.raises(ValueError, match="one metadata entry a query tile"):
        ragged_latent_attention(
            jnp.zeros((12, 4, 128)), jnp.zeros((4, 8, 128)),
            jnp.zeros((1, 2), jnp.int32), one, one, one + 1,
            jnp.zeros((2,), jnp.int32), one, scale=1.0, rank=32,
            interpret=True)
    with pytest.raises(ValueError, match="latent pool is"):
        ragged_latent_attention(
            jnp.zeros((8, 4, 64)), jnp.zeros((4, 8, 128)),
            jnp.zeros((1, 2), jnp.int32), one, one, one + 1, one, one,
            scale=1.0, rank=32, interpret=True)


# ----------------------------------------------------------------------
# the dense token axis: a tile moves, clears and finalises its live
# tokens alone (PR 48)
# ----------------------------------------------------------------------

_H, _RANK, _ROPE, _WIDTH, _MB, _NBP = 4, 32, 8, 128, 12, 40


def _pack(segments, d_w, dead_after=()):
    """A packed batch as the tick's packer lays it out.  ``segments``:
    ``(row, first cache slot, tokens, dense lane of the first)`` in TILE
    order (which need not be the dense order); a dead tile follows every
    segment whose index is in ``dead_after``.  Returns the tile metadata
    ``(tile_row, tile_qpos0, tile_qlen, tile_tok)`` and the twin's
    per-token ``(tok_row, tok_slot, tok_live)`` over ``d_w`` dense
    lanes (a lane no segment covers is dead)."""
    tiles = []
    tok_row, tok_slot = np.zeros(d_w, np.int32), np.zeros(d_w, np.int32)
    tok_live = np.zeros(d_w, bool)
    for i, (row, slot, n, lane) in enumerate(segments):
        assert not tok_live[lane:lane + n].any()
        tok_row[lane:lane + n] = row
        tok_slot[lane:lane + n] = slot + np.arange(n)
        tok_live[lane:lane + n] = True
        tiles += [(row, slot + q0, min(QT, n - q0), lane + q0)
                  for q0 in range(0, n, QT)]
        if i in dead_after:
            tiles.append((0, 0, 0, 0))
    meta = tuple(jnp.asarray(c, jnp.int32) for c in zip(*tiles))
    return meta, (jnp.asarray(tok_row), jnp.asarray(tok_slot),
                  jnp.asarray(tok_live))


def _operands(d_w, block_s, dtype, poison=None):
    """``q [d_w, H, W]``, a pool and three rows' tables and pads (row 1
    left-padded by more than a block).  ``poison``: the dense lanes whose
    queries, with scratch block 0, are set to NaN."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(3))
    live = jnp.arange(_WIDTH) < _RANK + _ROPE
    q = jnp.where(live, jax.random.normal(k1, (d_w, _H, _WIDTH)), 0)
    pool = jnp.where(live, jax.random.normal(k2, (_NBP, block_s, _WIDTH)), 0)
    if poison is not None:
        q = jnp.where(poison[:, None, None], jnp.nan, q)
        pool = pool.at[0].set(jnp.nan)
    tables = jnp.asarray(
        (np.arange(3 * _MB) * 7 % 37 + 1).reshape(3, _MB), jnp.int32)
    pads = jnp.asarray([5, block_s + 2, 0], jnp.int32)
    return q.astype(dtype), pool.astype(dtype), tables, pads


def _attend(q, pool, tables, pads, meta):
    return ragged_latent_attention(
        q, pool, tables, *meta, pads, scale=(_RANK + _ROPE) ** -0.5,
        rank=_RANK, interpret=True).astype(jnp.float32)


def _twin(q, pool, tables, pads, toks):
    tok_row, tok_slot, tok_live = toks
    out = ragged_latent_attention_xla(
        q[..., :_RANK + _ROPE], pool, tables, tok_row, tok_slot, tok_live,
        pads, scale=(_RANK + _ROPE) ** -0.5, rank=_RANK)
    return jnp.where(tok_live[:, None, None], out.astype(jnp.float32), 0)


def _tick(block_s):
    """Two decode rows' one-token tiles around a dead tile, a 19-token
    chunk of the left-padded row (two full tiles and a PARTIAL one of
    ``tile_qlen`` 3) whose last token lies right before row 2's, dead
    tiles between live ones, one dead dense lane in the middle and three
    at the end."""
    first = block_s + 2  # row 1's first live slot
    segments = [
        (0, 9 * block_s + 7, 1, 0),      # a decode row deep in block 10
        (1, first + 11, 19, 2),          # lanes 2..20: tiles of 8, 8, 3
        (2, 3 * block_s + 1, 1, 21),     # the partial tile's neighbour
    ]
    return _pack(segments, 25, dead_after=(0, 1))


@pytest.mark.parametrize("case", [
    "f32", "bf16", "poisoned-dead-lanes", "one-token-tile-is-a-chunk-lane",
    "partial-tile-before-its-neighbour", "partial-tile-after-its-neighbour",
])
def test_kernel_moves_the_live_tokens_of_the_dense_axis_alone(case):
    dtype = jnp.bfloat16 if case == "bf16" else jnp.float32
    block_s = 16 if case == "bf16" else 8
    tol = support.KERNEL_TOLERANCE if case == "bf16" else 2e-5
    meta, toks = _tick(block_s)
    assert list(np.asarray(meta[2])) == [1, 0, QT, QT, 3, 0, 1]
    live = np.asarray(toks[2])
    assert live.sum() == 21 and not live[1] and not live[22:].any()
    q, pool, tables, pads = _operands(25, block_s, dtype)
    want = _twin(q, pool, tables, pads, toks)
    assert float(jnp.abs(want).max()) > 0.1

    if case in ("f32", "bf16"):
        got = _attend(q, pool, tables, pads, meta)
        assert float(jnp.abs(got - want).max()) < tol
        # a lane no tile owns is zeros, not what the buffer held
        assert not np.asarray(got)[~live].any()
    elif case == "poisoned-dead-lanes":
        # the dead lanes' queries and scratch block 0 hold NaN: no live
        # token's result may touch either, and the dead lanes of the
        # RESULT are finite (the next layer writes their rows to block 0)
        qn, pooln, _, _ = _operands(25, block_s, dtype, poison=~toks[2])
        got = _attend(qn, pooln, tables, pads, meta)
        assert bool(jnp.isfinite(got).all())
        assert not np.asarray(got)[~live].any()
        assert float(jnp.abs(got - want).max()) < tol
    elif case == "one-token-tile-is-a-chunk-lane":
        # the chunk's tokens 8..15 (a full tile) each attended as a tile
        # of ONE token: the same rows of the same sheet, to the bit
        chunk = _attend(q, pool, tables, pads, meta)
        singles, _ = _pack(
            [(1, block_s + 2 + 11 + 8 + i, 1, 10 + i) for i in range(QT)], 25)
        alone = _attend(q, pool, tables, pads, singles)
        assert np.array_equal(np.asarray(alone)[10:18], np.asarray(chunk)[10:18])
        assert not np.asarray(alone)[:10].any()
    else:
        # the partial tile's 3 tokens end at lane 20; lane 21 is another
        # row's.  Whichever of the two tiles the grid reaches first, the
        # neighbour's lane holds the neighbour's own result
        segments = [(1, block_s + 2 + 11, 19, 2), (2, 3 * block_s + 1, 1, 21)]
        if case == "partial-tile-after-its-neighbour":
            segments.reverse()
        meta2, toks2 = _pack(segments, 25)
        got = _attend(q, pool, tables, pads, meta2)
        want2 = _twin(q, pool, tables, pads, toks2)
        assert float(jnp.abs(want2[21]).max()) > 0.05
        assert float(jnp.abs(got - want2).max()) < tol
        # ... to the bit what it is with no chunk in the tick at all
        alone, _ = _pack([s for s in segments if s[0] == 2], 25)
        assert np.array_equal(
            np.asarray(got)[21],
            np.asarray(_attend(q, pool, tables, pads, alone))[21])
