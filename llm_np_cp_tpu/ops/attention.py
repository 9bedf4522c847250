"""Grouped-query attention.

Reference behavior (llama3.2_model.py:399-508): project → RoPE → cache →
``repeat_kv_np`` (materializes KV across query groups, :180-196) → full
``q@k.T/sqrt(d)`` score matrix → tril mask (only when q_len>2, :471 — a bug
we do not copy; masks here are computed from positions, never from shape
branches) → softmax (live = custom CUDA kernel, stable) → ``@v`` → o_proj.

TPU-first differences:
- no KV repetition: q is reshaped to [B, S, K, G, D] and contracted against
  the K kv-heads directly — the Gemma-2 table (4 KV heads × 256 dim) never
  gets duplicated in HBM;
- softmax is computed in float32 with max-subtraction (the reference's live
  kernel is also max-stabilized, SURVEY §2.4);
- masks are additive bias built from *positions*, so the same code path is
  correct for prefill (q_len=S), chunked prefill, and decode (q_len=1), and
  sliding-window layers just tighten the predicate;
- layouts keep head_dim last and sequence second ([B, S, H, D]) so KV-cache
  writes are contiguous dynamic-slice updates.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from llm_np_cp_tpu.ops.activations import softcap as _softcap

NEG_INF = float(jnp.finfo(jnp.float32).min)


def causal_mask(
    q_positions: jnp.ndarray,
    kv_positions: jnp.ndarray,
    *,
    window: int | None = None,
    kv_valid: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """Boolean attention predicate.

    q_positions: [B, Sq] absolute positions of the query tokens.
    kv_positions: [Skv] or [B, Skv] absolute positions of cache slots.
    window: if set, also require ``q_pos - kv_pos < window`` (sliding-window
        local attention — the Gemma-2 feature the reference drops, SURVEY §2.7).
    kv_valid: optional [B, Skv] validity of cache slots (slots beyond the
        written length, or padding).

    Returns bool [B, Sq, Skv]; True = attend.
    """
    if kv_positions.ndim == 1:
        kv_positions = kv_positions[None, :]
    q = q_positions[:, :, None]  # [B, Sq, 1]
    kv = kv_positions[:, None, :]  # [B, 1, Skv]
    mask = kv <= q
    if window is not None:
        mask = mask & (q - kv < window)
    if kv_valid is not None:
        mask = mask & kv_valid[:, None, :]
    return mask


# float32 scores ``[B, H, Sq, Skv]`` above which ``gqa_attention`` goes
# over the queries in blocks, and what one block's scores may take: 4
# sequences of 4,864 tokens x 64 heads are 24 GB at once (the longest
# accepted check, 4 x 2,560 x 16 heads, is 1.7 GB and stays one einsum)
QUERY_BLOCK_SCORE_BYTES = 4 << 30
_BLOCK_SCORE_BYTES = 256 << 20


def query_block(b: int, h: int, sq: int, skv: int) -> int:
    """Queries ``gqa_attention`` attends at a time: all ``sq`` of them
    unless their float32 scores pass ``QUERY_BLOCK_SCORE_BYTES``, else
    the largest multiple of 8 whose scores stay within 256 MiB."""
    row = 4 * b * h * skv
    if row * sq <= QUERY_BLOCK_SCORE_BYTES:
        return sq
    return max(8, _BLOCK_SCORE_BYTES // row // 8 * 8)


def attend_in_query_blocks(q, k, v, mask, *, block: int, **kw):
    """``gqa_attention`` over ``block`` queries at a time (``lax.map``):
    every row's softmax whole, the scores of one block alive at a time."""
    b, sq = q.shape[:2]
    n = -(-sq // block)
    pad = n * block - sq
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
    mp = jnp.pad(jnp.broadcast_to(mask, (b, sq, k.shape[1])),
                 ((0, 0), (0, pad), (0, 0)))
    out = lax.map(
        lambda qm: gqa_attention(qm[0], k, v, qm[1], **kw),
        (jnp.moveaxis(qp.reshape(b, n, block, *q.shape[2:]), 1, 0),
         jnp.moveaxis(mp.reshape(b, n, block, -1), 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(b, n * block, *out.shape[3:])[:, :sq]


def gqa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    scale: float,
    logit_softcap: float | None = None,
    return_weights: bool = False,
    sink: jnp.ndarray | None = None,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """Attention over grouped KV heads.

    q: [B, Sq, H, D]  (H = K * G query heads)
    k: [B, Skv, K, D]; v: [B, Skv, K, Dv]
    mask: bool, broadcastable to [B, Sq, Skv] (True = attend)
    sink: optional [H] float32 — a learned logit a query head that joins
        every row of its softmax as a column with no value: it enters the
        running maximum and the denominator, nothing else (MiMo-V2's
        window layers).

    Above ``QUERY_BLOCK_SCORE_BYTES`` of float32 scores the queries are
    attended ``query_block(...)`` at a time (the same softmax a row: a
    row's scores are never split), so the scores of a long batch never
    exist at once; under it the one einsum every caller compiled stays.

    Returns [B, Sq, H, D] in q.dtype (weights additionally if requested —
    the reference's ``output_attentions`` surface, llama3.2_model.py:679-706).
    """
    b, sq, h, d = q.shape
    _, skv, kh, _ = k.shape
    g = h // kh
    block = query_block(b, h, sq, skv)
    if block < sq and not return_weights:
        return attend_in_query_blocks(
            q, k, v, mask, block=block, scale=scale,
            logit_softcap=logit_softcap, sink=sink)
    qg = q.reshape(b, sq, kh, g, d)

    # scores: contract head_dim; accumulate in f32 on the MXU.
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", qg, k, preferred_element_type=jnp.float32
    )
    scores = scores * scale
    if logit_softcap is not None:
        scores = _softcap(scores, logit_softcap)

    bias = jnp.where(mask[:, None, None, :, :], 0.0, NEG_INF).astype(jnp.float32)
    scores = scores + bias

    # Stable softmax in f32 (semantics of the reference's live CUDA kernel,
    # llama3.2_model.py:940-952).
    top = jnp.max(scores, axis=-1, keepdims=True)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(1, kh, g, 1, 1)
        top = jnp.maximum(top, sink)
    probs = jnp.exp(scores - top)
    total = jnp.sum(probs, axis=-1, keepdims=True)
    if sink is not None:
        total = total + jnp.exp(sink - top)
    probs = probs / total

    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    # (a value head may be narrower than a query head: latent attention)
    out = out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)
    if return_weights:
        return out, probs.reshape(b, h, sq, skv)
    return out
