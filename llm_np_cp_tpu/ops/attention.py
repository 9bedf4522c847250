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


def gqa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    mask: jnp.ndarray,
    *,
    scale: float,
    logit_softcap: float | None = None,
    return_weights: bool = False,
) -> jnp.ndarray | tuple[jnp.ndarray, jnp.ndarray]:
    """Attention over grouped KV heads.

    q: [B, Sq, H, D]  (H = K * G query heads)
    k: [B, Skv, K, D]; v: [B, Skv, K, Dv]
    mask: bool, broadcastable to [B, Sq, Skv] (True = attend)

    Returns [B, Sq, H, D] in q.dtype (weights additionally if requested —
    the reference's ``output_attentions`` surface, llama3.2_model.py:679-706).
    """
    b, sq, h, d = q.shape
    _, skv, kh, _ = k.shape
    g = h // kh
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
    scores = scores - jnp.max(scores, axis=-1, keepdims=True)
    probs = jnp.exp(scores)
    probs = probs / jnp.sum(probs, axis=-1, keepdims=True)

    out = jnp.einsum(
        "bkgqs,bskd->bqkgd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    # (a value head may be narrower than a query head: latent attention)
    out = out.reshape(b, sq, h, v.shape[-1]).astype(q.dtype)
    if return_weights:
        return out, probs.reshape(b, h, sq, skv)
    return out
