"""A learned sparse-attention indexer (GLM-5 / DeepSeek-V3.2's "DSA"), plain XLA.

A layer with an indexer lets a token attend a SELECTION of its context.
Beside the attention's own projections it has three small ones (``qr`` is
the query latent, ``h`` the input-normed residual):

    q_I = qr W_Iq          [heads_I, dim_I]   RoPE on the leading columns
    k_I = layernorm(h W_Ik) [dim_I], ONE head  RoPE alike: the cached index key
    w   = h W_Iw x heads_I^-0.5 x dim_I^-0.5   [heads_I], float32

    I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])          for s <= t
    S_t     = the min(t + 1, topk) positions of largest I[t, .]

and attention's softmax runs over ``S_t`` alone.  While a token sees no more
than ``topk`` positions ``S_t`` is all of them: dense causal attention.

**The tie rule.**  Equal scores are settled for the LOWER position, here, in
the Pallas kernel (ops/pallas/sparse_index.py) and in the plain reference
(benchmark/reference_glm_dsa.py: ``lax.top_k`` is stable).  ``-0.0`` and
``0.0`` are the same score.

**The selection is a MASK, and exact.**  ``select_topk`` never sorts: the
``k``-th largest score of a row is found bit by bit (32 counts of ``score >=
candidate`` over the row, on the scores' order-preserving integer keys), every
position above it is taken and the positions AT it from the lowest on until
``k`` are — the same set a stable sort would give.  An approximate top-k is
another result, not a faster one, and is not here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from llm_np_cp_tpu.ops.rope import apply_rope

INT32_MIN = -(1 << 31)

# ``jax.named_scope`` names (models/transformer.STEP_SCOPES lists them, so a
# device profile is cut by them): the indexer's three projections with their
# norm and RoPE; and, entered inside ``attn`` (the innermost scope names an
# operation), the index scores over the cached keys, the selection, and the
# attention over what was selected
SCOPE_DSA_PROJ = "dsa_proj"
SCOPE_DSA_SCORE = "dsa_score"
SCOPE_DSA_SELECT = "dsa_select"
SCOPE_DSA_ATTN = "dsa_attn"


def layer_norm(x: jnp.ndarray, weight: jnp.ndarray, bias: jnp.ndarray, *,
               eps: float = 1e-6) -> jnp.ndarray:
    """LayerNorm over the last axis (mean and variance in float32)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + eps)
    return (out * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def rope_leading(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray, *,
                 interleave: bool) -> jnp.ndarray:
    """RoPE on the leading ``cos.shape[-1]`` columns of ``x [.., S, heads,
    dim]`` (pairs side by side there when ``interleave``); the rest pass."""
    rd = cos.shape[-1]
    return jnp.concatenate(
        [apply_rope(x[..., :rd], cos, sin, interleave=interleave),
         x[..., rd:]], axis=-1)


def index_scores(q_idx: jnp.ndarray, w_idx: jnp.ndarray,
                 k_idx: jnp.ndarray) -> jnp.ndarray:
    """``I [.., T, S]`` float32 of ``q_idx [.., T, heads, dim]``, ``w_idx
    [.., T, heads]`` (float32, the two scales folded in) and ``k_idx [.., S,
    dim]``.  Materializes ``[.., T, heads, S]``: callers block ``T``."""
    s = jnp.einsum("...thd,...sd->...ths", q_idx, k_idx,
                   preferred_element_type=jnp.float32)
    return jnp.sum(jnp.maximum(s, 0.0) * w_idx[..., None].astype(jnp.float32),
                   axis=-2)


def score_keys(scores: jnp.ndarray, visible: jnp.ndarray) -> jnp.ndarray:
    """int32 keys in the scores' order (``a < b`` as floats iff ``key(a) <
    key(b)``; the two zeros one key), ``INT32_MIN`` where not ``visible``."""
    s = jnp.where(scores == 0, 0.0, scores.astype(jnp.float32))
    bits = lax.bitcast_convert_type(s, jnp.int32)
    keys = jnp.where(bits < 0, bits ^ jnp.int32(0x7FFFFFFF), bits)
    return jnp.where(visible, keys, jnp.int32(INT32_MIN))


def kth_largest_key(keys: jnp.ndarray, k: int) -> jnp.ndarray:
    """``[.., 1]``: the largest ``v`` with ``count(keys >= v) >= k`` over the
    last axis — the ``k``-th largest key (``INT32_MIN`` where a row holds
    fewer than ``k`` above it), built from the sign bit down."""
    def bit(i, thr):
        # (the first step's INT32_MIN + INT32_MIN wraps to 0, as meant)
        cand = thr + lax.shift_left(jnp.int32(1), jnp.int32(31) - i)
        enough = jnp.sum(keys >= cand, axis=-1, keepdims=True) >= k
        return jnp.where(enough, cand, thr)

    thr = jnp.full(keys.shape[:-1] + (1,), INT32_MIN, jnp.int32)
    return lax.fori_loop(0, 32, bit, thr)


def select_topk(scores: jnp.ndarray, visible: jnp.ndarray,
                k: int) -> jnp.ndarray:
    """bool ``[.., S]``: the ``min(k, visible positions)`` visible positions
    of largest ``scores [.., S]``, ties to the lower position (module
    docstring).  Exact."""
    keys = score_keys(scores, visible)
    thr = kth_largest_key(keys, k)
    above = keys > thr
    at = (keys == thr) & visible
    room = k - jnp.sum(above, axis=-1, keepdims=True)
    return visible & (above | (at & (jnp.cumsum(at, axis=-1) <= room)))


def sparse_latent_attention(q, k, v, k_sel_mask, *, scale: float):
    """Softmax attention of ``q [T, H, Dk]`` over ``k [S, H, Dk]`` / ``v [S,
    H, Dv]`` under ``k_sel_mask [T, S]``: ``[T, H, Dv]`` in ``q``'s dtype."""
    s = jnp.einsum("thd,shd->hts", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(k_sel_mask[None], s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - jnp.where(jnp.isfinite(top), top, 0.0))
    p = p / jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    return jnp.einsum("hts,shd->thd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def attend_selected_in_blocks(q, k, v, q_idx, w_idx, k_idx, mask, *,
                              topk: int, scale: float, block: int):
    """The expanded form of latent attention under an indexer, ``[b, s, H,
    Dv]``: one sequence at a time and ``block`` queries at a time, so that
    neither the index scores ``[block, heads_I, S]`` nor the attention's
    sheet ``[H, block, S]`` of a whole batch exists at once (4 x 8,832
    tokens of 64 heads: 80 GB of float32 scores whole, 0.9 GB a block of
    256).  q ``[b, s, H, Dk]``, k ``[b, S, H, Dk]``, v ``[b, S, H, Dv]``,
    q_idx ``[b, s, heads_I, dim_I]``, w_idx ``[b, s, heads_I]``, k_idx ``[b,
    S, dim_I]``, mask bool ``[b, s, S]``."""
    b, s = q.shape[:2]
    block = min(block, s)
    n = -(-s // block)
    pad = n * block - s

    def blocks(a):
        a = jnp.pad(a, ((0, pad),) + ((0, 0),) * (a.ndim - 1))
        return a.reshape((n, block) + a.shape[1:])

    def one_sequence(args):
        q1, k1, v1, qi1, wi1, ki1, m1 = args

        def one_block(xs):
            qb, qib, wib, mb = xs
            with jax.named_scope(SCOPE_DSA_SCORE):
                scores = index_scores(qib, wib, ki1)
            with jax.named_scope(SCOPE_DSA_SELECT):
                sel = select_topk(scores, mb, topk)
            with jax.named_scope(SCOPE_DSA_ATTN):
                return sparse_latent_attention(qb, k1, v1, sel, scale=scale)

        out = lax.map(one_block, (blocks(q1), blocks(qi1), blocks(wi1),
                                  blocks(m1)))
        return out.reshape((n * block,) + out.shape[2:])[:s]

    mask = jnp.broadcast_to(mask, (b, s, k.shape[1]))
    return lax.map(one_sequence, (q, k, v, q_idx, w_idx, k_idx, mask))
