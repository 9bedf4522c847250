"""Sparse Mixture-of-Experts MLPs: two routed layers.

``moe_mlp`` (below, first) is Mixtral-style CAPACITY routing for the
training / expert-parallel path; ``moe_dropless`` (at the end of the file)
is the dropless sigmoid-routed layer LFM2-MoE is served and run offline
with: every (token, expert) pair is computed, so a token's output does
not depend on what else is in the batch.

Capacity routing (Mixtral-style top-k routing).

Framework extension: neither reference family is MoE (SURVEY §2.9 lists
expert parallelism as N/A), but a real EP workload needs a real sparse
layer.  The design is the TPU-native dispatch/combine formulation
(GShard lineage): routing becomes two einsums against a one-hot dispatch
tensor, so the whole layer is static-shaped, differentiable, and GSPMD
shards it by annotating the expert axis — the compiler inserts the
all-to-all-equivalent collectives, no hand-written routing backend.

Tokens are processed in *groups* of ≤ ``group_size`` (the GShard group
dimension): the dispatch tensor is ``[G, gs, E, C]`` with per-group
capacity ``C = ceil(gs · k / E · capacity_factor)``, so its size stays
linear in the token count instead of the quadratic blow-up a single
global dispatch tensor would have.

Capacity semantics: each expert owns ``C`` slots per group.  Tokens that
overflow an expert's buffer are *dropped* for that expert (their combine
weight is zero) and pass through the residual unchanged — standard
GShard/Switch behavior, and the price of static shapes under jit.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from llm_np_cp_tpu.ops.pallas import grouped_matmul as gmm
from llm_np_cp_tpu.ops.pallas import support
from llm_np_cp_tpu.quant import quant_einsum


def _group_split(t: int, group_size: int) -> int:
    """Largest divisor of t that is ≤ group_size (group length gs; G=t/gs)."""
    gs = min(t, group_size)
    while t % gs:
        gs -= 1
    return gs


def moe_mlp(
    x: jnp.ndarray,
    router_w: jnp.ndarray,
    gate_w: jnp.ndarray,
    up_w: jnp.ndarray,
    down_w: jnp.ndarray,
    *,
    act,
    top_k: int,
    capacity_factor: float = 2.0,
    group_size: int = 1024,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Top-k routed SwiGLU experts.

    x: [B, S, H]; router_w: [H, E]; gate_w/up_w: [E, H, I]; down_w: [E, I, H].

    Returns ``(out [B, S, H], aux_loss scalar)`` where aux_loss is the
    load-balancing loss ``E · Σ_e f_e · P_e`` (f_e = fraction of token
    routes sent to expert e, P_e = mean router probability, both over the
    full token set) — the standard Switch/Mixtral auxiliary, ~1 when
    perfectly balanced.
    """
    b, s, h = x.shape
    e = router_w.shape[-1]
    t = b * s
    xt = x.reshape(t, h)

    # Routing in f32 (tiny GEMM; numerics matter more than speed here).
    router_logits = jnp.einsum(
        "th,he->te", xt.astype(jnp.float32), router_w.astype(jnp.float32)
    )
    probs = jax.nn.softmax(router_logits, axis=-1)  # [T, E]
    top_vals, top_idx = lax.top_k(probs, top_k)  # [T, k]
    top_vals = top_vals / jnp.sum(top_vals, axis=-1, keepdims=True)  # renorm (Mixtral)
    # gates: [T, E] — renormalized prob on chosen experts, 0 elsewhere
    gates = jnp.zeros_like(probs).at[jnp.arange(t)[:, None], top_idx].set(top_vals)
    routed = gates > 0.0

    # Group tokens; static per-expert capacity per group.
    gs = _group_split(t, group_size)
    g = t // gs
    capacity = max(1, math.ceil(gs * top_k / e * capacity_factor))
    routed_g = routed.reshape(g, gs, e)
    position = jnp.cumsum(routed_g.astype(jnp.int32), axis=1) - 1  # [G, gs, E]
    keep = routed_g & (position < capacity)
    # one_hot of -1 is the zero row → dropped tokens vanish from dispatch
    dispatch = jax.nn.one_hot(
        jnp.where(keep, position, -1), capacity, dtype=x.dtype
    )  # [G, gs, E, C]

    xg = xt.reshape(g, gs, h)
    expert_in = jnp.einsum(
        "gtec,gth->gech", dispatch, xg, preferred_element_type=jnp.float32
    ).astype(x.dtype)
    gate_h = act(quant_einsum("gech,ehi->geci", expert_in, gate_w)).astype(x.dtype)
    up_h = quant_einsum("gech,ehi->geci", expert_in, up_w).astype(x.dtype)
    expert_out = quant_einsum("geci,eih->gech", gate_h * up_h, down_w).astype(x.dtype)

    combine = dispatch * gates.reshape(g, gs, e).astype(x.dtype)[..., None]
    out = jnp.einsum(
        "gtec,gech->gth", combine, expert_out, preferred_element_type=jnp.float32
    ).astype(x.dtype)

    # Load-balancing auxiliary (f32): fraction of routes per expert × mean prob.
    route_frac = jnp.mean(routed.astype(jnp.float32), axis=0) / top_k  # [E]
    prob_frac = jnp.mean(probs, axis=0)  # [E]
    aux_loss = e * jnp.sum(route_frac * prob_frac)

    return out.reshape(b, s, h), aux_loss


# ----------------------------------------------------------------------
# Dropless sigmoid-routed experts (LFM2-MoE)
# ----------------------------------------------------------------------

# ``jax.named_scope`` names (models/transformer.STEP_SCOPES lists them, so
# serve/opmap.py cuts a device profile by them)
SCOPE_MOE_ROUTE = "moe_route"      # gate, sigmoid, bias, top-k, normalise, sort
SCOPE_MOE_EXPERTS = "moe_experts"  # the grouped matmuls: rows in, weighted sums out


def route_sigmoid_topk(
    x: jnp.ndarray,              # [T, H]
    router_w: jnp.ndarray,       # [H, E]
    expert_bias: jnp.ndarray | None,  # [E] — selection only
    *,
    top_k: int,
    norm_topk_prob: bool = True,
    scaling: float = 1.0,
    norm_eps: float = 1e-6,
    score_dtype: jnp.dtype = jnp.float32,
    n_group: int = 1,
    topk_group: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(chosen experts [T, k] int32, their weights [T, k] f32)``.

    Scores are ``sigmoid(x @ router_w)`` over ALL experts, in float32
    from end to end: ``x`` is read as it is given (the float32 normed
    activations where the caller keeps them — models/transformer
    ``experts_block``), the product is taken at the highest matmul
    precision and never rounded below float32.  The 4th and 5th score of
    a token are often a few bf16 ulps apart, and a flipped choice is a
    discrete change of the output that no dense layer has.  The top k are
    chosen by ``score + expert_bias``; the weights are the scores WITHOUT
    the bias, divided by their sum + ``norm_eps`` (``norm_topk_prob``; the
    configuration's: LFM2 1e-6, DeepSeek-V3 1e-20), times ``scaling``.
    With ``n_group > 1`` the choice is group-limited (DeepSeek-V3's
    ``noaux_tc``): the experts are ``n_group`` groups of consecutive
    ones, a group's score is the sum of its two best ``score + bias``,
    and only experts of a token's ``topk_group`` best groups can be
    chosen; ``n_group == 1`` traces nothing of it.  ``score_dtype``
    exists for the tests that show a bf16 router fails the float32
    tolerance."""
    logits = jnp.einsum(
        "th,he->te", x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=lax.Precision.HIGHEST, preferred_element_type=jnp.float32)
    scores = jax.nn.sigmoid(logits.astype(score_dtype)).astype(jnp.float32)
    select = scores
    if expert_bias is not None:
        select = scores + expert_bias.astype(jnp.float32)
    if n_group > 1:
        t, e = select.shape
        grouped = select.reshape(t, n_group, e // n_group)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
        _, kept = lax.top_k(group_score, topk_group)  # [T, topk_group]
        keep = jnp.any(
            kept[:, :, None] == jnp.arange(n_group, dtype=kept.dtype),
            axis=1)  # [T, n_group]
        select = jnp.where(keep[:, :, None], grouped, -jnp.inf).reshape(t, e)
    _, idx = lax.top_k(select, top_k)
    w = jnp.take_along_axis(scores, idx, axis=-1)
    if norm_topk_prob:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + norm_eps)
    return idx.astype(jnp.int32), w * scaling


def expert_row_tile(w: Any, rows: int, experts: int,
                    interpret: bool | None = None) -> int | None:
    """The row tile ops/pallas/grouped_matmul multiplies ``rows`` (token,
    expert) pairs routed over ``experts`` in, or None where expert weights
    ``w [E_held, H, I]`` (an array, or its shape and dtype) go through
    ``lax.ragged_dot``: told from the backend, the dtype and the shape,
    and on a TPU from the kernel's probe (a Mosaic refusal is one warning
    and the compiler's own lowering, not a dead server).  ``interpret``:
    ``moe_dropless``'s."""
    if not (hasattr(w, "dtype") and jnp.issubdtype(w.dtype, jnp.floating)
            and w.shape[1] % 128 == 0 and w.shape[2] % 128 == 0):
        return None  # quant.py's trees; matrices that are not whole lanes
    if interpret is None and (
            jax.default_backend() != "tpu"
            or support.kernel_or_warn("grouped_matmul", "lax.ragged_dot")):
        return None
    return gmm.row_tile(rows, experts)


def expert_rows_in_call(w: Any, tokens: int, top_k: int,
                        tm: int | None) -> bool:
    """Whether the grouped matmul's two calls move the rows themselves at
    row tile ``tm`` (``expert_row_tile``'s) — gather a tile's tokens, add
    its weighted result to theirs — or XLA gathers every laid row and
    un-sorts and sums all ``tokens * top_k`` pairs around them: the
    former wherever the kernel runs and the tokens' rows fit its VMEM
    (``grouped_matmul.token_rows_fit``: every program of a served tick;
    not a plain forward over thousands of tokens)."""
    if tm is None:
        return False
    held, h, inter = w.shape
    return gmm.token_rows_fit(
        tokens, gmm.tile_count(tokens * top_k, held, tm) * tm, h, inter,
        jnp.dtype(w.dtype).itemsize)


# (jitted: a stack's expert layers are alike, so the layer is traced once a
# program and not once a layer — a warm start re-traces all nine programs)
@functools.partial(jax.jit, static_argnames=(
    "act", "top_k", "norm_topk_prob", "scaling", "norm_eps", "first_expert",
    "out_dtype", "interpret", "n_group", "topk_group"))
def moe_dropless(
    x: jnp.ndarray,         # [T, H] — float32 where the caller has it
    router_w: jnp.ndarray,  # [H, E] — E: every expert of the layer
    expert_bias: jnp.ndarray | None,
    w1: jnp.ndarray,        # [E_held, H, I]  gate
    w3: jnp.ndarray,        # [E_held, H, I]  up
    w2: jnp.ndarray,        # [E_held, I, H]  down
    *,
    act,
    top_k: int,
    norm_topk_prob: bool = True,
    scaling: float = 1.0,
    norm_eps: float = 1e-6,
    live: jnp.ndarray | None = None,  # [T] bool — False: routed nowhere
    first_expert: int = 0,
    out_dtype: jnp.dtype | None = None,
    interpret: bool | None = None,
    n_group: int = 1,    # group-limited routing: ``route_sigmoid_topk``
    topk_group: int = 1,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Dropless routed SwiGLU experts: ``(out [T, H], chosen [T, k],
    load [E_held] int32)``.

    The (token, expert) pairs — a static ``T * k`` of them — are sorted by
    expert and multiplied by the experts held in grouped matmuls: no
    capacity, no dropped token, and an expert nobody chose reads no
    weights.  On a TPU that is ``ops/pallas/grouped_matmul``: the groups
    laid out in row tiles of one expert each, one call for ``act(gate) *
    up`` and one for down, each streaming a touched expert's matrices
    once — and, where the tokens' rows fit them (``expert_rows_in_call``:
    every program of a served tick), moving the rows themselves: a tile
    gathers its tokens out of ``x`` and adds its weighted result into
    ``[T, H]``, so nothing in the layer is as long as the pairs routed,
    only as the pairs held; anywhere else, and for weights the kernel
    does not take (``expert_row_tile``), three ``lax.ragged_dot`` over
    the sorted rows, between XLA's gather, un-sort and masked sum.
    ``interpret``: as the Pallas kernels take it — None lets the backend
    decide (the kernel compiled on a TPU, ``ragged_dot`` elsewhere), True
    runs the kernel in the interpreter (tests), False compiles it.  The
    layer routes over all ``E`` experts and holds
    ``w1.shape[0]`` of them from ``first_expert`` on (all of them on one
    chip); a pair whose expert is not held adds nothing here — that part
    of the result is another holder's.  ``live`` marks the tokens that
    are real: a dead lane of the serve tick's dense axis is routed to no
    expert, so it neither reads an expert's weights nor counts in
    ``load``.  The router reads ``x`` as given; the experts multiply it
    in THEIR dtype (the served one) and the weighted sum, taken in
    float32, comes back in ``out_dtype`` (default: the experts')."""
    t, h = x.shape
    held = w1.shape[0]
    tm = expert_row_tile(w1, t * top_k, router_w.shape[-1], interpret)
    in_call = expert_rows_in_call(w1, t, top_k, tm)
    with jax.named_scope(SCOPE_MOE_ROUTE):
        idx, wts = route_sigmoid_topk(
            x, router_w, expert_bias, top_k=top_k,
            norm_topk_prob=norm_topk_prob, scaling=scaling, norm_eps=norm_eps,
            n_group=n_group, topk_group=topk_group,
        )
        local = idx - first_expert
        here = (local >= 0) & (local < held)
        if live is not None:
            here = here & live[:, None]
        # pairs of no expert held sort last, past every group
        pair_expert = jnp.where(here, local, held).reshape(t * top_k)
        order = jnp.argsort(pair_expert, stable=True)
        load = jnp.sum(
            pair_expert[:, None] == jnp.arange(held, dtype=jnp.int32)[None, :],
            axis=0, dtype=jnp.int32,
        )
        if not in_call:
            inverse = jnp.argsort(order)  # the sorted row of a pair
        if tm is not None:
            # each group in whole row tiles: the kernel's rows, and the
            # pair each holds (two gathers a layer: one of a few thousand
            # scalars is 30-40 us on a v5e, PERF.md section 6, PR 49)
            layout = gmm.align_groups(load, t * top_k, tm)
            order = order[layout.src]
            if in_call:
                # a laid row's weight: its pair's (a row that pads names
                # sorted row 0's, which the calls never read)
                weight = wts.reshape(t * top_k)[order]
            else:
                inverse = layout.dest[inverse]  # where a pair's row went
        token = order // top_k  # of a sorted row; of a laid one
    with jax.named_scope(SCOPE_MOE_EXPERTS):
        if in_call:
            # the rows enter and leave inside the calls, those of the
            # pairs held alone: a tile cuts its tokens out of ``x`` and
            # adds its weighted result to their rows of ``out``, which
            # starts from zeros — nothing here is as long as ``T * k``
            out = gmm.grouped_experts(
                x.astype(jnp.float32), w1, w3, w2, layout, token, weight,
                act=act, tm=tm, interpret=bool(interpret))
            return out.astype(out_dtype or w1.dtype), idx, load
        x = x.astype(w1.dtype)
        xs = x[token]  # [rows, H], grouped by expert
        if tm is None:
            gate = lax.ragged_dot(
                xs, w1, load, preferred_element_type=jnp.float32)
            up = lax.ragged_dot(
                xs, w3, load, preferred_element_type=jnp.float32)
            hidden = (act(gate.astype(x.dtype))
                      * up.astype(x.dtype)).astype(x.dtype)
            ys = lax.ragged_dot(hidden, w2, load,
                                preferred_element_type=jnp.float32)
        else:
            ys = gmm.grouped_experts(
                xs, w1, w3, w2, layout, act=act, tm=tm,
                interpret=bool(interpret))
        # back to (token, choice) order; the weighted sum over a token's
        # k experts in float32.  Rows of no group hold nothing the sum
        # may use.
        ys = ys[inverse].reshape(t, top_k, h)
        out = jnp.sum(
            jnp.where(here[..., None], ys * wts[..., None], 0.0), axis=1)
    return out.astype(out_dtype or x.dtype), idx, load
