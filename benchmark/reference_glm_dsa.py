"""Plain reference of GLM-5 (``model_type: glm_moe_dsa``): the layer equations
in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.  No cache, no batching, no
kernels, attention in its EXPANDED form over ``q_block`` queries at a time, a
Python loop over the layers and over the experts held.  Imports ``jax``,
``numpy`` and the DeepSeek-V3 reference beside it (whose feed-forward, norms
and RoPE this layer shares), nothing of the program.

    logits = forward(params, config, ids)
    logits, chosen, picked = forward(params, config, ids, return_experts=True,
                                     return_selection=True)

``params`` is the program's parameter pytree (``models.init_params``),
``config`` the configuration file's dict (the published ``config.json`` keys;
``n_routed_experts`` the experts HELD, ``router_experts`` / ``first_expert``
where the file states one chip's share; ``rope_parameters.rope_theta``).

**The layer** (``h`` = the input-normed residual, RMSNorm ``rms_norm_eps``):

- query latent ``qr = rmsnorm(h W_qa)``; ``q = qr W_qb`` -> heads x ``[q_nope
  | q_pe]``, RoPE (interleaved pairs) on ``q_pe``;
- ``[c | k_pe] = h W_kva``, ``c' = rmsnorm(c)``, RoPE on ``k_pe`` (ONE for all
  heads); ``k = [c' W_UK | k_pe]``, ``v = c' W_UV`` (``kv_b_proj`` per head
  ``[k_nope | v]``); scale ``(nope + rope)^-0.5``;
- indexer: ``q_I = qr W_Iq`` -> ``index_n_heads`` heads of ``index_head_dim``
  (RoPE on its leading ``qk_rope_head_dim`` columns, pairs interleaved when
  ``indexer_rope_interleave``), ``k_I = layernorm(h W_Ik)`` (ONE head, weight
  and bias, eps 1e-6, RoPE alike), ``w = h W_Iw x heads_I^-0.5 x dim_I^-0.5``;
  ``I[t, s] = sum_h w[t, h] relu(q_I[t, h] . k_I[s])`` for ``s <= t``;
  ``S_t`` = the ``min(t + 1, index_topk)`` positions of largest ``I[t, .]``,
  ties to the LOWER position (``lax.top_k`` is stable);
- ``o_t = softmax over s in S_t of (q_t . k_s x scale) v_s``, then ``W_o``;
  for ``t < index_topk`` this IS dense causal latent attention;
- feed-forward: ``reference_deepseek_v3``'s (dense SwiGLU below
  ``first_k_dense_replace``; sigmoid scores, top k by score + correction bias,
  weights = scores of the chosen / their sum x ``routed_scaling_factor``, the
  experts HELD summed, one shared SwiGLU).

Departures from the published serving code, each stated in the configuration
file's ``assumed``: float32 throughout; index keys are not quantised (the
published code holds them in fp8 with a scale a token after a Hadamard
rotation of ``q_I`` and ``k_I``; the rotation is orthogonal, leaves ``q_I .
k_I`` unchanged and is not computed); ``k_norm`` as a LayerNorm with bias and
the indexer's RoPE on the FIRST ``qk_rope_head_dim`` columns are the
DeepSeek-V3.2 indexer's, assumed for GLM-5; the multi-token-prediction layer
(``num_nextn_predict_layers``) takes no part in these logits and is not read.

``variant`` names a CONTROL, a deliberately wrong layer that a comparison must
refuse (benchmark/parity_glm_dsa.py): ``recent`` (the last ``index_topk``
positions instead of the best), ``no_index_rope``, ``index_weights_one``,
``dense`` (no selection), ``no_q_a_layernorm``, ``halfsplit_rope``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from reference_deepseek_v3 import (  # noqa: F401 - runs is part of the surface
    _f32,
    experts_ff,
    rms_norm,
    rope_pairs,
    runs,
    swiglu,
)

VARIANTS = ("recent", "no_index_rope", "index_weights_one", "dense",
            "no_q_a_layernorm", "halfsplit_rope")


def rope_theta(config: dict) -> float:
    return float((config.get("rope_parameters") or {}).get(
        "rope_theta", config.get("rope_theta", 10000.0)))


def layer_norm(x: jnp.ndarray, w, b, eps: float = 1e-6) -> jnp.ndarray:
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + eps) * _f32(w) + _f32(b)


def rope_leading(x: jnp.ndarray, width: int, theta: float,
                 interleave: bool) -> jnp.ndarray:
    """RoPE on the leading ``width`` columns of ``x [S, heads, D]``."""
    return jnp.concatenate(
        [rope_pairs(x[..., :width], theta, interleave), x[..., width:]], axis=-1)


def select(scores: jnp.ndarray, causal: jnp.ndarray, topk: int) -> jnp.ndarray:
    """bool ``[q, S]``: each query's ``min(visible, topk)`` visible positions
    of largest score, ties to the lower position."""
    s = scores.shape[-1]
    if topk >= s:
        return causal
    # (-0.0 and 0.0 are one score; what is not visible sorts last)
    keyed = jnp.where(causal, jnp.where(scores == 0, 0.0, scores), -jnp.inf)
    _, idx = jax.lax.top_k(keyed, topk)
    picked = jnp.zeros(scores.shape, bool).at[
        jnp.arange(scores.shape[0])[:, None], idx].set(True)
    return picked & causal


def attention(u: jnp.ndarray, w: dict, config: dict, q_block: int | None = None,
              variant: str | None = None, forced: jnp.ndarray | None = None,
              keep_from: int = 0):
    """``(the attention's output [S, H], selection bool [S - keep_from, S] of
    the queries from ``keep_from`` on)``.  ``forced``: a selection (of those
    same queries) used in place of the reference's own."""
    s = u.shape[0]
    nh = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, dv = config["kv_lora_rank"], config["v_head_dim"]
    ih, idim = config["index_n_heads"], config["index_head_dim"]
    topk, eps = config["index_topk"], config["rms_norm_eps"]
    theta = rope_theta(config)
    inter = config.get("rope_interleave", False)
    if variant == "halfsplit_rope":
        inter = not inter
    qr = u @ _f32(w["q_a_proj"])
    if variant != "no_q_a_layernorm":
        qr = rms_norm(qr, w["ln_q_a"], eps)
    q = (qr @ _f32(w["q_b_proj"])).reshape(s, nh, dn + dr)
    kv_a = u @ _f32(w["kv_a_proj"])
    c = rms_norm(kv_a[:, :rank], w["ln_kv_a"], eps)
    kv = (c @ _f32(w["kv_b_proj"])).reshape(s, nh, dn + dv)
    q_pe = rope_pairs(q[..., dn:], theta, inter)
    k_pe = rope_pairs(kv_a[:, None, rank:], theta, inter)  # [S, 1, dr]
    qf = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    kf = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (s, nh, dr))], axis=-1)
    v = kv[..., dn:]
    del q, kv, q_pe  # (8k tokens of 64 heads: half a GB each in float32)
    # the indexer
    q_i = (qr @ _f32(w["idx_q_proj"])).reshape(s, ih, idim)
    k_i = layer_norm(u @ _f32(w["idx_k_proj"]), w["ln_idx_k"],
                     w["idx_k_norm_bias"])[:, None, :]
    if variant != "no_index_rope":
        i_inter = config.get("indexer_rope_interleave", False)
        q_i = rope_leading(q_i, dr, theta, i_inter)
        k_i = rope_leading(k_i, dr, theta, i_inter)
    w_i = (u @ _f32(w["idx_w_proj"])) * (ih ** -0.5 * idim ** -0.5)
    if variant == "index_weights_one":
        w_i = jnp.ones_like(w_i)
    scale = (dn + dr) ** -0.5
    step = q_block or s
    outs, sels = [], []
    for q0 in range(0, s, step):
        q1 = min(q0 + step, s)
        pos = jnp.arange(q0, q1)[:, None]
        causal = jnp.arange(s)[None, :] <= pos
        own = None
        if forced is not None and q1 > keep_from:
            # (queries before ``keep_from`` keep the reference's own choice)
            rows = jnp.asarray(forced)[max(q0 - keep_from, 0):q1 - keep_from]
            own = jnp.zeros((q1 - q0, s), bool).at[q1 - q0 - rows.shape[0]:].set(rows)
            given = (jnp.arange(q0, q1) >= keep_from)[:, None]
        if variant == "dense":
            sel = causal
        elif variant == "recent":
            sel = causal & (jnp.arange(s)[None, :] > pos - topk)
        else:
            index = jnp.einsum(
                "qh,qhs->qs", w_i[q0:q1], jax.nn.relu(
                    jnp.einsum("qhd,sd->qhs", q_i[q0:q1], k_i[:, 0])))
            sel = select(index, causal, topk)
        if own is not None:
            sel = jnp.where(given, own & causal, sel)
        scores = jnp.einsum("qhd,khd->hqk", qf[q0:q1], kf) * scale
        probs = jax.nn.softmax(jnp.where(sel[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
        if q1 > keep_from:
            sels.append(sel[max(keep_from - q0, 0):])
    out = jnp.concatenate(outs, axis=0).reshape(s, nh * dv)
    return out @ _f32(w["o_proj"]), jnp.concatenate(sels, axis=0)


def forward(params: dict, config: dict, ids, *, return_experts: bool = False,
            return_selection: bool = False, experts: list | None = None,
            selections: list | None = None, q_block: int | None = None,
            logits_from: int = 0, precision: str = "highest",
            variant: str | None = None):
    """Logits ``[S - logits_from, V]`` float32 of the token ids ``ids [S]``
    (one sequence, positions 0..S-1); on request each expert layer's chosen
    experts ``[expert layers, S, k]`` and each layer's selection ``[layers, S -
    logits_from, S]`` bool (of the queries whose logits are returned), in that
    order.  ``experts`` / ``selections`` (of those same queries): used in
    place of the reference's own choices.  ``precision``: the matmul precision;
    anything but ``highest``, and any ``variant``, is a control."""
    if variant is not None and variant not in VARIANTS:
        raise ValueError(f"unknown control {variant!r} (have: {VARIANTS})")
    eps = config["rms_norm_eps"]
    ids = np.asarray(ids).reshape(-1)
    chosen_all, picked_all = [], []
    with jax.default_matmul_precision(precision):
        x = _f32(jnp.asarray(params["embed_tokens"])[ids])
        for group, (ff, count) in zip(params["layers"], runs(config)):
            for i in range(count):
                w = {name: leaf[i] for name, leaf in group.items()}  # one layer
                forced = (None if selections is None
                          else selections[len(picked_all)])
                y, picked = attention(rms_norm(x, w["ln_attn_in"], eps), w,
                                      config, q_block, variant, forced,
                                      logits_from)
                picked_all.append(picked)
                x = x + y
                a = rms_norm(x, w["ln_mlp_in"], eps)
                if ff == "experts":
                    forced = None if experts is None else experts[len(chosen_all)]
                    y, chosen = experts_ff(a, w, config, forced)
                    chosen_all.append(chosen)
                    x = x + y
                else:
                    x = x + swiglu(a, w["gate_proj"], w["up_proj"], w["down_proj"])
        x = rms_norm(x[logits_from:], params["final_norm"], eps)
        logits = x @ _f32(params["lm_head"])
    out = (logits,)
    if return_experts:
        out += (jnp.stack(chosen_all),)
    if return_selection:
        out += (jnp.stack(picked_all),)
    return out if len(out) > 1 else logits
