"""Plain reference of the DeepSeek-V3 family (``model_type: deepseek_v3``,
Kanana-2-30B-A3B): the published layer equations in straightforward
``jax.numpy``, float32, under ``jax.default_matmul_precision("highest")``.
No cache, no batching, no kernels, attention in its EXPANDED form (every
head its own K and V through ``kv_b_proj``); a Python loop over the layers
and over the experts held, every expert applied to every token and masked
by the routing weights.  Imports ``jax`` and ``numpy`` only, nothing of the
program.

    logits, chosen = forward(params, config, ids, return_experts=True)

``params`` is the program's parameter pytree (``models.init_params``): a
list with one dict per layer run, leaves stacked on the run's length,
projection weights stored ``(in, out)``.  ``config`` is the configuration
file's dict: the published ``config.json`` keys, ``n_routed_experts`` being
the experts HELD, with the router's width under ``router_experts`` and the
first expert held under ``first_expert`` where the file states one chip's
share.  Leaves in bf16 are upcast one layer - and one expert - at a time.
``blocks=(q, k)`` computes attention over ``q`` queries at a time (the
same sums; a 2,176-token sequence at 32 heads then fits a chip).

Block ``l`` (pre-norm RMSNorm, ``rms_norm_eps``): ``h = x + Attn(norm(x))``,
``y = h + FF_l(norm(h))``; after the last block one RMSNorm, then the untied
head.

Attention: ``q = u Wq`` -> heads x ``[q_nope | q_pe]``; ``u Wkv_a`` ->
``[c | k_pe]`` (one ``k_pe`` for all heads); ``c' = rmsnorm(c)``;
``c' Wkv_b`` -> heads x ``[k_nope | v]``; RoPE on ``q_pe`` and ``k_pe``
over the PAIRS ``(2i, 2i+1)`` (``rope_interleave``); ``softmax(q k^T (nope +
rope)^-0.5) v``, causal.

Feed-forward: layer < ``first_k_dense_replace``: SwiGLU.  After: ``s =
sigmoid(a Wr)`` over every expert of the router, top k by ``s +
e_score_correction_bias``, weights ``s`` without the bias / their sum (+
1e-20) x ``routed_scaling_factor``; the routed experts HELD are summed
(the others are another holder's), plus ONE shared SwiGLU of
``n_shared_experts x moe_intermediate_size`` on every token.

Departures from the published ``modeling_deepseek_v3.py``, each marked
``DEPARTURE`` where it happens:

1. everything is float32 (the published model and the program run bf16:
   that difference is what the comparison measures);
2. RoPE is written on the pairs directly (``out[2i] = x[2i] cos - x[2i+1]
   sin``, ``out[2i+1] = x[2i+1] cos + x[2i] sin``) where the published code
   moves the pairs apart and rotates halves: the same rotation, the result
   left in the checkpoint's own order (a dot product does not see an
   order both sides share);
3. the experts are applied densely (each to every token, times a weight
   that is 0 where the token did not choose it);
4. only the experts HELD are summed: with all of them held
   (``router_experts`` = ``n_routed_experts``) it is the published layer;
5. the group mask of ``noaux_tc`` is left out: with ``n_group = topk_group
   = 1`` it is the identity.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def runs(config: dict) -> list[tuple[str, int]]:
    """``(feed-forward, count)`` for every run of like layers (an expert
    layer is always a run of its own: the program's rule)."""
    dense = config.get("first_k_dense_replace", 0)
    return ([("dense", dense)] if dense else []) + [
        ("experts", 1)] * (config["num_hidden_layers"] - dense)


def _f32(a) -> jnp.ndarray:
    return jnp.asarray(a).astype(jnp.float32)  # DEPARTURE 1


def rms_norm(x: jnp.ndarray, w, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope_pairs(x: jnp.ndarray, theta: float, interleave: bool = True) -> jnp.ndarray:
    """RoPE over ``x [S, heads, D]`` at positions 0..S-1, on the pairs
    ``(2i, 2i+1)`` (DEPARTURE 2), or ``(i, i + D/2)`` without
    ``interleave``."""
    s, _, d = x.shape
    inv = 1.0 / float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = (x[..., 0::2], x[..., 1::2]) if interleave else (
        x[..., :d // 2], x[..., d // 2:])
    ra, rb = a * cos - b * sin, b * cos + a * sin
    if interleave:
        return jnp.stack([ra, rb], axis=-1).reshape(x.shape)
    return jnp.concatenate([ra, rb], axis=-1)


def attention(u: jnp.ndarray, w: dict, config: dict,
              q_block: int | None = None) -> jnp.ndarray:
    s = u.shape[0]
    nh = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, dv = config["kv_lora_rank"], config["v_head_dim"]
    theta = config["rope_theta"]
    inter = config.get("rope_interleave", False)
    q = (u @ _f32(w["q_proj"])).reshape(s, nh, dn + dr)
    kv_a = u @ _f32(w["kv_a_proj"])
    c = rms_norm(kv_a[:, :rank], w["ln_kv_a"], config["rms_norm_eps"])
    kv = (c @ _f32(w["kv_b_proj"])).reshape(s, nh, dn + dv)
    q_pe = rope_pairs(q[..., dn:], theta, inter)
    k_pe = rope_pairs(kv_a[:, None, rank:], theta, inter)  # [S, 1, dr]
    qf = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    kf = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, (s, nh, dr))], axis=-1)
    v = kv[..., dn:]
    scale = (dn + dr) ** -0.5
    step = q_block or s
    outs = []
    for q0 in range(0, s, step):
        scores = jnp.einsum("qhd,khd->hqk", qf[q0:q0 + step], kf) * scale
        causal = (jnp.arange(s)[None, :]
                  <= jnp.arange(q0, min(q0 + step, s))[:, None])
        probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v))
    out = jnp.concatenate(outs, axis=0).reshape(s, nh * dv)
    return out @ _f32(w["o_proj"])


def swiglu(a: jnp.ndarray, gate, up, down) -> jnp.ndarray:
    return (jax.nn.silu(a @ _f32(gate)) * (a @ _f32(up))) @ _f32(down)


def route(a: jnp.ndarray, w: dict, config: dict) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(scores [S, E], chosen [S, k])`` over every expert of the router:
    sigmoid scores, the top k by score + correction bias (DEPARTURE 5)."""
    scores = jax.nn.sigmoid(a @ _f32(w["router"]))
    _, chosen = jax.lax.top_k(scores + _f32(w["expert_bias"]),
                              config["num_experts_per_tok"])
    return scores, chosen


def experts_ff(a: jnp.ndarray, w: dict, config: dict,
               chosen: jnp.ndarray | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(routed experts held + shared experts [S, H], chosen [S, k])``.
    ``chosen`` given: used in place of the reference's own choice."""
    scores, own = route(a, w, config)
    chosen = own if chosen is None else jnp.asarray(chosen)
    picked = jnp.take_along_axis(scores, chosen, axis=1)  # WITHOUT the bias
    if config.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    picked = picked * config.get("routed_scaling_factor", 1.0)
    weights = jnp.zeros_like(scores).at[
        jnp.arange(a.shape[0])[:, None], chosen].add(picked)
    first = config.get("first_expert", 0)
    out = jnp.zeros_like(a)
    for e in range(config["n_routed_experts"]):  # DEPARTURES 3, 4
        y = swiglu(a, w["w1"][e], w["w3"][e], w["w2"][e])
        out = out + y * weights[:, first + e:first + e + 1]
    if config.get("n_shared_experts"):
        out = out + swiglu(a, w["shared_gate"], w["shared_up"], w["shared_down"])
    return out, chosen


def forward(params: dict, config: dict, ids, *, return_experts: bool = False,
            experts: list | None = None, q_block: int | None = None,
            logits_from: int = 0, precision: str = "highest"):
    """Logits ``[S - logits_from, V]`` float32 of the token ids ``ids [S]``
    (one sequence, positions 0..S-1) and, on request, each expert layer's
    chosen experts ``[expert layers, S, k]``.  ``precision``: the matmul
    precision; anything but ``highest`` is a control, not the reference."""
    eps = config["rms_norm_eps"]
    ids = np.asarray(ids).reshape(-1)
    chosen_all = []
    with jax.default_matmul_precision(precision):
        x = _f32(jnp.asarray(params["embed_tokens"])[ids])
        for group, (ff, count) in zip(params["layers"], runs(config)):
            for i in range(count):
                w = {name: leaf[i] for name, leaf in group.items()}  # one layer
                x = x + attention(rms_norm(x, w["ln_attn_in"], eps), w, config,
                                  q_block)
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
    if return_experts:
        return logits, jnp.stack(chosen_all)
    return logits
