"""Plain reference of Ling-3.0-flash's language model (``model_type:
ling_hybrid``, this repository's own name for the family): the layer
equations in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.  No cache, no batching, no
kernels; the delta-rule recurrence token by token (``lax.scan`` over the
sequence), latent attention in its EXPANDED form (every head its own K and V
through ``kv_b_proj``), a Python loop over the layers and over the experts
held, every expert applied to every token and masked by the routing weights.
Imports ``jax`` and ``numpy`` only, nothing of the program.

    logits, chosen = forward(params, config, ids, return_experts=True)

``params`` is the program's parameter pytree (``models.init_params``): a
list with one dict per layer run, leaves stacked on the run's length,
projection weights stored ``(in, out)``.  ``config`` is the configuration
file's dict: the published keys, ``num_experts`` being the experts HELD,
with the router's width under ``router_experts`` and the first expert held
under ``first_expert`` where the file states one chip's share.  Leaves in
bf16 are upcast one layer - and one expert - at a time.

Block ``l`` (pre-norm RMSNorm, ``rms_norm_eps``): ``h = x + Op_l(norm(x))``,
``y = h + FF_l(norm(h))``; after the last block one RMSNorm, then the untied
head.  ``Op_l`` is latent attention where ``(l + 1) % layer_group_size == 0``
and KDA elsewhere.

KDA (``H`` heads of ``d = head_dim``): ``[q, k, v] = silu(conv4([Wq u, Wk u,
Wv u]))`` (depthwise, causal, ``short_conv_kernel_size`` taps, zeros before
the sequence); ``q, k`` L2-normalised per head (``x / sqrt(sum x^2 + 1e-6)``),
``q`` times ``d^-0.5``; ``g = L sigmoid(exp(A_log) (Wa u + dt_bias))`` per
channel, ``L = kda_lower_bound``; ``beta = sigmoid(w_beta . u)`` a head; per
token ``S <- Diag(exp g) S``, ``r = v - S^T k``, ``S <- S + beta k r^T``,
``o = S^T q``; then ``Wo [rmsnorm_d(o) * sigmoid(W_gamma u)]`` with one gate a
head.  No RoPE.

Latent attention: ``q = u Wq`` -> heads x ``[q_nope | q_pe]``; ``u Wkv_a`` ->
``[c | k_pe]``; ``c' = rmsnorm(c)``; ``c' Wkv_b`` -> heads x ``[k_nope |
v]``; RoPE (base ``rope_theta``) on the ``qk_rope_head_dim`` rotated columns
by HALVES ``(i, i + d/2)``; ``softmax(q k^T (nope + rope)^-0.5) v``, causal.

Feed-forward: layer < ``first_k_dense_replace``: SwiGLU.  After: ``s =
sigmoid(a Wr)`` over every expert of the router; the experts in ``n_group``
groups of consecutive ones, a group's score the sum of its two largest ``s +
bias``, the ``topk_group`` best groups stay and the others' experts are out
(-inf); top k by ``s + bias`` among what stays; weights ``s`` without the bias
/ their sum (+ 1e-20) x ``routed_scaling_factor``; the routed experts HELD
are summed (the others are another holder's), plus ONE shared SwiGLU of
``moe_shared_expert_intermediate_size`` on every token.

Departures from the published description, each marked ``DEPARTURE`` where
it happens:

1. everything is float32 (the published model and the program run bf16:
   that difference is what the comparison measures);
2. the experts are applied densely (each to every token, times a weight
   that is 0 where the token did not choose it);
3. only the experts HELD are summed: with all of them held
   (``router_experts`` = ``num_experts``) it is the published layer;
4. the recurrence runs in its token-by-token form, not the published
   chunked kernel's: the same function;
5. no vision tower, no multi-token prediction head, no clamped SwiGLU
   (``*_swiglu_limit_list`` entries are 0 in every layer the program
   accepts): the configuration's file says why.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def runs(config: dict) -> list[tuple[str, str, int]]:
    """``(operator, feed-forward, count)`` for every run of like layers (an
    expert layer is always a run of its own: the program's rule)."""
    period = config["layer_group_size"]
    dense = config.get("first_k_dense_replace", 0)
    out: list[tuple[str, str, int]] = []
    for i in range(config["num_hidden_layers"]):
        kind = ("kda" if (i + 1) % period else "latent",
                "dense" if i < dense else "experts")
        if out and out[-1][:2] == kind and kind[1] == "dense":
            out[-1] = (*kind, out[-1][2] + 1)
        else:
            out.append((*kind, 1))
    return out


def _f32(a) -> jnp.ndarray:
    return jnp.asarray(a).astype(jnp.float32)  # DEPARTURE 1


def rms_norm(x: jnp.ndarray, w, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope_halves(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """RoPE over ``x [S, heads, D]`` at positions 0..S-1, on the pairs
    ``(i, i + D/2)``."""
    s, _, d = x.shape
    inv = 1.0 / float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_conv(x: jnp.ndarray, filt) -> jnp.ndarray:
    """Depthwise causal convolution of ``x [S, C]`` with ``filt [C, K]``:
    tap ``j`` meets ``x[t - (K - 1) + j]``, zeros before the sequence."""
    s = x.shape[0]
    filt = _f32(filt)
    taps = filt.shape[1]
    ext = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(ext[j:j + s] * filt[:, j] for j in range(taps))


def kda(u: jnp.ndarray, w: dict, config: dict, *, decay: bool = True,
        delta: bool = True) -> jnp.ndarray:
    """One KDA layer's operator over ``u [S, hidden]``.  ``decay`` /
    ``delta`` False leave that part of the recurrence out: controls, not
    the reference."""
    s = u.shape[0]
    nh, d = config["num_attention_heads"], config["head_dim"]
    low = float(config["kda_lower_bound"])

    def stream(name):
        x = jax.nn.silu(causal_conv(u @ _f32(w[f"kda_{name}_proj"]),
                                    w[f"kda_{name}_conv"]))
        return x.reshape(s, nh, d)

    def unit(x):
        return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

    q, k, v = unit(stream("q")) * d ** -0.5, unit(stream("k")), stream("v")
    rate = jnp.exp(_f32(w["kda_A_log"]))[:, None]
    g = low * jax.nn.sigmoid(rate * (
        u @ _f32(w["kda_a_proj"]) + _f32(w["kda_dt_bias"])).reshape(s, nh, d))
    beta = jax.nn.sigmoid(u @ _f32(w["kda_beta_proj"]))  # [S, H]

    def step(state, xs):  # DEPARTURE 4: one token at a time
        q_t, k_t, v_t, g_t, b_t = xs
        if decay:
            state = jnp.exp(g_t)[:, :, None] * state
        r = v_t - jnp.einsum("hkv,hk->hv", state, k_t) if delta else v_t
        state = state + (b_t[:, None] * k_t)[:, :, None] * r[:, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((nh, d, d), jnp.float32),
                        (q, k, v, g, beta))
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + config["rms_norm_eps"]) * _f32(w["ln_kda_out"])
    gate = jax.nn.sigmoid(u @ _f32(w["kda_gate_proj"]))  # one a head
    return (o * gate[:, :, None]).reshape(s, nh * d) @ _f32(w["kda_out_proj"])


def attention(u: jnp.ndarray, w: dict, config: dict,
              q_block: int | None = None) -> jnp.ndarray:
    s = u.shape[0]
    nh = config["num_attention_heads"]
    dn, dr = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    rank, dv = config["kv_lora_rank"], config["v_head_dim"]
    theta = config["rope_theta"]
    q = (u @ _f32(w["q_proj"])).reshape(s, nh, dn + dr)
    kv_a = u @ _f32(w["kv_a_proj"])
    c = rms_norm(kv_a[:, :rank], w["ln_kv_a"], config["rms_norm_eps"])
    kv = (c @ _f32(w["kv_b_proj"])).reshape(s, nh, dn + dv)
    q_pe = rope_halves(q[..., dn:], theta)
    k_pe = rope_halves(kv_a[:, None, rank:], theta)  # [S, 1, dr]
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


def route(a: jnp.ndarray, w: dict, config: dict, *, group_mask: bool = True,
          score_dtype=jnp.float32) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(scores [S, E], chosen [S, k])`` over every expert of the router:
    sigmoid scores, the group limit, the top k by score + bias.
    ``group_mask`` False and ``score_dtype`` are controls."""
    scores = jax.nn.sigmoid((a @ _f32(w["router"])).astype(score_dtype)
                            ).astype(jnp.float32)
    select = scores + _f32(w["expert_bias"])
    n_group, keep = config.get("n_group", 1), config.get("topk_group", 1)
    if group_mask and n_group > 1:
        s, e = select.shape
        grouped = select.reshape(s, n_group, e // n_group)
        group_score = jax.lax.top_k(grouped, 2)[0].sum(axis=-1)  # [S, groups]
        kept = jax.lax.top_k(group_score, keep)[1]
        stays = jnp.zeros((s, n_group), bool).at[
            jnp.arange(s)[:, None], kept].set(True)
        select = jnp.where(stays[:, :, None], grouped, -jnp.inf).reshape(s, e)
    _, chosen = jax.lax.top_k(select, config["num_experts_per_tok"])
    return scores, chosen


def routing_weights(scores: jnp.ndarray, chosen: jnp.ndarray,
                    config: dict) -> jnp.ndarray:
    """``[S, E]``: a token's weight on every expert of the router (0 where
    not chosen)."""
    picked = jnp.take_along_axis(scores, chosen, axis=1)  # WITHOUT the bias
    if config.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    picked = picked * config.get("routed_scaling_factor", 1.0)
    return jnp.zeros_like(scores).at[
        jnp.arange(scores.shape[0])[:, None], chosen].add(picked)


def experts_ff(a: jnp.ndarray, w: dict, config: dict,
               chosen: jnp.ndarray | None = None, *, shared: bool = True,
               group_mask: bool = True) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(routed experts held + the shared expert [S, H], chosen [S, k])``.
    ``chosen`` given: used in place of the reference's own choice."""
    scores, own = route(a, w, config, group_mask=group_mask)
    chosen = own if chosen is None else jnp.asarray(chosen)
    weights = routing_weights(scores, chosen, config)
    first = config.get("first_expert", 0)
    out = jnp.zeros_like(a)
    for e in range(np.shape(w["w1"])[0]):  # DEPARTURES 2, 3
        y = swiglu(a, w["w1"][e], w["w3"][e], w["w2"][e])
        out = out + y * weights[:, first + e:first + e + 1]
    if shared and "shared_gate" in w:
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
    ids = jnp.asarray(ids).reshape(-1)
    chosen_all = []
    with jax.default_matmul_precision(precision):
        x = _f32(jnp.asarray(params["embed_tokens"])[ids])
        for group, (op, ff, count) in zip(params["layers"], runs(config)):
            for i in range(count):
                w = {name: leaf[i] for name, leaf in group.items()}  # one layer
                u = rms_norm(x, w["ln_attn_in"], eps)
                x = x + (kda(u, w, config) if op == "kda"
                         else attention(u, w, config, q_block))
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
