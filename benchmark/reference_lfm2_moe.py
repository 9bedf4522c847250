"""Plain reference of LFM2-MoE (``model_type: lfm2_moe``): the published
layer equations in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.  No cache, no batching, no
kernels; a Python loop over the layers and over the experts, every expert
applied to every token and masked by the routing weights - obviously
dropless.  Imports ``jax`` and ``numpy`` only, nothing of the program.

    logits, chosen = forward(params, config, ids, return_experts=True)

``params`` is the program's parameter pytree (``models.init_params``):
``embed_tokens [V, H]``, ``final_norm [H]`` and ``layers``, a list with one
dict per RUN of like layers, its leaves stacked on the run's length
(``runs`` below re-derives the runs from ``layer_types`` and
``num_dense_layers``; projection weights are stored ``(in, out)``).
``config`` is the configuration file's dict (the published ``config.json``
keys).  Leaves in bf16 are upcast one layer - and one expert - at a time, so
the 5.4 B parameters of the benchmark's cut never exist in float32 at once.

Block ``l`` (pre-norm RMSNorm, ``norm_eps``, weight ``w`` not ``1 + w``):
``h = x + Op_l(norm(x))``, ``y = h + FF_l(norm(h))``; after the last block
one RMSNorm, then the head, which is the embedding matrix.

Departures from the published ``modeling_lfm2_moe.py``, each marked
``DEPARTURE`` where it happens:

1. everything is float32 (the published model runs in bf16, and so does the
   program: that difference is what the comparison measures);
2. the short convolution is written as the sum of its three shifted taps,
   not as a padded ``conv1d`` cut to the sequence's length;
3. the experts are applied densely (each to every token, times a weight
   that is 0 where the token did not choose it) where the published code
   gathers each expert's tokens - the same sums, in another order;
4. the head is tied to the embedding (the family does; ``config.json`` has no
   key for it) and the scores are a sigmoid (the published modeling code's
   choice, no config key either).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def runs(config: dict) -> list[tuple[str, str, int]]:
    """``(operator, feed-forward, count)`` for every run of like layers
    (an expert layer is always a run of its own: the program's rule)."""
    out: list[tuple[str, str, int]] = []
    for i, kind in enumerate(config["layer_types"]):
        op = "conv" if kind == "conv" else "attn"
        ff = "dense" if i < config.get("num_dense_layers", 0) else "experts"
        if out and out[-1][:2] == (op, ff) and ff != "experts":
            out[-1] = (op, ff, out[-1][2] + 1)
        else:
            out.append((op, ff, 1))
    return out


def _f32(a) -> jnp.ndarray:
    return jnp.asarray(a).astype(jnp.float32)  # DEPARTURE 1


def rms_norm(x: jnp.ndarray, w, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate-half RoPE over ``x [S, heads, D]`` at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def conv_operator(u: jnp.ndarray, w: dict, config: dict) -> jnp.ndarray:
    """Gated short convolution over ``u [S, H]``."""
    taps = config.get("conv_L_cache", 3)
    gate_b, gate_c, x = jnp.split(u @ _f32(w["in_proj"]), 3, axis=-1)
    z = gate_b * x
    filt = _f32(w["conv_filter"])  # [H, taps]
    # DEPARTURE 2: c_t = sum_j filt[:, j] * z_{t-(taps-1)+j}, z = 0 before 0
    c = jnp.zeros_like(z)
    for j in range(taps):
        shift = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((shift, z.shape[1]), z.dtype), z[:z.shape[0] - shift]])
        c = c + shifted * filt[:, j]
    return (gate_c * c) @ _f32(w["out_proj"])


def attention_operator(u: jnp.ndarray, w: dict, config: dict) -> jnp.ndarray:
    s, h = u.shape
    nh, nk = config["num_attention_heads"], config["num_key_value_heads"]
    d = config.get("head_dim") or h // nh
    eps = config["norm_eps"]
    q = (u @ _f32(w["q_proj"])).reshape(s, nh, d)
    k = (u @ _f32(w["k_proj"])).reshape(s, nk, d)
    v = (u @ _f32(w["v_proj"])).reshape(s, nk, d)
    # RMSNorm over head_dim on every q and k head, before RoPE
    q = rope(rms_norm(q, w["ln_q"], eps), config["rope_theta"])
    k = rope(rms_norm(k, w["ln_k"], eps), config["rope_theta"])
    k = jnp.repeat(k, nh // nk, axis=1)  # kv-major: q head i reads kv head i // group
    v = jnp.repeat(v, nh // nk, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    probs = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    out = jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, nh * d)
    return out @ _f32(w["o_proj"])


def dense_ff(a: jnp.ndarray, w: dict) -> jnp.ndarray:
    return (jax.nn.silu(a @ _f32(w["gate_proj"])) * (a @ _f32(w["up_proj"]))) \
        @ _f32(w["down_proj"])


def route(a: jnp.ndarray, w: dict, config: dict) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(scores [S, E], chosen [S, k])``: sigmoid scores over all experts,
    the top k by score + selection bias."""
    scores = jax.nn.sigmoid(a @ _f32(w["router"]))  # DEPARTURE 4
    select = scores
    if config.get("use_expert_bias") and "expert_bias" in w:
        select = scores + _f32(w["expert_bias"])
    _, chosen = jax.lax.top_k(select, config["num_experts_per_tok"])
    return scores, chosen


def experts_ff(a: jnp.ndarray, w: dict, config: dict,
               chosen: jnp.ndarray | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(output [S, H], chosen [S, k])``.  ``chosen`` given: those experts
    are used in place of the reference's own choice (the weights are still
    its own scores) - what separates a flipped choice from an error."""
    scores, own = route(a, w, config)
    chosen = own if chosen is None else jnp.asarray(chosen)
    n_exp = scores.shape[1]
    picked = jnp.take_along_axis(scores, chosen, axis=1)  # WITHOUT the bias
    if config.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-6)
    picked = picked * config.get("routed_scaling_factor", 1)
    # [S, E]: a token's weight for each expert, 0 where it did not choose it
    weights = jnp.zeros_like(scores).at[
        jnp.arange(a.shape[0])[:, None], chosen].add(picked)
    out = jnp.zeros_like(a)
    for e in range(n_exp):  # DEPARTURE 3: every expert sees every token
        y = (jax.nn.silu(a @ _f32(w["w1"][e])) * (a @ _f32(w["w3"][e]))) \
            @ _f32(w["w2"][e])
        out = out + y * weights[:, e:e + 1]
    return out, chosen


def forward(params: dict, config: dict, ids, *, return_experts: bool = False,
            experts: list | None = None):
    """Logits ``[S, V]`` float32 of the token ids ``ids [S]`` (one
    sequence, positions 0..S-1) and, on request, each expert layer's
    chosen experts ``[expert layers, S, k]``.  ``experts``: one ``[S, k]``
    array per expert layer to use in place of the reference's own choice."""
    eps = config["norm_eps"]
    ids = np.asarray(ids).reshape(-1)
    chosen_all = []
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.asarray(params["embed_tokens"])[ids])
        for group, (op, ff, count) in zip(params["layers"], runs(config)):
            for i in range(count):
                w = {name: leaf[i] for name, leaf in group.items()}  # one layer
                if op == "conv":
                    x = x + conv_operator(
                        rms_norm(x, w["ln_conv_in"], eps), w, config)
                else:
                    x = x + attention_operator(
                        rms_norm(x, w["ln_attn_in"], eps), w, config)
                a = rms_norm(x, w["ln_mlp_in"], eps)
                if ff == "experts":
                    forced = None if experts is None else experts[len(chosen_all)]
                    y, chosen = experts_ff(a, w, config, forced)
                    chosen_all.append(chosen)
                    x = x + y
                else:
                    x = x + dense_ff(a, w)
        x = rms_norm(x, params["final_norm"], eps)
        logits = x @ _f32(params["embed_tokens"]).T  # DEPARTURE 4: tied head
    if return_experts:
        return logits, jnp.stack(chosen_all)
    return logits
