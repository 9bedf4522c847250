"""Plain reference of Brumby's language model (``model_type: brumby``;
Brumby-14B-Base): the layer equations in straightforward ``jax.numpy``,
float32, under ``jax.default_matmul_precision("highest")``.  No cache, no
state, no feature map, no batching, no kernels: power retention in its
ATTENTION form, computed in query blocks so that a chip holds it at the
cell's lengths and beyond.  Imports ``jax`` and ``numpy`` only, nothing of
the program.

    logits = forward(params, config, ids)

``params`` is the program's parameter pytree (``models.init_params``): a
list with ONE dict (the stack is one run of like layers), leaves stacked on
the depth, projection weights stored ``(in, out)``.  ``config`` is the
configuration file's dict: the published keys.  Leaves in bf16 are upcast one
layer at a time.

Block ``l`` (Qwen3's, pre-norm RMSNorm, ``rms_norm_eps``): ``h = x +
Ret_l(norm(x))``, ``y = h + W_down(silu(W_gate n) * W_up n)`` with ``n =
norm(h)``; after the last block one RMSNorm, then the untied head.

``Ret``, for token ``t``, kv head ``m`` and its query heads ``i`` (``i //
(heads / kv heads) = m``), ``d = head_dim``: ``q_i = RoPE_t(rmsnorm_d(W_q
u)_i)``, ``k_m = RoPE_t(rmsnorm_d(W_k u)_m)`` (RoPE base ``rope_theta``, on
the pairs ``(c, c + d/2)``), ``v_m = (W_v u)_m``, ``log g_m,t =
logsigmoid((W_g u_t)_m)``;

    A[t, j] = (q_t . k_j)^2 exp(sum_{s=j+1..t} log g_s)      j <= t
    o_t     = sum_j A[t, j] v_j / sum_j A[t, j]

then ``W_o`` over the heads side by side.  The power is even, so every weight
is non-negative; a constant scale on ``q . k`` cancels, so none is applied.

Departures from the published description, each marked ``DEPARTURE`` where
it happens:

1. everything is float32 (the published model and the program run bf16
   weights and activations: that difference is what the comparison measures);
2. the attention form at EVERY length (the published kernels switch to the
   state form once a sequence is long enough for it to be cheaper: the same
   function; the program serves the state form from the first token);
3. a denominator of exactly 0 gives ``o = 0`` (the program's guard, the same
   expression: no published epsilon is known here);
4. ASSUMED, as ``benchmark/configs/brumby-14b-5l.json`` lists: degree 2; one
   gate a KV head through ``logsigmoid``; q / k RMSNorm and RoPE kept from
   Qwen3; the normaliser is the gated sum of the keys' features.

``controls``: ``"no_normaliser"`` returns the numerator alone, ``"no_gate"``
sets every gate to one — what a comparison must be able to tell from the
model (benchmark/parity_brumby.py), never the model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


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


def retention(u: jnp.ndarray, w: dict, config: dict,
              controls: frozenset = frozenset()) -> jnp.ndarray:
    """One power-retention layer's operator over ``u [S, hidden]``."""
    s = u.shape[0]
    d, eps = config["head_dim"], config["rms_norm_eps"]
    nh, nk = config["num_attention_heads"], config["num_key_value_heads"]
    group = nh // nk
    theta = config["rope_theta"]
    q = rope_halves(rms_norm((u @ _f32(w["q_proj"])).reshape(s, nh, d),
                             w["ln_q"], eps), theta)
    k = rope_halves(rms_norm((u @ _f32(w["k_proj"])).reshape(s, nk, d),
                             w["ln_k"], eps), theta)
    v = (u @ _f32(w["v_proj"])).reshape(s, nk, d)
    log_g = jax.nn.log_sigmoid(u @ _f32(w["ret_gate_proj"]))  # [S, kv heads]
    if "no_gate" in controls:
        log_g = jnp.zeros_like(log_g)
    cs = jnp.cumsum(log_g, axis=0).T  # [kv heads, S]: sum_{s<=t} log g_s
    at = jnp.arange(s)
    out = []
    # DEPARTURE 2: the attention form, a block of queries at a time
    for lo in range(0, s, QUERY_BLOCK):
        hi = min(lo + QUERY_BLOCK, s)
        qb = q[lo:hi].reshape(hi - lo, nk, group, d)
        seen = at[None, :hi] <= at[lo:hi, None]  # [t, j]
        score = jnp.einsum("tmgd,jmd->mgtj", qb, k[:hi])
        decay = jnp.exp(jnp.where(
            seen, cs[:, lo:hi, None] - cs[:, None, :hi], -jnp.inf))
        a = jnp.square(score) * decay[:, None]
        num = jnp.einsum("mgtj,jmv->tmgv", a, v[:hi])
        if "no_normaliser" in controls:
            out.append(num.reshape(hi - lo, nh * d))
            continue
        den = jnp.moveaxis(jnp.sum(a, axis=-1), 2, 0)  # [t, m, g]
        o = num / jnp.where(den > 0.0, den, 1.0)[..., None]  # DEPARTURE 3
        out.append(o.reshape(hi - lo, nh * d))
    return jnp.concatenate(out) @ _f32(w["o_proj"])


def swiglu(a: jnp.ndarray, w: dict) -> jnp.ndarray:
    return (jax.nn.silu(a @ _f32(w["gate_proj"])) * (a @ _f32(w["up_proj"]))
            ) @ _f32(w["down_proj"])


def hidden_states(params, config: dict, ids,
                  controls: frozenset = frozenset()) -> jnp.ndarray:
    """``ids [S]`` -> the last block's output ``[S, hidden]`` (before the
    final norm)."""
    eps = config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed_tokens"][jnp.asarray(ids)])
        (run,) = params["layers"]
        for i in range(config["num_hidden_layers"]):
            w = {name: leaf[i] for name, leaf in run.items()}
            x = x + retention(rms_norm(x, w["ln_attn_in"], eps), w, config,
                              controls)
            x = x + swiglu(rms_norm(x, w["ln_mlp_in"], eps), w)
        return x


def logits_of(params, config: dict, x: jnp.ndarray) -> jnp.ndarray:
    with jax.default_matmul_precision("highest"):
        return rms_norm(x, params["final_norm"], config["rms_norm_eps"]
                        ) @ _f32(params["lm_head"])


def forward(params, config: dict, ids,
            controls: frozenset = frozenset()) -> jnp.ndarray:
    """``ids [S]`` -> logits ``[S, vocab]`` float32."""
    return logits_of(params, config,
                     hidden_states(params, config, ids, controls))
