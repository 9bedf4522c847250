"""Plain reference of Falcon-H1 (``model_type: falcon_h1``): the published
layer equations in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.  No cache, no batching, no
chunks, no segments, no kernels: a Python loop over the layers, and the
state-space recurrence as a ``lax.scan`` over single tokens.  Imports
``jax`` and ``numpy`` only, nothing of the program.

    logits = forward(params, config, ids)            # ids [S] -> [S, V]

``params`` is the program's parameter pytree (``models.init_params``):
``embed_tokens [V, H]``, ``final_norm [H]``, ``lm_head [H, V]`` and
``layers``, a list with ONE dict (the stack is one run of like layers) whose
leaves are stacked on the layer count; projection weights are stored
``(in, out)``.  ``config`` is the configuration file's dict (the published
``config.json`` keys).  Leaves in bf16 are upcast one layer at a time, so
the 5.3 B parameters of the benchmark's cut never exist in float32 at once.

Block ``l`` (RMSNorm with ``rms_norm_eps``, weight ``w`` not ``1 + w``):
``u = norm(x)``; BOTH mixers read ``u`` and their results are summed,
``h = x + ssm_out_multiplier * Mamba(u) + attention_out_multiplier *
Attn(attention_in_multiplier * u)``; then ``y = h + FF(norm(h))``.  After
the last block one RMSNorm, then the (untied) head times
``lm_head_multiplier``; the embedding is times ``embedding_multiplier``.

Departures from the published ``modeling_falcon_h1.py``, each marked
``DEPARTURE`` where it happens:

1. everything is float32 (the published model runs in bf16, and so does the
   program: that difference is what the comparison measures);
2. the recurrence is the defining one, token by token, where the published
   code runs a chunked kernel (``mamba_chunk_size``) - the same sums, in
   another order;
3. the depthwise convolution is written as the sum of its shifted taps, not
   as a padded ``conv1d`` cut to the sequence's length;
4. the published code folds the multipliers of ``in_proj``'s five slices
   into one vector (``mup_vector``) and so does this; the order of the slices
   ``[z, x, B, C, dt]``, the group-wise gated norm and the head -> group map
   ``h // (heads / groups)`` are the modeling code's, not ``config.json``'s.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def _f32(a) -> jnp.ndarray:
    return jnp.asarray(a).astype(jnp.float32)  # DEPARTURE 1


def rms_norm(x: jnp.ndarray, w, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """Rotate-half RoPE over ``x [S, heads, D]`` at positions 0..S-1."""
    s, _, d = x.shape
    inv = 1.0 / float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + rot * sin


def sizes(config: dict) -> dict:
    """The mixer's sizes from the configuration's keys."""
    d_ssm = config.get("mamba_d_ssm") or config["mamba_expand"] * config["hidden_size"]
    heads, groups = config["mamba_n_heads"], config.get("mamba_n_groups", 1)
    return dict(d_ssm=d_ssm, heads=heads, d_head=config["mamba_d_head"],
                groups=groups, d_state=config["mamba_d_state"],
                taps=config.get("mamba_d_conv", 4),
                conv_dim=d_ssm + 2 * groups * config["mamba_d_state"])


def attention(u: jnp.ndarray, w: dict, config: dict) -> jnp.ndarray:
    """Causal GQA over ``u [S, H]`` (already times ``attention_in_multiplier``)."""
    s = u.shape[0]
    nq, nk = config["num_attention_heads"], config["num_key_value_heads"]
    d = config.get("head_dim") or config["hidden_size"] // nq
    q = (u @ _f32(w["q_proj"])).reshape(s, nq, d)
    k = (u @ _f32(w["k_proj"])).reshape(s, nk, d) * config.get("key_multiplier", 1.0)
    v = (u @ _f32(w["v_proj"])).reshape(s, nk, d)
    q, k = rope(q, config["rope_theta"]), rope(k, config["rope_theta"])
    k, v = jnp.repeat(k, nq // nk, axis=1), jnp.repeat(v, nq // nk, axis=1)
    scores = jnp.einsum("qhd,khd->hqk", q, k) * d ** -0.5
    causal = jnp.tril(jnp.ones((s, s), bool))
    p = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(s, nq * d) @ _f32(w["o_proj"])


def recurrence(x, dt, a, b, c, d_skip, h0=None, *, state_dtype=jnp.float32):
    """``H_t = exp(dt_t A) H_{t-1} + dt_t x_t B_t^T``, ``y_t = H_t C_t + D
    x_t``, one token at a time (DEPARTURE 2).  ``x [S, heads, P]``, ``dt
    [S, heads]``, ``a, d_skip [heads]``, ``b, c [S, groups, N]``; head ``h``
    reads group ``h // (heads / groups)``.  Returns ``(y [S, heads, P], H
    after the last token [heads, P, N])``.  ``state_dtype`` is what ``H`` is
    KEPT in between tokens: float32 as stated, or a lower precision for the
    control that the comparison must refuse."""
    heads, p = x.shape[1], x.shape[2]
    per = heads // b.shape[1]
    b, c = jnp.repeat(b, per, axis=1), jnp.repeat(c, per, axis=1)  # [S, heads, N]
    if h0 is None:
        h0 = jnp.zeros((heads, p, b.shape[-1]), jnp.float32)

    def step(h, tok):
        x_t, dt_t, b_t, c_t = tok
        h = (jnp.exp(dt_t * a)[:, None, None] * h.astype(jnp.float32)
             + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        h = h.astype(state_dtype)
        y_t = jnp.einsum("hpn,hn->hp", h.astype(jnp.float32), c_t)
        return h, y_t + d_skip[:, None] * x_t

    h_end, y = jax.lax.scan(step, h0.astype(state_dtype), (x, dt, b, c))
    return y, h_end.astype(jnp.float32)


def mamba(u: jnp.ndarray, w: dict, config: dict, *, state_dtype=jnp.float32,
          parts: dict | None = None) -> jnp.ndarray:
    """The Mamba-2 mixer over ``u [S, H]`` (the block's input norm)."""
    z_ = sizes(config)
    d_ssm, heads, groups, n = z_["d_ssm"], z_["heads"], z_["groups"], z_["d_state"]
    taps, s = z_["taps"], u.shape[0]
    mult = config.get("ssm_multipliers", [1.0] * 5)
    # DEPARTURE 4: the five slices' multipliers as one vector
    m = np.repeat(np.asarray(mult, np.float32),
                  (d_ssm, d_ssm, groups * n, groups * n, heads))
    p = ((u * config.get("ssm_in_multiplier", 1.0)) @ _f32(w["ssm_in_proj"])) * m
    z, xbc, dt = jnp.split(p, (d_ssm, d_ssm + z_["conv_dim"]), axis=-1)
    # DEPARTURE 3: depthwise causal convolution as shifted taps; what
    # precedes the sequence's start is 0; tap j meets the input at t-(K-1)+j
    filt = _f32(w["ssm_conv"])  # [C, K]
    padded = jnp.concatenate([jnp.zeros((taps - 1, xbc.shape[1]), jnp.float32), xbc])
    conv = sum(padded[j:j + s] * filt[:, j] for j in range(taps))
    if config.get("mamba_conv_bias", True):
        conv = conv + _f32(w["ssm_conv_bias"])
    xbc = jax.nn.silu(conv)
    x, b, c = jnp.split(xbc, (d_ssm, d_ssm + groups * n), axis=-1)
    dt = jax.nn.softplus(dt + _f32(w["ssm_dt_bias"]))
    operands = dict(
        x=x.reshape(s, heads, -1), dt=dt, a=-jnp.exp(_f32(w["ssm_A_log"])),
        b=b.reshape(s, groups, n), c=c.reshape(s, groups, n), d_skip=_f32(w["ssm_D"]))
    y, h_end = recurrence(**operands, state_dtype=state_dtype)
    if parts is not None:
        if "recurrence" in parts:
            # a layer's recurrence by itself: its operands and what it gave,
            # for whoever holds another statement of it to the same inputs
            parts["recurrence"].append(
                {k: np.asarray(v) for k, v in dict(operands, y=y, h=h_end).items()})
        # diagnostics: the state's share of y before the gated norm
        xs = _f32(w["ssm_D"])[:, None] * x.reshape(s, heads, -1)
        parts.setdefault("from_state_rms", []).append(
            float(jnp.sqrt(jnp.mean(jnp.square(y - xs)))))
        parts.setdefault("skip_rms", []).append(float(jnp.sqrt(jnp.mean(jnp.square(xs)))))
    # gated norm (mamba_rms_norm, not norm_before_gate): the mean square
    # over each GROUP's channels
    g = (y.reshape(s, d_ssm) * jax.nn.silu(z)).reshape(s, groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + config.get("rms_norm_eps", 1e-5))
    return (g.reshape(s, d_ssm) * _f32(w["ln_ssm"])) @ _f32(w["ssm_out_proj"])


def feed_forward(a: jnp.ndarray, w: dict, config: dict) -> jnp.ndarray:
    gate_m, down_m = config.get("mlp_multipliers", [1.0, 1.0])
    gate = jax.nn.silu((a @ _f32(w["gate_proj"])) * gate_m)
    return ((a @ _f32(w["up_proj"])) * gate) @ _f32(w["down_proj"]) * down_m


def forward(params: dict, config: dict, ids, *, state_dtype=jnp.float32,
            parts: dict | None = None) -> jnp.ndarray:
    """Logits ``[S, V]`` (float32) of ONE sequence ``ids [S]``."""
    eps = config.get("rms_norm_eps", 1e-5)
    (stack,) = params["layers"]  # one run of like layers
    with jax.default_matmul_precision("highest"):
        x = _f32(jnp.asarray(params["embed_tokens"])[jnp.asarray(ids)])
        x = x * config.get("embedding_multiplier", 1.0)
        for l in range(config["num_hidden_layers"]):
            w = {k: v[l] for k, v in stack.items()}
            u = rms_norm(x, w["ln_attn_in"], eps)
            x = (x + config.get("ssm_out_multiplier", 1.0) * mamba(
                    u, w, config, state_dtype=state_dtype, parts=parts)
                 + config.get("attention_out_multiplier", 1.0) * attention(
                    u * config.get("attention_in_multiplier", 1.0), w, config))
            x = x + feed_forward(rms_norm(x, w["ln_mlp_in"], eps), w, config)
        x = rms_norm(x, params["final_norm"], eps)
        # the head a slice of the vocabulary at a time: 261,120 x 5,120 in
        # float32 would be 5.3 GB beside the bf16 leaves
        head, step = params["lm_head"], 32768
        logits = jnp.concatenate([x @ _f32(head[:, lo:lo + step])
                                  for lo in range(0, head.shape[1], step)], axis=-1)
        return logits * config.get("lm_head_multiplier", 1.0)
