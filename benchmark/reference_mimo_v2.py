"""Plain reference of the MiMo-V2 family (``model_type: mimo_v2``;
MiMo-V2-Flash, MiMo-V2.5): the layer equations in straightforward
``jax.numpy``, float32, under ``jax.default_matmul_precision("highest")``.
No cache, no paging, no batching, no kernels; a Python loop over the
layers and over the experts held, every expert applied to every token and
masked by the routing weights.  Written from the configuration's keys, not
from ``models/transformer.py``; imports ``jax`` and ``numpy`` only.

    logits, chosen = forward(params, config, ids, return_experts=True)

``params`` is the program's parameter pytree (``models.init_params``): a
list with one dict per run of like layers, leaves stacked on the run's
length (an expert layer is a run of its own), projection weights stored
``(in, out)``.  ``config`` is the configuration
file's dict: the published ``config.json`` keys, ``n_routed_experts`` being
the experts HELD, the router's width under ``router_experts`` and the
first expert held under ``first_expert`` where the file states one chip's
share.  ``q_block`` computes attention over that many queries at a time
(the same sums: a 4,864-token sequence at 64 heads then fits a chip).

Layer ``l`` (pre-norm RMSNorm, ``layernorm_epsilon``): ``h = x +
Attn_l(norm(x))``, ``y = h + FF_l(norm(h))``; after the last one RMSNorm,
then the untied head.

Attention: ``q = u Wq`` as ``num_attention_heads`` heads of ``head_dim``
(192); ``k = u Wk`` as ``K`` heads of ``head_dim``; ``v = u Wv`` as ``K``
heads of ``v_head_dim`` (128); ``K = num_key_value_heads`` where
``hybrid_layer_pattern[l]`` is 0 (a global layer), ``swa_num_key_value_heads``
where it is 1 (a window layer).  RoPE on the first ``int(head_dim x
partial_rotary_factor)`` (64) columns of every q and k head, pairs ``(i, i
+ 32)``, base ``rope_theta`` (global) / ``swa_rope_theta`` (window); the
other columns pass.  ``s_ij = q_i . k_j / sqrt(head_dim)`` for ``j <= i``,
in a window layer only for ``i - j < sliding_window``.  Global layer:
``p = softmax_j(s)``.  Window layer (``add_swa_attention_sink_bias``): one
learned float ``b_h`` a query head joins the row as a column with no
value: ``m_i = max(b_h, max_j s_ij)``, ``p_ij = exp(s_ij - m_i) / (exp(b_h
- m_i) + sum_j exp(s_ij - m_i))``.  ``o_i = attention_value_scale x sum_j
p_ij v_j``, then ``Wo``.

Feed-forward: ``moe_layer_freq[l]`` 0: SwiGLU of ``intermediate_size``.
1: ``sc = sigmoid(a Wr)`` over every expert of the router, the top k of
``sc + e_score_correction_bias``, weights ``sc`` without the bias over
their sum (+ 1e-20), times ``routed_scaling_factor`` (null = 1); the
experts HELD are summed (the others are another holder's); no shared
expert.

Departures and assumptions, each marked where it happens:

1. DEPARTURE: everything is float32 (the published model and the program
   run bf16: that difference is what the comparison measures);
2. DEPARTURE: the experts are applied densely (each to every token, times
   a weight that is 0 where the token did not choose it);
3. DEPARTURE: only the experts HELD are summed: with all of them held
   (``router_experts`` = ``n_routed_experts``) it is the published layer;
4. DEPARTURE: the group mask of ``noaux_tc`` is left out: with ``n_group =
   topk_group = 1`` it is the identity;
5. ASSUMED: ``attention_value_scale`` multiplies the attention's output
   (it is linear: on ``v`` or on ``o`` is the same number);
6. ASSUMED: rotate-half pairing ``(i, i + rope_dim / 2)``, not interleaved;
7. ASSUMED: the sink as above (the form the family's published modelling
   code uses); ``attention_chunk_size`` and ``attention_projection_layout``
   describe an implementation's blocking and a checkpoint's layout, not
   the mathematics.

``controls`` (the parity runs' and the tests' switches, each a departure
the comparison must REFUSE; none is the reference): ``"no_sink"``,
``"window_off_by_one"`` (a window of ``sliding_window + 1``),
``"no_value_scale"``, ``"rotate_all"`` (all of ``head_dim`` rotated).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def runs(config: dict) -> list[tuple[str, str]]:
    """``(attention kind, feed-forward)`` of every layer: ``"global"`` /
    ``"window"`` by ``hybrid_layer_pattern``, ``"dense"`` / ``"experts"``
    by ``moe_layer_freq``."""
    return [("window" if w else "global", "experts" if f else "dense")
            for w, f in zip(config["hybrid_layer_pattern"],
                            config["moe_layer_freq"])]


def _f32(a) -> jnp.ndarray:
    return jnp.asarray(a).astype(jnp.float32)  # DEPARTURE 1


def rms_norm(x: jnp.ndarray, w, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope_leading(x: jnp.ndarray, theta: float, rope_dim: int) -> jnp.ndarray:
    """RoPE over the leading ``rope_dim`` columns of ``x [S, heads, D]`` at
    positions 0..S-1, pairs ``(i, i + rope_dim / 2)`` (ASSUMED 6); the
    other columns pass."""
    s = x.shape[0]
    half = rope_dim // 2
    inv = 1.0 / float(theta) ** (
        jnp.arange(0, rope_dim, 2, dtype=jnp.float32) / rope_dim)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:rope_dim]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, x[..., rope_dim:]], axis=-1)


def attention(u: jnp.ndarray, w: dict, config: dict, kind: str,
              q_block: int | None = None,
              controls: frozenset = frozenset()) -> jnp.ndarray:
    s = u.shape[0]
    window = kind == "window"
    nh, d = config["num_attention_heads"], config["head_dim"]
    dv = config.get("v_head_dim", d)
    nk = config["swa_num_key_value_heads" if window else "num_key_value_heads"]
    theta = config["swa_rope_theta" if window else "rope_theta"]
    rope_dim = int(d * config.get("partial_rotary_factor", 1.0)) // 2 * 2
    if "rotate_all" in controls:
        rope_dim = d
    q = rope_leading((u @ _f32(w["q_proj"])).reshape(s, nh, d), theta, rope_dim)
    k = rope_leading((u @ _f32(w["k_proj"])).reshape(s, nk, d), theta, rope_dim)
    v = (u @ _f32(w["v_proj"])).reshape(s, nk, dv)
    g = nh // nk
    qg = q.reshape(s, nk, g, d)
    sink = None
    if window and config.get("add_swa_attention_sink_bias") and (
            "no_sink" not in controls):
        sink = _f32(w["attn_sink"]).reshape(nk, g)[:, :, None, None]
    span = config["sliding_window"] + ("window_off_by_one" in controls)
    step = q_block or s
    outs = []
    kv_pos = jnp.arange(s)[None, :]
    for q0 in range(0, s, step):
        q_pos = jnp.arange(q0, min(q0 + step, s))[:, None]
        scores = jnp.einsum("qkgd,skd->kgqs", qg[q0:q0 + step], k) * d ** -0.5
        seen = kv_pos <= q_pos
        if window:
            seen = seen & (q_pos - kv_pos < span)
        scores = jnp.where(seen[None, None], scores, -jnp.inf)
        top = scores.max(axis=-1, keepdims=True)
        if sink is not None:
            top = jnp.maximum(top, sink)
        e = jnp.exp(scores - top)
        total = e.sum(axis=-1, keepdims=True)
        if sink is not None:
            total = total + jnp.exp(sink - top)  # ASSUMED 7
        outs.append(jnp.einsum("kgqs,skd->qkgd", e / total, v))
    out = jnp.concatenate(outs, axis=0).reshape(s, nh * dv)
    if "no_value_scale" not in controls:
        out = out * float(config.get("attention_value_scale") or 1.0)  # ASSUMED 5
    return out @ _f32(w["o_proj"])


def swiglu(a: jnp.ndarray, gate, up, down) -> jnp.ndarray:
    return (jax.nn.silu(a @ _f32(gate)) * (a @ _f32(up))) @ _f32(down)


def route(a: jnp.ndarray, w: dict, config: dict) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(scores [S, E], chosen [S, k])`` over every expert of the router:
    sigmoid scores, the top k by score + correction bias (DEPARTURE 4)."""
    scores = jax.nn.sigmoid(a @ _f32(w["router"]))
    _, chosen = jax.lax.top_k(scores + _f32(w["expert_bias"]),
                              config["num_experts_per_tok"])
    return scores, chosen


def experts_ff(a: jnp.ndarray, w: dict, config: dict,
               chosen: jnp.ndarray | None = None) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(routed experts held [S, H], chosen [S, k])``.  ``chosen`` given:
    used in place of the reference's own choice."""
    scores, own = route(a, w, config)
    chosen = own if chosen is None else jnp.asarray(chosen)
    picked = jnp.take_along_axis(scores, chosen, axis=1)  # WITHOUT the bias
    if config.get("norm_topk_prob", True):
        picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)
    scaling = config.get("routed_scaling_factor")
    picked = picked * (1.0 if scaling is None else scaling)
    weights = jnp.zeros_like(scores).at[
        jnp.arange(a.shape[0])[:, None], chosen].add(picked)
    first = config.get("first_expert", 0)
    out = jnp.zeros_like(a)
    for e in range(config["n_routed_experts"]):  # DEPARTURES 2, 3
        y = swiglu(a, w["w1"][e], w["w3"][e], w["w2"][e])
        out = out + y * weights[:, first + e:first + e + 1]
    return out, chosen


def forward(params: dict, config: dict, ids, *, return_experts: bool = False,
            experts: list | None = None, q_block: int | None = None,
            logits_from: int = 0, precision: str = "highest",
            controls=()):
    """Logits ``[S - logits_from, V]`` float32 of the token ids ``ids [S]``
    (one sequence, positions 0..S-1) and, on request, each expert layer's
    chosen experts ``[expert layers, S, k]``.  ``precision``: the matmul
    precision; anything but ``highest`` is a control, not the reference,
    and so is any of ``controls`` (module docstring)."""
    eps = config.get("layernorm_epsilon", 1e-5)
    controls = frozenset(controls)
    ids = np.asarray(ids).reshape(-1)
    chosen_all = []
    with jax.default_matmul_precision(precision):
        x = _f32(jnp.asarray(params["embed_tokens"])[ids])
        # the program stacks a run of like layers on a leading axis; one
        # layer's leaves are taken out at a time (a generator: all seven
        # at once would be a second copy of the weights on the device)
        layers = ({name: leaf[i] for name, leaf in group.items()}
                  for group in params["layers"]
                  for i in range(len(group["ln_attn_in"])))
        for w, (kind, ff) in zip(layers, runs(config)):
            x = x + attention(rms_norm(x, w["ln_attn_in"], eps), w, config,
                              kind, q_block, controls)
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
