"""Plain reference of the AFMoE family (``model_type: afmoe``; Trinity-Large):
the layer equations in straightforward ``jax.numpy``, float32, under
``jax.default_matmul_precision("highest")``.  No cache, no paging, no
batching, no kernels; a Python loop over the layers and over the experts
held, every expert applied to every token and masked by the routing
weights.  Written from the configuration's keys, not from
``models/transformer.py``; imports ``jax`` and ``numpy`` only.

    logits, chosen = forward(params, config, ids, return_experts=True)

``params`` is the program's parameter pytree (``models.init_params``): a
list with one dict per run of like layers, leaves stacked on the run's
length (an expert layer is a run of its own), projection weights stored
``(in, out)``.  ``config`` is the configuration file's dict: the published
``config.json`` keys, ``num_experts`` being the experts HELD, the router's
width under ``router_experts`` and the first expert held under
``first_expert`` where the file states one chip's share.  ``q_block``
computes attention over that many queries at a time (the same sums: an
8,832-token sequence at 48 heads then fits a chip).

``RMSNorm(x; w) = w * x / sqrt(mean(x^2) + rms_norm_eps)`` (no unit
offset).  ``x = E[ids] * sqrt(hidden_size)`` (``mup_enabled``).  Layer
``i``, ``L`` where ``layer_types[i] == "sliding_attention"``, ``G`` where
``"full_attention"``:

    h    = RMSNorm(x; input_layernorm)
    q, k, v, g = h Wq [heads x 128], h Wk [K x 128], h Wv [K x 128], h Wg [heads x 128]
    q, k = RMSNorm_128(q; q_norm), RMSNorm_128(k; k_norm)    one weight of 128 each
    L:   q, k = RoPE(q, k)    theta rope_theta, all columns, pairs (i, i + 64)
    G:   no positional encoding
    a    = softmax(q k^T / sqrt(128) + mask) v      mask: j <= i;  L also i - j < sliding_window
    a    = a * sigmoid(g)                           per head and column, BEFORE Wo
    x    = x + RMSNorm(a Wo; post_attention_layernorm)
    h2   = RMSNorm(x; pre_mlp_layernorm)
    i < num_dense_layers:  m = SwiGLU(h2)           intermediate_size wide
    else:  s = sigmoid(h2 Wr) [router_experts];  C = top-k of (s + expert_bias)
           w_e = s_e / (sum_C s + 1e-20) * route_scale
           m = SwiGLU_shared(h2) + sum_{e in C, e HELD} w_e SwiGLU_e(h2)
    x    = x + RMSNorm(m; post_mlp_layernorm)       of the SUM of shared and routed parts
    logits = RMSNorm(x; norm) W_head

Departures and assumptions, each marked where it happens:

1. DEPARTURE: everything is float32 (the published model and the program
   run bf16: that difference is what the comparison measures);
2. DEPARTURE: the experts are applied densely (each to every token, times
   a weight that is 0 where the token did not choose it);
3. DEPARTURE: only the experts HELD are summed, and the post-norm is taken
   of that partial sum (shared expert + the held experts' part): with all
   experts held (``router_experts`` = ``num_experts``) it is the published
   layer;
4. DEPARTURE: the group mask is left out: ``n_group = num_expert_groups =
   topk_group = num_limited_groups = 1`` make it the identity;
5. ASSUMED: rotate-half pairing ``(i, i + head_dim / 2)``, not interleaved;
6. ASSUMED: q/k norm BEFORE RoPE; the gate read from the same normed input
   as q;
7. ASSUMED: ``expert_bias`` joins the scores for the SELECTION only; the
   ``1e-20``; the shared expert ``moe_intermediate_size x
   num_shared_experts`` wide; muP scaling on the embedding only.

``controls`` (the parity runs' and the tests' switches, each a departure
the comparison must REFUSE; none is the reference): ``"window_off_by_one"``
(a window of ``sliding_window + 1``), ``"rope_in_global"`` (the global
layers rotated like the window ones), ``"no_gate"``,
``"post_norm_routed_only"`` (``x + SwiGLU_shared + RMSNorm(routed part)``),
``"bf16_router"`` (the router's scores computed in bfloat16).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def runs(config: dict) -> list[tuple[str, str]]:
    """``(attention kind, feed-forward)`` of every layer: ``"window"`` /
    ``"global"`` by ``layer_types``, ``"dense"`` for the first
    ``num_dense_layers``, ``"experts"`` after them."""
    return [("window" if t == "sliding_attention" else "global",
             "dense" if i < config.get("num_dense_layers", 0) else "experts")
            for i, t in enumerate(config["layer_types"])]


def _f32(a) -> jnp.ndarray:
    return jnp.asarray(a).astype(jnp.float32)  # DEPARTURE 1


def rms_norm(x: jnp.ndarray, w, eps: float) -> jnp.ndarray:
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f32(w)


def rope(x: jnp.ndarray, theta: float) -> jnp.ndarray:
    """RoPE over all columns of ``x [S, heads, D]`` at positions 0..S-1,
    pairs ``(i, i + D / 2)`` (ASSUMED 5)."""
    s, _, d = x.shape
    half = d // 2
    inv = 1.0 / float(theta) ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(u: jnp.ndarray, w: dict, config: dict, kind: str,
              q_block: int | None = None,
              controls: frozenset = frozenset()) -> jnp.ndarray:
    """``(a * sigmoid(g)) Wo`` of one layer, before its post-norm."""
    s = u.shape[0]
    window = kind == "window"
    nh, nk, d = (config["num_attention_heads"], config["num_key_value_heads"],
                 config["head_dim"])
    eps = config.get("rms_norm_eps", 1e-5)
    q = rms_norm((u @ _f32(w["q_proj"])).reshape(s, nh, d), w["ln_q"], eps)
    k = rms_norm((u @ _f32(w["k_proj"])).reshape(s, nk, d), w["ln_k"], eps)
    v = (u @ _f32(w["v_proj"])).reshape(s, nk, d)
    if window or "rope_in_global" in controls:  # ASSUMED 6: after the norm
        q, k = rope(q, config["rope_theta"]), rope(k, config["rope_theta"])
    g = nh // nk
    qg = q.reshape(s, nk, g, d)
    span = (config.get("sliding_window") or 0) + ("window_off_by_one" in controls)
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
        outs.append(jnp.einsum(
            "kgqs,skd->qkgd", jax.nn.softmax(scores, axis=-1), v))
    out = jnp.concatenate(outs, axis=0).reshape(s, nh * d)
    if "no_gate" not in controls:
        out = out * jax.nn.sigmoid(u @ _f32(w["attn_gate_proj"]))
    return out @ _f32(w["o_proj"])


def swiglu(a: jnp.ndarray, gate, up, down) -> jnp.ndarray:
    return (jax.nn.silu(a @ _f32(gate)) * (a @ _f32(up))) @ _f32(down)


def route(a: jnp.ndarray, w: dict, config: dict,
          controls: frozenset = frozenset()) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(scores [S, E], chosen [S, k])`` over every expert of the router:
    sigmoid scores, the top k by score + ``expert_bias`` (DEPARTURE 4)."""
    if "bf16_router" in controls:
        scores = jax.nn.sigmoid(
            a.astype(jnp.bfloat16) @ jnp.asarray(w["router"]).astype(jnp.bfloat16)
        ).astype(jnp.float32)
    else:
        scores = jax.nn.sigmoid(a @ _f32(w["router"]))
    _, chosen = jax.lax.top_k(scores + _f32(w["expert_bias"]),
                              config["num_experts_per_tok"])
    return scores, chosen


def routed_part(a: jnp.ndarray, w: dict, config: dict,
                chosen: jnp.ndarray | None = None,
                controls: frozenset = frozenset()
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(the held experts' part of the sum [S, H], chosen [S, k])``.
    ``chosen`` given: used in place of the reference's own choice."""
    scores, own = route(a, w, config, controls)
    chosen = own if chosen is None else jnp.asarray(chosen)
    picked = jnp.take_along_axis(scores, chosen, axis=1)  # WITHOUT the bias
    picked = picked / (picked.sum(axis=1, keepdims=True) + 1e-20)  # route_norm
    picked = picked * float(config.get("route_scale", 1.0))
    weights = jnp.zeros_like(scores).at[
        jnp.arange(a.shape[0])[:, None], chosen].add(picked)
    first = config.get("first_expert", 0)
    out = jnp.zeros_like(a)
    for e in range(config["num_experts"]):  # DEPARTURES 2, 3
        y = swiglu(a, w["w1"][e], w["w3"][e], w["w2"][e])
        out = out + y * weights[:, first + e:first + e + 1]
    return out, chosen


def layer(x: jnp.ndarray, w: dict, config: dict, kind: str, ff: str, *,
          q_block: int | None = None, forced=None,
          controls: frozenset = frozenset()):
    """One layer: ``(x_out, chosen experts | None, m)`` with ``m`` the
    feed-forward's sum BEFORE its post-norm (what the share test adds up)."""
    eps = config.get("rms_norm_eps", 1e-5)
    a = attention(rms_norm(x, w["ln_attn_in"], eps), w, config, kind,
                  q_block, controls)
    x = x + rms_norm(a, w["ln_attn_out"], eps)
    h2 = rms_norm(x, w["ln_mlp_in"], eps)
    if ff == "dense":
        m = swiglu(h2, w["gate_proj"], w["up_proj"], w["down_proj"])
        return x + rms_norm(m, w["ln_mlp_out"], eps), None, m
    routed, chosen = routed_part(h2, w, config, forced, controls)
    shared = swiglu(h2, w["shared_gate"], w["shared_up"], w["shared_down"])
    m = shared + routed
    if "post_norm_routed_only" in controls:
        return x + shared + rms_norm(routed, w["ln_mlp_out"], eps), chosen, m
    return x + rms_norm(m, w["ln_mlp_out"], eps), chosen, m


def forward(params: dict, config: dict, ids, *, return_experts: bool = False,
            experts: list | None = None, q_block: int | None = None,
            logits_from: int = 0, precision: str = "highest",
            controls=()):
    """Logits ``[S - logits_from, V]`` float32 of the token ids ``ids [S]``
    (one sequence, positions 0..S-1) and, on request, each expert layer's
    chosen experts ``[expert layers, S, k]``.  ``precision``: the matmul
    precision; anything but ``highest`` is a control, not the reference,
    and so is any of ``controls`` (module docstring)."""
    eps = config.get("rms_norm_eps", 1e-5)
    controls = frozenset(controls)
    ids = np.asarray(ids).reshape(-1)
    chosen_all = []
    with jax.default_matmul_precision(precision):
        x = _f32(jnp.asarray(params["embed_tokens"])[ids])
        if config.get("mup_enabled", False):  # ASSUMED 7: the embedding only
            x = x * float(config["hidden_size"]) ** 0.5
        # the program stacks a run of like layers on a leading axis; one
        # layer's leaves are taken out at a time (a generator: all five at
        # once would be a second copy of the weights on the device)
        layers = ({name: leaf[i] for name, leaf in group.items()}
                  for group in params["layers"]
                  for i in range(len(group["ln_attn_in"])))
        for w, (kind, ff) in zip(layers, runs(config)):
            forced = (None if experts is None or ff == "dense"
                      else experts[len(chosen_all)])
            x, chosen, _ = layer(x, w, config, kind, ff, q_block=q_block,
                                 forced=forced, controls=controls)
            if chosen is not None:
                chosen_all.append(chosen)
        x = rms_norm(x[logits_from:], params["final_norm"], eps)
        logits = x @ _f32(params["lm_head"])
    if return_experts:
        return logits, jnp.stack(chosen_all)
    return logits
