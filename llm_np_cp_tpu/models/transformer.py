"""Generic decoder-only transformer forward pass.

Covers the reference's two families with one traced function:
- Llama-3.2: pre-norm residual blocks, SwiGLU MLP, tied lm_head
  (llama3.2_model.py:511-822)
- Gemma-2: sandwich norms (4/layer, post-norms inside the residual,
  gemma2_model.py:588-643), embedding scaling (:738-739), GeGLU, attention
  and final-logit softcapping, alternating sliding/global attention —
  including the two features the reference dropped (SURVEY §2.7).

Architecture (TPU-first, not a translation):
- params are a dict pytree; per-layer weights are stacked on a leading
  ``[num_layers, ...]`` axis and the layer loop is ``lax.scan`` — compile
  time is O(1) in depth and XLA double-buffers the per-layer weight fetch
  from HBM (the reference re-dispatches Python per layer,
  llama3.2_model.py:685-697).
- projection weights are stored **(in, out)** so every matmul is
  ``x @ W`` with f32 accumulation on the MXU (HF checkpoints store
  [out, in]; the loader transposes once at load time).
- activations keep layout [B, S, H*D] / [B, S, K, D]: sequence second,
  head_dim last — KV-cache writes are contiguous and the lane dim is the
  128-wide axis.
- masks derive from positions, never from shape branches (the reference's
  ``q_len > 2`` mask guard, llama3.2_model.py:471, is a bug we don't copy).
"""

from __future__ import annotations

import math
import os
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from llm_np_cp_tpu.cache import (
    STATE_LEAVES,
    KVCache,
    write_at,
    update_layer,
    update_layer_quantized,
)
from llm_np_cp_tpu.config import STATE_ONLY_OPS, ModelConfig
from llm_np_cp_tpu.ops.activations import ACT2FN, softcap
from llm_np_cp_tpu.ops.attention import (
    attend_in_query_blocks,
    causal_mask,
    gqa_attention,
)
from llm_np_cp_tpu.ops.moe import (
    SCOPE_MOE_EXPERTS,
    SCOPE_MOE_ROUTE,
    moe_dropless,
)
from llm_np_cp_tpu.ops import sparse_index
from llm_np_cp_tpu.ops.sparse_index import (
    SCOPE_DSA_ATTN,
    SCOPE_DSA_PROJ,
    SCOPE_DSA_SCORE,
    SCOPE_DSA_SELECT,
)
from llm_np_cp_tpu.ops.norms import rms_norm
from llm_np_cp_tpu.ops.rope import apply_rope, rope_cos_sin
from llm_np_cp_tpu.quant import dequantize_kv, quant_einsum

Params = dict[str, Any]

# The parts of a step, as ``jax.named_scope`` names.  A scope is metadata
# on the operations traced under it (the compiled program is the same
# with or without it); serve/opmap.py reads the names back out of the
# compiled module so a device profile can be cut by them.  The layer's
# five are entered in ``run_decoder_layer`` (every caller shares them);
# ``embed`` and ``tail`` belong to the step around the layer loop.
SCOPE_EMBED = "embed"
SCOPE_QKV = "qkv"            # input norm, q/k/v projections, RoPE
SCOPE_KV_WRITE = "kv_write"  # the cache / pool write
SCOPE_ATTN = "attn"
SCOPE_O_PROJ = "o_proj"      # output projection, post-norm, residual
SCOPE_MLP = "mlp"            # input norm, MLP, post-norm, residual
SCOPE_TAIL = "tail"          # final norm, head, sampling
# a stack with conv layers and routed experts adds three: the gated short
# convolution whole (norm, in_proj, gates, filter, out_proj, state read /
# write), and the two halves of the expert layer (ops/moe.py); its dense
# feed-forwards stay under ``mlp``
SCOPE_CONV = "conv"
# a state-space mixer beside attention adds two: everything around the
# recurrence (in_proj, multipliers, the convolution and its history, the
# gated norm, out_proj; the input norm is the attention's), and the
# recurrence itself with the state it reads and writes (ops/ssm.py)
SCOPE_SSM_PROJ = "ssm_proj"
SCOPE_SSM_SCAN = "ssm_scan"
# shared experts beside the routed ones add one: the SwiGLU every token
# takes (its input norm is the router's, under ``moe_route``)
SCOPE_MOE_SHARED = "moe_shared"
# a stack whose window layers hold pages of their own tells its two kinds
# of attention apart (entered inside ``attn``: the innermost scope names
# an operation, serve/opmap.py)
SCOPE_ATTN_GLOBAL = "attn_global"
SCOPE_ATTN_WINDOW = "attn_window"
# a delta-rule linear-attention layer adds two, as the state-space mixer
# does: everything around the recurrence (input norm, the projections, the
# convolution and its history, gates, the output norm, out_proj), and
# everything that touches the matrix state (ops/kda.py)
SCOPE_KDA_PROJ = "kda_proj"
SCOPE_KDA_SCAN = "kda_scan"
# a power-retention layer adds two, likewise: the input norm, the q / k / v
# / gate projections, the q / k norms, RoPE and ``o_proj``, and everything
# that touches the state (ops/retention.py)
SCOPE_RETENTION_PROJ = "retention_proj"
SCOPE_RETENTION_SCAN = "retention_scan"
# an output gate on attention adds one: the gate's projection, its
# sigmoid and the product with the attention's result (before ``o_proj``)
SCOPE_ATTN_GATE = "attn_gate"
# a sparse-attention indexer (ops/sparse_index.py) adds four: its three
# projections with their norm and RoPE, and (entered inside ``attn``: the
# innermost scope names an operation) the index scores over the cached
# keys, the selection, and the attention over what was selected
# (ops/sparse_index.py names them: its served forms enter the last three)
# ... which only a stack with such layers enters
HYBRID_SCOPES = (SCOPE_CONV, SCOPE_MOE_ROUTE, SCOPE_MOE_EXPERTS,
                 SCOPE_SSM_PROJ, SCOPE_SSM_SCAN, SCOPE_MOE_SHARED,
                 SCOPE_ATTN_GLOBAL, SCOPE_ATTN_WINDOW,
                 SCOPE_KDA_PROJ, SCOPE_KDA_SCAN, SCOPE_ATTN_GATE,
                 SCOPE_RETENTION_PROJ, SCOPE_RETENTION_SCAN,
                 SCOPE_DSA_PROJ, SCOPE_DSA_SCORE, SCOPE_DSA_SELECT,
                 SCOPE_DSA_ATTN)
STEP_SCOPES = (SCOPE_EMBED, SCOPE_QKV, SCOPE_KV_WRITE, SCOPE_ATTN,
               SCOPE_O_PROJ, SCOPE_MLP, SCOPE_TAIL) + HYBRID_SCOPES


# ----------------------------------------------------------------------
# Parameter pytree
# ----------------------------------------------------------------------

def param_shapes(config: ModelConfig) -> dict[str, Any]:
    """Shape/dtype-free spec of the parameter pytree (stacked layers)."""
    L = config.num_hidden_layers
    H = config.hidden_size
    D = config.head_dim
    NH = config.num_attention_heads
    NK = config.num_key_value_heads
    I = config.intermediate_size
    if config.is_hybrid:
        return _top_level_shapes(config, [
            _group_shapes(config, op, ff, n)
            for op, ff, _, n in config.layer_groups()])
    layers: dict[str, tuple[int, ...]] = {
        "ln_attn_in": (L, H),
        "q_proj": (L, H, NH * D),
        "k_proj": (L, H, NK * D),
        "v_proj": (L, H, NK * D),
        "o_proj": (L, NH * D, H),
        "ln_mlp_in": (L, H),
    }
    if config.attention_bias:
        # HF Llama-family attention_bias puts a bias on all four attention
        # projections; Qwen-2 biases only Q/K/V (attention_out_bias=False)
        layers.update(
            q_bias=(L, NH * D), k_bias=(L, NK * D), v_bias=(L, NK * D),
        )
    if config.o_proj_bias:
        # independent gate: o_proj_bias defaults to attention_bias but an
        # explicit attention_out_bias=True stands alone too
        layers.update(o_bias=(L, H))
    if config.mlp_bias:
        if config.is_moe:
            raise NotImplementedError("mlp_bias is not supported for MoE configs")
        layers.update(gate_bias=(L, I), up_bias=(L, I), down_bias=(L, H))
    if config.is_moe:
        E = config.num_local_experts
        layers.update(
            router=(L, H, E),
            gate_proj=(L, E, H, I),
            up_proj=(L, E, H, I),
            down_proj=(L, E, I, H),
        )
    else:
        layers.update(
            gate_proj=(L, H, I),
            up_proj=(L, H, I),
            down_proj=(L, I, H),
        )
    if config.sandwich_norms:
        layers["ln_attn_out"] = (L, H)
        layers["ln_mlp_out"] = (L, H)
    return _top_level_shapes(config, layers)


def _top_level_shapes(config: ModelConfig, layers: Any) -> dict[str, Any]:
    """The pytree around the layers: embedding, final norm, untied head."""
    H, V = config.hidden_size, config.vocab_size
    spec: dict[str, Any] = {
        "embed_tokens": (V, H),
        "layers": layers,
        "final_norm": (H,),
    }
    if not config.tie_word_embeddings:
        spec["lm_head"] = (H, V)
    return spec


def _group_shapes(
    config: ModelConfig, op: str, ff: str, n: int
) -> dict[str, tuple[int, ...]]:
    """One run of ``n`` like layers of a hybrid stack (``layer_groups``):
    the operator's leaves, then the feed-forward's, stacked on ``n``."""
    H, D = config.hidden_size, config.head_dim
    NH, NK = config.num_attention_heads, config.num_key_value_heads
    if config.attention_bias or config.mlp_bias or config.conv_bias:
        raise NotImplementedError("a hybrid stack has no biased projection")
    if op not in ("conv", "attn", "attn_ssm", "latent", "swa", "kda",
                  "retention"):
        raise ValueError(f"unknown layer operator {op!r}")
    if op == "retention":
        # power retention (ops/retention.py): a GQA layer's projections and
        # q / k norms, and one forget-gate logit a KV head
        shapes = {
            "ln_attn_in": (n, H),
            "q_proj": (n, H, NH * D), "k_proj": (n, H, NK * D),
            "v_proj": (n, H, NK * D), "o_proj": (n, NH * D, H),
            "ln_q": (n, D), "ln_k": (n, D),
            "ret_gate_proj": (n, H, NK),
        }
    elif op == "kda":
        # delta-rule linear attention (ops/kda.py): four full-rank
        # projections onto heads x kda_head_dim channels (q, k, v and the
        # per-channel decay's), a scalar a head for beta and for the
        # output gate, one depthwise filter a channel of each of q, k and
        # v, the decay's own scalars, an output norm of one head's width
        nd, taps = config.kda_dim, config.kda_conv_taps
        shapes = {
            "ln_attn_in": (n, H),
            "kda_q_proj": (n, H, nd), "kda_k_proj": (n, H, nd),
            "kda_v_proj": (n, H, nd), "kda_a_proj": (n, H, nd),
            "kda_beta_proj": (n, H, NH), "kda_gate_proj": (n, H, NH),
            # tap j meets u[t-(K-1)+j]
            "kda_q_conv": (n, nd, taps), "kda_k_conv": (n, nd, taps),
            "kda_v_conv": (n, nd, taps),
            "kda_A_log": (n, NH), "kda_dt_bias": (n, nd),
            "ln_kda_out": (n, config.kda_head_dim),
            "kda_out_proj": (n, nd, H),
        }
    elif op == "latent":
        # latent attention: a query head is [q_nope | q_pe]; kv_a_proj's
        # columns are [c | k_pe] (k_pe ONE for all heads), kv_b_proj's per
        # head [k_nope | v], read from the normed c
        dn, dr = config.qk_nope_head_dim, config.qk_rope_head_dim
        rank, dv = config.kv_lora_rank, config.v_head_dim
        shapes = {
            "ln_attn_in": (n, H),
            "q_proj": (n, H, NH * (dn + dr)),
            "kv_a_proj": (n, H, rank + dr),
            "ln_kv_a": (n, rank),
            "kv_b_proj": (n, rank, NH * (dn + dv)),
            "o_proj": (n, NH * dv, H),
        }
        if config.q_lora_rank:
            # a query latent: q = rmsnorm(h W_qa) W_qb
            qr = config.q_lora_rank
            del shapes["q_proj"]
            shapes.update(q_a_proj=(n, H, qr), ln_q_a=(n, qr),
                          q_b_proj=(n, qr, NH * (dn + dr)))
        if config.has_indexer:
            # the sparse-attention indexer (ops/sparse_index.py): its
            # queries read the query latent, its ONE key a token and its
            # head weights the normed input; the key's norm is a LayerNorm
            ih, idim = config.index_n_heads, config.index_head_dim
            shapes.update(
                idx_q_proj=(n, config.q_lora_rank, ih * idim),
                idx_k_proj=(n, H, idim), ln_idx_k=(n, idim),
                idx_k_norm_bias=(n, idim), idx_w_proj=(n, H, ih))
    elif op == "conv":
        shapes = {
            "ln_conv_in": (n, H),
            "in_proj": (n, H, 3 * H),  # B, C, x — in that order
            "conv_filter": (n, H, config.conv_L_cache),  # tap j meets z[t-(L-1)+j]
            "out_proj": (n, H, H),
        }
    else:
        # a layer kind's own kv heads and value width (config.attn_kind)
        kind = config.attn_kind("window" if op == "swa" else "global")
        NK, Dv = kind.kv_heads, kind.value_dim
        shapes = {
            "ln_attn_in": (n, H),
            "q_proj": (n, H, NH * D),
            "k_proj": (n, H, NK * D),
            "v_proj": (n, H, NK * Dv),
            "o_proj": (n, NH * Dv, H),
        }
        if kind.sink:
            shapes["attn_sink"] = (n, NH)  # one learned logit a query head
        if config.qk_norm:
            shapes.update(ln_q=(n, D), ln_k=(n, D))
        if config.attn_output_gate:
            # one gate a query head and column of its result
            shapes["attn_gate_proj"] = (n, H, NH * Dv)
        if config.sandwich_norms:
            shapes["ln_attn_out"] = (n, H)
    if op == "attn_ssm":
        # the state-space mixer beside the attention; in_proj's columns
        # are [z, x, B, C, dt], the convolution runs over [x, B, C]
        d_ssm, heads = config.mamba_d_ssm, config.mamba_n_heads
        conv_dim = config.mamba_conv_dim
        shapes.update(
            ssm_in_proj=(n, H, d_ssm + conv_dim + heads),
            ssm_conv=(n, conv_dim, config.mamba_d_conv),  # tap j meets u[t-(K-1)+j]
            ssm_dt_bias=(n, heads), ssm_A_log=(n, heads), ssm_D=(n, heads),
            ln_ssm=(n, d_ssm),
            ssm_out_proj=(n, d_ssm, H),
        )
        if config.mamba_conv_bias:
            shapes["ssm_conv_bias"] = (n, conv_dim)
    shapes["ln_mlp_in"] = (n, H)
    if config.sandwich_norms:
        shapes["ln_mlp_out"] = (n, H)
    if ff == "experts":
        # the router scores every expert of the layer; the tensors are
        # the experts HELD (all of them unless the configuration states
        # a share)
        E, I = config.num_experts, config.moe_intermediate_size
        held = config.experts_held
        shapes.update(router=(n, H, E), w1=(n, held, H, I),
                      w3=(n, held, H, I), w2=(n, held, I, H))
        if config.use_expert_bias:
            shapes["expert_bias"] = (n, E)
        if config.shared_expert_intermediate_size:
            Is = config.shared_expert_intermediate_size
            shapes.update(shared_gate=(n, H, Is), shared_up=(n, H, Is),
                          shared_down=(n, Is, H))
    else:
        I = config.intermediate_size
        shapes.update(gate_proj=(n, H, I), up_proj=(n, H, I),
                      down_proj=(n, I, H))
    return shapes


# depthwise causal convolution filters ``[channels, taps]``: drawn of order
# 1 by ``init_params``, stored ``[channels, 1, taps]`` by a checkpoint
CONV_FILTER_LEAVES = frozenset(
    ("conv_filter", "ssm_conv", "kda_q_conv", "kda_k_conv", "kda_v_conv"))

# jitted init program per (config, dtype) — see init_params
_INIT_PROGRAMS: dict = {}


def init_params(
    rng: jax.Array, config: ModelConfig, dtype: jnp.dtype = jnp.bfloat16
) -> Params:
    """Random small-scale init (for tests and synthetic benchmarks).

    The whole init runs as ONE jitted program: eager per-leaf
    ``jax.random.normal`` costs a device dispatch per leaf plus an f32
    intermediate materialization each.  Under jit the init is a single
    dispatch and every leaf materializes on-device in its final dtype.
    """
    spec = param_shapes(config)
    paths_leaves, treedef = jax.tree_util.tree_flatten_with_path(
        spec, is_leaf=lambda x: isinstance(x, tuple)
    )
    # cache the jitted program per (config, dtype) — a fresh closure per
    # call would re-trace and recompile the identical init every time
    # (the test suite calls init_params hundreds of times)
    cache_key = (config, jnp.dtype(dtype).name)
    _init = _INIT_PROGRAMS.get(cache_key)
    if _init is None:

        @jax.jit
        def _init(rng: jax.Array) -> list[jnp.ndarray]:
            keys = jax.random.split(rng, len(paths_leaves))

            def make(key: jax.Array, path: tuple, shape: tuple[int, ...]) -> jnp.ndarray:
                name = path[-1].key  # leaf name in the dict pytree
                if name.startswith("ln_") or name == "final_norm":
                    # norm gammas: zeros under unit-offset (so 1+w == 1), ones otherwise
                    init = 0.0 if config.rms_norm_unit_offset else 1.0
                    return jnp.full(shape, init, dtype=dtype)
                if name == "expert_bias":
                    # selection-only bias, float32 and not zero: choosing
                    # by score + bias and by score differ for some tokens.
                    # 0.02 is a tenth of the scores' own spread: the load
                    # stays as even as the scores make it (at 0.1 the
                    # bias decided, one expert got 5 x the mean and a
                    # dozen (layer, expert) pairs none: PERF.md §6, PR 32)
                    return jax.random.normal(key, shape, jnp.float32) * 0.02
                if name in ("ssm_A_log", "ssm_dt_bias", "ssm_D"):
                    # the recurrence's own scalars, one a head, float32:
                    # A = -a with a uniform in [1, 16]; a step
                    # softplus(dt_bias) log-uniform in [1e-3, 1e-1]; D = 1
                    # (the published code's initialisation)
                    u = jax.random.uniform(key, shape, jnp.float32)
                    if name == "ssm_A_log":
                        return jnp.log(1.0 + 15.0 * u)
                    if name == "ssm_D":
                        return jnp.ones(shape, jnp.float32)
                    step = jnp.exp(math.log(1e-3) + u * math.log(1e2))
                    return step + jnp.log(-jnp.expm1(-step))
                if name == "kda_A_log":
                    # one rate a head about 1 (float32): exp(A_log) in
                    # [0.8, 1.25]
                    return (jax.random.uniform(key, shape, jnp.float32)
                            - 0.5) * (2 * math.log(1.25))
                if name == "kda_dt_bias":
                    # float32, one a channel.  Zero unless the
                    # configuration states a span ``(slowest, fastest)``
                    # of log-decays a token: then log-uniform over it,
                    # through the inverse of the gate at ``W_a x = 0`` and
                    # ``A_log = 0`` (the projection moves each token's
                    # about that)
                    if config.init_kda_log_decay is None:
                        return jnp.zeros(shape, jnp.float32)
                    slow, fast = (math.log(-g) for g in
                                  config.init_kda_log_decay)
                    share = jnp.exp(slow + (fast - slow) * jax.random.uniform(
                        key, shape, jnp.float32)) / -config.kda_lower_bound
                    return jnp.log(share) - jnp.log1p(-share)
                if name == "attn_sink":
                    # float32, of the order of a row's largest scores (a
                    # tenth to a half of the softmax's denominator over a
                    # full window of seeded keys): at 0 a sink would be a
                    # five-hundredth of it and no comparison could see
                    # one dropped
                    return 5.0 + jax.random.normal(key, shape, jnp.float32)
                if name.endswith("_bias"):
                    # biases start small-but-nonzero so tests exercise the add path
                    return (jax.random.normal(key, shape, jnp.float32) * 0.02).astype(dtype)
                own_share = config.init_expert_specific
                if name in ("w1", "w3", "w2") and own_share is not None:
                    # dropless experts [n, E, in, out] of a configuration
                    # that asks for correlated draws: one shared draw and
                    # ``own_share`` of each expert's own, at the overall
                    # scale every other matrix has
                    k_shared, k_own = jax.random.split(key)
                    shared = jax.random.normal(
                        k_shared, shape[:1] + (1,) + shape[2:], jnp.float32)
                    own = jax.random.normal(k_own, shape, jnp.float32)
                    return ((shared + own_share * own) * (
                        0.02 / math.sqrt(1.0 + own_share ** 2))
                    ).astype(dtype)
                # a conv filter's three taps are of order 1 (the published
                # code's default init is uniform in +-1/sqrt(3))
                scale = 0.3 if name in CONV_FILTER_LEAVES else 0.02
                if name == "ssm_in_proj" and config.init_ssm_in_proj_std:
                    scale = config.init_ssm_in_proj_std
                if name == "w2" and config.init_expert_out_std:
                    scale = config.init_expert_out_std
                return (jax.random.normal(key, shape, dtype=jnp.float32) * scale).astype(dtype)

            return [make(k, p, s) for k, (p, s) in zip(keys, paths_leaves)]

        _INIT_PROGRAMS[cache_key] = _init

    return jax.tree.unflatten(treedef, _init(rng))


# ----------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------

def compute_dtype(params: Params) -> jnp.dtype:
    """Activation dtype: the norm gammas' dtype (always a float leaf, even
    when the matmul weights are int8-quantized — quant.py)."""
    return params["final_norm"].dtype


def scan_unroll(config: ModelConfig) -> int:
    """Layer-scan unroll factor so the compiler can software-pipeline the
    per-layer weight stream across layer boundaries — decode is bound by
    that stream.  config.scan_unroll is the API (part of every jit cache
    key the config closes over); LLMTPU_SCAN_UNROLL overrides it at TRACE
    time only — an env change after a fn's first trace does nothing for
    that fn (the bench A/Bs via the env var in fresh subprocesses).
    Non-divisors and malformed values degrade to 1.  The ONE definition
    shared by ``forward`` and the serve engine's paged decode scan."""
    try:
        unroll = int(
            os.environ.get("LLMTPU_SCAN_UNROLL", str(config.scan_unroll)).strip()
        )
    except ValueError:
        unroll = 1  # malformed values degrade like non-divisors do
    if unroll < 1 or config.num_hidden_layers % unroll:
        unroll = 1
    return unroll


def _project(x: jnp.ndarray, w: Any, out_dtype: Any = None) -> jnp.ndarray:
    """``x @ w`` accumulated in float32, rounded to ``x``'s dtype — or to
    ``out_dtype``: a projection that writes to a residual stream kept
    wider than the block computes in hands over what it accumulated."""
    return quant_einsum("bsh,ho->bso", x, w).astype(out_dtype or x.dtype)


def embed_inputs(params: Params, input_ids: jnp.ndarray, config: ModelConfig) -> jnp.ndarray:
    """Token embedding lookup (+ Gemma's sqrt(hidden) scaling,
    gemma2_model.py:738-739, applied in the weight dtype to match the
    reference's bf16 rounding)."""
    dtype = compute_dtype(params)
    emb = params["embed_tokens"]
    if isinstance(emb, dict):  # int8 rows with per-row scales
        x = (emb["q"][input_ids].astype(jnp.float32) * emb["s"][input_ids]).astype(dtype)
    else:
        x = emb[input_ids].astype(dtype)
    if config.scale_embeddings:
        normalizer = jnp.array(math.sqrt(config.hidden_size), dtype=dtype)
        x = x * normalizer
    if config.embedding_multiplier != 1.0:
        x = x * jnp.array(config.embedding_multiplier, dtype=dtype)
    return x


def final_logits(
    params: Params, x: jnp.ndarray, config: ModelConfig, *, last_only: bool = False
) -> jnp.ndarray:
    """Final RMSNorm → (tied) lm_head → optional softcap → float32 logits."""
    x = rms_norm(
        x, params["final_norm"], eps=config.rms_norm_eps,
        unit_offset=config.rms_norm_unit_offset,
    )
    if last_only:
        x = x[:, -1:, :]
    if config.tie_word_embeddings:
        logits = quant_einsum("bsh,vh->bsv", x, params["embed_tokens"])
    else:
        logits = quant_einsum("bsh,hv->bsv", x, params["lm_head"])
    if config.lm_head_multiplier != 1.0:
        logits = logits.astype(jnp.float32) * config.lm_head_multiplier
    if config.final_logit_softcapping is not None:
        logits = softcap(logits, config.final_logit_softcapping)
    return logits.astype(jnp.float32)


def head_quant_mode(params: Params, config: ModelConfig) -> str | None:
    """How the lm-head weight is stored: ``"float"`` (plain array),
    ``"int8"`` (quant.py ``"q"`` payload — the fused sampling epilogue's
    int8 kernel streams it), or ``None`` for payloads the epilogue
    kernel does not cover (``q4``/``qa`` — those keep the XLA tail).
    The ONE classification shared by the serve engine's epilogue gate
    and the offline Generator, so the two cannot drift."""
    w = (params.get("embed_tokens") if config.tie_word_embeddings
         else params.get("lm_head"))
    if w is None:
        return None
    if isinstance(w, dict):
        return "int8" if "q" in w and "s" in w else None
    return "float"


def epilogue_params(
    params: Params, config: ModelConfig
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray | None]:
    """``(final-norm gamma, lm-head weight payload, [1, V] f32 scales
    or None)`` — the leaves the fused sampling epilogue kernel streams
    (ops/pallas/sample_epilogue.py).  Tied heads hand over the
    embedding table ``[V, H]`` (per-row scales reshaped to the kernel's
    per-column layout), untied heads ``[H, V]``.  Callers gate on
    ``head_quant_mode`` first — this raises on unsupported payloads."""
    w = (params["embed_tokens"] if config.tie_word_embeddings
         else params["lm_head"])
    if isinstance(w, dict):
        return params["final_norm"], w["q"], w["s"].reshape(1, -1)
    return params["final_norm"], w, None


def sample_epilogue_tail(
    params: Params, x: jnp.ndarray, config: ModelConfig
) -> jnp.ndarray:
    """Greedy-sample rows of PRE-final-norm hidden states ``x [N, H]``
    through the fused sampling epilogue kernel → ``[N]`` int32 token
    ids.  The ONE invocation shared by the serve engine's three step
    builders and the offline Generator's decode tail, so the kernel
    kwargs (norm eps/offset, softcap, head layout+scales) cannot drift
    between paths — a new config knob lands here once or nowhere."""
    from llm_np_cp_tpu.ops.pallas.sample_epilogue import sample_epilogue

    gamma, w, w_scale = epilogue_params(params, config)
    return sample_epilogue(
        x, gamma, w, w_scale=w_scale,
        tied=config.tie_word_embeddings,
        eps=config.rms_norm_eps,
        unit_offset=config.rms_norm_unit_offset,
        logit_softcap=config.final_logit_softcapping,
        logit_scale=config.lm_head_multiplier,
    )


def epilogue_gate_error(
    params: Params, config: ModelConfig, sampler_kind: str
) -> str | None:
    """None when the fused sampling epilogue reproduces this
    (params, sampler) pair's draw bit-identically and the kernel is
    available, else the reason it cannot — the ONE gate shared by
    ``ServeEngine`` and the offline ``Generator`` (callers add their
    own topology constraints, e.g. the engine's unsharded-mesh check,
    on top)."""
    if sampler_kind != "greedy":
        return (f"sampler kind {sampler_kind!r} (only the greedy draw "
                "is bit-identical to the streamed argmax)")
    hq = head_quant_mode(params, config)
    if hq is None:
        return "unsupported lm-head payload (q4/qa heads keep the XLA tail)"
    from llm_np_cp_tpu.ops.pallas.support import (
        epilogue_kernel_name,
        kernel_error,
    )

    return kernel_error(epilogue_kernel_name(hq == "int8"))


def attention_block(
    w: Params,
    x: jnp.ndarray,
    *,
    config: ModelConfig,
    cos: jnp.ndarray | None,
    sin: jnp.ndarray | None,
    mask_global: jnp.ndarray | None = None,
    mask_local: jnp.ndarray | None = None,
    sliding: jnp.ndarray | bool = False,
    attn_impl: str = "xla",
    kv_update: Any = None,
    output_attentions: bool = False,
    attn_fn: Any = None,
    normed: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, tuple[jnp.ndarray, jnp.ndarray], jnp.ndarray | None]:
    """The attention operator of a block with its residual: ``(x_out,
    (k_att, v_att), attn_weights | None)``.  The first half of
    ``run_decoder_layer`` (see there for the arguments), and the operator
    of a hybrid stack's attention layers.

    cos, sin: the RoPE tables of the tokens' positions, or None for a
        layer that carries no positional encoding (``AttnKind.rope_theta``
        None): q and k are then attended as projected (and normed).
    normed: the block's input norm of ``x``, where a second mixer reads
        the same one (``input_norm``): the operator then returns what it
        ADDS to the stream, ``attention_out_multiplier`` applied, and the
        caller sums the mixers into the residual."""
    if attn_fn is None:
        mask = (
            jnp.where(sliding, mask_local, mask_global)
            if config.sliding_window is not None and mask_local is not None
            else mask_global
        )
    b, s = x.shape[:2]

    def _proj_b(x, wname):
        y = _project(x, w[wname])
        bias = w.get(wname.replace("_proj", "_bias"))
        return y + bias.astype(y.dtype) if bias is not None else y

    with jax.named_scope(SCOPE_QKV):
        h = input_norm(w, x, config) if normed is None else normed
        if config.attention_in_multiplier != 1.0:
            h = h * jnp.array(config.attention_in_multiplier, h.dtype)
        q = _proj_b(h, "q_proj").reshape(b, s, config.num_attention_heads, config.head_dim)
        # (the kv heads are the layer's own: a window layer may have more
        # than a global one, and a value head another width than a key's)
        k = _proj_b(h, "k_proj").reshape(b, s, -1, config.head_dim)
        v = _proj_b(h, "v_proj").reshape(b, s, k.shape[2], config.value_dim)
        if config.attention_value_scale != 1.0:
            # linear in v: on the values (K times fewer than the outputs)
            v = v * jnp.array(config.attention_value_scale, v.dtype)
        if config.key_multiplier != 1.0:
            k = k * jnp.array(config.key_multiplier, k.dtype)
        if config.qk_norm:
            # RMSNorm over head_dim on every q and k head, BEFORE RoPE
            q = rms_norm(q, w["ln_q"], eps=config.rms_norm_eps)
            k = rms_norm(k, w["ln_k"], eps=config.rms_norm_eps)
        if cos is not None:
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

    with jax.named_scope(SCOPE_KV_WRITE):
        if kv_update is not None:
            k_att, v_att = kv_update(k, v)
        else:
            k_att, v_att = k, v

    attn_weights = None
    with jax.named_scope(SCOPE_ATTN):
        if attn_fn is not None:
            attn = attn_fn(q, k_att, v_att, sliding)
        elif attn_impl in ("flash", "ring"):
            if attn_impl == "flash":
                from llm_np_cp_tpu.ops.pallas.flash_attention import flash_attention as _impl_fn
            else:
                from llm_np_cp_tpu.parallel.ring_attention import ring_attention_ctx as _impl_fn

            def _fresh_attn(window):
                return _impl_fn(
                    q, k, v,  # current K/V: self-attention over 0..S-1
                    scale=config.attn_scale,
                    logit_softcap=config.attn_logit_softcapping,
                    window=window,
                )

            if config.sliding_window is not None:
                attn = lax.cond(
                    sliding,
                    lambda: _fresh_attn(config.sliding_window),
                    lambda: _fresh_attn(None),
                )
            else:
                attn = _fresh_attn(None)
        elif attn_impl == "flash_decode" and s == 1:
            # Fused single-token attention over the cache slab; consumes the
            # same mask as the XLA path (validity ∧ window ∧ ragged pads), so
            # every decode feature works unchanged.  Prefill/chunked calls
            # (s > 1) under this impl fall through to the XLA path below.
            # An int8 cache arrives as (values, scales) tuples: the kernel
            # streams 1-byte slabs and dequantizes in VMEM.
            from llm_np_cp_tpu.ops.pallas.decode_attention import decode_attention

            if isinstance(k_att, tuple):
                (k_vals, k_sc), (v_vals, v_sc) = k_att, v_att
            else:
                k_vals, k_sc, v_vals, v_sc = k_att, None, v_att, None
            attn = decode_attention(
                q, k_vals, v_vals,
                jnp.broadcast_to(mask, (b, 1, k_vals.shape[1]))[:, 0],
                k_scale=k_sc, v_scale=v_sc,
                scale=config.attn_scale,
                logit_softcap=config.attn_logit_softcapping,
            )
        else:
            attn = gqa_attention(
                q, k_att, v_att, mask,
                scale=config.attn_scale,
                logit_softcap=config.attn_logit_softcapping,
                return_weights=output_attentions,
                sink=w.get("attn_sink"),
            )
            if output_attentions:
                attn, attn_weights = attn

    if config.attn_output_gate:
        # per head and column, BEFORE o_proj; read from the same normed
        # input as q
        with jax.named_scope(SCOPE_ATTN_GATE):
            gate = jax.nn.sigmoid(
                _project(h, w["attn_gate_proj"]).astype(jnp.float32))
            attn = (attn.reshape(b, s, -1).astype(jnp.float32) * gate
                    ).astype(attn.dtype)

    with jax.named_scope(SCOPE_O_PROJ):
        attn = _project(attn.reshape(b, s, -1), w["o_proj"], x.dtype)
        if "o_bias" in w:
            attn = attn + w["o_bias"].astype(attn.dtype)
        if config.sandwich_norms:
            attn = rms_norm(
                attn, w["ln_attn_out"], eps=config.rms_norm_eps,
                unit_offset=config.rms_norm_unit_offset,
            )
        if normed is not None:
            x = attn * config.attention_out_multiplier
        else:
            x = x + attn

    return x, (k_att, v_att), attn_weights


def latent_attention_block(
    w: Params,
    x: jnp.ndarray,
    *,
    config: ModelConfig,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    mask: jnp.ndarray | None = None,
    kv_update: Any = None,
    attn_fn: Any = None,
    q_block: int = 256,
) -> tuple[jnp.ndarray, Any]:
    """Latent attention (MLA) with its residual: ``(x_out, the cache rows
    as kv_update left them)``.

    A token's cached row is ``[c' | k_pe]``: ``c' = rmsnorm(c)`` of the
    compressed K/V and the ONE rotated key part every head shares, after
    the norm and after RoPE.  Two forms of the same function:

    - EXPANDED (``attn_fn is None``: ``models.forward``, what decides
      ``correct``): ``c' Wkv_b`` gives every head its ``[k_nope | v]``,
      ``k = [k_nope | k_pe]``, softmax attention over ``q_block`` queries
      at a time (the scores of a whole batch never exist at once);
    - ABSORBED (the serve tick): with ``Wkv_b`` cut per head into ``W_UK``
      and ``W_UV``, ``score = (q_nope W_UK) . c' + q_pe . k_pe`` and
      ``out = (softmax . c') W_UV`` — multi-query attention of all heads
      over the cached rows themselves, whose first ``kv_lora_rank``
      columns are also the values.  ``attn_fn(q_lat [b, s, heads, rank +
      rope], rows) -> [b, s, heads, rank]`` is the caller's (a kernel
      over pages).

    The query is one projection of the normed input, or (``q_a_proj``: a
    query latent) ``rmsnorm(h W_qa) W_qb``.  Under an INDEXER
    (``config.has_indexer``, ops/sparse_index.py) both forms attend each
    token's selection and not all it may see: the token's index key is
    cached beside its row (``kv_update(row, index_key) -> (rows, index
    keys)``), the expanded form scores, selects and attends a sequence and
    ``q_block`` queries at a time, and the absorbed form hands ``attn_fn``
    a third argument ``(q_idx [b, s, heads_I, dim_I], w_idx [b, s,
    heads_I] float32, index keys)`` to score, select and attend with.

    kv_update: ``row [b, s, rank + rope] -> rows``: the cache write;
        returns what attention reads (all rows so far ``[b, S, rank +
        rope]`` for the expanded form, the pages for ``attn_fn``).
    mask: bool ``[b, s, S]`` (expanded form only)."""
    b, s = x.shape[:2]
    nh = config.num_attention_heads
    dn, dr = config.qk_nope_head_dim, config.qk_rope_head_dim
    rank, dv = config.kv_lora_rank, config.v_head_dim
    w_kv_b = w["kv_b_proj"].reshape(rank, nh, dn + dv)
    index = None
    with jax.named_scope(SCOPE_QKV):
        h = input_norm(w, x, config)
        if "q_a_proj" in w:
            q_lat_in = rms_norm(_project(h, w["q_a_proj"]), w["ln_q_a"],
                                eps=config.rms_norm_eps)
            q = _project(q_lat_in, w["q_b_proj"])
        else:
            q = _project(h, w["q_proj"])
        q = q.reshape(b, s, nh, dn + dr)
        kv_a = _project(h, w["kv_a_proj"])
        c = rms_norm(kv_a[..., :rank], w["ln_kv_a"], eps=config.rms_norm_eps)
        q_pe = apply_rope(q[..., dn:], cos, sin,
                          interleave=config.rope_interleave)
        k_pe = apply_rope(kv_a[..., None, rank:], cos, sin,
                          interleave=config.rope_interleave)[..., 0, :]
        row = jnp.concatenate([c, k_pe], axis=-1)
        if attn_fn is not None:
            q_lat = jnp.concatenate([
                jnp.einsum("bshd,rhd->bshr", q[..., :dn], w_kv_b[..., :dn],
                           preferred_element_type=jnp.float32).astype(q.dtype),
                q_pe], axis=-1)
    if config.has_indexer:
        with jax.named_scope(SCOPE_DSA_PROJ):
            ih, idim = config.index_n_heads, config.index_head_dim
            rot = dict(interleave=config.indexer_rope_interleave)
            q_idx = sparse_index.rope_leading(
                _project(q_lat_in, w["idx_q_proj"]).reshape(b, s, ih, idim),
                cos, sin, **rot)
            k_idx = sparse_index.rope_leading(
                sparse_index.layer_norm(
                    _project(h, w["idx_k_proj"]), w["ln_idx_k"],
                    w["idx_k_norm_bias"])[..., None, :], cos, sin,
                **rot)[..., 0, :]
            w_idx = _project(h, w["idx_w_proj"], jnp.float32) * (
                float(ih) ** -0.5 * float(idim) ** -0.5)
            index = (q_idx, w_idx)

    with jax.named_scope(SCOPE_KV_WRITE):
        if index is None:
            rows = kv_update(row) if kv_update is not None else row
            cached = rows
        else:
            cached = (kv_update(row, k_idx) if kv_update is not None
                      else (row, k_idx))
            rows, idx_rows = cached

    with jax.named_scope(SCOPE_ATTN):
        if attn_fn is not None:
            o_lat = (attn_fn(q_lat, rows) if index is None
                     else attn_fn(q_lat, rows, (*index, idx_rows)))
        else:
            kv = jnp.einsum("bsr,rhd->bshd", rows[..., :rank].astype(q.dtype),
                            w_kv_b, preferred_element_type=jnp.float32
                            ).astype(q.dtype)
            k = jnp.concatenate([
                kv[..., :dn], jnp.broadcast_to(
                    rows[:, :, None, rank:].astype(q.dtype),
                    kv.shape[:3] + (dr,))], axis=-1)
            q_full = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
            if index is None:
                attn = _attend_in_query_blocks(
                    q_full, k, kv[..., dn:], mask, scale=config.attn_scale,
                    block=q_block)
            else:
                # (half the dense form's block: a block also holds its index
                # scores [block, heads_I, S] and the selection's passes)
                attn = sparse_index.attend_selected_in_blocks(
                    q_full, k, kv[..., dn:], q_idx, w_idx,
                    idx_rows.astype(q_idx.dtype), mask,
                    topk=config.index_topk, scale=config.attn_scale,
                    block=max(q_block // 2, 1))

    with jax.named_scope(SCOPE_O_PROJ):
        if attn_fn is not None:
            attn = jnp.einsum("bshr,rhd->bshd", o_lat, w_kv_b[..., dn:],
                              preferred_element_type=jnp.float32
                              ).astype(q.dtype)
        x = x + _project(attn.reshape(b, s, nh * dv), w["o_proj"], x.dtype)
    return x, cached


def _attend_in_query_blocks(q, k, v, mask, *, scale: float, block: int):
    """``gqa_attention`` over ``block`` queries at a time (one kv head a
    query head): ``[b, s, h, dv]``.  The float32 scores of 4 x 2,176
    tokens x 32 heads at once would be 2.4 GB beside the weights."""
    if q.shape[1] <= block:
        return gqa_attention(q, k, v, mask, scale=scale)
    return attend_in_query_blocks(q, k, v, mask, block=block, scale=scale)


def input_norm(w: Params, x: jnp.ndarray, config: ModelConfig) -> jnp.ndarray:
    """A block's input norm, in the dtype the block computes in
    (``compute_dtype``: the gammas').  A residual stream kept wider (a
    hybrid stack's float32 one) is normed as it is and added to as it
    is — a no-op cast otherwise."""
    return rms_norm(
        x, w["ln_attn_in"], eps=config.rms_norm_eps,
        unit_offset=config.rms_norm_unit_offset,
    ).astype(w["ln_attn_in"].dtype)


def ff_block(
    w: Params, x: jnp.ndarray, *, config: ModelConfig, act: Any,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """The dense (or capacity-routed) feed-forward of a block with its
    residual: ``(x_out, moe_aux_loss)``."""

    def _proj_b(x, wname, out_dtype=None):
        y = _project(x, w[wname], out_dtype)
        bias = w.get(wname.replace("_proj", "_bias"))
        return y + bias.astype(y.dtype) if bias is not None else y

    with jax.named_scope(SCOPE_MLP):
        h = rms_norm(
            x, w["ln_mlp_in"], eps=config.rms_norm_eps,
            unit_offset=config.rms_norm_unit_offset,
        ).astype(w["ln_mlp_in"].dtype)
        moe_aux = jnp.zeros((), jnp.float32)
        if config.is_moe:
            from llm_np_cp_tpu.ops.moe import moe_mlp

            mlp, moe_aux = moe_mlp(
                h, w["router"], w["gate_proj"], w["up_proj"], w["down_proj"],
                act=act, top_k=config.num_experts_per_tok,
                capacity_factor=config.moe_capacity_factor,
                group_size=config.moe_group_size,
            )
        else:
            gate_m, down_m = config.mlp_multipliers
            gate = _proj_b(h, "gate_proj")
            if gate_m != 1.0:
                gate = gate * jnp.array(gate_m, gate.dtype)
            up = _proj_b(h, "up_proj")
            mlp = _proj_b(act(gate) * up, "down_proj", x.dtype)
            if down_m != 1.0:
                mlp = mlp * down_m
        if config.sandwich_norms:
            mlp = rms_norm(
                mlp, w["ln_mlp_out"], eps=config.rms_norm_eps,
                unit_offset=config.rms_norm_unit_offset,
            )
        x = x + mlp
    return x, moe_aux



def conv_block(
    w: Params,
    x: jnp.ndarray,
    *,
    config: ModelConfig,
    history: Any,
    token_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """The gated short convolution of a block with its residual (LFM2's
    ``conv`` operator): ``[B, C, X] = split3(in_proj(u))``, ``z = B * X``,
    ``c_t = sum_j filter[:, j] * z_{t-(L-1)+j}`` (depthwise, causal, one
    ``L``-tap filter a channel), ``out_proj(C * c)``.

    history: ``z -> [z_{t-1}, .., z_{t-(L-1)}]``, each shaped like ``z`` —
        where a token's predecessors IN ITS OWN SEQUENCE come from is the
        caller's: the same array shifted (a cache-less forward), a
        carried state in front of it (a cache), the packed neighbours or
        the slot's state (the serve tick).  The hook also keeps what the
        next step needs; ``z`` before a sequence's start is 0.
    token_mask: ``[b, s]`` bool — False at padding, whose ``z`` is 0."""
    taps = config.conv_L_cache
    with jax.named_scope(SCOPE_CONV):
        h = rms_norm(x, w["ln_conv_in"], eps=config.rms_norm_eps).astype(
            w["ln_conv_in"].dtype)
        gate_b, gate_c, xin = jnp.split(_project(h, w["in_proj"]), 3, axis=-1)
        z = gate_b * xin
        if token_mask is not None:
            z = jnp.where(token_mask[..., None], z, jnp.zeros_like(z))
        filt = w["conv_filter"].astype(jnp.float32)  # [H, L]
        c = z.astype(jnp.float32) * filt[:, taps - 1]
        for d, z_prev in enumerate(history(z), start=1):
            c = c + z_prev.astype(jnp.float32) * filt[:, taps - 1 - d]
        y = _project(gate_c * c.astype(h.dtype), w["out_proj"], x.dtype)
        return x + y


def ssm_block(
    w: Params,
    u: jnp.ndarray,
    *,
    config: ModelConfig,
    history: Any,
    scan: Any,
    token_mask: jnp.ndarray | None = None,
    out_dtype: Any = None,
) -> jnp.ndarray:
    """The state-space mixer of a block (Mamba-2, Falcon-H1's), WITHOUT a
    residual: what it adds to the stream, ``ssm_out_multiplier`` applied.
    ``[z, x, B, C, dt] = in_proj(u * ssm_in_multiplier) * m`` (``m`` holds
    ``ssm_multipliers`` on the five slices), ``[x, B, C] <- silu(conv1d)``
    (depthwise, causal, ``mamba_d_conv`` taps + bias), ``dt <-
    softplus(dt + dt_bias)``, the recurrence (ops/ssm.py), then the gated
    norm ``RMSNorm_groups(y * silu(z)) * w`` and ``out_proj``.

    u: the block's input norm ``[B, S, H]``, which attention reads too.
    history: the hook of ``conv_block``, over the convolution's inputs.
    scan: ``(x [B, S, nh, P], dt [B, S, nh], a [nh], b, c [B, S, ng, N],
        d_skip [nh]) -> y [B, S, nh, P]`` float32 — the recurrence over
        the tokens as the CALLER lays sequences out, which owns the state
        (a cache's, the serving tick's rows).
    token_mask: ``[b, s]`` bool — False at padding, which neither enters
        the convolution nor moves the state (``dt = 0``)."""
    b_, s_ = u.shape[:2]
    taps, d_ssm = config.mamba_d_conv, config.mamba_d_ssm
    nh, ng, n = config.mamba_n_heads, config.mamba_n_groups, config.mamba_d_state
    f32 = jnp.float32
    with jax.named_scope(SCOPE_SSM_PROJ):
        h = u
        if config.ssm_in_multiplier != 1.0:
            h = h * jnp.array(config.ssm_in_multiplier, h.dtype)
        # the five slices' multipliers as one per-channel vector
        m = jnp.concatenate([jnp.full((width,), mult, h.dtype) for mult, width in zip(
            config.ssm_multipliers, (d_ssm, d_ssm, ng * n, ng * n, nh))])
        p = _project(h, w["ssm_in_proj"]) * m
        z, xbc, dt = jnp.split(p, (d_ssm, d_ssm + config.mamba_conv_dim), axis=-1)
        if token_mask is not None:
            xbc = jnp.where(token_mask[..., None], xbc, jnp.zeros_like(xbc))
        filt = w["ssm_conv"].astype(f32)  # [C, K]
        acc = xbc.astype(f32) * filt[:, taps - 1]
        for d, prev in enumerate(history(xbc), start=1):
            acc = acc + prev.astype(f32) * filt[:, taps - 1 - d]
        if "ssm_conv_bias" in w:
            acc = acc + w["ssm_conv_bias"].astype(f32)
        xbc = jax.nn.silu(acc).astype(h.dtype)
        x, b, c = jnp.split(xbc, (d_ssm, d_ssm + ng * n), axis=-1)
    with jax.named_scope(SCOPE_SSM_SCAN):
        dt = jax.nn.softplus(dt.astype(f32) + w["ssm_dt_bias"].astype(f32))
        if token_mask is not None:
            dt = jnp.where(token_mask[..., None], dt, 0.0)
        y = scan(
            x.reshape(b_, s_, nh, -1), dt, -jnp.exp(w["ssm_A_log"].astype(f32)),
            b.reshape(b_, s_, ng, n), c.reshape(b_, s_, ng, n), w["ssm_D"])
    with jax.named_scope(SCOPE_SSM_PROJ):
        # gated norm: the mean square over each GROUP's channels, float32
        g = (y.reshape(b_, s_, d_ssm) * jax.nn.silu(z.astype(f32))).reshape(
            b_, s_, ng, -1)
        g = g * lax.rsqrt(jnp.mean(jnp.square(g), axis=-1, keepdims=True)
                          + config.rms_norm_eps)
        g = (g.reshape(b_, s_, d_ssm) * w["ln_ssm"].astype(f32)).astype(h.dtype)
        return (_project(g, w["ssm_out_proj"], out_dtype)
                * config.ssm_out_multiplier)


def kda_block(
    w: Params,
    x: jnp.ndarray,
    *,
    config: ModelConfig,
    history: Any,
    scan: Any,
    token_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """A delta-rule linear-attention layer's operator (KDA, Ling-3.0's)
    with its residual.  With ``h`` the block's input norm: ``[q, k, v] <-
    silu(conv1d([W_q h, W_k h, W_v h]))`` (depthwise, causal,
    ``kda_conv_taps`` taps, a filter a channel), ``q`` and ``k``
    L2-normalised per head and ``q`` scaled by ``d^-0.5``; the log-decay
    ``g = L sigmoid(exp(A_log) (W_a h + dt_bias))`` per channel in ``[L,
    0]`` (``L = kda_lower_bound``), ``beta = sigmoid(w_beta . h)`` a head;
    the recurrence (ops/kda.py); then ``W_o [RMSNorm_d(o) * sigmoid(W_gamma
    h)]`` with one gate a head.  No RoPE: the decay carries position.

    history: the hook of ``conv_block``, over the convolution's inputs.
    scan: ``(q, k, v, g [B, S, H, d], beta [B, S, H]) -> o [B, S, H, d]``
        float32 — the recurrence over the tokens as the CALLER lays
        sequences out, which owns the state (a cache's, the tick's rows).
    token_mask: ``[b, s]`` bool — False at padding, which neither enters
        the convolution nor moves the state (``g = 0``, ``beta = 0``)."""
    b_, s_ = x.shape[:2]
    nh, d, taps = config.num_attention_heads, config.kda_head_dim, config.kda_conv_taps
    f32 = jnp.float32
    with jax.named_scope(SCOPE_KDA_PROJ):
        h = input_norm(w, x, config)
        qkv = jnp.concatenate(
            [_project(h, w[name]) for name in
             ("kda_q_proj", "kda_k_proj", "kda_v_proj")], axis=-1)
        if token_mask is not None:
            qkv = jnp.where(token_mask[..., None], qkv, jnp.zeros_like(qkv))
        filt = jnp.concatenate(
            [w[name].astype(f32) for name in
             ("kda_q_conv", "kda_k_conv", "kda_v_conv")])  # [3 H d, K]
        acc = qkv.astype(f32) * filt[:, taps - 1]
        for back, prev in enumerate(history(qkv), start=1):
            acc = acc + prev.astype(f32) * filt[:, taps - 1 - back]
        q, k, v = (t.reshape(b_, s_, nh, d) for t in jnp.split(
            jax.nn.silu(acc).astype(h.dtype).astype(f32), 3, axis=-1))
        unit = lambda t: t * lax.rsqrt(
            jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
        q, k = unit(q) * d ** -0.5, unit(k)
        rate = jnp.exp(w["kda_A_log"].astype(f32))[:, None]
        g = config.kda_lower_bound * jax.nn.sigmoid(rate * (
            _project(h, w["kda_a_proj"]).astype(f32)
            + w["kda_dt_bias"].astype(f32)).reshape(b_, s_, nh, d))
        beta = jax.nn.sigmoid(_project(h, w["kda_beta_proj"]).astype(f32))
        if token_mask is not None:
            g = jnp.where(token_mask[..., None, None], g, 0.0)
            beta = jnp.where(token_mask[..., None], beta, 0.0)
    with jax.named_scope(SCOPE_KDA_SCAN):
        o = scan(q, k, v, g, beta)
    with jax.named_scope(SCOPE_KDA_PROJ):
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                          + config.rms_norm_eps)
        gate = jax.nn.sigmoid(_project(h, w["kda_gate_proj"]).astype(f32))
        o = (o * w["ln_kda_out"].astype(f32) * gate[..., None]).astype(h.dtype)
        return x + _project(o.reshape(b_, s_, nh * d), w["kda_out_proj"],
                            x.dtype)


def retention_block(
    w: Params,
    x: jnp.ndarray,
    *,
    config: ModelConfig,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    scan: Any,
    token_mask: jnp.ndarray | None = None,
) -> jnp.ndarray:
    """A power-retention layer's operator (Brumby's) with its residual.
    With ``h`` the block's input norm: ``q = RoPE(RMSNorm_d(W_q h))``, ``k =
    RoPE(RMSNorm_d(W_k h))``, ``v = W_v h`` as a GQA layer has them, the
    log-gate ``log g = logsigmoid(W_g h)`` one a KV head (float32 from the
    projection's accumulator on); the recurrence (ops/retention.py), float32;
    then ``W_o`` over the heads' results side by side.

    scan: ``(q [B, S, H, d], k, v [B, S, Hk, d], log_g [B, S, Hk]) -> o [B,
        S, H, d]`` float32 — the recurrence over the tokens as the CALLER
        lays sequences out, which owns the state (a cache's, the tick's
        rows).
    token_mask: ``[b, s]`` bool — False at padding, which does not move the
        state (``k = 0``, ``log g = 0``)."""
    b_, s_ = x.shape[:2]
    d = config.head_dim
    f32 = jnp.float32
    with jax.named_scope(SCOPE_RETENTION_PROJ):
        h = input_norm(w, x, config)
        q = _project(h, w["q_proj"]).reshape(b_, s_, -1, d)
        k = _project(h, w["k_proj"]).reshape(b_, s_, -1, d)
        v = _project(h, w["v_proj"]).reshape(b_, s_, -1, d)
        q = apply_rope(rms_norm(q, w["ln_q"], eps=config.rms_norm_eps), cos, sin)
        k = apply_rope(rms_norm(k, w["ln_k"], eps=config.rms_norm_eps), cos, sin)
        log_g = jax.nn.log_sigmoid(_project(h, w["ret_gate_proj"], f32))
        if token_mask is not None:
            k = jnp.where(token_mask[..., None, None], k, jnp.zeros_like(k))
            log_g = jnp.where(token_mask[..., None], log_g, 0.0)
    with jax.named_scope(SCOPE_RETENTION_SCAN):
        o = scan(q, k, v, log_g)
    with jax.named_scope(SCOPE_RETENTION_PROJ):
        return x + _project(o.astype(h.dtype).reshape(b_, s_, -1),
                            w["o_proj"], x.dtype)


def shifted_history(state: jnp.ndarray, z: jnp.ndarray, taps: int) -> tuple:
    """A convolution's ``history`` hook over whole sequences ``z [B, S,
    C]`` that continue ``state [B, taps - 1, C]`` (zeros before a
    sequence's start): ``([z_{t-1}, .., z_{t-(taps-1)}], state after)`` —
    token t's d-th predecessor sits d places before it."""
    s = z.shape[1]
    ext = jnp.concatenate([state.astype(z.dtype), z], axis=1)
    return ([ext[:, taps - 1 - d:taps - 1 - d + s] for d in range(1, taps)],
            ext[:, s:])


def experts_parts(
    w: Params,
    x: jnp.ndarray,
    *,
    config: ModelConfig,
    act: Any,
    live: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, Any, jnp.ndarray, jnp.ndarray]:
    """What an expert layer's feed-forward computes before anything is
    added to the stream: ``(routed [b, s, H], shared, chosen experts [b *
    s, k], load [E] int32)``.  ``routed`` is the weighted sum over a
    token's chosen experts that are HELD (``config.first_expert`` on);
    ``shared()`` traces the shared experts' SwiGLU on every token (None
    where the configuration has none): the caller says where, so that a
    stack adds the two as it always has.  ``live`` ``[b, s]`` marks real
    tokens (ops/moe.moe_dropless).  The router reads the normed
    activations in the residual stream's own dtype (float32 in a hybrid
    stack: nothing is rounded on the way to a discrete choice); the
    experts multiply them in the served dtype."""
    b, s, hdim = x.shape
    with jax.named_scope(SCOPE_MOE_ROUTE):
        h = rms_norm(x, w["ln_mlp_in"], eps=config.rms_norm_eps)

    def routed(h, live):  # [T, H], [T] | None
        return moe_dropless(
            h, w["router"], w.get("expert_bias"),
            w["w1"], w["w3"], w["w2"], act=act,
            top_k=config.num_experts_per_tok,
            norm_topk_prob=config.norm_topk_prob,
            scaling=config.routed_scaling_factor,
            norm_eps=config.router_norm_eps,
            live=live, first_expert=config.first_expert,
            out_dtype=x.dtype,
            n_group=config.n_group, topk_group=config.topk_group,
        )

    t, chunk = b * s, EXPERT_CHUNK_PAIRS // config.num_experts_per_tok
    flat_live = None if live is None else live.reshape(t)
    if t <= 2 * chunk:
        out, chosen, load = routed(h.reshape(t, hdim), flat_live)
    else:
        # a long plain forward (the benchmark's check: 4 x 4,864 tokens x
        # 8 experts): the sorted rows of all pairs at once, in and out in
        # float32, are 6 GB beside the weights.  Routing is a token's
        # own, so ``chunk`` tokens at a time give the same result
        n = -(-t // chunk)
        pad = n * chunk - t
        hp = jnp.pad(h.reshape(t, hdim), ((0, pad), (0, 0)))
        lp = jnp.pad(jnp.ones((t,), jnp.bool_) if flat_live is None
                     else flat_live, (0, pad))
        out, chosen, load = lax.map(
            lambda hl: routed(*hl),
            (hp.reshape(n, chunk, hdim), lp.reshape(n, chunk)))
        out = out.reshape(n * chunk, hdim)[:t]
        chosen = chosen.reshape(n * chunk, -1)[:t]
        load = load.sum(axis=0)

    def shared():
        hs = h.astype(w["shared_gate"].dtype)
        return _project(
            act(_project(hs, w["shared_gate"]))
            * _project(hs, w["shared_up"]), w["shared_down"], x.dtype)

    return (out.reshape(b, s, hdim), shared if "shared_gate" in w else None,
            chosen, load)


def experts_block(
    w: Params,
    x: jnp.ndarray,
    *,
    config: ModelConfig,
    act: Any,
    live: jnp.ndarray | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """The dropless routed feed-forward of a block with its residual:
    ``(x_out, chosen experts [b, s, k], load [E] int32)``
    (``experts_parts``).  The tensors are the experts HELD, from
    ``config.first_expert`` on (``load`` counts those); shared experts,
    where the configuration has them, are one SwiGLU on every token,
    added ONCE whatever is held.  A pre-norm stack adds the routed part
    and the shared one to the stream one after the other; a stack with
    sandwich norms adds ``RMSNorm(routed + shared; ln_mlp_out)``, the
    post-norm of their SUM (under a share: of the partial sum this
    program holds), and closes the residual once."""
    routed, shared, chosen, load = experts_parts(
        w, x, config=config, act=act, live=live)
    if config.sandwich_norms:
        with jax.named_scope(SCOPE_MOE_SHARED):
            m = routed if shared is None else routed + shared()
        with jax.named_scope(SCOPE_MOE_EXPERTS):
            x = x + rms_norm(m, w["ln_mlp_out"], eps=config.rms_norm_eps,
                             unit_offset=config.rms_norm_unit_offset)
    else:
        with jax.named_scope(SCOPE_MOE_EXPERTS):
            x = x + routed
        if shared is not None:
            with jax.named_scope(SCOPE_MOE_SHARED):
                x = x + shared()
    return x, chosen.reshape(*x.shape[:2], -1), load


# (token, expert) pairs ``experts_block`` sorts and multiplies at once:
# above twice this it goes over the tokens in chunks of it (a serving
# tick's pairs, and every accepted check's, are far below)
EXPERT_CHUNK_PAIRS = 32768


def scan_group(body: Any, carry: Any, xs: Any, count: int) -> tuple:
    """``lax.scan(body, carry, xs)`` over one run of a hybrid stack's
    like layers; a run of one is the body itself (no loop, and the
    leading 1 of its leaves is a bitcast)."""
    if count > 1:
        return lax.scan(body, carry, xs)
    carry, ys = body(carry, jax.tree.map(lambda a: a[0], xs))
    return carry, jax.tree.map(lambda a: a[None], ys)

def run_decoder_layer(
    w: Params,
    x: jnp.ndarray,
    *,
    config: ModelConfig,
    act: Any,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    mask_global: jnp.ndarray | None = None,
    mask_local: jnp.ndarray | None = None,
    sliding: jnp.ndarray | bool = False,
    attn_impl: str = "xla",
    kv_update: Any = None,
    output_attentions: bool = False,
    attn_fn: Any = None,
) -> tuple[
    jnp.ndarray,
    tuple[jnp.ndarray, jnp.ndarray],
    jnp.ndarray | None,
    jnp.ndarray,
]:
    """One decoder block (pre-norm or Gemma sandwich-norm residual).

    w: one layer's weight dict (un-stacked leaves).
    kv_update: optional ``(k, v) -> (k_att, v_att)`` hook — the cache write;
        when None, attention runs over the freshly projected K/V (the
        reference's cache-less mode, llama3.2_model.py:874-880).
    sliding: traced bool — selects ``mask_local`` (and the flash kernel's
        window) for Gemma-2's alternating local layers.
    attn_fn: optional ``(q, k_att, v_att, sliding) -> attn`` override — the
        serving engine's paged decode path supplies the block-table-native
        kernel here (its visibility comes from per-row scalars, not a
        [B, Sq, Skv] mask, so ``mask_global``/``mask_local`` may be None).

    Returns ``(x_out, (k_att, v_att), attn_weights | None, moe_aux_loss)``
    (aux loss is 0.0 for dense layers).  Shared by ``forward``'s lax.scan,
    the pipeline-parallel schedule (parallel/pipeline.py), and the serve
    engine's paged decode scan, so all trace identical layer math.
    """
    x, kv_att, attn_weights = attention_block(
        w, x, config=config, cos=cos, sin=sin, mask_global=mask_global,
        mask_local=mask_local, sliding=sliding, attn_impl=attn_impl,
        kv_update=kv_update, output_attentions=output_attentions,
        attn_fn=attn_fn,
    )
    x, moe_aux = ff_block(w, x, config=config, act=act)
    return x, kv_att, attn_weights, moe_aux



def _hybrid_stack(
    groups: list,
    x: jnp.ndarray,
    config: ModelConfig,
    cache: KVCache | None,
    *,
    offset: jnp.ndarray,
    cos: jnp.ndarray,
    sin: jnp.ndarray,
    mask: jnp.ndarray,
    token_mask: jnp.ndarray | None,
    mask_local: jnp.ndarray | None = None,
    rope_window: tuple | None = None,
) -> tuple:
    """``forward``'s layer loop for a stack of more than one kind of
    layer (``mask_local`` and ``rope_window``: a window layer's mask and
    its own RoPE tables, where the configuration has such layers): every
    run of like layers (``config.layer_groups``) is scanned
    over its own stacked leaves, an attention run carrying its cache
    slabs, a conv run its short-convolution state and a run with a
    state-space mixer both and the recurrent state as ``xs`` / ``ys``.
    Returns ``(x, (k, v) | None, {"conv", "ssm", "kda", "retention",
    "retention_z"} states | None each, chosen experts [expert layers, B, S,
    k])``."""
    if cache is not None and (cache.quantized or offset.ndim == 1):
        raise NotImplementedError(
            "a hybrid layer stack runs a float cache with one length: an "
            "int8 cache and per-row rollback (batched speculative decoding) "
            "are not implemented for it"
        )
    if cache is not None and config.has_indexer:
        raise NotImplementedError(
            "the offline cache holds no index keys: a stack with a "
            "sparse-attention indexer is served by the paged pool "
            "(serve/block_pool.py) or run without a cache")
    if cache is not None and config.two_page_classes:
        raise NotImplementedError(
            "the offline cache holds one kind of K/V page: window layers "
            "with kv heads of their own are served by the paged pool "
            "(serve/block_pool.py) or run without a cache")
    act = ACT2FN[config.hidden_act]
    b = x.shape[0]
    # The residual stream is float32 between the blocks (each block
    # norms it, computes in the served dtype and adds its result back):
    # summed in bf16, two computations of the same tokens at different
    # batch shapes drift apart an ulp at a time, and a router turns such
    # a drift into another expert (measured on the chip: PERF.md §6)
    stream_dtype, x = x.dtype, x.astype(jnp.float32)
    new_k, new_v, experts = [], [], []
    new_state: dict[str, list] = {name: [] for name in STATE_LEAVES}
    a0 = c0 = 0  # layers with K/V / with a state seen so far
    # what a sequence carries besides K/V (zeros without a cache: every
    # sequence starts here), as ``config.state_shapes`` lays it out
    fresh = ({name: jnp.zeros(shape, dt) for name, (shape, dt)
              in config.state_shapes(b, stream_dtype).items()}
             if cache is None else
             {name: getattr(cache, name) for name in STATE_LEAVES})
    for w_g, (op, ff, _, n) in zip(groups, config.layer_groups()):
        xs: dict[str, Any] = {}
        if op not in STATE_ONLY_OPS:
            if cache is not None:
                xs["k"] = cache.k[a0:a0 + n]
                if cache.v is not None:  # a latent row has no V beside it
                    xs["v"] = cache.v[a0:a0 + n]
            a0 += n
        if op in STATE_ONLY_OPS or op == "attn_ssm":
            xs.update({name: a[c0:c0 + n] for name, a in fresh.items()
                       if a is not None})
            c0 += n

        def body(x, layer, op=op, ff=ff):
            w, state = layer
            ys: dict[str, Any] = {}

            def history(z):
                hist, ys["conv"] = shifted_history(
                    state["conv"], z, state["conv"].shape[1] + 1)
                return hist

            if op == "latent":
                x, rows = latent_attention_block(
                    w, x, config=config, cos=cos, sin=sin, mask=mask,
                    kv_update=(
                        (lambda row: write_at(
                            state["k"], row.astype(state["k"].dtype), offset))
                        if cache is not None else None))
                if cache is not None:
                    ys["k"] = rows
            elif op == "kda":
                from llm_np_cp_tpu.ops import kda

                def scan(q, k, v, g, beta):
                    o, ys["kda"] = kda.kda_scan(
                        state["kda"], q, k, v, g, beta, chunk=kda.CHUNK,
                        lower_bound=config.kda_lower_bound)
                    return o

                x = kda_block(w, x, config=config, history=history,
                              scan=scan, token_mask=token_mask)
            elif op == "retention":
                from llm_np_cp_tpu.ops import retention

                def scan(q, k, v, log_g):
                    o, ys["retention"], ys["retention_z"] = (
                        retention.retention_scan(
                            state["retention"], state["retention_z"], q, k,
                            v, log_g, chunk=retention.CHUNK))
                    return o

                x = retention_block(w, x, config=config, cos=cos, sin=sin,
                                    scan=scan, token_mask=token_mask)
            elif op != "conv":
                normed = input_norm(w, x, config) if op == "attn_ssm" else None
                l_cos, l_sin = (rope_window if op == "swa" else
                                (cos, sin) if config.global_rope else
                                (None, None))
                mixed, kv_att, _ = attention_block(
                    w, x, config=config, cos=l_cos, sin=l_sin,
                    mask_global=mask_local if op == "swa" else mask,
                    kv_update=(
                        (lambda k, v: update_layer(
                            state["k"], state["v"], k, v, offset))
                        if cache is not None else None),
                    normed=normed,
                )
                if cache is not None:
                    ys["k"], ys["v"] = kv_att
                if op == "attn_ssm":
                    from llm_np_cp_tpu.ops.ssm import ssm_scan

                    def scan(xh, dt, a, bm, cm, d_skip):
                        y, ys["ssm"] = ssm_scan(
                            state["ssm"], xh, dt, a, bm, cm, d_skip,
                            chunk=config.mamba_chunk_size)
                        return y

                    mixed = mixed + ssm_block(
                        w, normed, config=config, history=history, scan=scan,
                        token_mask=token_mask, out_dtype=x.dtype)
                    x = x + mixed
                else:
                    x = mixed
            else:
                x = conv_block(w, x, config=config, history=history,
                               token_mask=token_mask)
            if ff == "experts":
                x, ys["experts"], _ = experts_block(
                    w, x, config=config, act=act, live=token_mask)
            else:
                x, _ = ff_block(w, x, config=config, act=act)
            return x, ys

        x, ys = scan_group(body, x, (w_g, xs), n)
        if "k" in ys:
            new_k.append(ys["k"])
        if "v" in ys:
            new_v.append(ys["v"])
        if cache is not None:
            for name in STATE_LEAVES:
                if name in ys:
                    new_state[name].append(
                        ys[name].astype(getattr(cache, name).dtype))
        if "experts" in ys:
            experts.append(ys["experts"])
    cat = lambda parts: jnp.concatenate(parts, axis=0) if parts else None
    x = x.astype(stream_dtype)
    # (a stack with no layer that has pages hands its empty slabs back)
    return (x, ((cat(new_k) if new_k else cache.k,
                 cat(new_v) if new_v else cache.v)
                if cache is not None else None),
            {name: cat(parts) for name, parts in new_state.items()},
            cat(experts))

def forward(
    params: Params,
    input_ids: jnp.ndarray,
    config: ModelConfig,
    cache: KVCache | None = None,
    *,
    positions: jnp.ndarray | None = None,
    attn_mask: jnp.ndarray | None = None,
    pad_offsets: jnp.ndarray | None = None,
    logits_last_only: bool = False,
    output_hidden_states: bool = False,
    output_attentions: bool = False,
    output_router_losses: bool = False,
    attn_impl: str = "xla",
    skip_logits: bool = False,
    output_experts: bool = False,
) -> tuple:
    """Run the decoder.

    output_experts=True (a stack with dropless expert layers) adds
    ``aux["experts"]``: every expert layer's chosen experts,
    ``[expert layers, B, S, k]`` int32.

    skip_logits=True returns the PRE-final-norm hidden states in the
    logits slot ([B, S, H], or [B, 1, H] under logits_last_only)
    instead of running ``final_logits`` — the fused sampling epilogue
    (ops/pallas/sample_epilogue.py) consumes them and computes
    norm→lm_head→sample in one kernel, so the ``[B, S, V]`` logits
    never materialize.  Callers own the epilogue; everything else about
    the forward (cache writes, masks, aux outputs) is unchanged.

    input_ids: [B, S] int32.
    cache: static KVCache, or None for the reference's cache-less
        full-recompute mode (llama3.2_model.py:874-880).
    positions: [B, S] absolute positions; defaults to
        ``cache.length + arange(S)`` (cache-aware positions, the reference's
        llama3.2_model.py:651-664).
    attn_mask: optional [B, S] bool marking valid (non-pad) input tokens.
    pad_offsets: optional [B] int32 — per-row LEFT-padding amounts for
        ragged batches.  Row b's token in cache slot j carries absolute
        position ``j - pad_offsets[b]``; RoPE and causal masks become
        row-aware, so sequences of different lengths batch together with
        correct relative positions (combine with attn_mask marking the pad
        slots invalid).  The reference can't batch at all (bs=1 generate
        loop, SURVEY §2.8).
    logits_last_only: compute lm_head for the final position only — the
        reference computes logits for ALL positions then samples from the
        last (llama3.2_model.py:803, :891), an O(S·V) waste in prefill.
    output_hidden_states / output_attentions: collect per-layer inputs
        ([L, B, S, H]) / attention probabilities ([L, B, H, Sq, Skv]) as
        scan outputs.  The reference accumulates these tuples on EVERY
        forward (llama3.2_model.py:623-624, 679-706) — a memory tax; here
        they are opt-in (SURVEY §2.6 quirks).  output_attentions requires
        the XLA attention path (the flash kernel never materializes them).
    attn_impl: "xla" (default), "flash" (the Pallas blockwise kernel), or
        "ring" (sequence-parallel ring attention over the ambient mesh's
        "seq" axis — parallel/ring_attention.py; replaces the reference's
        single-device full [S,S] score matrix, llama3.2_model.py:467-469).
        Both are valid only for self-attention over positions 0..S-1
        (fresh-cache prefill or cache-less forward with no padding); the
        cache is still written, but attention reads the current K/V
        directly (identical by causality since later slots are masked).
        "flash_decode" fuses the single-token decode step over the cache
        slab (ops/pallas/decode_attention.py); it consumes the standard
        mask, so it composes with caches, ragged batches, and sliding
        windows, and falls back to XLA for q_len > 1.

    Returns (logits, new_cache) — logits [B, S, V] float32 (or [B, 1, V]
    when logits_last_only) — plus an aux dict with "hidden_states" /
    "attentions" when either output flag is set.
    """
    if output_attentions and attn_impl != "xla":
        raise ValueError("output_attentions requires attn_impl='xla'")
    if attn_impl in ("flash", "ring"):
        if attn_mask is not None or pad_offsets is not None:
            # these kernels build their causal mask from slot index alone —
            # they cannot see per-row validity/position shifts, so ragged
            # inputs would silently attend pad slots
            raise ValueError(
                f"attn_impl={attn_impl!r} does not support attn_mask/"
                "pad_offsets (ragged batches); use attn_impl='xla'"
            )
        # Fresh-cache-only contract: attention reads the freshly projected
        # K/V, so cached history would be silently dropped.  length is
        # traced under jit (the prefill fns pass a fresh cache by
        # construction); enforce host-side whenever it is concrete.
        if cache is not None and not isinstance(cache.length, jax.core.Tracer):
            if int(cache.length) != 0:
                raise ValueError(
                    f"attn_impl={attn_impl!r} requires a fresh cache "
                    f"(length 0, got {int(cache.length)}): cached history "
                    "is not visible to these kernels"
                )
    b, s = input_ids.shape
    if (config.has_indexer and cache is None and b > 1 and positions is None
            and attn_mask is None and pad_offsets is None
            and not (output_hidden_states or output_attentions
                     or output_router_losses)):
        # A stack under a sparse-attention indexer, without a cache: ONE
        # SEQUENCE AT A TIME.  A batch's activations at the published widths
        # (4 x 8,832 tokens: the queries, the expanded keys and values and
        # the results of 64 heads 1.2 GB each, the residual stream 0.9 GB)
        # came to 8.6 GiB of temporaries beside 7.3 GiB of weights when
        # compiled for a v5e; a sequence's come to a quarter.  The rows of
        # a batch without a cache share nothing.
        def one(ids):
            logits, _, *aux = forward(
                params, ids[None], config, logits_last_only=logits_last_only,
                attn_impl=attn_impl, skip_logits=skip_logits,
                output_experts=output_experts)
            return (logits[0], *aux)

        logits, *aux = lax.map(one, input_ids)
        if aux:  # experts [B, expert layers, 1, S, k] -> [layers, B, S, k]
            aux = [{"experts": jnp.moveaxis(aux[0]["experts"][:, :, 0], 0, 1)}]
        return (logits, None, *aux)
    act_dtype = compute_dtype(params)

    # offset: scalar, or [B] per-row lengths (batched speculative decoding)
    offset = cache.length if cache is not None else jnp.zeros((), jnp.int32)
    if positions is None:
        off_rows = offset[:, None] if offset.ndim == 1 else offset
        positions = off_rows + jnp.arange(s, dtype=jnp.int32)[None, :]
        positions = jnp.broadcast_to(positions, (b, s))
        if pad_offsets is not None:
            # left-padded ragged rows: clamp so pad slots get position 0
            # (they are masked out of attention; RoPE just needs validity)
            positions = jnp.maximum(positions - pad_offsets[:, None], 0)

    x = embed_inputs(params, input_ids, config)

    cos, sin = rope_cos_sin(positions, config, dtype=jnp.float32)

    # Masks (shared across layers; sliding-window layers select the local
    # variant inside the scan).
    if cache is not None:
        kv_positions = jnp.arange(cache.max_seq_len, dtype=jnp.int32)
        if pad_offsets is not None:
            kv_positions = kv_positions[None, :] - pad_offsets[:, None]
        # Persist per-slot validity so pad tokens masked out in an earlier
        # chunk stay masked in later calls (the bitmap is the source of
        # truth; slots never written are also False).
        new_tokens_valid = (
            jnp.broadcast_to(attn_mask, (b, s))
            if attn_mask is not None
            else jnp.ones((b, s), dtype=jnp.bool_)
        )
        if offset.ndim == 1:
            cache_valid = jax.vmap(
                lambda row, new, off: lax.dynamic_update_slice(row, new, (off,))
            )(cache.valid, new_tokens_valid, offset)
        else:
            cache_valid = lax.dynamic_update_slice(
                cache.valid, new_tokens_valid, (jnp.zeros((), jnp.int32), offset)
            )
        kv_valid = cache_valid
    else:
        kv_positions = positions
        cache_valid = None
        kv_valid = (
            jnp.broadcast_to(attn_mask, (b, s)) if attn_mask is not None else None
        )
    mask_global = causal_mask(positions, kv_positions, kv_valid=kv_valid)
    if config.sliding_window is not None:
        mask_local = causal_mask(
            positions, kv_positions, window=config.sliding_window, kv_valid=kv_valid
        )
    else:
        mask_local = mask_global

    if config.is_hybrid:
        if output_attentions or output_hidden_states or attn_impl != "xla":
            raise NotImplementedError(
                "a hybrid layer stack runs attn_impl='xla' and collects "
                "neither attentions nor hidden states"
            )
        x, new_kv, new_state, experts = _hybrid_stack(
            params["layers"], x, config, cache, offset=offset, cos=cos,
            sin=sin, mask=mask_global, token_mask=(
                jnp.broadcast_to(attn_mask, (b, s))
                if attn_mask is not None else None),
            mask_local=mask_local,
            rope_window=(rope_cos_sin(
                positions, config, dtype=jnp.float32,
                theta=config.swa_rope_theta)
                if config.swa_rope_theta else (cos, sin)),
        )
        logits = (
            (x[:, -1:, :] if logits_last_only else x) if skip_logits
            else final_logits(params, x, config, last_only=logits_last_only)
        )
        new_cache = None
        if cache is not None:
            new_cache = KVCache(
                k=new_kv[0], v=new_kv[1], valid=cache_valid,
                length=offset + s, **new_state,
            )
        if output_experts:
            return logits, new_cache, {"experts": experts}
        return logits, new_cache

    lp = params["layers"]
    num_layers = config.num_hidden_layers
    is_sliding = jnp.array(
        [config.layer_is_sliding(i) for i in range(num_layers)], dtype=jnp.bool_
    )
    act = ACT2FN[config.hidden_act]

    quantized = cache is not None and cache.quantized
    if cache is not None:
        k_cache, v_cache = cache.k, cache.v
        ks_cache = cache.k_scale if quantized else jnp.zeros((num_layers, 0))
        vs_cache = cache.v_scale if quantized else jnp.zeros((num_layers, 0))
    else:
        # Scan still needs per-layer xs of uniform shape; use zero-size dummies.
        k_cache = jnp.zeros((num_layers, 0), dtype=act_dtype)
        v_cache = jnp.zeros((num_layers, 0), dtype=act_dtype)
        ks_cache = jnp.zeros((num_layers, 0))
        vs_cache = jnp.zeros((num_layers, 0))

    def layer_step(x: jnp.ndarray, xs: tuple) -> tuple[jnp.ndarray, tuple]:
        w, k_l, v_l, ks_l, vs_l, sliding = xs
        x_in = x  # layer input (collected when output_hidden_states)
        written = {}  # int8 mode: slabs+scales stashed by the write hook
        if quantized:

            def kv_update(k, v):
                slabs = update_layer_quantized(
                    k_l, v_l, ks_l, vs_l, k, v, offset
                )
                written["slabs"] = slabs
                if attn_impl == "flash_decode" and k.shape[1] == 1:
                    # the decode kernel reads int8 + scales natively —
                    # hand it the raw slabs as (values, scales) pairs
                    return (slabs[0], slabs[2]), (slabs[1], slabs[3])
                # XLA attention reads the dequantized view; XLA fuses the
                # convert+scale into the einsum operand, so the HBM read
                # of the slab stays int8
                return (
                    dequantize_kv(slabs[0], slabs[2], k.dtype),
                    dequantize_kv(slabs[1], slabs[3], v.dtype),
                )

        elif cache is not None:
            kv_update = lambda k, v: update_layer(k_l, v_l, k, v, offset)
        else:
            kv_update = None
        x, kv_att, attn_weights, moe_aux = run_decoder_layer(
            w, x, config=config, act=act, cos=cos, sin=sin,
            mask_global=mask_global, mask_local=mask_local,
            sliding=sliding, attn_impl=attn_impl, kv_update=kv_update,
            output_attentions=output_attentions,
        )
        if quantized:
            k_l, v_l, ks_l, vs_l = written["slabs"]
        elif cache is not None:
            k_l, v_l = kv_att  # updated cache slabs (flash also writes them)

        ys: tuple = (k_l, v_l, ks_l, vs_l, moe_aux)
        if output_hidden_states:
            ys += (x_in,)
        if output_attentions:
            ys += (attn_weights,)
        return x, ys

    x, scan_out = lax.scan(
        layer_step, x, (lp, k_cache, v_cache, ks_cache, vs_cache, is_sliding),
        unroll=scan_unroll(config),
    )
    new_k, new_v = scan_out[0], scan_out[1]
    new_ks, new_vs = scan_out[2], scan_out[3]
    aux: dict[str, jnp.ndarray] = {}
    if config.is_moe and output_router_losses:
        aux["moe_aux_loss"] = jnp.mean(scan_out[4])  # mean over layers
    pos_idx = 5
    if output_hidden_states:
        aux["hidden_states"] = scan_out[pos_idx]  # [L, B, S, H] layer inputs
        pos_idx += 1
    if output_attentions:
        aux["attentions"] = scan_out[pos_idx]  # [L, B, H, Sq, Skv]

    if skip_logits:
        logits = x[:, -1:, :] if logits_last_only else x
    else:
        logits = final_logits(params, x, config, last_only=logits_last_only)

    new_cache = None
    if cache is not None:
        new_cache = KVCache(
            k=new_k, v=new_v, valid=cache_valid, length=offset + s,
            k_scale=new_ks if quantized else None,
            v_scale=new_vs if quantized else None,
        )

    if output_hidden_states:
        # final normed output appended (reference collects it after the
        # final norm too, llama3.2_model.py:708-713); the same rms_norm is
        # traced inside final_logits — XLA CSEs the duplicate
        aux["final_hidden_state"] = rms_norm(
            x, params["final_norm"], eps=config.rms_norm_eps,
            unit_offset=config.rms_norm_unit_offset,
        )
    if aux:
        return logits, new_cache, aux
    return logits, new_cache
