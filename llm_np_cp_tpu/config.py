"""Model configuration.

The reference consumes a raw HF ``config.json`` through an ``AttributeDict``
with no validation or defaults (llama3.2_model.py:204-207, 1068-1073).  Here
the consumed key set (SURVEY §2.1) becomes an explicit frozen dataclass so a
config is a static, hashable object that can close over a jitted step.

One dataclass covers both model families; the Gemma-2 deltas
(gemma2_model.py per SURVEY §2.7) are expressed as explicit fields rather
than a parallel class hierarchy:

- ``rms_norm_unit_offset``    — Gemma's (1 + w) RMSNorm parameterization
  (gemma2_model.py:334)
- ``sandwich_norms``          — 4 norms/layer with post-norms inside the
  residual (gemma2_model.py:588-591, 621-643)
- ``scale_embeddings``        — hidden *= sqrt(hidden_size) after lookup
  (gemma2_model.py:738-739)
- ``final_logit_softcapping`` — tanh soft cap on logits (gemma2_model.py:867-870)
- ``attn_logit_softcapping``  — soft cap on attention scores.  Present in the
  Gemma-2 config (gemma2_model.py:48) but NOT applied by the reference; we
  implement it correctly and expose ``reference_parity()`` to reproduce the
  reference's simplified behavior.
- ``sliding_window``          — local attention window, alternating
  local/global layers.  Also dropped by the reference (SURVEY §2.7).
- ``query_pre_attn_scalar``   — Gemma attention scale.  The reference
  computes it and then ignores it (gemma2_model.py:434 vs :541-543); we use
  it (identical for 2B/9B where it equals head_dim, correct for 27B).
"""

from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path
from typing import Any, Mapping


@dataclasses.dataclass(frozen=True)
class AttnKind:
    """One kind of attention layer (``ModelConfig.attn_kind``)."""

    kind: str  # "global" | "window"
    kv_heads: int
    key_dim: int
    value_dim: int
    rope_theta: float | None  # None: the kind carries no positional encoding
    window: int | None
    sink: bool


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Static architecture description for a decoder-only transformer."""

    model_type: str = "llama"
    vocab_size: int = 128256
    hidden_size: int = 2048
    intermediate_size: int = 8192
    num_hidden_layers: int = 16
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    head_dim: int = 64
    max_position_embeddings: int = 131072
    rope_theta: float = 500000.0
    rms_norm_eps: float = 1e-5
    hidden_act: str = "silu"
    tie_word_embeddings: bool = True
    attention_bias: bool = False
    # o_proj bias: None = follow attention_bias (HF Llama puts a bias on
    # all four attention projections); False = Qwen-2's pattern (Q/K/V
    # biased, o_proj not)
    attention_out_bias: bool | None = None
    mlp_bias: bool = False

    # --- RoPE scaling (llama-3 style). The reference ignores `rope_scaling`
    # entirely (SURVEY §2.2: "no llama-3 rope scaling"); we support it so
    # Llama-3.1/3.2 long-context positions are correct, and disable it in
    # reference-parity mode.
    rope_scaling_type: str | None = None  # None | "llama3"
    rope_scaling_factor: float = 8.0
    rope_scaling_low_freq_factor: float = 1.0
    rope_scaling_high_freq_factor: float = 4.0
    rope_scaling_original_max_position: int = 8192

    # --- Gemma-2 deltas (SURVEY §2.7) ---
    rms_norm_unit_offset: bool = False
    sandwich_norms: bool = False
    scale_embeddings: bool = False
    final_logit_softcapping: float | None = None
    attn_logit_softcapping: float | None = None
    sliding_window: int | None = None
    # Which layers are WINDOW layers when `sliding_window` is set, as data
    # of the layer declaration: layer ``i`` is one where
    # ``window_pattern[i % len(window_pattern)]`` is 1.  The default is
    # Gemma-2's alternation (window, global, ..: config key
    # `cache_implementation: hybrid`, gemma2_model.py:104); MiMo-V2 states
    # its published ``hybrid_layer_pattern`` whole.
    window_pattern: tuple[int, ...] = (1, 0)
    query_pre_attn_scalar: float | None = None

    # --- Layer-scan unroll (performance knob, no numeric effect): unroll
    # the lax.scan over layers so XLA can software-pipeline the per-layer
    # weight stream across layer boundaries.  Part of the config — and so
    # of every jit cache key a config closes over — because an env-var
    # read at trace time silently pins the first-seen value (ADVICE r4).
    # The LLMTPU_SCAN_UNROLL env var still overrides it at TRACE time for
    # bench A/Bs; library users should set this field instead.  Values
    # that don't divide num_hidden_layers degrade to 1.
    scan_unroll: int = 1

    # --- Mixture-of-Experts (framework extension; neither reference family
    # is MoE — SURVEY §2.9 lists EP as N/A — but the framework supports
    # Mixtral-style sparse MLPs so expert parallelism has a real workload).
    num_local_experts: int | None = None
    num_experts_per_tok: int = 2
    moe_capacity_factor: float = 2.0  # per-expert buffer = gs*k/E * this
    moe_group_size: int = 1024  # GShard token-group length (keeps dispatch linear in T)
    router_aux_loss_coef: float = 0.02

    # --- A layer stack that is not one kind of layer (LFM2-MoE).  Each
    # layer declares its OPERATOR (``layer_types[l]``: "full_attention" or
    # "conv", a gated short convolution that carries ``conv_L_cache - 1``
    # values a channel between steps instead of K/V) and its FEED-FORWARD
    # (dense SwiGLU for the first ``num_dense_layers``, dropless routed
    # experts after them).  ``layer_types is None`` is the homogeneous
    # stack every other family has: one stacked pytree, one scanned body.
    layer_types: tuple[str, ...] | None = None
    conv_L_cache: int = 3
    conv_bias: bool = False
    qk_norm: bool = False  # RMSNorm over head_dim on q and k before RoPE
    # dropless sigmoid-routed experts (ops/moe.moe_dropless): scores are
    # sigmoid(gate) in float32, the top k are chosen by score + a
    # per-expert selection bias, weighted by the scores WITHOUT the bias
    num_experts: int | None = None
    num_dense_layers: int = 0
    moe_intermediate_size: int | None = None
    use_expert_bias: bool = False
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    # Seeded random weights only (models.init_params; no forward reads
    # it), like a checkpoint's ``initializer_range``: how much of a random
    # expert is its own — expert e's matrices are ``(shared + a * own_e) /
    # sqrt(1 + a^2)``.  None: every expert an independent draw.  A
    # configuration's file sets it (key ``init_expert_specific``) and
    # says why; the program has no value of its own.
    init_expert_specific: float | None = None
    # ... and the scale a random routed expert's output projection (``w2``)
    # is drawn at (None: 0.02, every matrix's): how much of the residual
    # stream one routed expert's answer is, which is what a flipped choice
    # between two near-tied experts changes
    init_expert_out_std: float | None = None

    # --- A state-space mixer in parallel with attention in every layer
    # (Falcon-H1, ``model_type: falcon_h1``).  ``mamba_d_ssm is None`` is
    # every other family.  The mixer is Mamba-2: ``mamba_n_heads`` heads of
    # ``mamba_d_head`` channels, a ``[d_head, d_state]`` float32 recurrent
    # state a head, B and C shared by the heads of a group, a depthwise
    # causal convolution of ``mamba_d_conv`` taps over [x, B, C] in front.
    # Both mixers read ONE normed input and their results are summed into
    # the residual stream; the muP multipliers below are applied where the
    # published modeling code applies them (models/transformer.py).
    mamba_d_ssm: int | None = None
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_conv_bias: bool = True
    # the chunk of the program's own scan (ops/ssm.py): the published
    # kernel's chunk, not part of the mathematics
    mamba_chunk_size: int = 128
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)  # gate, down
    ssm_multipliers: tuple[float, ...] = (1.0,) * 5  # z, x, B, C, dt
    # Seeded random weights only (models.init_params; no forward reads it):
    # the standard deviation ``ssm_in_proj`` is drawn with, where a
    # configuration's FILE states one and says why (at 0.02, the value of
    # every other matrix, the recurrent state's share of the mixer's output
    # is a thousandth of the skip ``D x`` and no comparison of outputs can
    # see a broken state: PERF.md section 6, PR 34).
    init_ssm_in_proj_std: float | None = None

    # --- Latent attention (MLA; ``model_type: deepseek_v3``).
    # ``kv_lora_rank is None`` is every other family.  A token leaves ONE
    # row a layer in a cache, ``[c' kv_lora_rank | k_pe qk_rope_head_dim]``
    # (the compressed K/V after ``kv_a_layernorm``, and the one rotated
    # key part all heads share), instead of K and V per kv head; a query
    # head is ``[q_nope | q_pe]``, its value ``v_head_dim`` wide.
    # ``head_dim`` is the rotated width (``qk_rope_head_dim``), so the
    # RoPE tables are the ones every family builds.
    kv_lora_rank: int | None = None
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # RoPE over pairs (2i, 2i+1) instead of (i, i + d/2): ops/rope.py
    rope_interleave: bool = False
    # shared experts beside the routed ones: ONE SwiGLU of this width
    # (``n_shared_experts x moe_intermediate_size``) on every token
    shared_expert_intermediate_size: int | None = None
    # the share of the routed experts THIS program holds: the router is
    # ``num_experts`` wide, the expert tensors ``num_experts_held`` from
    # ``first_expert`` on (None: all of them); a pair whose expert is not
    # held adds nothing here, that part of the sum is another holder's
    num_experts_held: int | None = None
    first_expert: int = 0
    # what ``norm_topk_prob`` adds to the sum of a token's top-k scores
    # (LFM2's published code: 1e-6; DeepSeek-V3's: 1e-20)
    router_norm_eps: float = 1e-6
    # a QUERY latent (``model_type: glm_moe_dsa``; None: one full-rank
    # ``q_proj``): ``q = rmsnorm(h W_qa) W_qb``
    q_lora_rank: int | None = None
    # ... under a learned sparse-attention indexer (``index_topk is None``
    # is every other family): a token scores every visible position with
    # ``index_n_heads`` heads of ``index_head_dim`` against ONE cached
    # index key a token and layer (its leading ``qk_rope_head_dim``
    # columns rotated), and attends the ``index_topk`` best of them, all
    # of them while it sees no more (ops/sparse_index.py has the
    # equations and the tie rule)
    index_topk: int | None = None
    index_n_heads: int = 0
    index_head_dim: int = 0
    indexer_rope_interleave: bool = False

    # --- Window and global layers that differ in more than the mask
    # (MiMo-V2, ``model_type: mimo_v2``): a window layer has its own kv
    # heads and RoPE base and a learned per-head SINK logit in its
    # softmax's denominator; K heads are ``head_dim`` wide of which the
    # leading ``rope_dim`` columns are rotated, V heads ``v_head_dim``;
    # the values are multiplied by ``attention_value_scale``.
    # ``swa_num_key_value_heads is None`` is every other family: one kind
    # of K/V page, one pool class (``attn_kind``).
    swa_num_key_value_heads: int | None = None
    swa_rope_theta: float | None = None
    swa_sink: bool = False
    rope_dim: int | None = None  # None: all of head_dim
    attention_value_scale: float = 1.0

    # --- Window and global layers of ONE shape that are still two kinds
    # (Trinity / AFMoE, ``model_type: afmoe``): the window layers rotate
    # q and k, the global layers carry no positional encoding at all
    # (``global_rope`` False); every layer multiplies its attention's
    # result by ``sigmoid(W_g h)``, per head and column, before ``o_proj``
    # (``attn_output_gate``); and the window layers' pages are a bounded
    # class of their own although a page of theirs has the global layers'
    # shape (``window_page_class``: a class is a KIND's, not a shape's).
    attn_output_gate: bool = False
    global_rope: bool = True
    window_page_class: bool = False

    # --- Delta-rule linear-attention layers (KDA) among latent ones
    # (Ling-3.0, ``model_type: ling_hybrid``).  ``layer_group_size is
    # None`` is every other family.  Layer ``i`` is latent attention where
    # ``(i + 1) % layer_group_size == 0`` and a KDA layer elsewhere:
    # ``num_attention_heads`` heads of ``kda_head_dim`` channels (keys and
    # values alike), a ``[kda_head_dim, kda_head_dim]`` float32 matrix
    # state a head, a depthwise causal convolution of ``kda_conv_taps``
    # taps over q, k and v in front, a per-channel log-decay in
    # ``[kda_lower_bound, 0]`` (ops/kda.py has the equations).
    layer_group_size: int | None = None
    kda_head_dim: int = 0
    kda_conv_taps: int = 4
    kda_lower_bound: float = -5.0
    # Seeded random weights only (models.init_params; no forward reads
    # it): the span ``(slowest, fastest)`` of a token's log-decay over a
    # head's channels that ``kda_dt_bias`` is drawn for, where a
    # configuration's FILE states one and says why (with zero biases the
    # gate sits at ``kda_lower_bound / 2``, the state forgets in two
    # tokens and no comparison of outputs can see one carried wrongly)
    init_kda_log_decay: tuple[float, float] | None = None
    # group-limited routing (DeepSeek-V3's): the router's experts in
    # ``n_group`` groups of consecutive ones, a token's choice limited to
    # its ``topk_group`` best groups (ops/moe.route_sigmoid_topk).
    # ``n_group == 1`` is no limit and traces nothing.
    n_group: int = 1
    topk_group: int = 1

    # --- Power-retention layers in EVERY layer (Brumby, ``model_type:
    # brumby``): an attention-free stack.  ``retention_degree is None`` is
    # every other family.  A layer's mixer is linear attention whose
    # feature map is the symmetric ``retention_degree``-th power of the key
    # (2: the monomials ``k_a k_b``), with a scalar forget gate a KV head
    # and token and a normaliser (ops/retention.py has the equations): q /
    # k / v / o projections, q / k RMSNorm and RoPE as a GQA layer has
    # them, no pages — a float32 state a kv head instead.
    retention_degree: int | None = None

    def __post_init__(self) -> None:
        # Note: hidden_size need not equal heads*head_dim (Gemma-2-2B:
        # 2304 hidden, 8 heads of 256), so no divisibility constraint there.
        if self.num_attention_heads % self.num_key_value_heads != 0:
            raise ValueError(
                f"num_attention_heads {self.num_attention_heads} not divisible "
                f"by num_key_value_heads {self.num_key_value_heads}"
            )
        if (self.layer_types is not None
                and len(self.layer_types) != self.num_hidden_layers):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}"
            )
        if self.is_latent and (
                self.head_dim != self.qk_rope_head_dim
                or self.qk_rope_head_dim % 2):
            raise ValueError(
                f"latent attention rotates head_dim {self.head_dim} columns: "
                f"qk_rope_head_dim is {self.qk_rope_head_dim} (and is even)")
        if self.has_indexer and not (
                self.is_latent and self.q_lora_rank
                and self.layer_group_size is None and self.index_topk > 0
                and self.index_n_heads > 0
                and self.index_head_dim >= self.qk_rope_head_dim):
            raise ValueError(
                "a sparse-attention indexer reads a query latent "
                f"(q_lora_rank {self.q_lora_rank}) beside latent attention "
                f"in every layer: {self.index_n_heads} heads of "
                f"{self.index_head_dim} (rotated {self.qk_rope_head_dim}), "
                f"top {self.index_topk}")
        if self.two_page_classes:
            kv_heads = self.attn_kind("window").kv_heads
            if self.sliding_window is None or self.is_latent or (
                    self.num_attention_heads % kv_heads):
                raise ValueError(
                    "window layers in a page class of their own "
                    f"({kv_heads} kv heads) need a sliding_window, "
                    "K/V per head and query heads they divide "
                    f"({self.num_attention_heads})")
        if self.rope_dim is not None and not (
                0 < self.rope_dim <= self.head_dim and self.rope_dim % 2 == 0):
            raise ValueError(
                f"rope_dim {self.rope_dim} is not an even number of head_dim "
                f"{self.head_dim}'s columns")
        if self.num_experts is not None and not (
                0 <= self.first_expert
                and self.first_expert + self.experts_held <= self.num_experts):
            raise ValueError(
                f"experts {self.first_expert}..+{self.experts_held} are not "
                f"among the router's {self.num_experts}")
        if self.layer_group_size is not None and (
                not self.is_latent or self.kda_head_dim < 1
                or self.layer_group_size < 2):
            raise ValueError(
                "KDA layers among latent ones (layer_group_size "
                f"{self.layer_group_size}) need kv_lora_rank, a kda_head_dim "
                "and a group of at least 2 layers")
        if self.n_group > 1 and (
                self.num_experts is None or self.num_experts % self.n_group
                or not 0 < self.topk_group <= self.n_group
                or self.topk_group * (self.num_experts // self.n_group)
                < self.num_experts_per_tok):
            raise ValueError(
                f"group-limited routing: {self.n_group} groups over "
                f"{self.num_experts} experts, {self.topk_group} kept, for "
                f"{self.num_experts_per_tok} experts a token")
        if self.retention_degree is not None and (
                self.retention_degree != 2 or self.head_dim % 8
                or self.value_dim != self.head_dim):
            raise ValueError(
                f"power retention is implemented at degree 2 (got "
                f"{self.retention_degree}) over heads of whole blocks of 8 "
                f"channels (head_dim {self.head_dim}), values as wide as keys")
        if self.mamba_d_ssm is not None:
            if self.mamba_d_ssm != self.mamba_n_heads * self.mamba_d_head:
                raise ValueError(
                    f"mamba_d_ssm {self.mamba_d_ssm} is not mamba_n_heads "
                    f"{self.mamba_n_heads} x mamba_d_head {self.mamba_d_head}")
            if self.mamba_n_heads % self.mamba_n_groups:
                raise ValueError(
                    f"mamba_n_heads {self.mamba_n_heads} not divisible by "
                    f"mamba_n_groups {self.mamba_n_groups}")

    # ------------------------------------------------------------------
    @property
    def is_moe(self) -> bool:
        return self.num_local_experts is not None

    @property
    def o_proj_bias(self) -> bool:
        if self.attention_out_bias is not None:
            return self.attention_out_bias
        return self.attention_bias

    @property
    def num_query_groups(self) -> int:
        return self.num_attention_heads // self.num_key_value_heads

    @property
    def attn_scale(self) -> float:
        """Scale applied to q·k scores.

        Llama: 1/sqrt(head_dim) (llama3.2_model.py:467-469).  Gemma-2:
        query_pre_attn_scalar**-0.5 — the reference assigns this then ignores
        it (gemma2_model.py:434); we apply it.  Latent attention: the whole
        query head's width, ``qk_nope_head_dim + qk_rope_head_dim``.
        """
        if self.query_pre_attn_scalar is not None:
            return float(self.query_pre_attn_scalar) ** -0.5
        if self.is_latent:
            return float(self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5
        return float(self.head_dim) ** -0.5

    def layer_is_sliding(self, layer_idx: int) -> bool:
        return self.sliding_window is not None and bool(
            self.window_pattern[layer_idx % len(self.window_pattern)])

    @property
    def value_dim(self) -> int:
        """A value head's width (a key head's unless stated)."""
        return self.v_head_dim or self.head_dim

    @property
    def two_page_classes(self) -> bool:
        """Window layers hold their K/V pages in a class of their own,
        bounded by the window: a pool keeps two.  A class belongs to a
        KIND of layer, not to a page shape: MiMo-V2's window layers have
        kv heads of their own, AFMoE's (``window_page_class``) the global
        layers' 8 heads of 128 and are bounded all the same."""
        return self.swa_num_key_value_heads is not None or self.window_page_class

    def attn_kind(self, kind: str) -> "AttnKind":
        """What an attention layer of ``kind`` (``"global"`` /
        ``"window"``) is: kv heads, key and value widths, RoPE base
        (None: no positional encoding), window (None: the whole context)
        and whether its softmax has a sink.  The ONE statement of it:
        parameters, caches, pools, the forward and the cost files read
        it.  Two kinds need not differ in shape, and do not decide the
        pool's classes by it: that is ``two_page_classes``.  Gemma-2's
        alternating family states neither kv heads of its own nor
        ``window_page_class``: its two kinds share the one class and its
        window layers hold full-length chains."""
        window = kind == "window"
        theta = (self.swa_rope_theta if window and self.swa_rope_theta
                 else self.rope_theta)
        return AttnKind(
            kind=kind,
            kv_heads=(self.swa_num_key_value_heads
                      if window and self.swa_num_key_value_heads is not None
                      else self.num_key_value_heads),
            key_dim=self.head_dim, value_dim=self.value_dim,
            rope_theta=(float(theta) if window or self.global_rope
                        else None),
            window=self.sliding_window if window else None,
            sink=window and self.swa_sink)

    @property
    def window_layers(self) -> tuple[int, ...]:
        """Layers whose pages are the window class's, in order (none
        where the pool has one class)."""
        if not self.two_page_classes:
            return ()
        return tuple(i for i in self.attn_layers if self.layer_is_sliding(i))

    @property
    def global_layers(self) -> tuple[int, ...]:
        """Layers whose pages are the growing class's, in order."""
        if not self.two_page_classes:
            return self.attn_layers
        return tuple(i for i in self.attn_layers
                     if not self.layer_is_sliding(i))

    # -- the per-layer declaration (what a layer IS, not a schedule) ----
    @property
    def is_hybrid(self) -> bool:
        """The stack is not the one scanned body every dense family has
        (an operator other than GQA attention, or a routed feed-forward,
        in any layer): params are groups of like layers
        (``layer_groups``), not one stacked pytree."""
        return any(
            self.layer_op(i) != "attn" or self.layer_ff(i) != "dense"
            for i in range(self.num_hidden_layers))

    def layer_op(self, layer_idx: int) -> str:
        """``"attn"``, ``"conv"``, ``"attn_ssm"`` (attention and a
        state-space mixer side by side, both reading one normed input),
        ``"latent"`` (attention over one compressed row a token),
        ``"kda"`` (delta-rule linear attention: a matrix state, no pages),
        ``"retention"`` (power retention: a symmetric-power matrix state a
        kv head, no pages) or ``"swa"`` (a window layer whose pages are a class of their
        own, ``two_page_classes``, whatever their shape:
        ``attn_kind("window")``): the operator of layer ``layer_idx``."""
        if self.retention_degree is not None:
            return "retention"
        if self.layer_group_size is not None and (
                (layer_idx + 1) % self.layer_group_size):
            return "kda"
        if self.is_latent:
            return "latent"
        if self.mamba_d_ssm is not None:
            return "attn_ssm"
        if self.two_page_classes and self.layer_is_sliding(layer_idx):
            return "swa"
        if self.layer_types is None:
            return "attn"
        return "conv" if self.layer_types[layer_idx] == "conv" else "attn"

    def layer_ff(self, layer_idx: int) -> str:
        """``"dense"`` or ``"experts"``: its feed-forward."""
        if self.num_experts is None or layer_idx < self.num_dense_layers:
            return "dense"
        return "experts"

    @property
    def attn_layers(self) -> tuple[int, ...]:
        """Layers that hold K/V (or a latent row), in order: the only
        ones a cache or a pool has pages for (page ``i`` belongs to
        ``attn_layers[i]``; with two page classes, to ``global_layers[i]``
        / ``window_layers[i]`` of its class)."""
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.layer_op(i) not in STATE_ONLY_OPS)

    @property
    def has_pages(self) -> bool:
        """Some layer holds K/V (or a latent row): a cache or a pool has a
        page class.  False for a stack of state-only operators alone (an
        attention-free model): the ONE statement of it the pool, the engine
        and the CLI's sizing read."""
        return bool(self.attn_layers)

    @property
    def conv_layers(self) -> tuple[int, ...]:
        """Layers that carry a short-convolution state, in order."""
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.layer_op(i) == "conv")

    @property
    def ssm_layers(self) -> tuple[int, ...]:
        """Layers that carry a state-space mixer's recurrent state (and
        the history of the convolution in front of it), in order."""
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.layer_op(i) == "attn_ssm")

    @property
    def kda_layers(self) -> tuple[int, ...]:
        """Layers that carry a delta-rule matrix state (and the history
        of the convolution in front of it), in order."""
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.layer_op(i) == "kda")

    @property
    def retention_layers(self) -> tuple[int, ...]:
        """Layers that carry a power-retention state, in order."""
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.layer_op(i) == "retention")

    @property
    def retention_rows(self) -> int:
        """Rows of a kv head's power-retention state: the distinct
        monomials ``k_a k_b`` in whole registers of 8
        (ops/pallas/retention_state_update.phi_rows: 8,704 where the
        mathematics needs 8,256, at ``head_dim`` 128)."""
        nb = self.head_dim // 8
        return 64 * nb * (nb + 1) // 2

    @property
    def kda_dim(self) -> int:
        """Channels of one of a KDA layer's streams (q, k, v, decay)."""
        return self.num_attention_heads * self.kda_head_dim

    @property
    def carries_state(self) -> bool:
        """A sequence carries more than K/V between steps: a function of
        the WHOLE sequence so far, which no block of a pool holds."""
        return bool(self.state_kind)

    @property
    def state_kind(self) -> str | None:
        """What the recurrent state of this stack's layers is, in the words
        a refusal or a banner uses (None: K/V alone)."""
        for layers, kind in ((self.conv_layers, "conv"),
                             (self.kda_layers, "delta-rule"),
                             (self.ssm_layers, "state-space"),
                             (self.retention_layers, "power-retention")):
            if layers:
                return kind
        return None

    @property
    def mamba_conv_dim(self) -> int:
        """Channels of the state-space mixer's convolution: [x, B, C]."""
        return self.mamba_d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    def state_shapes(self, slots: int, dtype: Any) -> dict[str, tuple]:
        """What a sequence carries between steps besides K/V, as
        ``{leaf: (shape, dtype)}`` with ``slots`` rows a layer; empty for
        a stack of attention layers alone.  ``conv`` is the history of a
        short convolution (a conv layer's gated inputs, or the [x, B, C]
        inputs in front of a state-space mixer) in the served ``dtype``;
        ``ssm`` is the mixer's recurrent state, float32 whatever is
        served: it is rounded once a token for hundreds of tokens; ``kda``
        is a delta-rule layer's matrix state, float32 likewise (its
        ``conv`` holds the q, k and v streams side by side);
        ``retention`` and ``retention_z`` are a power-retention layer's two
        leaves, float32 likewise: a kv head's ``S [retention_rows,
        head_dim]`` (the gated sum of ``phi(k) v^T``) and ``Z [head_dim,
        head_dim]`` (the gated sum of ``k k^T``: ``phi(q) . z`` for the
        published sum of keys' features IS ``q^T Z q``) — TWO leaves and
        not one of ``head_dim + 1`` columns, which a TPU would pad to 256
        lanes.  The ONE statement of these shapes: the pool
        (``PagedKV.state``) and the offline cache (``KVCache.conv`` /
        ``.ssm`` / ``.kda`` / ``.retention`` / ``.retention_z``) both read
        it."""
        out: dict[str, tuple] = {}
        if self.conv_layers:
            out["conv"] = ((len(self.conv_layers), slots,
                            self.conv_L_cache - 1, self.hidden_size), dtype)
        if self.ssm_layers:
            n = len(self.ssm_layers)
            out["conv"] = ((n, slots, self.mamba_d_conv - 1,
                            self.mamba_conv_dim), dtype)
            out["ssm"] = ((n, slots, self.mamba_n_heads, self.mamba_d_head,
                           self.mamba_d_state), "float32")
        if self.kda_layers:
            n, d = len(self.kda_layers), self.kda_head_dim
            out["conv"] = ((n, slots, self.kda_conv_taps - 1,
                            3 * self.kda_dim), dtype)
            out["kda"] = ((n, slots, self.num_attention_heads, d, d),
                          "float32")
        if self.retention_layers:
            lead = (len(self.retention_layers), slots,
                    self.num_key_value_heads)
            out["retention"] = (
                lead + (self.retention_rows, self.head_dim), "float32")
            out["retention_z"] = (
                lead + (self.head_dim, self.head_dim), "float32")
        return out

    @property
    def is_latent(self) -> bool:
        """The layers that have pages hold one compressed row a token
        (MLA): every layer, or (``layer_group_size``) the one latent
        layer of each group of delta-rule ones."""
        return self.kv_lora_rank is not None

    @property
    def has_indexer(self) -> bool:
        """Latent attention over a learned SELECTION of the context: every
        layer holds an index key a token beside its latent row."""
        return self.index_topk is not None

    def kv_token_shapes(self, kind: str = "global") -> dict[str, tuple[int, ...]]:
        """What ONE token leaves in a cache, a layer of ``kind`` that has
        pages, as ``{leaf: shape}``: K and V per kv head (a layer kind's
        own heads and widths: ``attn_kind``), or (latent attention) the
        one row ``[c' | k_pe]`` whose first ``kv_lora_rank`` columns are
        also the values, and no ``v``.  The ONE statement of it: the pool
        (``PagedKV``), the offline cache (``KVCache``) and a
        configuration's cost file read it (a store may pad a row to the
        device's lanes: serve/block_pool.py says where)."""
        if self.is_latent:
            return {"k": (self.kv_lora_rank + self.qk_rope_head_dim,)}
        a = self.attn_kind(kind)
        return {"k": (a.kv_heads, a.key_dim), "v": (a.kv_heads, a.value_dim)}

    def kv_bytes_per_token(self, itemsize: int = 2,
                           kind: str | None = None) -> int:
        """Bytes a token holds in a cache over all layers with pages (of
        ``kind``, where one is named), as the algorithm needs them
        (``kv_token_shapes``; int8 scale pages not counted; a window
        layer counted as if it kept every token — what a pool bounds)."""
        def of(k: str, layers: int) -> int:
            # (an indexer's key lies beside the latent row: has_indexer)
            return layers * itemsize * (sum(
                math.prod(shape)
                for shape in self.kv_token_shapes(k).values())
                + (self.index_head_dim if self.has_indexer else 0))
        n_window = len(self.window_layers)
        per_kind = {"global": of("global", len(self.attn_layers) - n_window),
                    "window": of("window", n_window)}
        return per_kind[kind] if kind else sum(per_kind.values())

    @property
    def experts_held(self) -> int:
        """Routed experts whose weights this program holds."""
        return (self.num_experts if self.num_experts_held is None
                else self.num_experts_held)

    @property
    def expert_layers(self) -> tuple[int, ...]:
        return tuple(i for i in range(self.num_hidden_layers)
                     if self.layer_ff(i) == "experts")

    def layer_groups(self) -> tuple[tuple[str, str, int, int], ...]:
        """The stack as runs of like layers, ``(op, ff, first, count)``
        (a window layer with pages of its own is an operator of its own,
        ``"swa"``):
        each run is one stacked pytree and one scanned body.  An expert
        layer is always a run of its own: the grouped matmul wants each
        expert tensor as a whole buffer, and a scan would copy a layer's
        2 x 235 MB out of the stack every step (compiled for a v5e: 6 GB
        of copies a tick for LFM2's nine stacked expert layers).  LFM2's
        16 layers are one run of two conv + dense blocks, then 14 runs of
        one."""
        groups: list[tuple[str, str, int, int]] = []
        for i in range(self.num_hidden_layers):
            kind = (self.layer_op(i), self.layer_ff(i))
            if groups and groups[-1][:2] == kind and kind[1] != "experts":
                op, ff, first, count = groups[-1]
                groups[-1] = (op, ff, first, count + 1)
            else:
                groups.append((*kind, i, 1))
        return tuple(groups)

    # ------------------------------------------------------------------
    @classmethod
    def from_hf_dict(cls, d: Mapping[str, Any]) -> "ModelConfig":
        """Build from a raw HF ``config.json`` mapping.

        Mirrors the key set the reference actually reads (SURVEY §2.1) plus
        the Gemma-2 keys it reads-but-drops (sliding_window,
        attn_logit_softcapping).
        """
        model_type = d.get("model_type", "llama")
        if model_type not in KNOWN_MODEL_TYPES:
            # an architecture this package has no equations for must not
            # be answered as a llama of the same widths
            raise ValueError(
                f"unknown model_type {model_type!r}: this package runs "
                f"{', '.join(sorted(KNOWN_MODEL_TYPES))}"
            )
        num_heads = d["num_attention_heads"]
        head_dim = d.get("head_dim") or d["hidden_size"] // num_heads
        kwargs: dict[str, Any] = dict(
            model_type=model_type,
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_hidden_layers=d["num_hidden_layers"],
            num_attention_heads=num_heads,
            num_key_value_heads=d.get("num_key_value_heads", num_heads),
            head_dim=head_dim,
            max_position_embeddings=d.get("max_position_embeddings", 8192),
            rope_theta=d.get("rope_theta", 10000.0),
            rms_norm_eps=d.get("rms_norm_eps", d.get("norm_eps", 1e-6)),
            hidden_act=d.get("hidden_act", d.get("hidden_activation", "silu")),
            tie_word_embeddings=d.get("tie_word_embeddings", True),
            attention_bias=d.get("attention_bias", False),
            mlp_bias=d.get("mlp_bias", False),
        )
        if d.get("num_local_experts"):
            kwargs.update(
                num_local_experts=d["num_local_experts"],
                num_experts_per_tok=d.get("num_experts_per_tok", 2),
                router_aux_loss_coef=d.get("router_aux_loss_coef", 0.02),
            )
        rope_scaling = d.get("rope_scaling") or None
        if rope_scaling and rope_scaling.get("rope_type", rope_scaling.get("type")) == "llama3":
            kwargs.update(
                rope_scaling_type="llama3",
                rope_scaling_factor=rope_scaling.get("factor", 8.0),
                rope_scaling_low_freq_factor=rope_scaling.get("low_freq_factor", 1.0),
                rope_scaling_high_freq_factor=rope_scaling.get("high_freq_factor", 4.0),
                rope_scaling_original_max_position=rope_scaling.get(
                    "original_max_position_embeddings", 8192
                ),
            )
        if model_type == "gemma2":
            kwargs.update(
                rms_norm_unit_offset=True,
                sandwich_norms=True,
                scale_embeddings=True,
                final_logit_softcapping=d.get("final_logit_softcapping"),
                attn_logit_softcapping=d.get("attn_logit_softcapping"),
                sliding_window=d.get("sliding_window"),
                query_pre_attn_scalar=d.get("query_pre_attn_scalar"),
                hidden_act=d.get("hidden_activation", d.get("hidden_act", "gelu_pytorch_tanh")),
            )
        if model_type == "lfm2_moe":
            # LFM2-MoE: gated short convolutions between GQA layers (q/k
            # RMSNorm before RoPE, no biases), two leading dense SwiGLU
            # blocks, then dropless sigmoid-routed experts.  The family
            # ties the head (the published config has no key for it).
            layer_types = tuple(d["layer_types"])
            if len(layer_types) != d["num_hidden_layers"]:
                raise ValueError(
                    f"layer_types names {len(layer_types)} layers, "
                    f"num_hidden_layers is {d['num_hidden_layers']}"
                )
            bad = set(layer_types) - {"conv", "full_attention"}
            if bad:
                raise ValueError(f"unknown layer_types {sorted(bad)}")
            kwargs.update(
                layer_types=layer_types,
                conv_L_cache=d.get("conv_L_cache", 3),
                conv_bias=d.get("conv_bias", False),
                qk_norm=True,
                num_experts=d["num_experts"],
                num_experts_per_tok=d["num_experts_per_tok"],
                num_dense_layers=d.get("num_dense_layers", 0),
                moe_intermediate_size=d["moe_intermediate_size"],
                use_expert_bias=d.get("use_expert_bias", False),
                norm_topk_prob=d.get("norm_topk_prob", True),
                routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
                tie_word_embeddings=d.get("tie_word_embeddings", True),
                init_expert_specific=d.get("init_expert_specific"),
            )
        if model_type == "falcon_h1":
            # Falcon-H1: every block runs a Mamba-2 mixer beside GQA
            # attention on one normed input; plain RMSNorm weights, no
            # projection bias, an untied head, muP multipliers as keys
            for key in ("attention_bias", "mlp_bias", "mamba_proj_bias",
                        "projectors_bias"):
                if d.get(key, False):
                    raise ValueError(f"falcon_h1 with {key} is not implemented")
            if not (d.get("mamba_rms_norm", True)
                    and not d.get("mamba_norm_before_gate", False)
                    and d.get("mamba_use_mlp", True)
                    and d.get("attn_layer_indices") is None):
                raise ValueError(
                    "falcon_h1 is implemented with mamba_rms_norm, "
                    "mamba_use_mlp, no mamba_norm_before_gate and attention "
                    "in every layer (attn_layer_indices null)")
            d_ssm = d.get("mamba_d_ssm") or (
                d["mamba_expand"] * d["hidden_size"])
            kwargs.update(
                mamba_d_ssm=d_ssm,
                mamba_n_heads=d["mamba_n_heads"],
                mamba_d_head=d["mamba_d_head"],
                mamba_d_state=d["mamba_d_state"],
                mamba_n_groups=d.get("mamba_n_groups", 1),
                mamba_d_conv=d.get("mamba_d_conv", 4),
                mamba_conv_bias=d.get("mamba_conv_bias", True),
                mamba_chunk_size=d.get("mamba_chunk_size", 128),
                init_ssm_in_proj_std=d.get("init_ssm_in_proj_std"),
                tie_word_embeddings=d.get("tie_word_embeddings", False),
                mlp_multipliers=tuple(
                    float(v) for v in d.get("mlp_multipliers", (1.0, 1.0))),
                ssm_multipliers=tuple(
                    float(v) for v in d.get("ssm_multipliers", (1.0,) * 5)),
                **{k: float(d.get(k, 1.0)) for k in (
                    "embedding_multiplier", "lm_head_multiplier",
                    "key_multiplier", "attention_in_multiplier",
                    "attention_out_multiplier", "ssm_in_multiplier",
                    "ssm_out_multiplier")},
            )
        if model_type == "deepseek_v3":
            # DeepSeek-V3 family (Kanana-2): latent attention without a
            # query latent, a leading dense block, then sigmoid-routed
            # experts chosen by score + a correction bias (``noaux_tc``)
            # beside shared experts; RoPE on the 64 rotated columns alone.
            # What has no equations here is refused by its key.
            if d.get("q_lora_rank") is not None:
                raise ValueError(
                    "deepseek_v3 with q_lora_rank (a query latent) is not "
                    "implemented")
            kwargs.update(_deepseek_kwargs(d, rope_scaling))
        if model_type == "glm_moe_dsa":
            # GLM-5: DeepSeek-V3's layer with a QUERY latent, and a learned
            # sparse-attention indexer that reads it (five ``index_*``
            # keys); everything else is ``_deepseek_kwargs``'s
            kwargs.update(_deepseek_kwargs(d, rope_scaling))
            if d.get("ep_size", 1) != 1:
                raise ValueError(
                    "glm_moe_dsa with ep_size != 1 is not implemented (a "
                    "share of the experts is stated by router_experts / "
                    "first_expert)")
            if kwargs["n_group"] != 1 or kwargs["topk_group"] != 1:
                raise ValueError(
                    "glm_moe_dsa with n_group != 1 is not implemented")
            # the published file states its RoPE base in a group of its own
            rope = d.get("rope_parameters") or {}
            if rope.get("rope_type", "default") != "default":
                raise ValueError(
                    f"glm_moe_dsa with rope_type {rope['rope_type']!r} "
                    "(rope_scaling) is not implemented")
            kwargs.update(
                rope_theta=float(rope.get("rope_theta", kwargs["rope_theta"])),
                q_lora_rank=d["q_lora_rank"],
                index_topk=d["index_topk"],
                index_n_heads=d["index_n_heads"],
                index_head_dim=d["index_head_dim"],
                indexer_rope_interleave=d.get("indexer_rope_interleave",
                                              False),
            )
        if model_type == "ling_hybrid":
            kwargs.update(_ling_hybrid_kwargs(d, head_dim))
        if model_type == "mimo_v2":
            # MiMo-V2 (MiMo-V2-Flash / V2.5): global and window attention
            # layers by ``hybrid_layer_pattern`` (0 / 1), each kind with
            # its own kv heads and RoPE base, K heads ``head_dim`` wide
            # (the leading ``int(head_dim * partial_rotary_factor)``
            # columns rotated), V heads ``v_head_dim``, a learned sink
            # logit a head in the window layers' softmax, values times
            # ``attention_value_scale``; dense SwiGLU where
            # ``moe_layer_freq`` is 0 (leading layers), else sigmoid-routed
            # experts chosen by score + a correction bias, no shared
            # expert.  What has no equations here is refused by its key.
            depth = d["num_hidden_layers"]
            pattern = tuple(int(v) for v in d["hybrid_layer_pattern"])
            freq = d.get("moe_layer_freq", 1)
            freq = (tuple(int(v) for v in freq) if isinstance(freq, (list, tuple))
                    else (int(freq),) * depth)
            for key, seq in (("hybrid_layer_pattern", pattern),
                             ("moe_layer_freq", freq)):
                if len(seq) != depth or set(seq) - {0, 1}:
                    raise ValueError(
                        f"mimo_v2 {key} names {len(seq)} layers "
                        f"({sorted(set(seq))}), num_hidden_layers is {depth} "
                        "(one 0 / 1 a layer)")
            n_dense = next((i for i, v in enumerate(freq) if v), depth)
            if not all(freq[n_dense:]):
                raise ValueError(
                    "mimo_v2 with a dense feed-forward after an expert layer "
                    "(moe_layer_freq not leading zeros) is not implemented")
            if d.get("add_full_attention_sink_bias", False):
                raise ValueError(
                    "mimo_v2 with add_full_attention_sink_bias (a sink in "
                    "the global layers) is not implemented")
            if d.get("n_shared_experts"):
                raise ValueError(
                    "mimo_v2 with n_shared_experts is not implemented")
            if (rope_scaling or {}).get(
                    "rope_type", (rope_scaling or {}).get("type", "default")
            ) != "default":
                raise ValueError(
                    "mimo_v2 with rope_scaling other than default is not "
                    "implemented")
            if d.get("scoring_func", "sigmoid") != "sigmoid":
                raise ValueError(
                    f"mimo_v2 with scoring_func {d['scoring_func']!r} is not "
                    "implemented (sigmoid)")
            if d.get("topk_method", "noaux_tc") != "noaux_tc":
                raise ValueError(
                    f"mimo_v2 with topk_method {d['topk_method']!r} is not "
                    "implemented (noaux_tc)")
            if d.get("attention_bias", False):
                raise ValueError(
                    "mimo_v2 with attention_bias is not implemented")
            for key, same in (("swa_num_attention_heads", num_heads),
                              ("swa_head_dim", head_dim),
                              ("swa_v_head_dim", d.get("v_head_dim", head_dim)),
                              ("sliding_window_size", d.get("sliding_window"))):
                if d.get(key, same) != same:
                    raise ValueError(
                        f"mimo_v2 with {key} {d[key]} (window layers whose "
                        f"query heads or widths differ from the global "
                        f"layers' {same}) is not implemented")
            held = d["n_routed_experts"]
            router = d.get("router_experts", held)
            scaling = d.get("routed_scaling_factor")
            kwargs.update(
                rms_norm_eps=d.get("layernorm_epsilon",
                                   d.get("rms_norm_eps", 1e-5)),
                sliding_window=d["sliding_window"],
                window_pattern=pattern,
                swa_num_key_value_heads=d.get(
                    "swa_num_key_value_heads", kwargs["num_key_value_heads"]),
                swa_rope_theta=float(d.get("swa_rope_theta",
                                           kwargs["rope_theta"])),
                swa_sink=bool(d.get("add_swa_attention_sink_bias", False)),
                v_head_dim=d.get("v_head_dim", head_dim),
                # an even number of columns: they rotate in pairs
                rope_dim=int(head_dim * d.get("partial_rotary_factor", 1.0))
                // 2 * 2,
                attention_value_scale=float(
                    d.get("attention_value_scale") or 1.0),
                num_experts=router,
                num_experts_held=None if held == router else held,
                first_expert=d.get("first_expert", 0),
                num_experts_per_tok=d["num_experts_per_tok"],
                num_dense_layers=n_dense,
                moe_intermediate_size=d["moe_intermediate_size"],
                use_expert_bias=True,  # e_score_correction_bias
                norm_topk_prob=d.get("norm_topk_prob", True),
                routed_scaling_factor=1.0 if scaling is None else float(scaling),
                router_norm_eps=1e-20,
                n_group=d.get("n_group") or 1,
                topk_group=d.get("topk_group") or 1,
                tie_word_embeddings=d.get("tie_word_embeddings", False),
                init_expert_specific=d.get("init_expert_specific"),
                init_expert_out_std=d.get("init_expert_out_std"),
            )
        if model_type == "afmoe":
            kwargs.update(_afmoe_kwargs(d))
        if model_type == "brumby":
            kwargs.update(_brumby_kwargs(d))
        if model_type == "qwen2":
            # Qwen-2/2.5: llama architecture with Q/K/V projection biases
            # and an unbiased o_proj (HF Qwen2Attention), untied head on
            # the larger sizes
            kwargs.update(
                attention_bias=True,
                attention_out_bias=False,
                tie_word_embeddings=d.get("tie_word_embeddings", False),
            )
        return cls(**kwargs)

    @classmethod
    def from_json(cls, path: str | Path) -> "ModelConfig":
        with open(path) as f:
            return cls.from_hf_dict(json.load(f))

    def reference_parity(self) -> "ModelConfig":
        """Variant reproducing the reference's *simplified* semantics.

        The reference drops attention-logit softcapping and sliding-window
        attention for Gemma-2 (SURVEY §2.7), divides scores by sqrt(head_dim)
        even when query_pre_attn_scalar differs, and ignores rope_scaling.
        Used for parity testing against the NumPy oracle in reference mode.
        """
        return dataclasses.replace(
            self,
            attn_logit_softcapping=None,
            sliding_window=None,
            query_pre_attn_scalar=None,
            rope_scaling_type=None,
        )


# ----------------------------------------------------------------------
# Presets: the model families the reference targets (SURVEY §0 table) plus
# the BASELINE.md configs 4-5 families.  Values match the published HF
# config.json for each model.
# ----------------------------------------------------------------------

LLAMA_3_2_1B = ModelConfig(
    model_type="llama",
    vocab_size=128256,
    hidden_size=2048,
    intermediate_size=8192,
    num_hidden_layers=16,
    num_attention_heads=32,
    num_key_value_heads=8,
    head_dim=64,
    max_position_embeddings=131072,
    rope_theta=500000.0,
    rms_norm_eps=1e-5,
    tie_word_embeddings=True,
    rope_scaling_type="llama3",
    rope_scaling_factor=32.0,
)

LLAMA_3_2_3B = dataclasses.replace(
    LLAMA_3_2_1B,
    hidden_size=3072,
    intermediate_size=8192,
    num_hidden_layers=28,
    num_attention_heads=24,
    num_key_value_heads=8,
    head_dim=128,
)

LLAMA_3_1_8B = dataclasses.replace(
    LLAMA_3_2_1B,
    hidden_size=4096,
    intermediate_size=14336,
    num_hidden_layers=32,
    num_attention_heads=32,
    num_key_value_heads=8,
    head_dim=128,
    rope_scaling_factor=8.0,
    tie_word_embeddings=False,
)

GEMMA_2_2B = ModelConfig(
    model_type="gemma2",
    vocab_size=256000,
    hidden_size=2304,
    intermediate_size=9216,
    num_hidden_layers=26,
    num_attention_heads=8,
    num_key_value_heads=4,
    head_dim=256,
    max_position_embeddings=8192,
    rope_theta=10000.0,
    rms_norm_eps=1e-6,
    hidden_act="gelu_pytorch_tanh",
    tie_word_embeddings=True,
    rms_norm_unit_offset=True,
    sandwich_norms=True,
    scale_embeddings=True,
    final_logit_softcapping=30.0,
    attn_logit_softcapping=50.0,
    sliding_window=4096,
    query_pre_attn_scalar=256.0,
)

GEMMA_2_9B = dataclasses.replace(
    GEMMA_2_2B,
    hidden_size=3584,
    intermediate_size=14336,
    num_hidden_layers=42,
    num_attention_heads=16,
    num_key_value_heads=8,
    head_dim=256,
)

# The one published Gemma-2 size where query_pre_attn_scalar (hidden /
# num_heads = 4608/32 = 144) differs from head_dim (128) — the scaling
# delta the reference computes and then ignores (gemma2_model.py:434 vs
# :541-543); we apply it, so this preset exercises the correct path.
GEMMA_2_27B = dataclasses.replace(
    GEMMA_2_2B,
    hidden_size=4608,
    intermediate_size=36864,
    num_hidden_layers=46,
    num_attention_heads=32,
    num_key_value_heads=16,
    head_dim=128,
    query_pre_attn_scalar=144.0,
)

QWEN_2_5_0_5B = ModelConfig(
    model_type="qwen2",
    vocab_size=151936,
    hidden_size=896,
    intermediate_size=4864,
    num_hidden_layers=24,
    num_attention_heads=14,
    num_key_value_heads=2,
    head_dim=64,
    max_position_embeddings=32768,
    rope_theta=1000000.0,
    rms_norm_eps=1e-6,
    tie_word_embeddings=True,
    attention_bias=True,
    attention_out_bias=False,
)

QWEN_2_5_1_5B = dataclasses.replace(
    QWEN_2_5_0_5B,
    hidden_size=1536,
    intermediate_size=8960,
    num_hidden_layers=28,
    num_attention_heads=12,
    num_key_value_heads=2,
    head_dim=128,
)

def _deepseek_kwargs(d: Mapping[str, Any], rope_scaling: Any) -> dict[str, Any]:
    """``from_hf_dict``'s keys for DeepSeek-V3's layer (``deepseek_v3``,
    ``glm_moe_dsa``): latent attention, a leading run of dense blocks, then
    sigmoid-routed experts chosen by score + a correction bias
    (``noaux_tc``) beside shared experts; RoPE on the rotated columns
    alone.  What has no equations here is refused by its key."""
    name = d["model_type"]
    if rope_scaling is not None:
        raise ValueError(
            f"{name} with rope_scaling (YaRN and its mscale) is "
            "not implemented")
    if d.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError(
            f"{name} with scoring_func "
            f"{d['scoring_func']!r} is not implemented (sigmoid)")
    if d.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(
            f"{name} with topk_method {d['topk_method']!r} is "
            "not implemented (noaux_tc)")
    if d.get("moe_layer_freq", 1) != 1:
        raise ValueError(
            f"{name} with moe_layer_freq != 1 is not implemented")
    if d.get("attention_bias", False):
        raise ValueError(
            f"{name} with attention_bias is not implemented")
    rope_dim = d["qk_rope_head_dim"]
    if d.get("head_dim", rope_dim) != rope_dim:
        raise ValueError(
            f"{name} head_dim {d['head_dim']} is not "
            f"qk_rope_head_dim {rope_dim}")
    # ``n_routed_experts`` is what is HELD; a file that states one
    # chip's share of a deployment names the router's width and
    # the first expert held under keys of its own
    held = d["n_routed_experts"]
    router = d.get("router_experts", held)
    shared = d.get("n_shared_experts") or 0
    return dict(
        head_dim=rope_dim,
        kv_lora_rank=d["kv_lora_rank"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=rope_dim,
        v_head_dim=d["v_head_dim"],
        rope_interleave=d.get("rope_interleave", False),
        num_experts=router,
        num_experts_held=None if held == router else held,
        first_expert=d.get("first_expert", 0),
        num_experts_per_tok=d["num_experts_per_tok"],
        num_dense_layers=d.get("first_k_dense_replace", 0),
        moe_intermediate_size=d["moe_intermediate_size"],
        shared_expert_intermediate_size=(
            shared * d["moe_intermediate_size"] or None),
        use_expert_bias=True,  # e_score_correction_bias
        norm_topk_prob=d.get("norm_topk_prob", True),
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        router_norm_eps=1e-20,
        n_group=d.get("n_group") or 1,
        topk_group=d.get("topk_group") or 1,
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        init_expert_specific=d.get("init_expert_specific"),
        init_expert_out_std=d.get("init_expert_out_std"),
    )


def _ling_hybrid_kwargs(d: Mapping[str, Any], head_dim: int) -> dict[str, Any]:
    """``from_hf_dict`` for Ling-3.0 (``model_type: ling_hybrid``, this
    package's own name for the family: the published row states none this
    program could tell from its predecessors).  Groups of
    ``layer_group_size`` layers, the last of each latent attention without
    a query latent (DeepSeek-V3's operator, RoPE on ``qk_rope_head_dim``
    columns by halves), the others KDA (ops/kda.py); ``first_k_dense_replace``
    leading dense SwiGLU blocks, then sigmoid-routed experts chosen by score
    + bias under a group limit beside one shared expert; an untied head.
    What has no equations here is refused by its key."""
    for key in ("q_lora_rank", "rope_scaling"):
        if d.get(key) is not None:
            raise ValueError(f"ling_hybrid with {key} is not implemented")
    for key in ("use_nGPT", "value_norm", "up_proj_norm", "scale_router_input",
                "use_kda_lora", "mtp_use_kda", "use_mla_nope", "use_bias",
                "use_qkv_bias", "attention_bias"):
        if d.get(key, False):
            raise ValueError(f"ling_hybrid with {key} is not implemented")
    for key in ("expert_swiglu_limit_list", "share_expert_swiglu_limit_list"):
        limits = list(d.get(key) or ())[:d["num_hidden_layers"]]
        if any(limits):
            raise ValueError(
                f"ling_hybrid with a non-zero {key} entry (a clamped SwiGLU, "
                f"layer {next(i for i, v in enumerate(limits) if v)}) is not "
                "implemented")
    score = d.get("score_function", d.get("scoring_func", "sigmoid"))
    if score != "sigmoid" or d.get("scoring_func", score) != score:
        raise ValueError(
            f"ling_hybrid with score_function {score!r} is not implemented "
            "(sigmoid)")
    if d.get("topk_method", "noaux_tc") != "noaux_tc":
        raise ValueError(
            f"ling_hybrid with topk_method {d['topk_method']!r} is not "
            "implemented (noaux_tc)")
    for key, only in (("no_kda_lora", True), ("kda_safe_gate", True),
                      ("use_qk_norm", True), ("linear_silu", True),
                      ("group_norm_size", 1),
                      ("num_kv_heads_for_linear_attn", 0),
                      ("gated_attention_proj_granularity_type", "head_wise")):
        if d.get(key, only) != only:
            raise ValueError(
                f"ling_hybrid with {key} {d[key]!r} is not implemented "
                f"({only!r})")
    rope_dim = d["qk_rope_head_dim"]
    if d.get("rotary_dim", rope_dim) != rope_dim:
        raise ValueError(
            f"ling_hybrid rotary_dim {d['rotary_dim']} is not "
            f"qk_rope_head_dim {rope_dim}")
    # ``num_experts`` is what is HELD; a file that states one chip's share
    # names the router's width and the first expert held (as deepseek_v3)
    held = d["num_experts"]
    router = d.get("router_experts", held)
    moe_i = d["moe_intermediate_size"]
    shared = d.get("moe_shared_expert_intermediate_size")
    if shared is None:
        shared = (d.get("num_shared_experts") or 0) * moe_i
    span = d.get("init_kda_log_decay")
    return dict(
        head_dim=rope_dim,
        kda_head_dim=head_dim,
        layer_group_size=d["layer_group_size"],
        kda_conv_taps=d.get("short_conv_kernel_size", 4),
        kda_lower_bound=float(d.get("kda_lower_bound", -5.0)),
        init_kda_log_decay=None if span is None else (
            float(span[0]), float(span[1])),
        kv_lora_rank=d["kv_lora_rank"],
        qk_nope_head_dim=d["qk_nope_head_dim"],
        qk_rope_head_dim=rope_dim,
        v_head_dim=d["v_head_dim"],
        rope_interleave=False,
        num_experts=router,
        num_experts_held=None if held == router else held,
        first_expert=d.get("first_expert", 0),
        num_experts_per_tok=d["num_experts_per_tok"],
        num_dense_layers=d.get("first_k_dense_replace", 0),
        moe_intermediate_size=moe_i,
        shared_expert_intermediate_size=shared or None,
        use_expert_bias=bool(d.get("moe_router_enable_expert_bias", True)),
        norm_topk_prob=d.get("norm_topk_prob", True),
        routed_scaling_factor=float(d.get("routed_scaling_factor", 1.0)),
        router_norm_eps=1e-20,
        n_group=d.get("n_group") or 1,
        topk_group=d.get("topk_group") or 1,
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        init_expert_specific=d.get("init_expert_specific"),
        init_expert_out_std=d.get("init_expert_out_std"),
    )


def _afmoe_kwargs(d: Mapping[str, Any]) -> dict[str, Any]:
    """``from_hf_dict`` for the AFMoE family (``model_type: afmoe``;
    Trinity-Large): gated GQA attention with q/k RMSNorm, window layers
    (``layer_types`` ``sliding_attention``: RoPE, a ``sliding_window``)
    and global ones (``full_attention``: no positional encoding), four
    norms a layer with the post-norms inside the residual, the embedding
    times ``sqrt(hidden_size)`` (``mup_enabled``), ``num_dense_layers``
    leading dense SwiGLU blocks, then one shared expert beside
    sigmoid-routed ones chosen by score + ``expert_bias``, normalised
    (``route_norm``) and times ``route_scale``; an untied head.  What has
    no equations here is refused by its key."""
    depth = d["num_hidden_layers"]
    layer_types = tuple(d["layer_types"])
    if len(layer_types) != depth:
        raise ValueError(
            f"afmoe layer_types names {len(layer_types)} layers, "
            f"num_hidden_layers is {depth}")
    bad = set(layer_types) - {"sliding_attention", "full_attention"}
    if bad:
        raise ValueError(
            f"afmoe with layer_types {sorted(bad)} is not implemented "
            "(sliding_attention, full_attention)")
    for key in ("n_group", "num_expert_groups", "topk_group",
                "num_limited_groups"):
        if (d.get(key) or 1) != 1:
            raise ValueError(
                f"afmoe with {key} {d[key]} (group-limited routing) is not "
                "implemented (1)")
    if d.get("rope_scaling") is not None:
        raise ValueError("afmoe with rope_scaling is not implemented (null)")
    if d.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError(
            f"afmoe with score_func {d['score_func']!r} is not implemented "
            "(sigmoid)")
    if not d.get("route_norm", True):
        raise ValueError(
            "afmoe with route_norm false (unnormalised routing weights) is "
            "not implemented")
    if d.get("num_shared_experts", 1) != 1:
        raise ValueError(
            f"afmoe with num_shared_experts {d['num_shared_experts']} is not "
            "implemented (1)")
    for key in ("attention_bias", "mlp_bias"):
        if d.get(key, False):
            raise ValueError(f"afmoe with {key} is not implemented")
    if any(t == "sliding_attention" for t in layer_types) and not d.get(
            "sliding_window"):
        raise ValueError("afmoe with sliding_attention layers needs a "
                         "sliding_window")
    # ``num_experts`` is what is HELD; a file that states one chip's share
    # names the router's width and the first expert held (as deepseek_v3)
    held = d["num_experts"]
    router = d.get("router_experts", held)
    moe_i = d["moe_intermediate_size"]
    return dict(
        rms_norm_eps=d.get("rms_norm_eps", 1e-5),
        sandwich_norms=True,
        scale_embeddings=bool(d.get("mup_enabled", False)),
        qk_norm=True,
        attn_output_gate=True,
        global_rope=False,
        window_page_class=True,
        sliding_window=d.get("sliding_window"),
        window_pattern=tuple(int(t == "sliding_attention")
                             for t in layer_types),
        num_experts=router,
        num_experts_held=None if held == router else held,
        first_expert=d.get("first_expert", 0),
        num_experts_per_tok=d["num_experts_per_tok"],
        num_dense_layers=d.get("num_dense_layers", 0),
        moe_intermediate_size=moe_i,
        shared_expert_intermediate_size=moe_i,
        use_expert_bias=True,
        norm_topk_prob=True,
        routed_scaling_factor=float(d.get("route_scale", 1.0)),
        router_norm_eps=1e-20,
        tie_word_embeddings=d.get("tie_word_embeddings", False),
        init_expert_specific=d.get("init_expert_specific"),
        init_expert_out_std=d.get("init_expert_out_std"),
    )


def _brumby_kwargs(d: Mapping[str, Any]) -> dict[str, Any]:
    """``from_hf_dict`` for Brumby (``model_type: brumby``; Brumby-14B-Base):
    Qwen3's block — pre-norm, q / k RMSNorm over ``head_dim`` before RoPE,
    no projection bias, dense SwiGLU, an untied head — with EVERY attention
    layer replaced by power retention of degree 2 (ops/retention.py).  The
    published config has no key for the degree, the gate or the normaliser
    (benchmark/configs/brumby-14b-5l.json lists what is ASSUMED);
    ``max_window_layers`` and ``sliding_window`` are read by nothing.  What
    has no equations here is refused by its key."""
    if d.get("rope_scaling") is not None:
        raise ValueError("brumby with rope_scaling is not implemented (null)")
    for key in ("use_sliding_window", "attention_bias", "mlp_bias",
                "tie_word_embeddings"):
        if d.get(key, False):
            raise ValueError(f"brumby with {key} is not implemented")
    return dict(
        retention_degree=int(d.get("retention_degree", 2)),
        qk_norm=True,
        tie_word_embeddings=False,
    )


# operators that carry a state and hold no pages (``attn_layers`` leaves
# them out; a stack of them alone has no page class at all)
STATE_ONLY_OPS = ("conv", "kda", "retention")

# model_type values ``from_hf_dict`` has equations for ("mistral" and
# "mixtral" are the llama block, the latter with capacity-routed experts
# when ``num_local_experts`` is set)
KNOWN_MODEL_TYPES = frozenset(
    ("llama", "mistral", "mixtral", "gemma2", "qwen2", "lfm2_moe",
     "falcon_h1", "deepseek_v3", "mimo_v2", "ling_hybrid", "afmoe",
     "brumby", "glm_moe_dsa"))

PRESETS: dict[str, ModelConfig] = {
    "meta-llama/Llama-3.2-1B": LLAMA_3_2_1B,
    "meta-llama/Llama-3.2-3B": LLAMA_3_2_3B,
    "meta-llama/Llama-3.1-8B": LLAMA_3_1_8B,
    "google/gemma-2-2b": GEMMA_2_2B,
    "google/gemma-2-9b": GEMMA_2_9B,
    "google/gemma-2-27b": GEMMA_2_27B,
    "Qwen/Qwen2.5-0.5B": QWEN_2_5_0_5B,
    "Qwen/Qwen2.5-1.5B": QWEN_2_5_1_5B,
}


def tiny_config(model_type: str = "llama", **overrides: Any) -> ModelConfig:
    """Small config for tests: real structure, toy sizes."""
    base: dict[str, Any] = dict(
        model_type=model_type,
        vocab_size=256,
        hidden_size=64,
        intermediate_size=128,
        num_hidden_layers=3,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=16,
        max_position_embeddings=512,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
    )
    if model_type == "gemma2":
        base.update(
            hidden_act="gelu_pytorch_tanh",
            rms_norm_unit_offset=True,
            sandwich_norms=True,
            scale_embeddings=True,
            final_logit_softcapping=30.0,
            attn_logit_softcapping=50.0,
            sliding_window=16,
            query_pre_attn_scalar=16.0,
        )
    if model_type == "qwen2":
        base.update(
            attention_bias=True,
            attention_out_bias=False,
            tie_word_embeddings=True,
        )
    if model_type == "lfm2_moe":
        # two leading dense blocks, then two periods of the published
        # pattern (the first 10 of LFM2-8B-A1B's layer_types), 8 experts
        # top-2: every kind of layer, no width of the real model
        base.update(
            num_hidden_layers=10,
            layer_types=("conv", "conv", "full_attention", "conv", "conv",
                         "conv", "full_attention", "conv", "conv", "conv"),
            rms_norm_eps=1e-5,
            qk_norm=True,
            num_experts=8,
            num_experts_per_tok=2,
            num_dense_layers=2,
            moe_intermediate_size=32,
            use_expert_bias=True,
            tie_word_embeddings=True,
        )
    if model_type == "falcon_h1":
        # 2 groups of 2 state-space heads, d_state 16, every multiplier
        # different from 1 (and from each other): no width of the model
        base.update(
            rms_norm_eps=1e-5,
            tie_word_embeddings=False,
            mamba_d_ssm=64, mamba_n_heads=4, mamba_d_head=16,
            mamba_d_state=16, mamba_n_groups=2, mamba_d_conv=4,
            mamba_chunk_size=8,
            embedding_multiplier=5.5, lm_head_multiplier=0.125,
            key_multiplier=0.7, attention_in_multiplier=1.25,
            attention_out_multiplier=0.6, ssm_in_multiplier=1.5,
            ssm_out_multiplier=0.8, mlp_multipliers=(0.9, 0.45),
            ssm_multipliers=(0.85, 1.2, 1.4, 1.1, 0.75),
            # the state's share of the mixer's output level with the
            # skip's (at 0.02 it is a thousandth: a test could not see it)
            init_ssm_in_proj_std=0.2,
        )
    if model_type == "deepseek_v3":
        # a leading dense block, then two expert layers: 8 routed experts
        # top-2 beside a shared SwiGLU, latent attention over rows of
        # 32 + 8 values; no width of the real model
        base.update(
            num_key_value_heads=4,
            head_dim=8,
            tie_word_embeddings=False,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, rope_interleave=True,
            num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
            moe_intermediate_size=32, shared_expert_intermediate_size=64,
            use_expert_bias=True, routed_scaling_factor=2.448,
            router_norm_eps=1e-20,
        )
    if model_type == "glm_moe_dsa":
        # GLM-5's shape at toy sizes: a leading dense block, then two
        # expert layers (8 routed experts top-2 beside a shared SwiGLU),
        # a query latent of 24, latent rows of 32 + 8, heads as wide on
        # the value side as on the key side, an indexer of 2 heads of 16
        # (8 of them rotated) that keeps 12 positions; no width of the model
        base.update(
            num_key_value_heads=4,
            head_dim=8,
            tie_word_embeddings=False,
            q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=24, rope_interleave=True,
            index_topk=12, index_n_heads=2, index_head_dim=16,
            indexer_rope_interleave=True,
            num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            use_expert_bias=True, routed_scaling_factor=2.5,
            router_norm_eps=1e-20,
        )
    if model_type == "mimo_v2":
        # MiMo-V2's shape at toy sizes: a leading dense global layer, then
        # expert layers ``w w g w`` (2 kinds, unequal kv heads), keys 24
        # wide of which 8 rotate, values 16, window 8, a sink in the window
        # layers, 16 experts top-4 (a test holds 4); no width of the model
        base.update(
            num_hidden_layers=5,
            num_attention_heads=4, num_key_value_heads=1,
            swa_num_key_value_heads=2, head_dim=24, v_head_dim=16,
            rope_dim=8, rope_theta=1e7, swa_rope_theta=1e4,
            rms_norm_eps=1e-5, tie_word_embeddings=False,
            sliding_window=8, window_pattern=(0, 1, 1, 0, 1), swa_sink=True,
            attention_value_scale=0.707,
            num_experts=16, num_experts_per_tok=4, num_dense_layers=1,
            moe_intermediate_size=32, use_expert_bias=True,
            router_norm_eps=1e-20,
        )
    if model_type == "ling_hybrid":
        # Ling-3.0's shape at toy sizes: two groups of THREE layers (kda,
        # kda, latent) so that both operators and both feed-forwards
        # appear in six: a leading dense block, 16 experts in 4 groups of
        # which 2 stay, top-4, a shared expert; no width of the model
        base.update(
            num_hidden_layers=6, layer_group_size=3,
            num_key_value_heads=4, head_dim=8, kda_head_dim=16,
            tie_word_embeddings=False,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, rope_theta=6e6,
            num_experts=16, num_experts_per_tok=4, num_dense_layers=1,
            n_group=4, topk_group=2,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            use_expert_bias=True, routed_scaling_factor=2.5,
            router_norm_eps=1e-20,
            init_kda_log_decay=(-0.005, -0.5),
        )
    if model_type == "afmoe":
        # Trinity's shape at toy sizes: a leading dense window layer, then
        # expert layers ``s f s s`` (2 kinds of ONE shape, 2 kv heads
        # each), window 8, a gate, q/k norms, sandwich norms, no RoPE in
        # the global layer, 16 experts top-4 beside one shared expert (a
        # test holds 4); no width of the model
        base.update(
            num_hidden_layers=5,
            rms_norm_eps=1e-5, tie_word_embeddings=False,
            sandwich_norms=True, scale_embeddings=True, qk_norm=True,
            attn_output_gate=True, global_rope=False, window_page_class=True,
            sliding_window=8, window_pattern=(1, 1, 0, 1, 1),
            num_experts=16, num_experts_per_tok=4, num_dense_layers=1,
            moe_intermediate_size=32, shared_expert_intermediate_size=32,
            use_expert_bias=True, routed_scaling_factor=2.448,
            router_norm_eps=1e-20,
        )
    if model_type == "brumby":
        # Brumby's shape at toy sizes: 2 kv heads read by 4 query heads of
        # 8 channels (36 distinct monomials a kv head, held as 64 rows), q /
        # k norms, RoPE, an untied head; no width of the model
        base.update(
            num_attention_heads=4, num_key_value_heads=2, head_dim=8,
            rope_theta=1e6, tie_word_embeddings=False, qk_norm=True,
            retention_degree=2,
        )
    base.update(overrides)
    return ModelConfig(**base)
