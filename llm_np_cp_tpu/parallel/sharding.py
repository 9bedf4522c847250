"""Mesh + sharding specs: the framework's distributed backbone.

The reference has zero distributed capability (SURVEY §2.9: no DP/TP/PP/SP,
no collective backend; its only "communication layer" is DLPack interop on
one GPU).  This module is the TPU-native replacement: a
``jax.sharding.Mesh`` over the chip grid and NamedShardings for every
parameter / cache / activation, compiled by XLA's GSPMD partitioner into
``psum`` / ``all_gather`` / ``reduce_scatter`` collectives that ride ICI
within a slice (and DCN across slices — same API, XLA picks transport).

Tensor-parallel layout (Megatron-style, per BASELINE north star):
- q/k/v/gate/up projections: column-sharded (output features) on "model"
- o/down projections: row-sharded (input features) on "model" — XLA inserts
  the psum for the partial sums
- embed/lm_head: vocab-sharded on "model"; logits stay vocab-sharded until
  sampling reduces them
- KV cache: kv-head axis sharded on "model" when divisible (Gemma-2-2B has
  4 KV heads — on an 8-way mesh the cache falls back to replication, the
  SURVEY §7 "TP + GQA" hard part; shard "seq" instead for long context,
  see parallel/ring_attention)
- batch axis: sharded on "data" everywhere

No hand-written collectives are needed for TP/DP — annotate + jit is the
whole programming model (the "How to Scale Your Model" recipe).  Explicit
``shard_map`` collectives appear only where GSPMD can't infer the schedule
(ring attention).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llm_np_cp_tpu.config import ModelConfig

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"
EXPERT_AXIS = "expert"


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    """Static parallelism plan: how many ways each mesh axis is split.

    data: batch sharding (DP); model: tensor parallelism (TP);
    seq: sequence/context parallelism for the KV cache and ring attention;
    pipe: pipeline parallelism over the stacked layer axis (GPipe schedule,
    parallel/pipeline.py — training/no-cache forward only);
    expert: expert parallelism for MoE configs (ops/moe.py — the expert
    axis of router dispatch/combine einsums; GSPMD inserts the
    all-to-all-equivalent collectives).
    """

    data: int = 1
    model: int = 1
    seq: int = 1
    pipe: int = 1
    expert: int = 1

    @property
    def num_devices(self) -> int:
        return self.data * self.model * self.seq * self.pipe * self.expert

    def validate(self, config: ModelConfig) -> None:
        if self.model > 1:
            for dim, name in [
                (config.num_attention_heads, "num_attention_heads"),
                (config.intermediate_size, "intermediate_size"),
                (config.vocab_size, "vocab_size"),
            ]:
                if dim % self.model != 0:
                    raise ValueError(
                        f"{name}={dim} not divisible by model={self.model}"
                    )
        if self.pipe > 1 and config.num_hidden_layers % self.pipe != 0:
            raise ValueError(
                f"num_hidden_layers={config.num_hidden_layers} not divisible "
                f"by pipe={self.pipe}"
            )
        if self.expert > 1:
            if not config.is_moe:
                raise ValueError("expert>1 requires a MoE config")
            if config.num_local_experts % self.expert != 0:
                raise ValueError(
                    f"num_local_experts={config.num_local_experts} not "
                    f"divisible by expert={self.expert}"
                )


def parse_mesh_spec(text: str) -> MeshPlan:
    """CLI mesh syntax → MeshPlan, shared by the inference and training
    CLIs: named axes ``data=2,pipe=2,model=2`` (any of data/seq/model/
    pipe/expert) or the positional ``data,seq,model`` triple.  Raises
    SystemExit with a usage message on any malformed input (axis typos,
    non-integer values, wrong arity)."""
    axes = ("data", "seq", "model", "pipe", "expert")
    usage = (
        f"--mesh {text!r}: use named axes like data=2,pipe=2,model=2 "
        "(axes: data/seq/model/pipe/expert) or the positional "
        "data,seq,model triple"
    )
    kw = {}
    parts = [p for p in text.split(",") if p]
    try:
        if parts and all("=" in p for p in parts):
            for p in parts:
                name, _, val = p.partition("=")
                if name not in axes:
                    raise SystemExit(f"unknown mesh axis {name!r}; {usage}")
                kw[name] = int(val)
        elif len(parts) == 3 and not any("=" in p for p in parts):
            kw = dict(zip(("data", "seq", "model"), (int(p) for p in parts)))
        else:
            raise SystemExit(usage)
    except ValueError:
        raise SystemExit(usage) from None
    return MeshPlan(**kw)


def make_mesh(plan: MeshPlan, devices: list | None = None) -> Mesh:
    devices = devices if devices is not None else jax.devices()
    n = plan.num_devices
    if n > len(devices):
        raise ValueError(f"plan needs {n} devices, have {len(devices)}")
    grid = np.asarray(devices[:n]).reshape(
        plan.data, plan.pipe, plan.seq, plan.expert, plan.model
    )
    return Mesh(grid, (DATA_AXIS, PIPE_AXIS, SEQ_AXIS, EXPERT_AXIS, MODEL_AXIS))


def _kv_heads_shardable(config: ModelConfig, plan: MeshPlan) -> bool:
    return plan.model > 1 and config.num_key_value_heads % plan.model == 0


def kv_heads_shardable(config: ModelConfig, plan: MeshPlan) -> bool:
    """Public twin of the kv-head divisibility rule: True when the KV
    cache's head axis can be tensor-parallel over "model" (the SURVEY §7
    "TP + GQA" hard part — Gemma-2's 4 kv heads on an 8-way mesh fall
    back to replication).  The serve engine keys its Pallas-under-
    shard_map path on this."""
    return _kv_heads_shardable(config, plan)


def normalize_specs(specs: Any) -> Any:
    """Strip trailing ``None`` entries from every PartitionSpec leaf.

    ``P(None, None, 'model', None)`` and ``P(None, None, 'model')`` mean
    the same placement, but GSPMD emits the NORMALIZED spelling on jit
    outputs while hand-written specs usually carry the trailing None —
    and jit's compile cache compares shardings by spelling, so an array
    that round-trips through a step (pool slabs, the serve temp cache)
    would hit one spurious recompile on its second dispatch.  Serving
    pins its in-avals through this normalization."""

    def norm(spec: P) -> P:
        entries = list(spec)
        while entries and entries[-1] is None:
            entries.pop()
        return P(*entries)

    return jax.tree.map(norm, specs, is_leaf=lambda x: isinstance(x, P))


def paged_kv_specs(config: ModelConfig, plan: MeshPlan,
                   quantized: bool = False) -> Any:
    """PartitionSpecs for the serving block pool's ``PagedKV`` slabs
    ``[L, NB, BS, K, D]`` — the paged analogue of ``cache_specs``: the
    kv-head axis shards over "model" when divisible (same rule as the
    contiguous cache, one decision shared by both layouts), everything
    else — layer, block, in-block slot — stays unsharded so block
    tables remain plain replicated scalars and the scalar-prefetch
    kernels see per-shard-identical indices.  int8 scale pages
    ``[L, NB, BS, K]`` shard like the values minus D.  A merged pool
    (``block_pool.merges_pages``: ``[L, NB, BS, K * D]``) is cut on the
    merged axis — kv-major, so a shard is still whole heads."""
    from llm_np_cp_tpu.serve.block_pool import (
        PagedKV,
        PageForm,
        merges_pages,
    )

    if config.is_latent:
        # one row a token and no head axis to cut: the pool is whole on
        # every device of a placement mesh (the engine refuses model > 1)
        return normalize_specs(PagedKV(
            k=P(), v=None,
            form=PageForm(config.kv_token_shapes()["k"][0], latent=True)))
    kv = MODEL_AXIS if _kv_heads_shardable(config, plan) else None
    scale = P(None, None, None, kv) if quantized else None
    merged = merges_pages(
        config.num_key_value_heads, config.head_dim, quantized,
        config.num_query_groups)
    page = P(None, None, None, kv) if merged else P(None, None, None, kv, None)
    return normalize_specs(PagedKV(
        k=page,
        v=page,
        k_scale=scale,
        v_scale=scale,
        form=PageForm(config.head_dim) if merged else None,
    ))


def param_specs(config: ModelConfig, plan: MeshPlan) -> dict[str, Any]:
    """PartitionSpec pytree matching models.transformer.param_shapes.

    The leading layer axis of stacked weights is sharded over "pipe" when
    pipeline parallelism is on (parallel/pipeline.py consumes the local
    block per stage); under plain ``forward`` (pipe=1) it stays unsharded
    (lax.scan consumes it).
    """
    m = MODEL_AXIS if plan.model > 1 else None
    kv = MODEL_AXIS if _kv_heads_shardable(config, plan) else None
    pp = PIPE_AXIS if plan.pipe > 1 else None
    layers = {
        "ln_attn_in": P(pp, None),
        "q_proj": P(pp, None, m),
        "k_proj": P(pp, None, kv),
        "v_proj": P(pp, None, kv),
        "o_proj": P(pp, m, None),
        "ln_mlp_in": P(pp, None),
        "gate_proj": P(pp, None, m),
        "up_proj": P(pp, None, m),
        "down_proj": P(pp, m, None),
    }
    if config.attention_bias:
        # biases follow their projection's output sharding
        layers["q_bias"] = P(pp, m)
        layers["k_bias"] = P(pp, kv)
        layers["v_bias"] = P(pp, kv)
    if config.o_proj_bias:
        # the same independent gate as param_shapes (Qwen-2 biases Q/K/V
        # but not o_proj); added after the row-parallel psum, so it stays
        # replicated on "model"
        layers["o_bias"] = P(pp, None)
    if config.mlp_bias:
        layers["gate_bias"] = P(pp, m)
        layers["up_bias"] = P(pp, m)
        layers["down_bias"] = P(pp, None)
    if config.is_moe:
        # expert weights [L, E, ...]: experts on "expert", feature dims on
        # "model" (EP × TP compose); the tiny router stays replicated
        ex = EXPERT_AXIS if plan.expert > 1 else None
        layers["router"] = P(pp, None, None)
        layers["gate_proj"] = P(pp, ex, None, m)
        layers["up_proj"] = P(pp, ex, None, m)
        layers["down_proj"] = P(pp, ex, m, None)
    if config.sandwich_norms:
        layers["ln_attn_out"] = P(pp, None)
        layers["ln_mlp_out"] = P(pp, None)
    specs: dict[str, Any] = {
        "embed_tokens": P(m, None),
        "layers": layers,
        "final_norm": P(None),
    }
    if not config.tie_word_embeddings:
        specs["lm_head"] = P(None, m)
    return specs


def cache_specs(config: ModelConfig, plan: MeshPlan, quantized: bool = False) -> Any:
    """KVCache sharding: [L, B, S, K, D] — batch on data, kv-heads on model
    (when divisible), seq on the seq axis for context parallelism.  The
    int8 cache's scale slabs [L, B, S, K] shard like the values minus D."""
    from llm_np_cp_tpu.cache import KVCache

    d = DATA_AXIS if plan.data > 1 else None
    kv = MODEL_AXIS if _kv_heads_shardable(config, plan) else None
    s = SEQ_AXIS if plan.seq > 1 else None
    scale = P(None, d, s, kv) if quantized else None
    return KVCache(
        k=P(None, d, s, kv, None),
        v=P(None, d, s, kv, None),
        valid=P(d, s),
        length=P(),
        k_scale=scale,
        v_scale=scale,
    )


def batch_spec(plan: MeshPlan) -> P:
    return P(DATA_AXIS if plan.data > 1 else None, None)


def to_shardings(mesh: Mesh, specs: Any) -> Any:
    """PartitionSpec pytree → NamedSharding pytree."""
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s),
        specs,
        is_leaf=lambda x: isinstance(x, P),
    )


def _scale_spec(spec: P, leaf: dict) -> P:
    """PartitionSpec for a quantized leaf's scale tensor: the weight's spec
    with contracted (size-1 in the scale, >1 in the payload) axes cleared —
    a size-1 axis cannot be sharded."""
    from llm_np_cp_tpu.quant import payload_key

    q = leaf[payload_key(leaf)]
    s = leaf["s"]
    entries = list(spec) + [None] * (q.ndim - len(spec))
    return P(*[
        None if (s.shape[i] == 1 and q.shape[i] != 1) else entries[i]
        for i in range(q.ndim)
    ])


def shard_params(params: Any, config: ModelConfig, plan: MeshPlan, mesh: Mesh) -> Any:
    """Place an existing param pytree onto the mesh.

    Quantized leaves (quant.py ``{"q", "s"}`` dicts) are handled: the int8
    payload takes the weight's spec, the scale takes the same spec with
    contracted axes cleared — so int8 weights compose with TP/DP/PP/EP.
    """
    from llm_np_cp_tpu.quant import is_quantized

    plan.validate(config)
    specs = param_specs(config, plan)

    def place(spec: P, leaf: Any) -> Any:
        if is_quantized(leaf):
            from llm_np_cp_tpu.quant import payload_key

            pk = payload_key(leaf)
            return {
                pk: jax.device_put(leaf[pk], NamedSharding(mesh, spec)),
                "s": jax.device_put(
                    leaf["s"], NamedSharding(mesh, _scale_spec(spec, leaf))
                ),
            }
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree.map(
        place, specs, params,
        is_leaf=lambda x: isinstance(x, P),
    )


def shard_cache(cache: Any, config: ModelConfig, plan: MeshPlan, mesh: Mesh) -> Any:
    shardings = to_shardings(
        mesh, cache_specs(config, plan, quantized=cache.k_scale is not None)
    )
    return jax.tree.map(jax.device_put, cache, shardings)
