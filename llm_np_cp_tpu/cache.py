"""Static preallocated KV cache.

The reference's ``KVCache`` (llama3.2_model.py:303-332) keeps per-layer
Python lists and appends by ``concatenate`` — an O(seq) copy per token per
layer with unbounded growth, and a dynamic shape XLA cannot trace.  The
TPU-native cache is a fixed-size pytree:

    k, v: [num_layers, batch, max_seq, num_kv_heads, head_dim]
    length: int32 scalar — number of tokens written (the reference's
        ``num_items()``, llama3.2_model.py:308-312) — or an int32 [B]
        vector of PER-ROW lengths (batched speculative decoding, where
        each row accepts a different number of draft tokens per round;
        writes become per-row dynamic_update_slices via vmap).

Updates are ``lax.dynamic_update_slice`` at the current offset: O(new
tokens), jit-traceable, donate-able.  The leading layer axis exists so the
model can ``lax.scan`` over layers, carrying each layer's cache slice
through as scan xs/ys.

Sequence-parallel note: the seq axis (2) is placed after batch so a
NamedSharding of P(None, "data", "seq", "model", None) shards cache slots
across chips for long-context decode (BASELINE config 5); head axis (3)
shards under tensor parallelism.
"""

from __future__ import annotations

from typing import NamedTuple

import jax.numpy as jnp
from jax import lax

from llm_np_cp_tpu.config import ModelConfig
from llm_np_cp_tpu.quant import quantize_kv

CAPACITY_ALIGN = 128


def align_capacity(n: int) -> int:
    """Round a requested capacity up to the framework-wide 128 contract
    (see KVCache.init docstring).  THE one definition — Generator,
    SpeculativeGenerator, and bench.py all size through this, so the
    contract can't silently diverge between production and measurement.
    """
    return -(-n // CAPACITY_ALIGN) * CAPACITY_ALIGN


# what a sequence carries besides K/V, as ``ModelConfig.state_shapes`` names
# it and ``KVCache`` holds it
STATE_LEAVES = ("conv", "ssm", "kda", "retention", "retention_z")


class KVCache(NamedTuple):
    # what a token leaves a layer is the configuration's
    # (``ModelConfig.kv_token_shapes``): K and V per kv head, or latent
    # attention's one row ``[c' | k_pe]`` in ``k`` and no ``v``
    k: jnp.ndarray  # [L, B, S_max, K, D]; latent: [L, B, S_max, rank + rope]
    v: jnp.ndarray | None  # [L, B, S_max, K, D]
    valid: jnp.ndarray  # [B, S_max] bool — written AND not a pad token
    length: jnp.ndarray  # int32 scalar
    # int8 cache mode (dtype=jnp.int8): per-token-per-head absmax scales;
    # None for float caches.  Halves cache HBM traffic for long-context
    # decode (scales are D=1/64..1/128 of the slab).
    k_scale: jnp.ndarray | None = None  # [L, B, S_max, K] f32
    v_scale: jnp.ndarray | None = None
    # short-convolution state of a configuration with conv layers
    # (config.conv_layers): the last ``conv_L_cache - 1`` gated inputs of
    # every row, most recent last.  ``L`` above then counts the attention
    # layers only (config.attn_layers): a conv layer has no K/V.
    conv: jnp.ndarray | None = None  # [n_conv, B, conv_L_cache - 1, H]
    # recurrent state of a configuration with state-space mixers
    # (config.ssm_layers), float32 whatever the cache's dtype; ``conv``
    # then holds the history of the convolution in front of the mixer.
    # Both are laid out by ``config.state_shapes``.
    ssm: jnp.ndarray | None = None  # [n_ssm, B, heads, d_head, d_state] f32
    # matrix state of a configuration with delta-rule linear-attention
    # layers (config.kda_layers), float32 likewise; ``conv`` then holds
    # the history of the convolution over [q | k | v] in front of it, and
    # ``L`` above counts the latent layers only
    kda: jnp.ndarray | None = None  # [n_kda, B, heads, d, d] f32
    # the two leaves of a configuration with power-retention layers
    # (config.retention_layers), float32 likewise: a kv head's gated sums of
    # ``phi(k) v^T`` and of ``k k^T``; ``k`` and ``v`` above then have NO
    # layer (an attention-free stack: ``L`` is 0)
    retention: jnp.ndarray | None = None    # [n, B, kv heads, rows, d] f32
    retention_z: jnp.ndarray | None = None  # [n, B, kv heads, d, d] f32

    @classmethod
    def init(
        cls,
        config: ModelConfig,
        batch_size: int,
        max_seq_len: int,
        dtype: jnp.dtype = jnp.bfloat16,
    ) -> "KVCache":
        """Allocate zeroed slabs with capacity ``max_seq_len``.

        Capacity contract: callers that derive capacity from request
        shapes (Generator, SpeculativeGenerator) round it UP to a
        multiple of 128 before calling — unused slots cost HBM but are
        masked off by ``valid``/per-row lengths, while aligned capacities
        keep the Pallas decode kernel's kv-block size near its requested
        512 (an unaligned — worst case prime — capacity would shrink the
        largest usable divisor toward 1) and make seq-axis sharding
        divisibility automatic.  ``init`` itself honours the exact value
        it is given so tests can build odd-capacity caches on purpose.
        """
        lead = (len(config.attn_layers), batch_size, max_seq_len)
        token = config.kv_token_shapes()
        shape = lead + token["k"]
        quantized = dtype == jnp.int8
        if quantized and config.is_latent:
            raise NotImplementedError(
                "an int8 cache of latent rows is not implemented")
        return cls(
            **{name: jnp.zeros(shp, dt) for name, (shp, dt) in
               config.state_shapes(
                   batch_size, jnp.bfloat16 if quantized else dtype).items()},
            k=jnp.zeros(shape, dtype=dtype),
            v=jnp.zeros(shape, dtype=dtype) if "v" in token else None,
            valid=jnp.zeros((batch_size, max_seq_len), dtype=jnp.bool_),
            length=jnp.zeros((), dtype=jnp.int32),
            k_scale=jnp.zeros(shape[:-1], jnp.float32) if quantized else None,
            v_scale=jnp.zeros(shape[:-1], jnp.float32) if quantized else None,
        )

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def max_seq_len(self) -> int:
        return self.k.shape[2]

    def positions(self) -> jnp.ndarray:
        """Absolute position of every cache slot: [S_max]."""
        return jnp.arange(self.max_seq_len, dtype=jnp.int32)


def truncate(cache: KVCache, new_length: jnp.ndarray) -> KVCache:
    """Logically roll the cache back to ``new_length`` tokens.

    The K/V slabs are left in place — slots ≥ new_length are marked invalid
    in the bitmap and ``length`` moves back, so subsequent writes overwrite
    them and attention (which masks on slot validity + position) never
    reads them.  O(1); the rollback primitive speculative decoding needs
    to discard rejected draft tokens.

    new_length: int32 scalar, or [B] for per-row rollback (each batch row
    keeps a different number of accepted tokens).
    """
    new_length = jnp.asarray(new_length, jnp.int32)
    bound = new_length[:, None] if new_length.ndim == 1 else new_length
    keep = jnp.arange(cache.max_seq_len, dtype=jnp.int32)[None, :] < bound
    return cache._replace(valid=cache.valid & keep, length=new_length)


def update_layer(
    k_layer: jnp.ndarray,
    v_layer: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    offset: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Write new keys/values at ``offset`` along the seq axis.

    k_layer/v_layer: [B, S_max, K, D]; k_new/v_new: [B, S_new, K, D];
    offset: int32 scalar (tokens already in the cache) or [B] per-row
    offsets (each row writes at its own length — vmapped update, the
    batched-speculative path).  Replaces the reference's per-layer concat
    append (llama3.2_model.py:321-330).

    Overflow contract: if ``offset + S_new > S_max`` the update start is
    silently clamped by ``dynamic_update_slice`` (XLA semantics — no
    data-dependent errors under jit), corrupting slot/position mapping.
    Callers must enforce capacity host-side; ``generate`` does.
    """
    k_new = k_new.astype(k_layer.dtype)
    v_new = v_new.astype(v_layer.dtype)
    return (
        write_at(k_layer, k_new, offset),
        write_at(v_layer, v_new, offset),
    )


def write_at(slab: jnp.ndarray, new: jnp.ndarray, offset: jnp.ndarray) -> jnp.ndarray:
    """dynamic_update_slice of ``new`` into ``slab`` along the seq axis
    (axis 1 of a [B, S_max, ...] array of any trailing rank), at a scalar
    offset or per-row [B] offsets (vmapped)."""
    trail = (jnp.zeros((), jnp.int32),) * (slab.ndim - 2)
    if offset.ndim == 1:
        import jax

        return jax.vmap(
            lambda sl, nw, off: lax.dynamic_update_slice(sl, nw, (off, *trail))
        )(slab, new, offset)
    zero = jnp.zeros((), jnp.int32)
    return lax.dynamic_update_slice(slab, new, (zero, offset, *trail))


def update_layer_quantized(
    k_layer: jnp.ndarray,
    v_layer: jnp.ndarray,
    ks_layer: jnp.ndarray,
    vs_layer: jnp.ndarray,
    k_new: jnp.ndarray,
    v_new: jnp.ndarray,
    offset: jnp.ndarray,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """update_layer for the int8 cache: quantize the new tokens' K/V
    (per-token-per-head absmax) and write values + scales at ``offset``.
    Returns (k_layer, v_layer, ks_layer, vs_layer) updated."""
    kq, ks = quantize_kv(k_new)
    vq, vs = quantize_kv(v_new)
    return (
        write_at(k_layer, kq, offset),
        write_at(v_layer, vq, offset),
        write_at(ks_layer, ks, offset),
        write_at(vs_layer, vs, offset),
    )
