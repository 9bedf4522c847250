"""Fused sampling epilogue: final RMSNorm → lm_head → sample, streamed
over vocab tiles — the tick-tail fusion kernel (PAPERS.md: "LLM
Inference Acceleration via Efficient Operation Fusion").

The serve engine's XLA tail materializes full ``[rows, V]`` float32
logits in HBM (a 128k-vocab row is 512 KB, written once by the lm_head
einsum and read back by the sampler) even though a non-logprobs request
only ever consumes ONE token id per row.  This kernel collapses the
chain: each grid step streams one ``[*, block_v]`` lm_head tile through
VMEM, computes that tile's logits for every row (final RMSNorm applied
once into scratch on the first step), and folds them into a running
per-row sample state — the logits never exist outside VMEM.

Sampling: the streaming state is the greedy argmax (running best value
+ first-occurrence index, bit-identical to ``jnp.argmax`` over the full
logits row — strict-greater tile combining preserves first-max
tie-breaking, which softcap saturation and int8 weights do produce).
Greedy is the one sampler kind whose fused draw is exactly
token-identical to the XLA ``final_logits`` + ``Sampler`` oracle, so
the serve/offline gates select the fused path only for greedy samplers;
extending the stream to the stochastic kinds (temperature / top-p via
an in-kernel counter-based threefry reproducing ``jax.random``'s exact
bits, plus a streaming nucleus-threshold pass) is recorded ROADMAP
debt — the fallback path keeps serving them byte-identically meanwhile.

Numerics mirror the XLA chain op for op so greedy argmax parity is
exact: RMSNorm reduces in f32 and casts back to the activation dtype
(ops/norms.rms_norm), the lm_head dot accumulates f32
(quant_einsum's ``preferred_element_type``), int8 weights rescale the
f32 product per vocab column, and the softcap runs on the f32 logits.

Weight layouts (models/transformer.epilogue_params hands them over):
tied heads stream the embedding table ``[V, H]`` (block ``(block_v,
H)``), untied heads ``[H, V]`` (block ``(H, block_v)``); int8 heads
(quant.py payload ``"q"``) stream the 1-byte payload with their
``[1, V]`` f32 scales riding along.  Benchmark-gated like every kernel
here: probe ``sample_epilogue[_int8]`` in ops/pallas/support.py, XLA
fallback everywhere (Mosaic-compiling this kernel on hardware is
recorded live-TPU debt).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float(jnp.finfo(jnp.float32).min)

# Default vocab-tile width: a multiple of 128 (Mosaic lane tile; also
# satisfies the (32, 128) int8 sublane tile on the tied layout's
# second-minor axis) small enough that a double-buffered bf16 tile of a
# 2k-hidden model stays ~2 MiB in VMEM.
BLOCK_V = 512


def _epilogue_kernel(
    *refs,
    tied: bool,
    quantized: bool,
    eps: float,
    unit_offset: bool,
    softcap: float | None,
    block_v: int,
    vocab: int,
    scale: float,
):
    if quantized:
        x_ref, g_ref, w_ref, s_ref, o_ref, xn_ref, bv_ref, bi_ref = refs
    else:
        x_ref, g_ref, w_ref, o_ref, xn_ref, bv_ref, bi_ref = refs
    j = pl.program_id(0)
    nj = pl.num_programs(0)

    @pl.when(j == 0)
    def _init():
        # final RMSNorm once per row into scratch, mirroring
        # ops/norms.rms_norm bit for bit: f32 reduction + rsqrt, weight
        # (+1 under unit offset) applied in f32, cast back to the
        # activation dtype — the dtype the lm_head dot consumes
        xf = x_ref[:].astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
        normed = xf * lax.rsqrt(var + eps)
        w = g_ref[:].astype(jnp.float32)  # [1, H]
        if unit_offset:
            w = w + 1.0
        xn_ref[:] = (normed * w).astype(xn_ref.dtype)
        bv_ref[:] = jnp.full_like(bv_ref, NEG_INF)
        bi_ref[:] = jnp.zeros_like(bi_ref)

    xn = xn_ref[:]  # [N, H]
    wb = w_ref[:]
    if quantized:
        wb = wb.astype(xn.dtype)
    # one vocab tile's logits for every row, f32 accumulation — the
    # same contraction quant_einsum("...h,vh->...v" / "...h,hv->...v")
    # traces, so values (and therefore argmax ties) match the oracle
    if tied:
        s = jax.lax.dot_general(
            xn, wb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [N, block_v]
    else:
        s = jax.lax.dot_general(
            xn, wb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
    if quantized:
        s = s * s_ref[:]  # [1, block_v] f32 per-column scales
    if scale != 1.0:
        s = s * scale  # the head's multiplier, on the f32 logits
    if softcap is not None:
        s = jnp.tanh(s / softcap) * softcap
    # mask the tail tile's fake columns (rank-2 iota: Mosaic rejects
    # rank-1 iota on TPU)
    col = j * block_v + jax.lax.broadcasted_iota(
        jnp.int32, s.shape, 1
    )
    s = jnp.where(col < vocab, s, NEG_INF)

    # streaming argmax: within-tile argmax takes the FIRST max, and the
    # strict-greater combine keeps the earlier tile on cross-tile ties —
    # exactly jnp.argmax's first-occurrence rule over the full row
    tile_best = jnp.max(s, axis=-1, keepdims=True)  # [N, 1]
    tile_idx = (
        j * block_v + jnp.argmax(s, axis=-1, keepdims=True)
    ).astype(jnp.int32)
    better = tile_best > bv_ref[:]
    bv_ref[:] = jnp.where(better, tile_best, bv_ref[:])
    bi_ref[:] = jnp.where(better, tile_idx, bi_ref[:])

    @pl.when(j == nj - 1)
    def _emit():
        o_ref[:] = bi_ref[:]


@functools.partial(
    jax.jit,
    static_argnames=(
        "tied", "eps", "unit_offset", "logit_softcap", "block_v",
        "interpret", "logit_scale",
    ),
)
def sample_epilogue(
    x: jnp.ndarray,
    gamma: jnp.ndarray,
    w: jnp.ndarray,
    *,
    w_scale: jnp.ndarray | None = None,
    tied: bool,
    eps: float,
    unit_offset: bool = False,
    logit_softcap: float | None = None,
    block_v: int = BLOCK_V,
    interpret: bool | None = None,
    logit_scale: float = 1.0,
) -> jnp.ndarray:
    """Greedy-sample the next token for each row of ``x`` without ever
    materializing the logits.

    x [N, H] — final-layer hidden states (pre final-norm; one row per
    sample slot).  gamma [H] — the final RMSNorm weight.  w — the
    lm-head weight: ``[V, H]`` when ``tied`` (the embedding table),
    ``[H, V]`` otherwise; int8 payloads ride with ``w_scale`` [1, V]
    f32 per-vocab-column scales (quant.py's ``"q"`` mode).  → [N] int32
    token ids, bit-identical to ``Sampler(kind="greedy")`` over
    ``final_logits`` (models/transformer.py) — pinned in tests.
    ``logit_scale`` is a head's constant multiplier (applied to the f32
    logits before the softcap, as ``final_logits`` does).

    Rows are padded to the f32 sublane tile internally; pad rows are
    zeros, normalize to zeros, and their draw is sliced off.
    interpret=None auto-selects like the other kernels here.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    quantized = w_scale is not None
    if quantized != (w.dtype == jnp.int8):
        raise ValueError(
            "int8 lm-head payloads require w_scale (and vice versa); "
            f"got w={w.dtype}, "
            f"w_scale={'set' if w_scale is not None else None}"
        )
    n, h = x.shape
    v = w.shape[0] if tied else w.shape[1]
    if (w.shape[1] if tied else w.shape[0]) != h:
        raise ValueError(
            f"lm-head weight {w.shape} does not match hidden size {h} "
            f"(tied={tied})"
        )
    if block_v % 128:
        raise ValueError(f"block_v must be a multiple of 128, got {block_v}")
    n8 = -(-n // 8) * 8
    if n8 != n:
        x = jnp.pad(x, [(0, n8 - n), (0, 0)])
    bv = v if v <= block_v else block_v
    nv = -(-v // bv)

    if tied:
        w_spec = pl.BlockSpec((bv, h), lambda j: (j, 0),
                              memory_space=pltpu.VMEM)
    else:
        w_spec = pl.BlockSpec((h, bv), lambda j: (0, j),
                              memory_space=pltpu.VMEM)
    in_specs = [
        pl.BlockSpec((n8, h), lambda j: (0, 0), memory_space=pltpu.VMEM),
        pl.BlockSpec((1, h), lambda j: (0, 0), memory_space=pltpu.VMEM),
        w_spec,
    ]
    operands = [x, gamma.reshape(1, h), w]
    if quantized:
        in_specs.append(
            pl.BlockSpec((1, bv), lambda j: (0, j),
                         memory_space=pltpu.VMEM)
        )
        operands.append(w_scale.astype(jnp.float32))
    out = pl.pallas_call(
        functools.partial(
            _epilogue_kernel, tied=tied, quantized=quantized, eps=eps,
            unit_offset=unit_offset, softcap=logit_softcap, block_v=bv,
            vocab=v, scale=logit_scale,
        ),
        out_shape=jax.ShapeDtypeStruct((n8, 1), jnp.int32),
        grid=(nv,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((n8, 1), lambda j: (0, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((n8, h), x.dtype),
            pltpu.VMEM((n8, 1), jnp.float32),
            pltpu.VMEM((n8, 1), jnp.int32),
        ],
        interpret=interpret,
    )(*operands)
    return out[:n, 0]
