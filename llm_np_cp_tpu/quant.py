"""Weight-only int8 quantization for decode.

Single-chip decode is HBM-bandwidth-bound: every step streams the full
weight set (bf16 Llama-3.2-1B = 2.47 GB ÷ ~819 GB/s ≈ 331 steps/s
ceiling — measured ~80% of that).  The reference has no quantization at
all; on TPU the natural lever is storing weights as int8 with
per-output-channel float scales and dequantizing *inside* the fused
matmul read: XLA folds the ``int8 → bf16`` convert and the scale multiply
into the GEMM's operand pipeline, so HBM traffic halves while the MXU
still runs bf16×bf16.

Representation: a quantized matrix is a dict in the original array's
pytree position — ``{"q": int8, "s": f32}`` (8-bit), ``{"q4": uint8
two-nibbles-per-byte packed along the contraction axis, "s": f32}``
(4-bit; see quantize_array4), or ``{"qa": int8, "s": f32}`` (8-bit
weights consumed with DYNAMIC per-token int8 activation quantization:
the einsum runs int8×int8 on the MXU's native int8 path, skipping the
per-element int8→bf16 weight convert of the ``q`` mode — W8A8) — with
``s`` broadcast along the *input* axis (consumers: ``payload()`` /
``payload_key()`` below, quant_einsum, sharding.shard_params):

- projections ``[in, out]`` → per-out-channel scale ``[out]``
- stacked layers ``[L, in, out]`` → ``[L, 1, out]``
- embedding ``[V, H]`` → per-row scale ``[V, 1]`` (the row is the output
  channel of the tied lm_head and the gather unit of the embed lookup)

Norm gammas, MoE routers, and anything 1-D stay in the float dtype —
they are noise in the byte budget and precision-critical.

Symmetric quantization: ``q = round(w / s)``, ``s = max|w| / 127`` per
channel.  No activation quantization (activations never touch HBM
between fused ops).
"""

from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

Params = dict[str, Any]

# weights quantized along their contraction-input axis (per-output scales)
_QUANT_KEYS = {
    "q_proj", "k_proj", "v_proj", "o_proj",
    "gate_proj", "up_proj", "down_proj", "lm_head",
}


_PAYLOAD_KEYS = ("q", "qa", "q4", "q4a")


def is_quantized(w: Any) -> bool:
    return (
        isinstance(w, dict)
        and any(k in w for k in _PAYLOAD_KEYS)
        and "s" in w
    )


def payload_key(w: dict) -> str:
    for k in _PAYLOAD_KEYS:
        if k in w:
            return k
    raise KeyError(f"not a quantized leaf: {list(w)}")


def payload(w: dict) -> jnp.ndarray:
    """The quantized leaf's full-width integer payload (int4 unpacked)."""
    key = payload_key(w)
    if key in ("q4", "q4a"):
        return _unpack4(w[key])
    return w[key]


def quantize_array(w: jnp.ndarray, *, axis: int) -> dict[str, jnp.ndarray]:
    """Symmetric int8 quantization of ``w`` along ``axis`` (the contraction
    axis): scales have size 1 there and the full size elsewhere is kept
    broadcastable."""
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 127.0, 1.0)
    q = jnp.clip(jnp.round(w32 / s), -127, 127).astype(jnp.int8)
    return {"q": q, "s": s.astype(jnp.float32)}


def quantize_array4(w: jnp.ndarray, *, axis: int = -2) -> dict[str, jnp.ndarray]:
    """Symmetric int4: q ∈ [-7, 7], stored offset-binary (q+8) two values
    per uint8, packed along the CONTRACTION axis (must be ``-2`` and even
    — every projection's in-dim is).  Payload is in-dim/2 × 1 byte: a
    quarter of bf16, half of int8."""
    if axis != -2:
        raise NotImplementedError("int4 packing is along axis -2 only")
    if w.shape[-2] % 2:
        raise ValueError(f"contraction dim {w.shape[-2]} must be even for int4")
    w32 = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w32), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 7.0, 1.0)
    q = (jnp.clip(jnp.round(w32 / s), -7, 7) + 8).astype(jnp.uint8)
    qr = q.reshape(*q.shape[:-2], q.shape[-2] // 2, 2, q.shape[-1])
    packed = qr[..., 0, :] | (qr[..., 1, :] << 4)
    return {"q4": packed, "s": s.astype(jnp.float32)}


def _unpack4_pairs(p: jnp.ndarray) -> jnp.ndarray:
    """uint8 [..., in/2, out] → int8 [..., in/2, 2, out] (n=0 low nibble).

    A single broadcast-shift-mask over the packed bytes — no stack, no
    concat, no axis merge — so the unpack stays a pure elementwise
    producer that XLA can fuse into the consuming GEMM's operand read
    (the r4 bench showed the earlier stack+reshape variant materializing
    the full unpacked tensor every decode step: int4 ran 4x SLOWER than
    bf16 at 5% roofline)."""
    shifts = jnp.asarray([0, 4], jnp.uint8).reshape(2, 1)
    q = (p[..., None, :] >> shifts) & jnp.uint8(0xF)
    return q.astype(jnp.int8) - 8


def _unpack4(p: jnp.ndarray) -> jnp.ndarray:
    """uint8 [..., in/2, out] → int8 [..., in, out] (row 2i = low nibble)."""
    u = _unpack4_pairs(p)  # [..., in/2, 2, out]
    return u.reshape(*p.shape[:-2], p.shape[-2] * 2, p.shape[-1])


def quantize_kv(x: jnp.ndarray) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Per-token-per-head symmetric int8: x [..., D] float →
    (int8 [..., D], f32 absmax/127 scale [...]).

    Same numeric contract as quantize_array (weight-side int8) but
    activation-shaped: squeezed scale tuple instead of a keepdims dict,
    and the amax==0 guard keeps scale 0 (slot reads as exact zero) rather
    than mapping it to 1.  Keep the two in sync if the contract changes.
    """
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    safe = jnp.where(scale == 0.0, 1.0, scale)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / safe[..., None]), -127, 127)
    return q.astype(jnp.int8), scale.astype(jnp.float32)


def dequantize_kv(q: jnp.ndarray, scale: jnp.ndarray, dtype: jnp.dtype) -> jnp.ndarray:
    """int8 [..., D] × scale [...] → float [..., D].  Left unfused here on
    purpose: XLA folds the convert+multiply into the attention einsum's
    operand, so HBM reads stay int8."""
    return q.astype(dtype) * scale[..., None].astype(dtype)


def dequantize(w: Any, dtype: jnp.dtype = jnp.float32) -> jnp.ndarray:
    if not is_quantized(w):
        return w
    return (payload(w).astype(jnp.float32) * w["s"]).astype(dtype)


def quantize_params(
    params: Params, *, embed: bool = True, bits: int = 8,
    act_quant: bool = False,
) -> Params:
    """Quantize every projection matrix (and optionally the embedding /
    tied lm_head table) of a transformer param pytree in place-shape.

    ``bits=4`` packs the projections two-per-byte (quarter of bf16); the
    embedding/lm_head stay int8 — per-row int4 on the gather table costs
    visible quality for a small byte win, and the lm_head matmul is once
    per step, not per layer.

    ``act_quant=True`` marks the per-layer projections for dynamic
    activation quantization (payload key ``qa`` at bits=8, ``q4a`` at
    bits=4): quant_einsum quantizes each token's activations to int8 on
    the fly (per-row absmax) and contracts all-integer with int32
    accumulation — the MXU's native int8 path, no weight convert in the
    operand stream.  The embed / lm_head table keeps the weight-only
    ``q`` mode (it serves the gather too, and logits set output
    quality).  Quality cost is measured by utils/quality.py's
    ``int8_a8`` / ``int4_a8`` modes — activation outliers make these
    lossier than their weight-only twins; both are opt-in.

    The result drops into ``models.transformer.forward`` unchanged —
    ``_project`` / ``embed_inputs`` / ``final_logits`` detect the dict
    leaves — and into ``parallel.sharding.shard_params``, which shards the
    payload like the original weight and the scales alongside it.
    """
    if bits not in (4, 8):
        raise ValueError(f"bits must be 4 or 8, got {bits}")
    qproj = quantize_array4 if bits == 4 else quantize_array
    out = dict(params)
    layers = dict(params["layers"])
    for key in list(layers.keys()):
        if key in _QUANT_KEYS:
            # stacked [L, in, out] (dense) or [L, E, in, out] (MoE experts):
            # contraction axis is always -2
            w = qproj(layers[key], axis=-2)
            if act_quant:  # W8A8 "qa" / W4A8 "q4a": int-MXU consumption
                pk = "q" if "q" in w else "q4"
                w = {pk + "a": w.pop(pk), **w}
            layers[key] = w
    out["layers"] = layers
    if embed:
        # [V, H]: per-row scales serve both the embed gather and the tied
        # lm_head (row = vocab output channel)
        out["embed_tokens"] = quantize_array(params["embed_tokens"], axis=-1)
    if "lm_head" in params:
        # int8 even at bits=4: the lm_head matmul runs once per step (not
        # per layer) and sets output-logit quality
        out["lm_head"] = quantize_array(params["lm_head"], axis=-2)
    return out


def _align_scale(spec: str, s: jnp.ndarray) -> jnp.ndarray:
    """Reshape a keepdims scale tensor (same rank as the einsum's second
    operand, size 1 on contracted axes) so it broadcasts against the
    einsum's OUTPUT — the single place that knows the scale layout."""
    ins, out = spec.replace(" ", "").split("->")
    _, w_idx = ins.split(",")
    drop = tuple(i for i, c in enumerate(w_idx) if c not in out)
    s2 = jnp.squeeze(s, axis=drop)
    kept = [c for c in w_idx if c in out]
    s2 = jnp.transpose(s2, sorted(range(len(kept)), key=lambda i: out.index(kept[i])))
    kept_sorted = sorted(kept, key=out.index)
    return s2.reshape([
        s2.shape[kept_sorted.index(c)] if c in kept_sorted else 1 for c in out
    ])


def _align_x_scale(spec: str, sx: jnp.ndarray) -> jnp.ndarray:
    """Reshape a keepdims ACTIVATION scale (same rank as the einsum's
    first operand, size 1 on contracted axes) to broadcast against the
    einsum's output — the x-side twin of _align_scale."""
    ins, out = spec.replace(" ", "").split("->")
    x_idx, _ = ins.split(",")
    drop = tuple(i for i, c in enumerate(x_idx) if c not in out)
    s2 = jnp.squeeze(sx, axis=drop)
    kept = [c for c in x_idx if c in out]
    s2 = jnp.transpose(s2, sorted(range(len(kept)), key=lambda i: out.index(kept[i])))
    kept_sorted = sorted(kept, key=out.index)
    return s2.reshape([
        s2.shape[kept_sorted.index(c)] if c in kept_sorted else 1 for c in out
    ])


def quant_einsum(spec: str, x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """``einsum(spec, x, w)`` in f32 accumulation, accepting a plain array
    or a quantized ``{"q"|"qa"|"q4", "s"}`` dict for ``w``.  ``q``/``q4``
    matmul the (unpacked) payload in x.dtype and rescale the output;
    ``qa`` additionally quantizes the activations on the fly (dynamic
    per-row absmax) and contracts int8×int8 with int32 accumulation —
    the W8A8 path.  All weight-consuming einsums in the model go through
    this."""
    if not is_quantized(w):
        return jnp.einsum(spec, x, w, preferred_element_type=jnp.float32)
    if "qa" in w or "q4a" in w:
        # dynamic activation quant (per-row absmax over the contracted
        # axes), then an all-integer contraction on the MXU's int8 path
        ins, out = spec.replace(" ", "").split("->")
        x_idx, _ = ins.split(",")
        contracted = tuple(i for i, c in enumerate(x_idx) if c not in out)
        amax = jnp.max(
            jnp.abs(x.astype(jnp.float32)), axis=contracted, keepdims=True
        )
        sx = jnp.where(amax > 0, amax / 127.0, 1.0)
        xq = jnp.clip(jnp.round(x.astype(jnp.float32) / sx), -127, 127).astype(
            jnp.int8
        )
        if "qa" in w:
            y = jnp.einsum(spec, xq, w["qa"], preferred_element_type=jnp.int32)
        else:
            y = _einsum4(spec, xq, w["q4a"], int_accum=True)
        return (
            y.astype(jnp.float32)
            * _align_x_scale(spec, sx)
            * _align_scale(spec, w["s"])
        )
    if "q4" in w:
        y = _einsum4(spec, x, w["q4"])
    else:
        y = jnp.einsum(
            spec, x, w["q"].astype(x.dtype), preferred_element_type=jnp.float32
        )
    return y * _align_scale(spec, w["s"])


def _einsum4(
    spec: str, x: jnp.ndarray, q4: jnp.ndarray, *, int_accum: bool = False
) -> jnp.ndarray:
    """int4 einsum that contracts over (packed-pair, nibble) axes
    directly: x's contraction axis splits [in] → [in/2, 2] (a free
    adjacent-dim reshape on the ACTIVATION, which is tiny at decode) and
    the weight unpacks as [..., in/2, 2, out] via _unpack4_pairs — no
    axis-merge reshape on the weight side, keeping the whole decode
    elementwise-fusable into the GEMM operand read.

    ``int_accum=True`` (W4A8: x already int8) keeps the unpacked nibbles
    int8 and accumulates in int32 — all-integer MXU contraction."""
    acc = jnp.int32 if int_accum else jnp.float32
    ins, out = spec.replace(" ", "").split("->")
    x_idx, w_idx = ins.split(",")
    c = w_idx[-2]  # quantize_array4 packs along axis -2 only
    if x_idx[-1] != c:
        # not a last-axis contraction (no in-repo spec hits this): fall
        # back to the explicit unpack
        return jnp.einsum(
            spec, x, _unpack4(q4).astype(x.dtype),
            preferred_element_type=acc,
        )
    n = next(ch for ch in "nmzyxwutsr" if ch not in spec)
    xr = x.reshape(*x.shape[:-1], x.shape[-1] // 2, 2)
    u = _unpack4_pairs(q4).astype(x.dtype)
    pair_spec = f"{x_idx[:-1]}{c}{n},{w_idx[:-1]}{n}{w_idx[-1]}->{out}"
    return jnp.einsum(pair_spec, xr, u, preferred_element_type=acc)


def param_bytes(params: Params) -> int:
    """Total HBM bytes of a (possibly quantized) param pytree."""
    return sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(params)
    )
