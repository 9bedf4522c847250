"""Fused stable softmax kernel.

Role-equivalent of the reference's CUDA ``softmax_kernel``
(llama3.2_model.py:924-975): max-subtracted softmax over the last axis,
fused in one pass over on-chip memory.  The reference launches one CUDA
thread per *element*, each rescanning the whole axis; here one grid step
owns a block of rows resident in VMEM and the VPU does the row reductions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _softmax_kernel(x_ref, o_ref):
    x = x_ref[:].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[:] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def softmax(
    x: jnp.ndarray, *, block_rows: int = 8, interpret: bool | None = None
) -> jnp.ndarray:
    """Softmax over the last axis via a Pallas kernel.

    Leading axes are flattened to rows; ``block_rows`` rows are processed
    per grid step (the whole axis must fit in VMEM — true for vocab-sized
    axes: 8 rows × 256000 f32 ≈ 8 MB per block).

    interpret=None auto-selects: compiled on TPU, interpreter elsewhere.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    orig_shape = x.shape
    axis = orig_shape[-1]
    rows = 1
    for d in orig_shape[:-1]:
        rows *= d
    x2 = x.reshape(rows, axis)

    pad = (-rows) % block_rows
    if pad:
        x2 = jnp.pad(x2, ((0, pad), (0, 0)))

    # in + out blocks are double-buffered and the kernel holds f32
    # temporaries of the same size: at vocab-sized axes that exceeds
    # Mosaic's default 16 MiB scoped-VMEM limit (refused at compile), so
    # the limit is stated from the block size, inside v5e's 128 MiB
    block_bytes = block_rows * axis * 4
    vmem_limit = min(8 * block_bytes + (4 << 20), 100 << 20)
    out = pl.pallas_call(
        _softmax_kernel,
        out_shape=jax.ShapeDtypeStruct(x2.shape, x.dtype),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem_limit),
        grid=(x2.shape[0] // block_rows,),
        in_specs=[
            pl.BlockSpec(
                (block_rows, axis), lambda i: (i, 0), memory_space=pltpu.VMEM
            )
        ],
        out_specs=pl.BlockSpec(
            (block_rows, axis), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )(x2)
    if pad:
        out = out[:rows]
    return out.reshape(orig_shape)
