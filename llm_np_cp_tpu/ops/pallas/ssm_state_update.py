"""One token a row through a state-space layer's recurrent state, in place.

The serving tick advances every row that has a token by that token
(``ops/ssm.ssm_packed``'s first pass, ``ssm_chunk`` at ``q = 1``):

    S  = sum_n(H * C)                  what the state gives the token's y
    H' = decay * H + (dt x) B^T        the state after it

over ``state [L, R, nh, P, N]`` float32, of which one layer's rows move.
As two XLA fusions that is two reads and one write of ALL ``R`` rows — a
row that has no token in the tick is read twice and written back as it
was.  Here a grid step copies a block of heads of ONE touched row into
VMEM, takes both results from that one copy and writes ``H'`` back where
it came from (the state is the call's aliased operand): a touched row is
read once and written once, and a row of no token is no step, so it
costs no traffic and keeps its bits.

The grid is (touched rows, blocks of heads); its first bound is a VALUE
(``ops/pallas/grouped_matmul``'s way with its live tiles).  The touched
rows come compacted to the front of a scalar-prefetch list, and every
block's place in HBM is read from it.

Layout.  A head's ``H`` is ``[P, N]`` with ``N`` on the lanes, so ``C``
and ``B`` (along ``N``) are rows broadcast down the sublanes, and what
runs along ``P`` — the token's ``dt x`` coming in, ``S`` going out — is a
column.  Both travel with the HEADS on the lanes (``[P, nh]``, head
``h`` in lane ``h``): a head's column is picked out of, and put back
into, its lane.  The decay is a scalar a head, read from SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
# sublanes of a head's [P, N] taken through the arithmetic at once: 8
# vregs of state at N = 256, so that a step's values stay in registers
_P_CHUNK = 32


def head_block(nh: int, ng: int, p: int, n: int) -> int | None:
    """Heads a block of the kernel holds for a state ``[.., nh, P, N]``
    of ``ng`` groups, or None where the kernel does not take the shape:
    ``N`` whole lanes, ``P`` whole sublanes, the heads in one row of
    lanes, a block inside one group or over whole ones.  A whole row
    where it is at most 4 MiB (in and out, double-buffered: 16 MiB of
    VMEM): the fewest steps, the longest copies."""
    if n % _LANES or p % 8 or nh > _LANES or nh % ng:
        return None
    hb = nh
    while hb * p * n * 4 > 4 * 2**20 and hb % 2 == 0:
        hb //= 2
    per_group = nh // ng
    if hb % per_group and per_group % hb:
        return None
    return hb


def _kernel(rows_ref, n_touched_ref, layer_ref, fresh_ref, decay_ref, h_ref,
            dtx_ref, b_ref, c_ref, o_ref, s_ref, *, per_group: int,
            p_chunk: int):
    i, j = pl.program_id(0), pl.program_id(1)
    hb, p, _ = h_ref.shape
    lanes = s_ref.shape[-1]
    row = rows_ref[i]
    touched = i < n_touched_ref[0]

    @pl.when(jnp.logical_not(touched))
    def _():
        # the one step of a tick that touches no row: the block as it was
        # (what the step leaves in ``s_ref`` the caller masks, as it does
        # every row's that no step visits)
        o_ref[...] = h_ref[...]

    @pl.when(touched)
    def _():
        started = fresh_ref[row] != 0  # nothing of the slot's old state
        lane = lax.broadcasted_iota(jnp.int32, (p_chunk, lanes), 1)

        def head(h, carry):
            at = j * hb + h  # the head, and its lane
            g = at // per_group
            decay = decay_ref[row, at]
            b_g, c_g = b_ref[pl.ds(g, 1), :], c_ref[pl.ds(g, 1), :]
            mine = lane == at
            for p0 in range(0, p, p_chunk):
                rows = pl.ds(p0, p_chunk)
                hh = jnp.where(started, 0.0, h_ref[h, rows, :])
                s = jnp.sum(hh * c_g, axis=-1, keepdims=True)
                dtx = jnp.sum(jnp.where(mine, dtx_ref[rows, :], 0.0),
                              axis=-1, keepdims=True)
                o_ref[h, rows, :] = decay * hh + dtx * b_g
                s_ref[rows, :] = jnp.where(mine, s, s_ref[rows, :])
            return carry

        lax.fori_loop(0, hb, head, 0)


@functools.partial(jax.jit, static_argnames=("heads", "interpret"))
def ssm_state_update(
    state: jnp.ndarray,  # [L, R, nh, P, N] float32: every layer's rows
    layer: jnp.ndarray,  # int32 scalar: the layer whose rows advance
    decay: jnp.ndarray,  # [R, nh] float32: exp(dt A)
    dtx: jnp.ndarray,    # [R, nh, P] float32: dt x
    b: jnp.ndarray,      # [R, ng, N] float32
    c: jnp.ndarray,      # [R, ng, N] float32
    *,
    count: jnp.ndarray,  # [R] int32: a row with 0 is not in the tick
    fresh: jnp.ndarray,  # [R] bool: the row starts from nothing
    heads: int,          # ``head_block``'s
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(S [R, nh, P], state)``: for every row with ``count > 0``,
    ``S = sum_n(H * C)`` of its state in layer ``layer`` (zeros where
    ``fresh``) and that state advanced in place to ``decay * H + dtx
    B^T``; a row with ``count == 0`` is not visited: its ``S`` is zero
    and its state untouched."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, r, nh, p, n = state.shape
    ng = b.shape[1]
    f32 = jnp.float32
    touched = count > 0
    # the touched rows, compacted to the front (as compare-and-sum: two
    # fused reductions where a sort of 64 keys is a dozen operations); a
    # place past them names row 0 and is no step
    row = jnp.arange(r, dtype=jnp.int32)
    place = jnp.sum(touched[None, :] & (row[None, :] < row[:, None]),
                    axis=1, dtype=jnp.int32)  # touched rows before a row
    rows = jnp.sum(jnp.where(
        touched[None, :] & (place[None, :] == row[:, None]), row[None, :], 0),
        axis=1, dtype=jnp.int32)
    n_touched = jnp.sum(touched, dtype=jnp.int32)
    p_chunk = next(c for c in (_P_CHUNK, 16, 8) if p % c == 0)
    block = heads * p * n * 4

    def per_row(shape):
        return pl.BlockSpec(
            (None,) + shape, lambda i, j, rows, *_: (rows[i], 0, 0),
            memory_space=pltpu.VMEM)

    def heads_of_row(i, j, rows, n_touched, layer, fresh):
        return (layer[0], rows[i], j, 0, 0)

    of_state = pl.BlockSpec((None, None, heads, p, n), heads_of_row,
                            memory_space=pltpu.VMEM)
    state, s_t = pl.pallas_call(
        functools.partial(_kernel, per_group=nh // ng, p_chunk=p_chunk),
        out_shape=(jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((r, p, nh), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(jnp.maximum(n_touched, 1), nh // heads),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # decay, whole
                of_state,
                per_row((p, nh)),  # columns: the heads on the lanes
                per_row((ng, n)),
                per_row((ng, n)),
            ],
            out_specs=(of_state, per_row((p, nh))),
        ),
        input_output_aliases={5: 0},  # the state, after the four lists
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(4 * block + (8 << 20), 100 << 20)),
        interpret=interpret,
        name="ssm_state_update",  # as a profile names the call
    )(rows, n_touched.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), decay.astype(f32), state,
      jnp.swapaxes(dtx.astype(f32), 1, 2), b.astype(f32), c.astype(f32))
    return jnp.where(touched[:, None, None], jnp.swapaxes(s_t, 1, 2), 0.0), state
