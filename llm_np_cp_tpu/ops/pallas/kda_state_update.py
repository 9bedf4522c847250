"""One token a row through a delta-rule layer's matrix state, in place.

The serving tick advances every row that has a token by that token
(``ops/kda.kda_packed``'s first pass, ``kda_step``):

    S  <- Diag(decay) S          decay = exp(g), one value a key channel
    u  =  v - S^T k
    S' =  S + beta k u^T
    o  =  S'^T q

over ``state [L, R, H, d, d]`` float32, of which one layer's rows move.
Unlike a state-space layer's ``h <- a h + dt x b^T`` (``ssm_state_update``)
the update READS the decayed state twice — ``S^T k`` before the rank-one
correction, ``S'^T q`` after it — and the second needs the first: as XLA
fusions that is three passes over ALL ``R`` rows.  Here a grid step copies
the heads of ONE touched row into VMEM, takes both products from that one
copy and writes ``S'`` back where it came from (the state is the call's
aliased operand): a touched row is read once and written once, and a row
of no token is no step, so it costs no traffic and keeps its bits.

The grid is (touched rows, blocks of heads); its first bound is a VALUE
(``ops/pallas/grouped_matmul``'s way with its live tiles).  The touched
rows come compacted to the front of a scalar-prefetch list, and every
block's place in HBM is read from it.

Layout.  A head's ``S`` is ``[d_k, d_v]`` with the VALUE channels on the
lanes, so ``v``, ``u`` and ``o`` are rows, and both products are sums down
the sublanes.  What runs along the key channels — ``decay``, ``k``, ``q`` —
is a column: the three travel with the HEADS on the lanes (``[d, H]``, head
``h`` in lane ``h``) and a head's column is picked out of its lane.
``beta`` is a scalar a head, read from SMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128


def takes(heads: int, d_k: int, d_v: int) -> bool:
    """Whether the kernel takes a state ``[.., heads, d_k, d_v]``: the
    value channels whole rows of lanes, the key channels whole sublanes,
    the heads in one row of lanes."""
    return d_v % _LANES == 0 and d_k % 8 == 0 and heads <= _LANES


def head_block(heads: int, d_k: int, d_v: int) -> int:
    """Heads a block of the kernel holds: a whole row where it is at most
    4 MiB (in and out, double-buffered: 16 MiB of VMEM) — the fewest
    steps, the longest copies."""
    hb = heads
    while hb * d_k * d_v * 4 > 4 * 2**20 and hb % 2 == 0:
        hb //= 2
    return hb


def _kernel(rows_ref, n_touched_ref, layer_ref, fresh_ref, beta_ref, s_ref,
            decay_ref, k_ref, q_ref, v_ref, s_out_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    hb, d_k, _ = s_ref.shape
    lanes = k_ref.shape[-1]
    row = rows_ref[i]
    touched = i < n_touched_ref[0]

    @pl.when(jnp.logical_not(touched))
    def _():
        # the one step of a tick that touches no row: the block as it was
        # (what the step leaves in ``o_ref`` the caller masks, as it does
        # every row's that no step visits)
        s_out_ref[...] = s_ref[...]

    @pl.when(touched)
    def _():
        started = fresh_ref[row] != 0  # nothing of the slot's old state
        lane = lax.broadcasted_iota(jnp.int32, (d_k, lanes), 1)

        def head(h, carry):
            at = j * hb + h  # the head, and its lane
            mine = lane == at

            def column(ref):  # [d_k, 1]: the head's lane of ``ref``
                return jnp.sum(jnp.where(mine, ref[...], 0.0), axis=-1,
                               keepdims=True)

            k_col = column(k_ref)
            s = jnp.where(started, 0.0, s_ref[h]) * column(decay_ref)
            u = v_ref[pl.ds(at, 1), :] - jnp.sum(s * k_col, axis=0,
                                                 keepdims=True)
            s = s + (beta_ref[row, at] * k_col) * u
            s_out_ref[h] = s
            o_ref[pl.ds(at, 1), :] = jnp.sum(s * column(q_ref), axis=0,
                                             keepdims=True)
            return carry

        lax.fori_loop(0, hb, head, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def kda_state_update(
    state: jnp.ndarray,  # [L, R, H, d, d] float32: every layer's rows
    layer: jnp.ndarray,  # int32 scalar: the layer whose rows advance
    decay: jnp.ndarray,  # [R, H, d] float32: exp(g), along the key channels
    k: jnp.ndarray,      # [R, H, d] float32
    q: jnp.ndarray,      # [R, H, d] float32
    v: jnp.ndarray,      # [R, H, d] float32
    beta: jnp.ndarray,   # [R, H] float32
    *,
    count: jnp.ndarray,  # [R] int32: a row with 0 is not in the tick
    fresh: jnp.ndarray,  # [R] bool: the row starts from nothing
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """``(o [R, H, d], state)``: for every row with ``count > 0`` its state
    in layer ``layer`` (zeros where ``fresh``) advanced in place by the
    row's token and ``o = S'^T q`` of the state after; a row with ``count
    == 0`` is not visited: its ``o`` is zero and its state untouched."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, r, nh, d_k, d_v = state.shape
    f32 = jnp.float32
    heads = head_block(nh, d_k, d_v)
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
    block = heads * d_k * d_v * 4

    def per_row(shape):
        return pl.BlockSpec(
            (None,) + shape, lambda i, j, rows, *_: (rows[i], 0, 0),
            memory_space=pltpu.VMEM)

    def heads_of_row(i, j, rows, n_touched, layer, fresh):
        return (layer[0], rows[i], j, 0, 0)

    of_state = pl.BlockSpec((None, None, heads, d_k, d_v), heads_of_row,
                            memory_space=pltpu.VMEM)
    columns = per_row((d_k, nh))  # the heads on the lanes
    state, o = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((r, nh, d_v), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(jnp.maximum(n_touched, 1), nh // heads),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # beta, whole
                of_state,
                columns, columns, columns,  # decay, k, q
                per_row((nh, d_v)),         # v: a row a head
            ],
            out_specs=(of_state, per_row((nh, d_v))),
        ),
        input_output_aliases={5: 0},  # the state, after the four lists
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(4 * block + (8 << 20), 100 << 20)),
        interpret=interpret,
        name="kda_state_update",  # as a profile names the call
    )(rows, n_touched.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), beta.astype(f32), state,
      *(jnp.swapaxes(a.astype(f32), 1, 2) for a in (decay, k, q)),
      v.astype(f32))
    return jnp.where(touched[:, None, None], o, 0.0), state


def step(s, decay, k, q, v, beta):
    """The update of one token, elementwise over ``s [.., d_k, d_v]``
    (``decay k q [.., d_k]``, ``v [.., d_v]``, ``beta [..]``): ``(o, s')``.
    What the kernel computes a head, and ``ops/kda.kda_step``."""
    s = s * decay[..., :, None]
    u = v - jnp.sum(s * k[..., :, None], axis=-2)
    s = s + (beta[..., None] * k)[..., :, None] * u[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def kda_state_update_xla(state, layer, decay, k, q, v, beta, *, count, fresh):
    """The kernel's twin in plain ``jnp`` over ALL of the layer's rows
    (what ``ops/kda.kda_packed`` runs where the kernel is not taken)."""
    there = count > 0
    # (the casts are no-ops: the state is float32 wherever the program
    # allocates it; a test keeps it lower to show that the tolerance sees it)
    s = lax.dynamic_index_in_dim(state, layer, 0, keepdims=False).astype(
        jnp.float32)
    o, s1 = step(jnp.where(fresh[:, None, None, None], 0.0, s),
                 decay, k, q, v, beta)
    s1 = jnp.where(there[:, None, None, None], s1, s)
    return (jnp.where(there[:, None, None], o, 0.0),
            lax.dynamic_update_index_in_dim(
                state, s1.astype(state.dtype), layer, 0))
