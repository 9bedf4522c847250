"""One token a row through a power-retention layer's state, in place.

The serving tick advances every row that has a token by that token
(``ops/retention.retention_packed``'s first pass, ``retention_step``).  For
a kv head with key ``k``, value ``v``, scalar forget gate ``g`` and the
``G`` query heads ``q_i`` that share it:

    S  <- g S + phi(k) v^T            S in R^{rows x d_v}
    Z  <- g Z + k k^T                 Z in R^{d x d}
    o_i = phi(q_i)^T S / q_i^T Z q_i

over ``s [L, R, H, rows, d_v]`` and ``z [L, R, H, d, d]`` float32, of which
one layer's rows move.  As XLA fusions that is ``phi`` of six vectors
written to HBM and three passes over ALL ``R`` rows of ``S``.  Here a grid
step copies ONE touched row's kv head into VMEM, forms ``phi(k)`` and the
``phi(q_i)`` from scalars as it walks the rows, and writes ``S`` back where
it came from (the state is the call's aliased operand): a touched head is
read once and written once, a row of no token is no step, and ``phi`` is
never in HBM.

The monomials (``phi_layout``).  ``phi(u) . phi(w) = (u . w)^2`` needs one
row a pair ``a <= b`` of the head's ``d`` channels: ``u_a u_b``, times
``sqrt 2`` off the diagonal.  The channels are cut into blocks of 8 (a
vector register's sublanes) and the rows laid out by block PAIRS: for
``J = 0 .. d/8 - 1``, for ``a = 0 .. 8 J + 7``, the 8 rows ``(a, b)``, ``b``
in block ``J``.  Off the diagonal block (``a < 8 J``) a row is ``sqrt 2 u_a
u_b``; on it all 64 pairs ``(a, b)`` are kept with factor 1 — ``(a, b)`` and
``(b, a)`` both, which together are the ``2 u_a u_b w_a w_b`` the two
``sqrt 2`` would give — so the inner product is exact and a register never
holds half a block: ``64 (d/8)(d/8 + 1) / 2`` rows, 8,704 at ``d`` = 128
where the distinct monomials are 8,256 (5.4 % more; the full outer product
would be 16,384).  ``Z`` is the gated sum of ``k k^T`` whole (``d x d``, 64
KiB a head beside ``S``'s 4.25 MiB): ``phi(q) . z`` for the gated sum ``z``
of ``phi(k)`` IS ``q^T Z q``.

In a register ``[8, 128]`` of ``S`` the sublanes are ``b`` and the lanes
the value's channels, so ``a`` is ONE scalar: the update is ``g s + (c
k_a) (k_b v^T)`` with ``k_b v^T`` a register kept through a group, and a
query head's read-out ``sum_a (c q_a) s`` five multiply-adds into five
registers, closed a group with ``sum_b q_b (..)``.  ``k`` and ``q`` come as
scalars (SMEM) for ``a`` and, heads on the lanes, as columns for ``b``.

The grid is (touched rows, kv heads); its first bound is a VALUE
(``ops/pallas/kda_state_update``'s way).  The touched rows come compacted
to the front of a scalar-prefetch list, and every block's place in HBM is
read from it.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANES = 128
BLOCK = 8  # channels a block of the monomials' layout holds
_SQRT2 = math.sqrt(2.0)


def phi_rows(d: int) -> int:
    """Rows of the state a kv head holds for a head of ``d`` channels."""
    nb = d // BLOCK
    return BLOCK * BLOCK * nb * (nb + 1) // 2


def distinct_monomials(d: int) -> int:
    """What the mathematics needs: the pairs ``a <= b``."""
    return d * (d + 1) // 2


@functools.lru_cache(maxsize=None)
def phi_layout(d: int) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """``(a, b, factor)`` of every row, in the order the state holds them
    (module docstring): ``phi(u)[r] = factor[r] u[a[r]] u[b[r]]``."""
    if d % BLOCK:
        raise ValueError(f"a head of {d} channels is not blocks of {BLOCK}")
    a, b, f = [], [], []
    for j in range(d // BLOCK):
        for a_ in range(BLOCK * (j + 1)):
            for b_ in range(BLOCK * j, BLOCK * (j + 1)):
                a.append(a_)
                b.append(b_)
                f.append(_SQRT2 if a_ < BLOCK * j else 1.0)
    return (np.asarray(a, np.int32), np.asarray(b, np.int32),
            np.asarray(f, np.float32))


def phi(u: jnp.ndarray) -> jnp.ndarray:
    """``[.., d] -> [.., phi_rows(d)]`` float32: a group ``J`` is the outer
    product of the channels up to its block's end with its block's."""
    u = u.astype(jnp.float32)
    d = u.shape[-1]
    parts = []
    for j in range(d // BLOCK):
        lo, hi = BLOCK * j, BLOCK * (j + 1)
        factor = jnp.where(jnp.arange(hi) < lo, _SQRT2, 1.0).astype(u.dtype)
        outer = (u[..., :hi] * factor)[..., :, None] * u[..., None, lo:hi]
        parts.append(outer.reshape(u.shape[:-1] + (hi * BLOCK,)))
    return jnp.concatenate(parts, axis=-1)


def takes(heads: int, rows: int, d_v: int) -> bool:
    """Whether the kernel takes a state ``[.., heads, rows, d_v]``: the
    value channels whole rows of lanes, the heads in one row of lanes."""
    return d_v % _LANES == 0 and rows % BLOCK == 0 and heads <= _LANES


def _kernel(rows_ref, n_touched_ref, layer_ref, fresh_ref, g_ref, kq_ref,
            s_ref, z_ref, k_col_ref, q_col_ref, k_row_ref, q_row_ref, v_ref,
            s_out_ref, z_out_ref, o_ref, *, group: int):
    i, j = pl.program_id(0), pl.program_id(1)
    d = z_ref.shape[-1]
    d_v = s_ref.shape[-1]
    row = rows_ref[i]
    touched = i < n_touched_ref[0]
    f32 = jnp.float32

    @pl.when(jnp.logical_not(touched))
    def _():
        # the one step of a tick that touches no row: the blocks as they
        # were (what the step leaves in ``o_ref`` the caller masks)
        s_out_ref[...] = s_ref[...]
        z_out_ref[...] = z_ref[...]

    @pl.when(touched)
    def _():
        started = fresh_ref[row] != 0  # nothing of the slot's old state
        g = g_ref[row, j]

        def column(ref, at):  # [d, 1]: lane ``at`` of ``ref [d, heads]``
            lane = lax.broadcasted_iota(jnp.int32, ref.shape, 1)
            return jnp.sum(jnp.where(lane == at, ref[...], 0.0), axis=-1,
                           keepdims=True)

        k_col = column(k_col_ref, j)              # [d, 1]
        k_row = k_row_ref[pl.ds(j, 1), :]         # [1, d]
        v_row = v_ref[pl.ds(j, 1), :]             # [1, d_v]
        q_cols = [column(q_col_ref, j * group + h) for h in range(group)]
        q_rows = [q_row_ref[pl.ds(j * group + h, 1), :] for h in range(group)]

        # Z, whole: the normaliser q^T Z q a query head
        z = jnp.where(started, 0.0, z_ref[...]) * g + k_col * k_row
        z_out_ref[...] = z
        dens = [jnp.sum(jnp.sum(z * q_cols[h], axis=0, keepdims=True)
                        * q_rows[h], axis=-1, keepdims=True)
                for h in range(group)]            # [1, 1] each

        # S, a group of the layout at a time
        k_b = jnp.broadcast_to(k_col, (d, d_v))
        nums = [jnp.zeros((1, d_v), f32) for _ in range(group)]
        for jj in range(d // BLOCK):
            lo, hi = BLOCK * jj, BLOCK * (jj + 1)
            base = BLOCK * BLOCK * jj * (jj + 1) // 2
            kv = k_b[lo:hi] * v_row               # [8, d_v]: k_b v^T

            def block(ii, accs, factor, base=base, kv=kv):
                # the 8 values of ``a`` in block ``ii``, a register each
                for a8 in range(BLOCK):
                    a = ii * BLOCK + a8
                    at = pl.multiple_of(base + BLOCK * a, BLOCK)
                    s = jnp.where(started, 0.0, s_ref[pl.ds(at, BLOCK), :])
                    s = s * g + (factor * kq_ref[0, a]) * kv
                    s_out_ref[pl.ds(at, BLOCK), :] = s
                    accs = tuple(acc + (factor * kq_ref[1 + h, a]) * s
                                 for h, acc in enumerate(accs))
                return accs

            accs = tuple(jnp.zeros((BLOCK, d_v), f32) for _ in range(group))
            if jj:
                accs = lax.fori_loop(
                    0, jj, functools.partial(block, factor=_SQRT2), accs)
            accs = block(jj, accs, 1.0)
            for h in range(group):
                q_b = jnp.broadcast_to(q_cols[h][lo:hi], (BLOCK, d_v))
                nums[h] = nums[h] + jnp.sum(accs[h] * q_b, axis=0,
                                            keepdims=True)
        for h in range(group):
            den = jnp.where(dens[h] > 0.0, dens[h], 1.0)
            o_ref[pl.ds(h, 1), :] = nums[h] / den


@functools.partial(jax.jit, static_argnames=("interpret",))
def retention_state_update(
    s: jnp.ndarray,      # [L, R, H, rows, d_v] float32: every layer's rows
    z: jnp.ndarray,      # [L, R, H, d, d] float32
    layer: jnp.ndarray,  # int32 scalar: the layer whose rows advance
    g: jnp.ndarray,      # [R, H] float32: the forget gate, in (0, 1]
    k: jnp.ndarray,      # [R, H, d] float32
    q: jnp.ndarray,      # [R, H, G, d] float32: a kv head's query heads
    v: jnp.ndarray,      # [R, H, d_v] float32
    *,
    count: jnp.ndarray,  # [R] int32: a row with 0 is not in the tick
    fresh: jnp.ndarray,  # [R] bool: the row starts from nothing
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """``(o [R, H, G, d_v], s, z)``: for every row with ``count > 0`` its
    state in layer ``layer`` (zeros where ``fresh``) advanced in place by
    the row's token and read through the kv heads' query heads; a row with
    ``count == 0`` is not visited: its ``o`` is zero and its state
    untouched."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    _, r, nh, rows, d_v = s.shape
    d, group = k.shape[-1], q.shape[2]
    f32 = jnp.float32
    touched = count > 0
    # the touched rows, compacted to the front (kda_state_update's way)
    row = jnp.arange(r, dtype=jnp.int32)
    place = jnp.sum(touched[None, :] & (row[None, :] < row[:, None]),
                    axis=1, dtype=jnp.int32)
    order = jnp.sum(jnp.where(
        touched[None, :] & (place[None, :] == row[:, None]), row[None, :], 0),
        axis=1, dtype=jnp.int32)
    n_touched = jnp.sum(touched, dtype=jnp.int32)
    k, q, v = k.astype(f32), q.astype(f32), v.astype(f32)
    heads = q.reshape(r, nh * group, d)
    # the scalars of a kv head's step: k, then its query heads
    kq = jnp.concatenate([k[:, :, None], q], axis=2)  # [R, H, 1 + G, d]

    def of_state(shape):
        return pl.BlockSpec(
            (None, None, None) + shape,
            lambda i, j, rows_, n, layer_, fresh_: (
                layer_[0], rows_[i], j, 0, 0),
            memory_space=pltpu.VMEM)

    def per_row(shape):
        return pl.BlockSpec(
            (None,) + shape, lambda i, j, rows_, *_: (rows_[i], 0, 0),
            memory_space=pltpu.VMEM)

    s, z, o = pl.pallas_call(
        functools.partial(_kernel, group=group),
        out_shape=(jax.ShapeDtypeStruct(s.shape, s.dtype),
                   jax.ShapeDtypeStruct(z.shape, z.dtype),
                   jax.ShapeDtypeStruct((r, nh, group, d_v), f32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(jnp.maximum(n_touched, 1), nh),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),  # g, whole
                pl.BlockSpec((None, None, 1 + group, d),
                             lambda i, j, rows_, *_: (rows_[i], j, 0, 0),
                             memory_space=pltpu.SMEM),
                of_state((rows, d_v)), of_state((d, d)),
                per_row((d, nh)), per_row((d, nh * group)),  # columns
                per_row((nh, d)), per_row((nh * group, d)),  # rows
                per_row((nh, d_v)),
            ],
            out_specs=(
                of_state((rows, d_v)), of_state((d, d)),
                pl.BlockSpec((None, None, group, d_v),
                             lambda i, j, rows_, *_: (rows_[i], j, 0, 0),
                             memory_space=pltpu.VMEM)),
        ),
        input_output_aliases={6: 0, 7: 1},  # s and z, after the lists
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=min(4 * rows * d_v * 4 + (16 << 20), 100 << 20)),
        interpret=interpret,
        name="retention_state_update",  # as a profile names the call
    )(order, n_touched.reshape(1), jnp.asarray(layer, jnp.int32).reshape(1),
      fresh.astype(jnp.int32), g.astype(f32), kq, s, z,
      jnp.swapaxes(k, 1, 2), jnp.swapaxes(heads, 1, 2), k, heads, v)
    return jnp.where(touched[:, None, None, None], o, 0.0), s, z


def step(s, z, g, k, q, v):
    """The update of one token, elementwise over the leading axes (``s
    [.., rows, d_v]``, ``z [.., d, d]``, ``g [..]``, ``k [.., d]``, ``q
    [.., G, d]``, ``v [.., d_v]``): ``(o [.., G, d_v], s', z')``.  What the
    kernel computes a kv head, and ``ops/retention.retention_step``."""
    hi = lax.Precision.HIGHEST
    s = s * g[..., None, None] + phi(k)[..., :, None] * v[..., None, :]
    z = z * g[..., None, None] + k[..., :, None] * k[..., None, :]
    num = jnp.einsum("...gr,...rv->...gv", phi(q), s, precision=hi)
    den = jnp.einsum("...ga,...ab,...gb->...g", q, z, q, precision=hi)
    return num / jnp.where(den > 0.0, den, 1.0)[..., None], s, z


def retention_state_update_xla(s, z, layer, g, k, q, v, *, count, fresh):
    """The kernel's twin in plain ``jnp`` over ALL of the layer's rows
    (what ``ops/retention.retention_packed`` runs where the kernel is not
    taken)."""
    f32 = jnp.float32
    there = count > 0
    # (the casts are no-ops: the state is float32 wherever the program
    # allocates it; a test keeps it lower to show that the tolerance sees it)
    s_l = lax.dynamic_index_in_dim(s, layer, 0, keepdims=False).astype(f32)
    z_l = lax.dynamic_index_in_dim(z, layer, 0, keepdims=False).astype(f32)
    new = fresh[:, None, None, None]
    o, s1, z1 = step(jnp.where(new, 0.0, s_l), jnp.where(new, 0.0, z_l),
                     g.astype(f32), k.astype(f32), q.astype(f32),
                     v.astype(f32))
    keep = there[:, None, None, None]
    s1, z1 = jnp.where(keep, s1, s_l), jnp.where(keep, z1, z_l)
    return (jnp.where(keep, o, 0.0),
            lax.dynamic_update_index_in_dim(s, s1.astype(s.dtype), layer, 0),
            lax.dynamic_update_index_in_dim(z, z1.astype(z.dtype), layer, 0))
