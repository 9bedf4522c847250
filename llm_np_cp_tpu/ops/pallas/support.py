"""Mosaic compile-support probes for the Pallas kernels.

The kernels auto-select the interpreter off-TPU, so CPU tests always
pass — but whether Mosaic accepts a kernel's BlockSpecs is only known on
real hardware at compile time (r3 postmortem: the decode kernel's
original layout passed every interpret-mode test and was rejected by
Mosaic at first hardware compile).  These probes compile each kernel
once at tiny shapes on the live backend and cache the verdict, so
selection sites (Generator, bench) can downgrade to the XLA path with a
warning instead of dying at first dispatch.

The reference's custom kernel is launched unconditionally at import
(/root/reference/llama3.2_model.py:977-980) and simply crashes the
process if the toolchain is broken; gating is the TPU-native upgrade.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

log = logging.getLogger("llm_np_cp_tpu")

# test hook: force every probe to report failure (monkeypatched in tests)
_FORCE_FAIL = False

# Runtime degradation ledger: kernels that PASSED their startup probe but
# then faulted at dispatch mid-traffic (serve engine runtime fallback).
# A faulted kernel stays disabled for the whole process — including
# supervisor engine rebuilds — so one bad dispatch becomes one fallback,
# not a crash loop.  kernel name → reason string.
_RUNTIME_DISABLED: dict[str, str] = {}


def disable_kernel(kernel: str, reason: str) -> None:
    """Record a dispatch-time fault for ``kernel``: every subsequent
    ``kernel_error`` call reports it unavailable."""
    _RUNTIME_DISABLED.setdefault(kernel, f"faulted at dispatch: {reason}")
    log.warning(
        "Pallas kernel %s disabled for this process (%s)", kernel, reason
    )


# Every kernel name the gates know.  The probes, bench.py's ``kernels``
# child and chip_smoke.py's on-chip matrix all iterate THIS tuple.
KERNELS = (
    "softmax",
    "flash_attention",
    "decode_attention", "decode_attention_int8",
    "ragged_paged_attention", "ragged_paged_attention_int8",
    "ragged_latent_attention",
    "sparse_latent_attention",
    "sample_epilogue", "sample_epilogue_int8",
    "grouped_matmul",
    "ssm_state_update",
    "kda_state_update",
    "retention_state_update",
)

# Pool block sizes the serve path uses (cli --block-size default 64,
# bench serve cells 128).
SERVE_BLOCK_SIZES = (64, 128)


@dataclasses.dataclass(frozen=True)
class KernelShape:
    """The widths a kernel case is built at: head layout for the
    attention kernels, hidden/vocab/head layout for the epilogue."""

    name: str
    heads: int
    kv_heads: int
    head_dim: int
    hidden: int
    vocab: int
    attn_softcap: float | None = None
    final_softcap: float | None = None
    window: int | None = None
    unit_offset: bool = False
    # lm-head layout the epilogue streams: the tied [V, H] embedding
    # table (every supported family) or an untied [H, V] head
    tied: bool = True
    # latent attention (MLA): a cached row is ``[c' latent_rank | k_pe
    # head_dim]`` with no head axis, which ``ragged_latent_attention``
    # alone reads; None: K and V per kv head, every other kernel
    latent_rank: int | None = None
    # ... under a sparse-attention indexer ``(heads_I, dim_I, topk)``, which
    # ``sparse_latent_attention`` (score, select, attend) alone runs
    index: tuple[int, int, int] | None = None
    # a recurrent state ``(layers, rows, heads, groups, P, N)``, which
    # ``ssm_state_update`` alone advances; None: every other kernel
    state: tuple[int, ...] | None = None
    # a delta-rule layer's matrix state ``(layers, rows, heads, d)`` (a
    # head's ``[d, d]``), which ``kda_state_update`` alone advances
    kda_state: tuple[int, ...] | None = None
    # a power-retention layer's state ``(layers, rows, kv heads, d)`` (a kv
    # head's ``[phi_rows(d), d]`` and ``[d, d]``, read through ``heads /
    # kv_heads`` query heads), which ``retention_state_update`` alone
    # advances
    retention_state: tuple[int, ...] | None = None

    @classmethod
    def of(cls, name: str, config) -> "KernelShape":
        return cls(
            name=name, heads=config.num_attention_heads,
            kv_heads=config.num_key_value_heads, head_dim=config.head_dim,
            hidden=config.hidden_size, vocab=config.vocab_size,
            attn_softcap=config.attn_logit_softcapping,
            final_softcap=config.final_logit_softcapping,
            window=config.sliding_window,
            unit_offset=config.rms_norm_unit_offset,
            tied=config.tie_word_embeddings,
        )


# The startup probe compiles at the head layout of the smallest real
# model the serve path targets (Qwen2.5-1.5B: 12 q / 2 kv heads, so the
# group is 6 — NOT a multiple of the 8-row sublane tile — and d = 128),
# with a small hidden size and a multi-tile vocab with a ragged tail
# (300 = 2*128 + 44) so it stays a sub-second compile.  "Probe passed"
# then means the layout classes of real widths lowered through Mosaic.
# Every supported family ties its head (the [V, H] stream); the second
# probe shape covers the untied [H, V] stream with Gemma's softcap +
# unit-offset norm, which only the epilogue kernels distinguish.
PROBE_SHAPE = KernelShape(
    "probe", heads=12, kv_heads=2, head_dim=128, hidden=256, vocab=300,
)
PROBE_SHAPES = (
    PROBE_SHAPE,
    dataclasses.replace(PROBE_SHAPE, name="probe/untied", tied=False,
                        final_softcap=30.0, unit_offset=True),
)
# ... and the latent kernel's at the one published row it serves
# (Kanana-2 / DeepSeek-V3: 32 heads over rows of 512 + 64 values, stored
# 640 wide): its score sheet and its 576-of-640 columns ARE the layout
# question, so a smaller probe would answer another one.
LATENT_PROBE_SHAPE = KernelShape(
    "probe/latent", heads=32, kv_heads=1, head_dim=64, hidden=256, vocab=300,
    latent_rank=512,
)
# ... and the sparse-attention indexer's three kernels at GLM-5's layer (64
# heads over the same rows, 32 index heads of 128), keeping 256 positions so
# that the case's deep decode row selects and its chunks do not
DSA_PROBE_SHAPE = KernelShape(
    "probe/dsa", heads=64, kv_heads=1, head_dim=64, hidden=256, vocab=300,
    latent_rank=512, index=(32, 128, 256),
)
# ... and the state update's at a state small enough to make at every
# start (0.8 MiB) with what the served one has: ``N`` two rows of lanes
# wide, two groups, a whole row of heads a block.  The second is the
# benchmark's state-space cell (Falcon-H1-34B cut to 6 layers, 64 slots:
# 1.5 GiB), compiled by tests/test_kernel_lowering.py and run by the
# on-chip matrix.
STATE_PROBE_SHAPE = KernelShape(
    "probe/state", heads=12, kv_heads=2, head_dim=128, hidden=256, vocab=300,
    state=(2, 6, 8, 2, 32, 256),
)
FALCON_H1_STATE_SHAPE = dataclasses.replace(
    STATE_PROBE_SHAPE, name="falcon-h1-34b-6l", state=(6, 64, 32, 2, 128, 256))


# ... and the delta-rule state update's: a small state of the served
# head (128 x 128, a whole row of heads a block), and the benchmark's
# cell (Ling-3.0-flash cut to 7 layers: 6 KDA layers, 64 slots, 768 MiB)
KDA_PROBE_SHAPE = KernelShape(
    "probe/kda", heads=12, kv_heads=2, head_dim=128, hidden=256, vocab=300,
    kda_state=(2, 6, 4, 128),
)
LING_V3_STATE_SHAPE = dataclasses.replace(
    KDA_PROBE_SHAPE, name="ling-3.0-flash-7l-ep4", kda_state=(6, 64, 32, 128))


# ... and the power-retention state update's: two kv heads of the served
# head (8,704 x 128 + 128 x 128 float32: 4.3 MiB each) read by a group of
# five, and the benchmark's cell (Brumby-14B: 32 slots of 8 kv heads read by
# 40 query heads; TWO of its five layers, 2.2 GiB — a call moves one layer's
# rows, and the matrix holds the state twice while it compares)
RETENTION_PROBE_SHAPE = KernelShape(
    "probe/retention", heads=10, kv_heads=2, head_dim=128, hidden=256,
    vocab=300, retention_state=(2, 3, 2, 128),
)
BRUMBY_STATE_SHAPE = dataclasses.replace(
    RETENTION_PROBE_SHAPE, name="brumby-14b-5l", heads=40, kv_heads=8,
    retention_state=(2, 32, 8, 128))


def family_shapes() -> tuple[KernelShape, ...]:
    """The three families the parity suite covers, at published widths."""
    from llm_np_cp_tpu.config import GEMMA_2_2B, LLAMA_3_2_1B, QWEN_2_5_1_5B

    return (
        KernelShape.of("qwen2.5-1.5b", QWEN_2_5_1_5B),
        KernelShape.of("llama-3.2-1b", LLAMA_3_2_1B),
        KernelShape.of("gemma-2-2b", GEMMA_2_2B),
    )


def kernel_case(kernel: str, shape: KernelShape, block_size: int = 64,
                *, interpret: bool = False):
    """``kernel`` at ``shape`` → ``(make_args, run, reference)``.

    ``make_args()`` builds the seeded operands (jittable, so
    ``jax.eval_shape`` yields their avals for a deviceless compile);
    ``run(*args)`` is the Pallas kernel lowered THROUGH MOSAIC
    (``interpret=False`` unless a CPU test asks otherwise);
    ``reference(*args)`` computes the same result with the kernel's XLA
    twin.  Attention kernels and softmax return the output array; the
    epilogue returns, per row, the XLA logit of the token each
    implementation chose (equal logits = same argmax up to rounding;
    comparing token ids would flip on ties with random weights).
    ``block_size`` is the pool block length for the paged kernels and is
    ignored by the others."""
    import jax.random as jr

    from llm_np_cp_tpu.quant import dequantize_kv, quantize_kv
    from llm_np_cp_tpu.ops.attention import causal_mask, gqa_attention

    int8 = kernel.endswith("_int8")
    base = kernel.removesuffix("_int8")
    h, kh, d = shape.heads, shape.kv_heads, shape.head_dim
    scale = float(d) ** -0.5
    softcap = shape.attn_softcap
    bf16 = jnp.bfloat16

    def normals(*shapes, dtype=bf16):
        keys = jr.split(jr.PRNGKey(0), len(shapes))
        return tuple(jr.normal(k, s, dtype) for k, s in zip(keys, shapes))

    def kv_operands(kv):
        """kv → the kernel's (k, v[, k_scale, v_scale]) operand tuple."""
        if not int8:
            return (kv, kv)
        q8, sc = quantize_kv(kv)
        return (q8, q8, sc, sc)

    def kv_kwargs(ops):
        return dict(k_scale=ops[2], v_scale=ops[3]) if int8 else {}

    def kv_float(ops):
        return dequantize_kv(ops[0], ops[2], bf16) if int8 else ops[0]

    if base == "softmax":
        from llm_np_cp_tpu.ops.pallas.softmax import softmax

        return (
            lambda: normals((16, shape.vocab), dtype=jnp.float32),
            lambda x: softmax(x, interpret=interpret),
            lambda x: jax.nn.softmax(x, axis=-1),
        )

    if base == "flash_attention":
        from llm_np_cp_tpu.ops.pallas.flash_attention import flash_attention

        s = 1024
        pos = jnp.arange(s, dtype=jnp.int32)
        return (
            lambda: normals((1, s, h, d), (1, s, kh, d)),
            lambda q, kv: flash_attention(
                q, kv, kv, scale=scale, logit_softcap=softcap,
                window=shape.window, interpret=interpret),
            lambda q, kv: gqa_attention(
                q, kv, kv, causal_mask(pos[None], pos, window=shape.window),
                scale=scale, logit_softcap=softcap),
        )

    if base == "decode_attention":
        from llm_np_cp_tpu.ops.pallas.decode_attention import decode_attention

        # ragged visibility: each row sees [pad, length) of a 1024-slot
        # cache, so both block-skip bounds are exercised
        b, s = 4, 1024

        def make_args():
            q, kv = normals((b, 1, h, d), (b, s, kh, d))
            pos = jnp.arange(s, dtype=jnp.int32)[None, :]
            lengths = jnp.asarray([[1024], [700], [300], [40]], jnp.int32)
            pads = jnp.asarray([[0], [130], [5], [33]], jnp.int32)
            return (q, (pos >= pads) & (pos < lengths), *kv_operands(kv))

        return (
            make_args,
            lambda q, mask, *ops: decode_attention(
                q, ops[0], ops[1], mask, scale=scale, logit_softcap=softcap,
                interpret=interpret, **kv_kwargs(ops)),
            lambda q, mask, *ops: gqa_attention(
                q, kv_float(ops), kv_float(ops), mask[:, None, :],
                scale=scale, logit_softcap=softcap),
        )

    if base == "grouped_matmul":
        from jax import lax

        from llm_np_cp_tpu.ops.pallas import grouped_matmul as gmm

        # the routed experts' two calls (ops/moe.moe_dropless) over 70
        # sorted rows of six experts: an empty first one, groups of one
        # row, a tile + 1 and two tiles, an empty one between, and eleven
        # rows of no group; the twin is three lax.ragged_dot.  Both forms
        # of the pair: laid rows in and out (the first 70 rows of the
        # result, XLA's gather around them), and the rows moved in the
        # calls — a sorted row's token cut out of the 40 tokens' float32
        # rows, the results added by token under a weight (the other 40;
        # the last token is nobody's)
        rows, tokens, inter, tm = 70, 40, 384, 16
        hid = shape.hidden
        sizes = (0, 17, 1, 32, 0, 9)
        grouped = jnp.arange(rows)[:, None] < sum(sizes)
        token = jnp.arange(rows, dtype=jnp.int32) % (tokens - 1)
        weight = 0.25 + (jnp.arange(rows) % 5).astype(jnp.float32) / 8

        def make_args():
            x, w1, w3, w2 = normals(
                (tokens, hid), (len(sizes), hid, inter),
                (len(sizes), hid, inter), (len(sizes), inter, hid))
            thin = jnp.asarray(hid ** -0.5, bf16)
            return (x.astype(jnp.float32), w1 * thin, w3 * thin,
                    w2 * jnp.asarray(inter ** -0.5, bf16),
                    jnp.asarray(sizes, jnp.int32))

        def run(x, w1, w3, w2, sizes):
            layout = gmm.align_groups(sizes, rows, tm)
            kw = dict(act=jax.nn.silu, tm=tm, interpret=interpret)
            ys = gmm.grouped_experts(
                x.astype(bf16)[token[layout.src]], w1, w3, w2, layout, **kw)
            by_token = gmm.grouped_experts(
                x, w1, w3, w2, layout, token[layout.src], weight[layout.src],
                **kw)
            return jnp.concatenate(
                [jnp.where(grouped, ys[layout.dest], 0), by_token])

        def reference(x, w1, w3, w2, sizes):
            xs = x.astype(bf16)[token]
            gate, up = (
                lax.ragged_dot(xs, w, sizes, preferred_element_type=jnp.float32)
                for w in (w1, w3))
            hidden = jax.nn.silu(gate.astype(bf16)) * up.astype(bf16)
            ys = jnp.where(grouped, lax.ragged_dot(
                hidden, w2, sizes, preferred_element_type=jnp.float32), 0)
            return jnp.concatenate([ys, jnp.zeros(
                (tokens, hid), jnp.float32).at[token].add(weight[:, None] * ys)])

        # (jitted: the probe runs what a case gives it as it is, and op
        # by op the layout alone is fifty compiles of a third of a second
        # at every start of a server)
        return jax.jit(make_args), jax.jit(run), reference

    if base == "ssm_state_update":
        from llm_np_cp_tpu.ops.pallas import ssm_state_update as ssu

        # a tick of a layer's rows (ops/ssm.ssm_packed's first pass): two
        # of every six have no token in it, every third that has starts
        # from nothing; the twin is the two results in plain jnp
        layers, rows, nh, ng, p, n = shape.state
        layer = layers - 1
        f32 = jnp.float32

        def make_args():
            state, dtx, b, c, rate = normals(
                (layers, rows, nh, p, n), (rows, nh, p), (rows, ng, n),
                (rows, ng, n), (rows, nh), dtype=f32)
            row = jnp.arange(rows)
            count = jnp.where(row % 3 == 1, 0, 1 + row % 4).astype(jnp.int32)
            return (state, jnp.exp(-jnp.abs(rate)), dtx,
                    b * n ** -0.5, c * n ** -0.5, count,
                    (row % 3 == 0) & (count > 0))

        def flat(held, state):
            return jnp.concatenate([held.ravel(), state[layer].ravel()])

        def run(state, decay, dtx, b, c, count, fresh):
            return flat(*ssu.ssm_state_update(
                state, jnp.int32(layer), decay, dtx, b, c, count=count,
                fresh=fresh, heads=ssu.head_block(nh, ng, p, n),
                interpret=interpret))

        def reference(state, decay, dtx, b, c, count, fresh):
            there = (count > 0)[:, None, None, None, None]
            h = jnp.where(fresh[:, None, None, None], 0.0, state[layer])
            h = h.reshape(rows, ng, nh // ng, p, n)
            held = jnp.sum(h * c[:, :, None, None, :], axis=-1)
            new = (decay.reshape(rows, ng, -1, 1, 1) * h
                   + dtx.reshape(rows, ng, -1, p, 1) * b[:, :, None, None, :])
            return flat(jnp.where(there[..., 0], held, 0.0), state.at[layer].set(
                jnp.where(there, new, h).reshape(state.shape[1:])))

        return jax.jit(make_args), jax.jit(run), reference

    bs = block_size
    # the mixed tick both ragged kernels' cases attend (six tiles over
    # three rows of a 12-block table into a 40-block pool)
    n_tiles, mb_r, nbp_r = 6, 12, 40

    def ragged_tick(qt):
        """``(tables, tile_row, tile_qpos0, tile_qlen, pads)``: host
        constants (no device op, no compile of their own)."""
        return (
            jnp.asarray((np.arange(3 * mb_r) * 7 % 37 + 1).reshape(3, mb_r),
                        jnp.int32),
            jnp.asarray([0, 0, 1, 2, 2, 0], jnp.int32),
            jnp.asarray([5, 5 + qt, 9 * bs + 7, bs - 4, bs - 4 + qt, 0],
                        jnp.int32),
            jnp.asarray([qt, qt - 3, 1, qt, qt, 0], jnp.int32),
            jnp.asarray([5, bs + 2, 0], jnp.int32))

    def live_lanes(tile_qlen, qt):
        lane = jnp.arange(n_tiles * qt, dtype=jnp.int32) % qt
        return lane, lane < jnp.repeat(tile_qlen, qt)

    if base == "ragged_paged_attention":
        from llm_np_cp_tpu.ops.pallas.decode_attention import (
            RAGGED_Q_TILE,
            ragged_paged_attention,
            ragged_paged_attention_xla,
        )

        # a representative mixed tick: row 0 prefills a 2-tile chunk
        # (ragged tail), row 1 decodes one token deep into its 10th
        # block behind a whole-block pad — past the first GROUP of pages
        # a kv grid step streams (8 of block size 64, 4 of 128), so the
        # next group's copies are in flight under it, and a tile of ONE
        # live token, which takes the kernel's one-token update: a chip
        # that cannot lower that branch degrades here, at start-up —,
        # row 2 prefills its 2nd chunk across a block boundary, then a
        # dead padding tile
        qt = RAGGED_Q_TILE
        window = jnp.int32(shape.window or (1 << 30))

        # the pages in the form the serve pool stores such heads in:
        # merged [BS, K * D] where it merges them (head_dim 64)
        from llm_np_cp_tpu.serve.block_pool import merges_pages

        page = (kh * d,) if merges_pages(kh, d, int8, h // kh) else (kh, d)

        def make_args():
            q, pages = normals((n_tiles * qt, h, d), (nbp_r, bs) + page)
            return (q, *ragged_tick(qt), *kv_operands(pages))

        # dead lanes are unspecified in both implementations: zero them
        def run(q, tables, tile_row, tile_qpos0, tile_qlen, pads, *ops):
            out = ragged_paged_attention(
                q, ops[0], ops[1], tables, tile_row, tile_qpos0, tile_qlen,
                pads, window, scale=scale, logit_softcap=softcap,
                interpret=interpret, **kv_kwargs(ops))
            return jnp.where(
                live_lanes(tile_qlen, qt)[1][:, None, None], out, 0)

        def reference(q, tables, tile_row, tile_qpos0, tile_qlen, pads,
                      *ops):
            lane, live = live_lanes(tile_qlen, qt)
            out = ragged_paged_attention_xla(
                q, ops[0], ops[1], tables, jnp.repeat(tile_row, qt),
                jnp.repeat(tile_qpos0, qt) + lane, live, pads, window,
                scale=scale, logit_softcap=softcap, **kv_kwargs(ops))
            return jnp.where(live[:, None, None], out, 0)

        return make_args, run, reference

    if base in ("ragged_latent_attention", "sparse_latent_attention"):
        from llm_np_cp_tpu.ops.pallas.decode_attention import RAGGED_Q_TILE
        from llm_np_cp_tpu.ops.pallas.latent_attention import (
            ragged_latent_attention,
            ragged_latent_attention_xla,
        )
        from llm_np_cp_tpu.serve.block_pool import latent_page_width

        # the ragged case's mixed tick (a 2-tile chunk, a decode row past
        # the first group of pages, a chunk across a block boundary, a
        # dead tile) over pages of latent rows, stored as the pool stores
        # them: zeros past ``rank + rope``.  The token axis is DENSE, as
        # the tick's: a tile's tokens lie from ``tile_tok`` on, the
        # chunk's partial tile is followed at once by the decode row's
        # token, and two lanes at the end belong to no tile
        qt = RAGGED_Q_TILE
        rank = shape.latent_rank
        row = rank + d
        width = latent_page_width(row)
        tick = ragged_tick(qt)  # tables, tile_row, tile_qpos0, tile_qlen, pads
        qlen = np.asarray(tick[3])
        n_live = int(qlen.sum())
        tile_tok = jnp.asarray(
            np.where(qlen > 0, np.cumsum(qlen) - qlen, 0), jnp.int32)
        # the twin's per-token metadata of the same tick
        tok_row = np.zeros(n_live + 2, np.int32)
        tok_slot = np.zeros(n_live + 2, np.int32)
        tok_row[:n_live] = np.repeat(np.asarray(tick[1]), qlen)
        tok_slot[:n_live] = np.concatenate([
            p0 + np.arange(n) for p0, n in zip(np.asarray(tick[2]), qlen)])
        tok_live = jnp.arange(n_live + 2) < n_live

        def make_args():
            q, pool = normals((n_live + 2, h, width), (nbp_r, bs, width))
            live = jnp.arange(width) < row
            return (jnp.where(live, q, 0), jnp.where(live, pool, 0),
                    *tick[:4], tile_tok, tick[4])

        # (a score is a sum over ``row`` products of unit normals: the
        # scale keeps the softmax off one-hot, where bf16 ``p`` is exact)
        scale = float(row) ** -0.5 / 4

        def run(q, pool, tables, tile_row, tile_qpos0, tile_qlen, tile_tok,
                pads):
            return ragged_latent_attention(
                q, pool, tables, tile_row, tile_qpos0, tile_qlen, tile_tok,
                pads, scale=scale, rank=rank, interpret=interpret)

        def reference(q, pool, tables, tile_row, tile_qpos0, tile_qlen,
                      tile_tok, pads):
            out = ragged_latent_attention_xla(
                q[..., :row], pool, tables, jnp.asarray(tok_row),
                jnp.asarray(tok_slot), tok_live, pads, scale=scale, rank=rank)
            return jnp.where(tok_live[:, None, None], out, 0)

        if base == "sparse_latent_attention":
            from llm_np_cp_tpu.ops.pallas.sparse_index import (
                sparse_latent_attention,
                sparse_latent_attention_xla,
            )

            # the same tick under an indexer: index queries and head
            # weights on the dense axis, a pool of index keys beside the rows
            ih, idim, topk = shape.index
            dense_args = make_args

            def make_args():
                q_idx, w_idx, keys = normals(
                    (n_live + 2, ih, idim), (n_live + 2, ih),
                    (nbp_r, bs, idim))
                return (*dense_args(), q_idx, w_idx.astype(jnp.float32), keys)

            def run(q, pool, tables, tile_row, tile_qpos0, tile_qlen,
                    tile_tok, pads, q_idx, w_idx, keys):
                return sparse_latent_attention(
                    q, pool, q_idx, w_idx, keys, tables, tile_row, tile_qpos0,
                    tile_qlen, tile_tok, pads, scale=scale, rank=rank,
                    topk=topk, interpret=interpret)

            def reference(q, pool, tables, tile_row, tile_qpos0, tile_qlen,
                          tile_tok, pads, q_idx, w_idx, keys):
                out = sparse_latent_attention_xla(
                    q[..., :row], pool, q_idx, w_idx, keys, tables,
                    jnp.asarray(tok_row), jnp.asarray(tok_slot), tok_live,
                    pads, scale=scale, rank=rank, topk=topk)
                return jnp.where(tok_live[:, None, None], out, 0)

        return make_args, run, reference

    if base == "kda_state_update":
        from llm_np_cp_tpu.ops.pallas import kda_state_update as ksu

        # a tick of a layer's rows (ops/kda.kda_packed's first pass): two
        # of every six have no token in it, every third that has starts
        # from nothing; the twin is the same step in plain jnp
        layers, rows, nh, d = shape.kda_state
        layer = layers - 1
        f32 = jnp.float32

        def make_args():
            state, rate, k, q, v, b = normals(
                (layers, rows, nh, d, d), (rows, nh, d), (rows, nh, d),
                (rows, nh, d), (rows, nh, d), (rows, nh), dtype=f32)
            row = jnp.arange(rows)
            count = jnp.where(row % 3 == 1, 0, 1 + row % 4).astype(jnp.int32)
            unit = lambda a: a * jax.lax.rsqrt(
                jnp.sum(a * a, axis=-1, keepdims=True) + 1e-6)
            return (state, jnp.exp(-jnp.abs(rate)), unit(k),
                    unit(q) * d ** -0.5, v, jax.nn.sigmoid(b), count,
                    (row % 3 == 0) & (count > 0))

        def flat(o, state):
            return jnp.concatenate([o.ravel(), state[layer].ravel()])

        def run(state, decay, k, q, v, beta, count, fresh):
            return flat(*ksu.kda_state_update(
                state, jnp.int32(layer), decay, k, q, v, beta, count=count,
                fresh=fresh, interpret=interpret))

        def reference(state, decay, k, q, v, beta, count, fresh):
            return flat(*ksu.kda_state_update_xla(
                state, jnp.int32(layer), decay, k, q, v, beta, count=count,
                fresh=fresh))

        return jax.jit(make_args), jax.jit(run), reference

    if base == "retention_state_update":
        from llm_np_cp_tpu.ops.pallas import retention_state_update as rsu

        # a tick of a layer's rows (ops/retention.retention_packed's first
        # pass): a row of every three has no token in it, a row of every
        # three starts from nothing; the twin is the same step in plain
        # jnp.  q and k are of order 1 a channel (an RMSNorm's), the
        # output a weighted mean of values of order 1
        layers, rows, nh, d = shape.retention_state
        group = shape.heads // shape.kv_heads
        layer = layers - 1
        f32 = jnp.float32

        def make_args():
            s, z, gate, k, q, v = normals(
                (layers, rows, nh, rsu.phi_rows(d), d),
                (layers, rows, nh, d, d), (rows, nh), (rows, nh, d),
                (rows, nh, group, d), (rows, nh, d), dtype=f32)
            row = jnp.arange(rows)
            count = jnp.where(row % 3 == 1, 0, 1 + row % 4).astype(jnp.int32)
            # a state some keys have been summed into: Z positive definite
            z = jnp.einsum("lrhab,lrhcb->lrhac", z, z) / d
            return (s, z, jax.nn.sigmoid(gate), k, q, v, count,
                    (row % 3 == 0) & (count > 0))

        def flat(o, s, z):
            return jnp.concatenate(
                [o.ravel(), z[layer].ravel(), s[layer].ravel()])

        def run(s, z, gate, k, q, v, count, fresh):
            return flat(*rsu.retention_state_update(
                s, z, jnp.int32(layer), gate, k, q, v, count=count,
                fresh=fresh, interpret=interpret))

        def reference(s, z, gate, k, q, v, count, fresh):
            return flat(*rsu.retention_state_update_xla(
                s, z, jnp.int32(layer), gate, k, q, v, count=count,
                fresh=fresh))

        return jax.jit(make_args), jax.jit(run), reference

    if base == "sample_epilogue":
        from llm_np_cp_tpu.ops.norms import rms_norm
        from llm_np_cp_tpu.ops.pallas.sample_epilogue import sample_epilogue
        from llm_np_cp_tpu.quant import quantize_array

        # 5 rows exercise the sublane pad
        n, hid, v = 5, shape.hidden, shape.vocab
        tied, cap, offset = shape.tied, shape.final_softcap, shape.unit_offset

        def make_args():
            x, gamma, w = normals(
                (n, hid), (hid,), (v, hid) if tied else (hid, v))
            w = w * jnp.asarray(0.02, bf16)
            if not int8:
                return (x, gamma, w)
            qw = quantize_array(w, axis=-1 if tied else -2)
            return (x, gamma, qw["q"], qw["s"].reshape(1, -1))

        def logits(x, gamma, w, w_scale=None):
            xn = rms_norm(x, gamma, eps=1e-6, unit_offset=offset)
            lg = jnp.einsum(
                "nh,vh->nv" if tied else "nh,hv->nv", xn, w.astype(bf16),
                preferred_element_type=jnp.float32)
            if w_scale is not None:
                lg = lg * w_scale
            if cap is not None:
                lg = jnp.tanh(lg / cap) * cap
            return lg

        def run(x, gamma, w, w_scale=None):
            tok = sample_epilogue(
                x, gamma, w, w_scale=w_scale, tied=tied, eps=1e-6,
                logit_softcap=cap, unit_offset=offset, interpret=interpret)
            return logits(x, gamma, w, w_scale)[jnp.arange(n), tok]

        return (make_args, run,
                lambda *args: jnp.max(logits(*args), axis=-1))

    raise ValueError(f"unknown kernel {kernel!r}")


# |kernel - XLA twin| a correct kernel stays inside at these seeded
# inputs: bf16 operands, f32 accumulation, outputs of magnitude <= ~1
# (the AMLA rescale and the XLA softmax round p to bf16 at different
# octaves, which is the dominant term).  A kernel that lowers and then
# computes garbage on hardware is off by O(1).
KERNEL_TOLERANCE = 5e-2


def kernel_cases(shapes=None):
    """Every ``(kernel, shape, block_size)`` the on-chip matrix covers:
    all of ``KERNELS`` at the probe shapes and the three family shapes,
    the paged kernels at both serve block sizes."""
    shapes = shapes if shapes is not None else (
        *PROBE_SHAPES, LATENT_PROBE_SHAPE, DSA_PROBE_SHAPE, STATE_PROBE_SHAPE,
        FALCON_H1_STATE_SHAPE, KDA_PROBE_SHAPE, LING_V3_STATE_SHAPE,
        RETENTION_PROBE_SHAPE, BRUMBY_STATE_SHAPE, *family_shapes())
    for shape in shapes:
        for kernel in KERNELS:
            if (kernel == "ragged_latent_attention") != (
                    shape.latent_rank is not None and shape.index is None):
                continue  # latent rows and their one kernel
            if (kernel == "sparse_latent_attention") != (
                    shape.index is not None):
                continue  # ... under an indexer, and its three
            if (kernel == "ssm_state_update") != (shape.state is not None):
                continue  # a recurrent state and its one kernel
            if (kernel == "kda_state_update") != (
                    shape.kda_state is not None):
                continue  # a matrix state and its one kernel
            if (kernel == "retention_state_update") != (
                    shape.retention_state is not None):
                continue  # a power-retention state and its one kernel
            if not shape.tied and not kernel.startswith("sample_epilogue"):
                continue  # only the epilogue distinguishes head layouts
            paged = kernel.startswith("ragged_") or shape.index is not None
            for bs in SERVE_BLOCK_SIZES if paged else (None,):
                yield kernel, shape, bs


def kernel_matrix(shapes=None, *, interpret: bool = False) -> list[dict]:
    """Compile+run every ``kernel_cases`` entry through Mosaic and
    compare it against its XLA twin.  One verdict dict per case:
    ``{"kernel", "shape", "block_size", "ok", "max_err" | "error"}``.
    Needs a TPU backend (``interpret=False`` cannot lower elsewhere);
    CPU tests pass ``interpret=True`` to keep the cases themselves
    honest."""
    out = []
    for kernel, shape, bs in kernel_cases(shapes):
        rec = {"kernel": kernel, "shape": shape.name, "block_size": bs}
        try:
            make_args, run, reference = kernel_case(
                kernel, shape, bs or SERVE_BLOCK_SIZES[0],
                interpret=interpret)
            args = make_args()
            got = np.asarray(jax.jit(run)(*args), np.float32)
            want = np.asarray(jax.jit(reference)(*args), np.float32)
            rec["max_err"] = float(np.max(np.abs(got - want)))
            rec["ok"] = bool(np.isfinite(got).all()
                             and rec["max_err"] <= KERNEL_TOLERANCE)
        except Exception as e:  # noqa: BLE001 — the verdict IS the error
            rec["ok"] = False
            rec["error"] = f"{type(e).__name__}: {e}"
        out.append(rec)
    return out


@functools.lru_cache(maxsize=None)
def _probe(kernel: str, backend: str) -> str | None:
    """Compile+run `kernel` at ``PROBE_SHAPES`` on `backend`.

    Returns None on success, else the error string.  Cached per process;
    off-TPU backends return None without compiling (the kernels run the
    interpreter there, which always works).
    """
    if _FORCE_FAIL:
        return "forced failure (test hook)"
    if backend != "tpu":
        return None
    if not isinstance(jnp.zeros(()), jax.core.Tracer):
        return _compile_and_run(kernel)
    # asked while a program is being traced (ops/moe.moe_dropless under a
    # jitted forward that no engine built): a trace is its thread's, so a
    # thread of its own runs the probe eagerly
    with concurrent.futures.ThreadPoolExecutor(1) as pool:
        return pool.submit(_compile_and_run, kernel).result()


def _compile_and_run(kernel: str) -> str | None:
    own = {"ragged_latent_attention": (LATENT_PROBE_SHAPE,),
           "sparse_latent_attention": (DSA_PROBE_SHAPE,),
           "ssm_state_update": (STATE_PROBE_SHAPE,),
           "kda_state_update": (KDA_PROBE_SHAPE,),
           "retention_state_update": (RETENTION_PROBE_SHAPE,)}
    try:
        for shape in own.get(kernel, PROBE_SHAPES):
            if shape.tied or kernel.startswith("sample_epilogue"):
                make_args, run, _ = kernel_case(kernel, shape,
                                                SERVE_BLOCK_SIZES[0])
                np.asarray(run(*make_args()))
    except Exception as e:  # noqa: BLE001 — any compile/runtime error gates
        return f"{type(e).__name__}: {e}"
    return None


def ragged_kernel_name(int8_cache: bool, latent: bool = False,
                       indexer: bool = False) -> str:
    """Probe/kernel name for the mixed prefill+decode ragged kernel
    (the tick's dispatch) — THE one int8-gating rule, shared by the
    engine's gate, its runtime degradation and chip_smoke.py so they
    can't drift.  ``latent``: the pool holds latent rows, which a
    kernel of its own reads — under a sparse-attention indexer
    (``indexer``) behind the score and the selection, the three of them
    one name and one probe."""
    if latent:
        return ("sparse_latent_attention" if indexer
                else "ragged_latent_attention")
    return (
        "ragged_paged_attention_int8" if int8_cache
        else "ragged_paged_attention"
    )


def epilogue_kernel_name(int8_head: bool) -> str:
    """Probe/kernel name for the fused sampling epilogue (final norm →
    lm_head → greedy sample over vocab tiles) — same one-rule
    discipline as ``ragged_kernel_name``, shared by the serve engine's
    epilogue gate and the offline Generator so the two can't drift.
    ``int8_head``: the lm-head weight is a quant.py int8 payload."""
    return "sample_epilogue_int8" if int8_head else "sample_epilogue"


def kernel_error(kernel: str) -> str | None:
    """None if `kernel` compiles on the current default backend and has
    not been disabled by a dispatch-time fault (``disable_kernel``)."""
    disabled = _RUNTIME_DISABLED.get(kernel)
    if disabled is not None:
        return disabled
    return _probe(kernel, jax.default_backend())


def kernel_available(kernel: str) -> bool:
    return kernel_error(kernel) is None


_WARNED: set[str] = set()


def kernel_or_warn(kernel: str, fallback: str) -> str | None:
    """``kernel_error(kernel)``, logged as ONE warning a process that
    names what runs in the kernel's place (a caller inside a layer asks
    once a layer and program)."""
    err = kernel_error(kernel)
    if err is not None and kernel not in _WARNED:
        _WARNED.add(kernel)
        log.warning(
            "Pallas kernel %s is unavailable on %s (%s); falling back to %s",
            kernel, jax.default_backend(), err, fallback)
    return err


def gate_attn_impl(impl: str, *, int8_cache: bool = False) -> str:
    """Downgrade a Pallas attn impl to 'xla' if Mosaic rejects it.

    Logs once per process per kernel (lru_cache on _probe); returns the
    impl to actually use.
    """
    kernel = {
        "flash": "flash_attention",
        "ring": None,  # ring uses the XLA path per shard; nothing to gate
        "flash_decode": (
            "decode_attention_int8" if int8_cache else "decode_attention"
        ),
        "xla": None,
    }.get(impl)
    if kernel is None:
        return impl
    err = kernel_error(kernel)
    if err is None:
        return impl
    log.warning(
        "Pallas kernel %s failed to compile on %s (%s); falling back to "
        "the XLA attention path",
        kernel, jax.default_backend(), err,
    )
    return "xla"
