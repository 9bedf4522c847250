"""ServeEngine: jit-stable continuous-batching serving over the block pool.

One engine owns params + ONE jitted program, ``mixed_step``, and drives
it from a host-side scheduler tick loop: one dispatch a tick.  A packed
ragged batch of prefill chunk slices and decode rows runs through a
single layer scan that CARRIES the pool (flat over layer and block) and
scatters every token's K/V into it in place (no temp prefill cache and
no copy program — shared prefix blocks are attended in place through
the block table; no slab sliced out of the pool, no copy of the pool
back), and attends via ``ragged_paged_attention`` over the carried
pool, the layer's offset added to the block tables.  Which attention
runs is the kernel probe's verdict (ops/pallas/support.py): the Pallas
kernel, or its XLA twin ``ragged_paged_attention_xla`` with a warning
that says so.  No flag names a tick.

Every shape is static: the step's operand is ONE packed int32 buffer
(``mixed_operand_layout``), pool = ``num_blocks``, table width =
``max_blocks_per_seq``.  Inactive lanes point their tables at the
reserved scratch block 0 and are fully masked, so the step runs
branchless; their outputs are discarded host-side.  Sampling happens
in-graph (keys derived from per-request seeds + content position, so a
preempted request resumes its exact RNG stream).

The scheduler's token-budget planner (``Scheduler.plan_tick``)
co-schedules chunked prefill with decode under ``tick_token_budget``
tokens per tick — decode rows first, so a long prefill can no longer
stall the decoding batch (the PR-5 trace finding); then a chunk for
every mid-prefill row, then the rest of the budget for the oldest
prompt, so a row's slice of one tick is as long as the budget allows
(the window class's rings are sized for it).  The step's token
axis is DENSE (one lane a token, ``dense_width``); the 8-lane query
tiles the ragged kernel wants (``packed_width`` lanes) exist only inside
attention, between two row gathers.  A program is the pair, and the set
is small and fixed (``mixed_buckets``: today's doubling ladder of tile
widths, each with ONE dense width — its capacity under the token budget
— plus one program for the steady decode tick, ``max_slots`` one-tile
rows at the width of their tokens), so the step compiles once per
program and NEVER per tick, whatever the prefill:decode row mix
(compile-counter lint), and warm-up pays for one program more than the
ladder has rungs.  Where the pool's pages have a WIDE query tile
(``ragged_wide_tile``: merged float pages) the rungs past ``max_slots``
one-tile rows — the ones only a tick with a prompt chunk runs — lay a
prompt segment's tokens 64 (or 32, 16) to a tile, which walks its row's
pages once where eight tiles of 8 would each stream them again
(``_wide_program``, ``_pack_mixed``); the programs are the same set.  ``/metrics`` counts the dense lanes dispatched
(``mixed_dense_lanes_total``) beside the tokens in them
(``mixed_tokens_total``).

Prefix sharing (``enable_prefix_cache``): at admission the prompt's
fully-filled leading blocks are looked up in a refcounted registry
(serve/prefix_cache.py); hits are claimed into the request's block table
and their prefill chunks are SKIPPED — they consume no tick budget and
are attended in place.

Mesh-sharded serving (``mesh_plan=MeshPlan(model=N)``): the engine
builds a ``jax.sharding.Mesh`` over its device slice, tensor-parallels
the params via ``parallel/sharding.param_specs`` and the pool slabs via
``paged_kv_specs`` (kv-head-partitioned K/V pages, int8 scale pages
included), and commits every per-tick operand — block tables above all
— FULLY REPLICATED, so the scalar-prefetch kernel walks per-shard-
identical indices over its head-slice of the slabs.  With kv heads
divisible by the model axis the Pallas ``ragged_paged_attention``
kernel runs UNMODIFIED inside ``shard_map`` (``_shard_attn``);
otherwise (the TP+GQA hard part) the engine holds the partitionable XLA
path.  Step in-avals are pinned — replicated operands,
``with_sharding_constraint`` on every returned ``PagedKV`` — so each
program still compiles once per shape bucket and NEVER per tick under
the mesh.  The engine is TP-only by design; data parallelism is N
engine replicas behind a prefix-affinity router (serve/replica.py),
each on its own mesh slice.

Speculative serving (``spec_k=K``): per-request HOST-SIDE prompt-lookup
draft streams (serve/spec.py) propose up to K tokens per tick, packed
as ragged verify slices of width ≤ K+1 into the same one dispatch; the
step samples at every packed position with the deterministic (seed,
content-pos) keys, so the accept walk emits the longest draft prefix
matching the samples plus the first correction — token-identical to
plain decode, up to K+1 tokens per HBM sweep.  Requests opt in
per-submit (``speculative=True``) and fall back per-request when
rolling acceptance collapses; the verify lanes are a static
[slots, K+1] extension of the step, so zero-recompiles survives.
"""

from __future__ import annotations

import collections
import contextlib
import itertools
import math
import time
from functools import partial
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from llm_np_cp_tpu.config import STATE_ONLY_OPS, ModelConfig
from llm_np_cp_tpu.generate import IncrementalDetok
from llm_np_cp_tpu.models.transformer import (
    SCOPE_ATTN_GLOBAL,
    SCOPE_ATTN_WINDOW,
    SCOPE_CONV,
    SCOPE_EMBED,
    SCOPE_KDA_PROJ,
    SCOPE_RETENTION_PROJ,
    SCOPE_SSM_PROJ,
    SCOPE_TAIL,
    attention_block,
    conv_block,
    embed_inputs,
    experts_block,
    ff_block,
    final_logits,
    input_norm,
    kda_block,
    latent_attention_block,
    retention_block,
    run_decoder_layer,
    scan_group,
    scan_unroll,
    ssm_block,
)
from llm_np_cp_tpu.ops import kda as kda_ops
from llm_np_cp_tpu.ops import retention as retention_ops
from llm_np_cp_tpu.ops import ssm as ssm_ops
from llm_np_cp_tpu.ops.activations import ACT2FN
from llm_np_cp_tpu.ops.moe import (
    SCOPE_MOE_EXPERTS,
    expert_row_tile,
    expert_rows_in_call,
)
from llm_np_cp_tpu.ops.rope import rope_cos_sin
from llm_np_cp_tpu.ops.sampling import Sampler
from llm_np_cp_tpu.quant import quantize_kv
from llm_np_cp_tpu.serve.block_pool import (
    BlockPool,
    PagedKV,
    window_blocks_per_slot,
)
from llm_np_cp_tpu.serve.faults import FaultInjected, FaultInjector
from llm_np_cp_tpu.serve.metrics import ServeMetrics
from llm_np_cp_tpu.serve.prefix_cache import prefix_block_keys
from llm_np_cp_tpu.serve.request_log import request_record
from llm_np_cp_tpu.serve.scheduler import (
    TTFT_COUNTS,
    TTFT_STAMPS,
    QueueFull,
    Request,
    RequestState,
    Scheduler,
    TenantThrottled,
)
from llm_np_cp_tpu.serve.telemetry import mixed_tick_kv_read
from llm_np_cp_tpu.serve.tracing import TraceRecorder, gen_trace_id
from llm_np_cp_tpu.utils.runtime import compile_cache_bypassed

Params = dict[str, Any]

# Shared no-op context for the tracing-off branch of the profiler-scope
# hooks: ``nullcontext()`` per tick would be a per-tick allocation on
# the hot path — exactly what the tracing-off discipline forbids.
_NULL_CTX = contextlib.nullcontext()

# Kinds of an item ``ServeEngine._owed`` holds: a token, the token that
# ends a prefill (the request's track turns to ``decode`` after it), a
# terminal reason.
_OWED_TOKEN, _OWED_FIRST, _OWED_FINISH = 0, 1, 2


def _ceil_to(n: int, g: int) -> int:
    return -(-n // g) * g


def _stop_hits(samples: jnp.ndarray,
               stop_tokens: tuple[int, ...]) -> jnp.ndarray:
    """[.., W] bool — which sampled tokens are stop tokens (the static
    stop set is tiny, so this is a handful of fused compares)."""
    hit = jnp.zeros(samples.shape, jnp.bool_)
    for t in stop_tokens:
        hit = hit | (samples == jnp.int32(t))
    return hit


def _pack_sync(
    samples: jnp.ndarray,       # [R, W] int32 sampled tokens
    stop_hit: jnp.ndarray,      # [R, W] bool
    accept: jnp.ndarray,        # [R] int32 leading draft matches
) -> jnp.ndarray:
    """The one-fetch host-sync contract: pack the tick's whole outcome
    into ONE int32 array so ``host_sync`` is a single device→host
    transfer.  Columns: ``[0:W)`` the sampled tokens, ``W`` a stop-hit
    bitmask over those columns, ``W+1`` the advance watermark (tokens
    the accept walk will emit this tick, pre-budget: up to the first
    stop inside the accepted prefix, else accept+1), ``W+2`` the
    accept length.

    The accept walk reads the token and accept columns; finish/budget
    semantics stay host-side in ``_accept_finish`` (one source of
    truth), so the stop-mask and watermark columns are currently
    redundant with it — they ride along because the packed row IS the
    contract (a consumer that wants the tick outcome without replaying
    host logic — journal watermark batching, a future async deliver —
    reads it from the same fetch), and three extra fused int32 ops per
    row cost nothing next to the transfer they share."""
    w = samples.shape[1]
    bits = jnp.asarray([1 << j for j in range(w)], jnp.int32)
    stop_mask = jnp.sum(
        jnp.where(stop_hit, bits[None, :], 0), axis=1, dtype=jnp.int32
    )
    kcol = jnp.arange(w, dtype=jnp.int32)[None, :]
    cand = stop_hit & (kcol <= accept[:, None])
    advance = jnp.where(
        jnp.any(cand, axis=1),
        jnp.argmax(cand, axis=1).astype(jnp.int32) + 1,
        accept + 1,
    )
    return jnp.concatenate(
        [samples, stop_mask[:, None], advance[:, None],
         accept[:, None]], axis=1,
    )


# the sections of the packed operand that say where a token lies in a pool
# and which attention tile it is in: what a pool with no page class leaves out
PAGE_SECTIONS = ("tok_blk", "tok_off", "tok_slot", "tok_lane", "lane_tok",
                 "tile_row", "tile_qpos0", "tile_qlen", "tables")


def mixed_operand_layout(
    t_w: int, d_w: int, q_tile: int, max_slots: int, max_blocks: int,
    spec_w: int, window_blocks: int = 0,
) -> tuple[dict[str, tuple[int, tuple[int, ...]]], int]:
    """The unified step's ONE host-built operand, stated once: an int32
    vector whose sections are the packed batch — ``{section: (offset,
    shape)}`` and the vector's length.  The batch has TWO widths, and
    the pair is the step's program.  ``d_w`` is the step's token axis,
    DENSE: one lane a token, segments consecutive with no alignment;
    every ``[D]`` token-level section, the embedding, qkv, the K/V
    write, o_proj, the MLP and the tail's ``last_idx`` live on it.
    ``t_w`` is the width INSIDE attention, where the ragged kernel wants
    every segment aligned to ``q_tile`` so that a query tile belongs to
    one row: ``[T/q_tile]`` tile metadata, and the two index maps that
    tie the axes — ``lane_tok [T]``, the dense token each tile lane
    reads (a dead lane reads token 0 and stays masked by ``tile_qlen``),
    and ``tok_lane [D]``, the tile lane each token's result comes back
    from.  ``[max_slots, ..]`` row-level sections follow.
    ``_pack_mixed`` writes through it, the jitted step slices by it
    (``split_mixed_operands``), so host and device cannot drift.  Two
    sections are not int32 values: ``tok_live`` is a bool written 0 / 1,
    ``seeds`` the bits of a uint32.  The length grows with both widths:
    a program is one aval and one compile, and an engine's programs have
    lengths of their own (``mixed_operand_program`` inverts it).  A pool
    with a window class (``window_blocks`` a slot > 0) adds the SECOND
    table as one more section: ``wtables``, every row's chain of the
    window class for this tick, and ``wfirst``, the logical block its
    column 0 is (the chain's first block is not position 0); where a
    token is written in that class follows from the two in-graph.  A pool
    with one class has neither section: its operand is what it was.  A pool
    with NO page class (``max_blocks`` 0: no layer of the stack has pages,
    serve/block_pool.py) has no block, no cache slot and no attention tile
    to speak of: its operand is the dense token axis and the rows' sections
    (``PAGE_SECTIONS`` are left out), and its programs differ by ``d_w``
    alone."""
    nt = t_w // q_tile
    shapes = {
        "tokens": (d_w,),      # packed input ids
        "positions": (d_w,),   # content positions (RoPE)
        "tok_blk": (d_w,),     # pool block per token
        "tok_off": (d_w,),     # in-block slot per token
        "tok_row": (d_w,),     # owning engine row
        "tok_slot": (d_w,),    # cache slot per token
        "tok_live": (d_w,),    # 0 = padding of the dense axis
        "tok_lane": (d_w,),    # each token's lane of the tiled axis
        "lane_tok": (t_w,),    # each tile lane's token
        "tile_row": (nt,), "tile_qpos0": (nt,), "tile_qlen": (nt,),
        "tables": (max_slots, max_blocks),  # scratch-0 padded
        "pads": (max_slots,),
        "last_idx": (max_slots, spec_w),    # dense sample indices
        "sample_pos": (max_slots, spec_w),  # content position of each
        "seeds": (max_slots,),
        "verify_len": (max_slots,),         # live sample slots per row
    }
    if window_blocks:
        shapes["wtables"] = (max_slots, window_blocks)  # scratch-0 padded
        shapes["wfirst"] = (max_slots,)  # logical block of column 0
    if not max_blocks:
        for name in PAGE_SECTIONS:
            del shapes[name]
    layout, size = {}, 0
    for name, shape in shapes.items():
        layout[name] = (size, shape)
        size += math.prod(shape)
    return layout, size


def split_mixed_operands(ops: Any, layout: dict) -> dict[str, Any]:
    """The sections of a packed operand by name.  Of a host array they
    are writable views (``seeds`` seen as the uint32 it carries); of a
    traced one static slices, with ``tok_live`` back as a bool and
    ``seeds`` as uint32 — the values the 16 separate operands had."""
    sec = {
        name: ops[off:off + math.prod(shape)].reshape(shape)
        for name, (off, shape) in layout.items()
    }
    if isinstance(ops, np.ndarray):
        sec["seeds"] = sec["seeds"].view(np.uint32)
    else:
        sec["seeds"] = lax.bitcast_convert_type(sec["seeds"], jnp.uint32)
        sec["tok_live"] = sec["tok_live"] != 0
    return sec


def mixed_operand_program(
    size: int, programs: Sequence[tuple[int, int]], *geometry: int,
) -> tuple[int, int]:
    """The ``(t_w, d_w)`` among a step's ``programs`` whose operand is
    ``size`` words long: what the jitted step, told nothing but its
    operand's aval, lays it out by.  Two widths do not follow from one
    length in general, so the engine gives its programs lengths of
    their own when it chooses them (``_make_buckets``)."""
    found = [p for p in programs
             if mixed_operand_layout(*p, *geometry)[1] == size]
    if len(found) != 1:
        raise ValueError(
            f"{size} words are the packed operand of {found or 'none'} "
            f"among the step's programs {tuple(programs)} (q_tile, "
            f"max_slots, max_blocks, spec_w = {geometry})")
    return found[0]


def _roofline_targs(tel: dict) -> dict:
    """The roofline slice of a tick's trace args (callers hold the
    tracer guard): what tools/summarize_trace.py's roofline section and
    a Perfetto tick click read."""
    return {
        "roofline_gbps": round(tel["achieved_gbps"], 3),
        "roofline_util": round(tel["roofline_util"], 6),
        "mfu": round(tel["mfu"], 6),
        "device_time_s": round(tel["device_time_s"], 6),
        "kv_read_bytes": int(tel["kv_read_bytes"]),
        "kv_write_bytes": int(tel["kv_write_bytes"]),
        "weight_bytes": int(tel["weight_bytes"]),
    }


def _pool_is_row_major(pages: PagedKV) -> bool:
    """Whether the device keeps every array of the pool in the order the
    paged-attention kernels read it: dimensions major to minor as the
    shape lists them.  A TPU lays an array out by its own rule, and the
    rule permutes the dimensions of a ``[.., BS, K, D]`` array whose
    minor two would waste most of a tile: ``D`` under 128 (which is why
    a float pool of such heads is allocated merged, ``[.., BS, K * D]``:
    serve/block_pool.py), one bf16 or up to two int8 kv heads on the
    chip, and every ``[.., BS, K]`` scale page (compiled for a described
    v5e: PERF.md §4).  A kernel cannot read such a pool where it lies,
    so the unified step then keeps one layer's slab, not the pool, as
    the unit it relays out."""
    return all(
        getattr(a.format.layout, "major_to_minor", None)
        == tuple(range(a.ndim))
        for a in pages.all_arrays()
    )


def worst_case_slots(prompt_len: int, max_new_tokens: int, chunk: int) -> int:
    """Peak cache slots a request can occupy over its whole lifetime,
    including re-prefills after preemption.

    A re-prefill with ``g`` tokens already generated left-pads the
    content ``p+g`` to whole chunks and the remaining ``m-g`` decode
    steps extend from there, so the peak is
    ``max_g ceil_to(p+g, chunk) + (m-g)`` over ``0 <= g < m``.  That
    maximum is either the uninterrupted path (g=0) or just past a chunk
    boundary (``p+g ≡ 1 mod chunk``), where it equals
    ``p + m + chunk - 1``.  One definition shared by the engine's
    admission check and the pool sizing in bench.py / the serve-bench
    CLI — three hand-rolled copies diverged here once already.
    """
    p, m = prompt_len, max_new_tokens
    worst = _ceil_to(p, chunk) + m
    g_cross = (1 - p) % chunk or chunk  # smallest g>0 with p+g ≡ 1 (mod chunk)
    if g_cross <= m - 1:
        worst = max(worst, p + m + chunk - 1)
    return worst


def pool_geometry(
    prompt_len: int,
    max_new_tokens: int,
    slots: int,
    block_size: int,
    prefill_chunk: int | None = None,
    spare_blocks: int = 2,
) -> tuple[int, int, int]:
    """Size a pool for a worst-case trace: ``(blocks_per_seq, num_blocks,
    max_seq_len)``.

    The ONE sizing recipe shared by the serve-bench CLI and
    bench.run_serve_config (their hand-rolled copies diverged once
    already): every slot can hold a worst-case request (incl. preemption
    re-prefills, see worst_case_slots) plus ``spare_blocks`` of headroom
    for the scratch block and the scheduler's decode reserve.
    ``prefill_chunk=None`` means the engine default (``block_size``).
    """
    chunk = prefill_chunk or block_size
    worst = worst_case_slots(prompt_len, max_new_tokens, chunk)
    blocks_per_seq = -(-worst // block_size)
    num_blocks = slots * blocks_per_seq + spare_blocks
    return blocks_per_seq, num_blocks, blocks_per_seq * block_size


class ServeEngine:
    def __init__(
        self,
        params: Params,
        config: ModelConfig,
        *,
        sampler: Sampler | None = None,
        stop_tokens: tuple[int, ...] = (),
        max_slots: int = 4,
        num_blocks: int = 64,
        block_size: int = 64,
        max_seq_len: int = 1024,
        prefill_chunk: int | None = None,
        cache_dtype: jnp.dtype = jnp.bfloat16,
        enable_prefix_cache: bool = False,
        max_queue: int | None = None,
        tokenizer: Any = None,
        clock: Callable[[], float] = time.perf_counter,
        fault_injector: FaultInjector | None = None,
        tracer: TraceRecorder | None = None,
        mixed_step: str = "auto",
        sample_epilogue: str = "auto",
        tick_token_budget: int | None = None,
        mesh_plan: Any = None,
        mesh_devices: list | None = None,
        journal: Any = None,
        request_log: Any = None,
        sentinel: Any = None,
        actions: Any = None,
        telemetry: Any = None,
        weights_version: int = 0,
        host_tier: Any = None,
        tenants: Any = None,
        spec_k: int = 0,
        spec_ngram: int = 3,
        spec_min_accept: float = 0.1,
        spec_window: int = 64,
        steps_from: "ServeEngine | None" = None,
    ) -> None:
        # ``mixed_step`` names no tick any more: there is one.  The
        # keyword stays only until the three builder-run scripts under
        # benchmark/ stop passing it (ROADMAP W8); "auto" and "on" are
        # the same thing
        if mixed_step == "off":
            raise ValueError(
                "mixed_step 'off' named the phase-split tick, which PR 46 "
                "deleted: the engine has one tick (the unified ragged "
                "tick); drop the keyword")
        if mixed_step not in ("auto", "on"):
            raise ValueError(
                f"mixed_step must be 'auto' or 'on' (the same thing), got "
                f"{mixed_step!r}"
            )
        if sample_epilogue not in ("auto", "on", "off"):
            raise ValueError(
                f"sample_epilogue must be 'auto', 'on' or 'off', got "
                f"{sample_epilogue!r}"
            )
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        if spec_k > 30:
            # the one-fetch packed sync carries a per-row stop-hit
            # BITMASK over the spec_k+1 sample columns in one int32
            raise ValueError(
                f"spec_k must be <= 30 (the packed host-sync stop mask "
                f"is an int32 bitmask over spec_k+1 columns), got {spec_k}"
            )
        if spec_k and spec_ngram < 2:
            # fail at construction, not at the first draft tick inside
            # the supervised tick thread (DraftState requires
            # ngram_min <= ngram_max and its lookup floor is 2)
            raise ValueError(
                f"spec_ngram must be >= 2, got {spec_ngram}"
            )
        if host_tier is not None and not enable_prefix_cache:
            raise ValueError(
                "host_tier requires enable_prefix_cache=True: the tier "
                "is keyed by the prefix cache's chained content hashes"
            )
        if config.carries_state:
            # a recurrent state (a conv layer's history, a state-space
            # mixer's) is a function of the WHOLE sequence so far and the
            # engine keeps no snapshot of it: whatever skips prefill,
            # rolls tokens back, restores blocks from elsewhere or cuts
            # the hidden dimension over chips is refused here, by the flag
            # that asked for it
            refused = [
                (enable_prefix_cache, "--prefix-cache (enable_prefix_cache): "
                 "a prefix hit skips the prefill that builds the state"),
                (host_tier is not None, "--kv-tier host (host_tier): blocks "
                 "restored from the host tier carry no state"),
                (spec_k > 0, "--spec-k (spec_k): rejected draft tokens "
                 "cannot be rolled back out of the state"),
                (mesh_plan is not None and mesh_plan.model > 1,
                 "--mesh model>1 (mesh_plan): the state and the mixer / "
                 "conv / expert weights have no sharding rule"),
            ]
            for hit, why in refused:
                if hit:
                    raise ValueError(
                        f"model_type {config.model_type!r} has "
                        f"{config.state_kind} layers "
                        f"with a recurrent state; refused: {why}")
        if config.is_latent:
            # a latent pool holds one row a token and layer, no K and V
            # per head: what reads, moves, quantizes or cuts K/V pages by
            # head has no rule for it yet, and is refused here by the
            # flag that asked for it (nothing of it is silently wrong)
            refused = [
                (host_tier is not None, "--kv-tier host (host_tier): the "
                 "tier spills and restores K and V pages"),
                (enable_prefix_cache, "--prefix-cache (enable_prefix_cache): "
                 "shared latent blocks are untested"),
                (spec_k > 0, "--speculative-serve / --spec-k (spec_k): the "
                 "verifier's lanes are untested on latent pages"),
                (jnp.dtype(cache_dtype) == jnp.int8, "--cache-dtype int8 "
                 "(cache_dtype): a latent row has no per-head scale"),
                (mesh_plan is not None and mesh_plan.model > 1,
                 "--mesh model>1 (mesh_plan): a latent row has no head "
                 "axis to cut, and the expert share no sharding rule"),
            ]
            for hit, why in refused:
                if hit:
                    raise ValueError(
                        f"model_type {config.model_type!r} keeps a latent "
                        f"(compressed) KV cache; refused: {why}")
        if config.two_page_classes:
            # window layers live in a second, bounded page class, whatever
            # the shape of their pages (serve/block_pool.py): what
            # shares, restores, quantizes or cuts the ONE class there was
            # has no rule for two yet, and is refused here by the flag
            # that asked for it
            refused = [
                (enable_prefix_cache, "--prefix-cache (enable_prefix_cache): "
                 "a shared prefix has no window blocks to hand a new slot"),
                (spec_k > 0, "--speculative-serve / --spec-k (spec_k): a "
                 "rejected draft cannot be rolled back out of a ring whose "
                 "blocks it recycled"),
                (host_tier is not None, "--kv-tier host (host_tier): the "
                 "tier spills and restores blocks of one class"),
                (jnp.dtype(cache_dtype) == jnp.int8, "--cache-dtype int8 "
                 "(cache_dtype): int8 pages of two classes are untested"),
                (mesh_plan is not None and mesh_plan.model > 1,
                 "--mesh model>1 (mesh_plan): two kv-head counts have no "
                 "sharding rule, nor has the expert share"),
            ]
            for hit, why in refused:
                if hit:
                    raise ValueError(
                        f"model_type {config.model_type!r} keeps window "
                        f"layers in a page class of their own; refused: {why}")
        from llm_np_cp_tpu.ops.pallas.support import (
            kernel_or_warn,
            ragged_kernel_name,
        )

        # set-up as spans (cat "setup"), with a tracer: the build whole,
        # and as its children what can take time in it — kernel probes
        # (a compile each, the first time a process asks), placing the
        # weights on a mesh, allocating the pool
        t_build = tracer.now_us() if tracer is not None else -1.0
        int8_cache = jnp.dtype(cache_dtype) == jnp.int8
        # -- mesh-sharded mode (ROADMAP item 1): params tensor-parallel
        # over "model" via param_specs, pool slabs kv-head-partitioned
        # via paged_kv_specs, block tables / per-tick operands committed
        # REPLICATED so every jitted step's in-avals (shardings included)
        # are identical tick after tick — zero recompiles under the mesh
        # is the same static-shape contract, extended to placement.  The
        # engine is TP-only by design: data parallelism is N engine
        # replicas behind a router (serve/replica.py), each on its own
        # mesh slice, not a batch axis inside one engine.
        self.mesh_plan = mesh_plan
        self._mesh_devices = mesh_devices
        self.mesh = None
        self._rep_sharding = None
        self._pool_shardings = None
        self._kv_sharded = False
        # model=1 with an explicit device slice is the DP-without-TP
        # placement: a one-device mesh pins this replica's params, pool
        # and operands onto ITS chip instead of the process default
        if mesh_plan is not None and (
            mesh_plan.num_devices > 1 or mesh_devices is not None
        ):
            from jax.sharding import NamedSharding, PartitionSpec as P

            from llm_np_cp_tpu.parallel.sharding import (
                kv_heads_shardable,
                make_mesh,
                paged_kv_specs,
                shard_params,
                to_shardings,
            )

            for axis in ("data", "seq", "pipe", "expert"):
                if getattr(mesh_plan, axis) != 1:
                    raise ValueError(
                        f"ServeEngine meshes are tensor-parallel only "
                        f"(model axis); got {axis}={getattr(mesh_plan, axis)}"
                        " — use serve/replica.py ReplicaSet for data "
                        "parallelism"
                    )
            mesh_plan.validate(config)
            self.mesh = make_mesh(mesh_plan, mesh_devices)
            t_place = tracer.now_us() if tracer is not None else -1.0
            params = shard_params(params, config, mesh_plan, self.mesh)
            if tracer is not None:
                tracer.complete("place_params", t_place, cat="setup")
            self._rep_sharding = NamedSharding(self.mesh, P())
            self._kv_sharded = kv_heads_shardable(config, mesh_plan)
            self._pool_shardings = to_shardings(
                self.mesh, paged_kv_specs(config, mesh_plan,
                                          quantized=int8_cache)
            )
        # -- which attention the tick runs: the ragged Pallas kernel where
        # its probe passes, else its XLA twin (said once a process, by a
        # warning) — chosen from what the probe observes, as
        # ``expert_row_tile`` and ``state_update_heads`` choose theirs.
        # ``mixed`` stays as an attribute because scripts read it
        # (chip_smoke.py, benchmark/tick_memory.py); it is always True
        self.mixed = True
        t_probe = tracer.now_us() if tracer is not None else -1.0
        err = kernel_or_warn(
            ragged_kernel_name(int8_cache, latent=config.is_latent,
                               indexer=config.has_indexer),
            "ragged_paged_attention_xla in the unified tick")
        if tracer is not None:
            tracer.complete("probe.ragged_attn", t_probe, cat="setup",
                            args={"ok": err is None})
        self.ragged_attn_impl = "pallas" if err is None else "xla"
        # -- speculative serving (draft-then-verify in the unified tick):
        # per-request host-side prompt-lookup draft streams propose up to
        # spec_k tokens; the mixed step packs each speculating request as
        # a ragged verify slice of width <= spec_k+1 and samples at EVERY
        # packed position with the (seed, content-pos) keys, so the
        # longest draft prefix matching those samples is accepted and the
        # stream stays token-identical to plain decode.  spec_k fixes the
        # verify-lane width of the compiled step ([R, spec_k+1] sample
        # operands), so it is an engine build parameter; requests opt in
        # per-submit and fall back per-request when rolling acceptance
        # collapses.
        self.spec_k = spec_k
        self.spec_ngram = spec_ngram
        self.spec_min_accept = spec_min_accept
        self.spec_window = spec_window
        # per-request draft streams (serve/spec.DraftState) by req_id;
        # entries leave with the request (finish/abort), rebuilt lazily
        # after recovery from prompt + generated
        self._draft_states: dict[int, Any] = {}
        if (
            self.mesh is not None
            and self.mesh_plan.model > 1 and not self._kv_sharded
        ):
            # replicated kv heads under real TP: no shard_map harness for
            # the ragged kernel — the XLA ragged attention partitions
            # under GSPMD (one-device placement meshes keep the kernel)
            self.ragged_attn_impl = "xla"
        # seeded chaos schedule (serve/faults.py); None = every injection
        # point is a single is-None check (zero overhead)
        self.faults = fault_injector
        # request/tick trace recorder (serve/tracing.py); None = every
        # hook is a single is-None check, same discipline as faults
        # (pinned by tools/compile_counter.assert_tracing_hooks_guarded)
        self.tracer = tracer
        # the unified tick's open ``serve.<phase>`` profiler annotation
        # (_phase_mark); only ever set while a tracer is attached
        self._phase_ann: Any = None
        # durable request journal (serve/journal.py): admissions,
        # per-tick delivery watermarks, and terminals go to an fsync'd
        # file a restarted PROCESS replays through recover(); same
        # is-None zero-overhead discipline as faults/tracer
        self.journal = journal
        # canonical request log (serve/request_log.py): one wide-event
        # JSON line per terminal, written off the tick thread; same
        # is-None zero-overhead discipline
        self.request_log = request_log
        # tick anomaly sentinel (serve/slo.py TickSentinel): rolling
        # per-phase EWMA baselines over the tick-phase slices; rides
        # the tracer's phase timestamps, so it observes only when a
        # tracer is attached.  Same is-None discipline
        self.sentinel = sentinel
        # lifecycle auto-actions (serve/lifecycle.ActionPolicy): the
        # sentinel's host_sync verdicts and the SLO burn rate feed it
        # once per tick; its shed-prefill verdict caps the planner's
        # budget and its shed-load verdict flips HTTP admission to
        # 503-first.  Same is-None zero-overhead discipline
        self.actions = actions
        # device roofline telemetry (serve/telemetry.TelemetryModel):
        # an analytic per-tick byte/FLOP bill combined with the
        # measured dispatch→host-sync wall → achieved GB/s vs the HBM
        # roofline, an MFU estimate, and per-request cost attribution.
        # Host-side arithmetic only — attaching it adds zero dispatches
        # and zero recompiles (compile-counter telemetry section).
        # Same is-None zero-overhead discipline as faults/tracer
        self.telemetry = telemetry
        # multi-tenant accounting ledger (serve/tenants.TenantLedger):
        # per-tenant requests/tokens/cost/SLO folded in at terminals,
        # the fairness sort for plan_tick, and the per-tenant in-flight
        # cap.  Host-side bookkeeping over existing tick outputs — zero
        # dispatches, zero host syncs, zero recompiles.  Same is-None
        # zero-overhead discipline as faults/tracer
        self.tenants = tenants
        # which checkpoint these params came from: stamped onto every
        # request at admission (journal/request-log carry it), bumped
        # by a rolling upgrade's clone_fresh(params=..., ...)
        if weights_version < 0:
            raise ValueError(
                f"weights_version must be >= 0, got {weights_version}"
            )
        self.weights_version = int(weights_version)
        # reason string once the tick faulted at dispatch and fell back
        # to its XLA twins (``_degrade_mixed``; None = healthy)
        self.decode_degraded: str | None = None
        self.params = params
        self.config = config
        self.sampler = sampler or Sampler(kind="greedy")
        self.stop_tokens = tuple(stop_tokens)
        self.tokenizer = tokenizer
        self.clock = clock
        self.cache_dtype = jnp.dtype(cache_dtype)
        self.block_size = block_size
        self.prefill_chunk = prefill_chunk or block_size
        # per-request cache ceiling, in whole blocks (fixes the decode
        # gather width S_max = max_blocks_per_seq * block_size)
        self.max_seq_len = _ceil_to(max_seq_len, block_size)
        self.max_blocks_per_seq = self.max_seq_len // block_size
        # a stack with no layer that has pages (serve/block_pool.py): no
        # table, no block, and a request's context bounded by the model's
        # positions alone
        self._paged = config.has_pages
        if not self._paged:
            self.max_seq_len = config.max_position_embeddings
            self.max_blocks_per_seq = 0
        # prefix-share granularity in BLOCKS: shared prefixes must cover
        # whole blocks (pool granularity) AND whole prefill chunks (so
        # skipped prefill work is exactly the shared region — a partial
        # chunk would re-prefill and re-WRITE a shared block)
        self._share_unit = (
            math.lcm(self.block_size, self.prefill_chunk) // self.block_size
        )

        # spec engines get verify headroom in the default budget:
        # drafts only ever spend budget prefill left over, so
        # without the extra room a busy admission window would trim
        # every draft to nothing and speculation would never engage
        budget = tick_token_budget or (
            max_slots * (1 + self.spec_k) + 2 * self.prefill_chunk
        )
        if budget < max_slots:
            raise ValueError(
                f"tick_token_budget ({budget}) must be >= max_slots "
                f"({max_slots}): every decode row needs one token per "
                "tick before prefill fills the remainder"
            )
        self.tick_token_budget = budget

        t_pool = tracer.now_us() if tracer is not None else -1.0
        # a pool with a window class: the ring a slot holds, from the
        # window and the widest slice one tick writes into a row — the
        # whole budget, which the planner hands the oldest prompt when
        # nothing else wants it (Scheduler.plan_tick)
        self.window_blocks = 0
        if config.two_page_classes:
            self.window_blocks = window_blocks_per_slot(
                config.sliding_window, budget, block_size)
        self.pool = BlockPool(
            config, num_blocks, block_size, dtype=cache_dtype,
            enable_prefix_cache=enable_prefix_cache,
            shardings=self._pool_shardings,
            state_slots=max_slots,
            window_blocks=self.window_blocks,
        )
        if tracer is not None:
            jax.block_until_ready(self.pool.pages)
            tracer.complete("pool_alloc", t_pool, cat="setup", args={
                "blocks": num_blocks, "bytes": int(sum(
                    a.nbytes for a in jax.tree.leaves(self.pool.pages))),
                "window_blocks": (self.pool.window.num_blocks
                                  if self.pool.window is not None else 0),
            })
        self.scheduler = Scheduler(
            self.pool,
            max_slots=max_slots,
            block_size=block_size,
            blocks_for_prefill=lambda req: self.pool.blocks_for(
                self._prefill_width(req)
            ),
            prefill_plan=self._prefill_plan,
            # (a pool with no page class has no block to keep spare)
            decode_reserve=int(self._paged),
            max_queue=max_queue,
            on_slot_release=(self.pool.window.release
                             if self.pool.window is not None else None),
        )
        self.metrics = ServeMetrics(clock=clock)
        # window blocks this tick's rows let go (tick args)
        self._window_recycled_tick = 0
        # the query tiles of this tick's pack that hold more than one
        # token, and the tokens in them (``/metrics``)
        self._prefill_tiles_tick = self._prefill_tile_tokens_tick = 0
        # -- host-RAM KV block tier (serve/host_tier.HostTier): spilled
        # prefix blocks keyed by the SAME chained content hash the
        # prefix cache uses, restored at admission as ordinary claimed
        # pool blocks.  None = every hook is a single is-None check
        # (tools/lint R4 `host_tier`), zero dispatches, zero recompiles.
        self.host_tier = host_tier
        # bytes one pool block holds across all layers (K+V + int8
        # scale pages) — the unit every tier ledger counts in
        self._block_nbytes = int(sum(
            a.nbytes // a.shape[1] for a in self.pool.pages.pool_arrays()
            if a.size
        ))
        # per-tick tier observables (engine-thread-owned, reset at tick
        # start, reported in the tick trace args when the tier is on)
        self._tier_spill_bytes = 0
        self._tier_restore_bytes = 0
        self._tier_restore_us = 0.0
        if self.pool.prefix_cache is not None:
            # LRU reclaim is no longer silent: the callback counts the
            # eviction (llm_serve_prefix_evicted_total), traces it, and
            # — with the tier attached — spills the block instead of
            # just dropping it
            self.pool.prefix_cache.on_reclaim = self._on_prefix_reclaim
        if host_tier is not None:
            self._restore_block: Callable | None = (
                self._make_restore_block()
            )
            self._slice_block: Callable | None = self._make_slice_block()
            # startup breakeven measurements: host→device bandwidth
            # from a block-sized device_put probe; the recompute side
            # seeds from the analytic telemetry model when attached and
            # is refined by measured prefill rates every dispatching
            # tick (HostTier.note_prefill_rate)
            shape = self.pool.pages.k.shape
            blk_shape = (shape[0],) + shape[2:]
            probes = [(blk_shape, self.cache_dtype)] * 2
            if self.pool.pages.quantized:
                probes += [(blk_shape[:-1], jnp.float32)] * 2
            host_tier.ensure_probe(probes)
            if telemetry is not None:
                w = telemetry.weight_bytes(self.prefill_chunk, 1)
                host_tier.note_prefill_rate(
                    self.prefill_chunk / (w / (telemetry.hbm_gbps * 1e9))
                )
            self.metrics.on_tier_gauge(
                resident_bytes=host_tier.resident_bytes,
                breakeven=host_tier.breakeven_ratio(self.block_size),
            )
        else:
            self._restore_block = None
            self._slice_block = None
        self._next_id = 0
        self._detok: dict[int, IncrementalDetok] = {}
        # live (queued or running) requests by id — the abort/deadline
        # index; entries leave on finish and abort
        self._requests: dict[int, Request] = {}
        # what the unified tick's ``accept`` owes the outside, in emit
        # order: ``(kind, request, token | terminal reason)``.  Tick N's
        # items are handed out (``_publish``) behind tick N+1's
        # dispatch, or on the spot when no dispatch follows
        self._owed: collections.deque[tuple[int, Request, Any]] = (
            collections.deque())
        self._publishing = False
        # device dispatches issued by this engine (every jitted-step
        # call) — the CPU-measurable observable for the unified tick's
        # "strictly fewer dispatches per tick" claim
        self.n_dispatches = 0

        # -- fused sampling epilogue gate (tick-tail fusion): the step's
        # final-norm → lm_head → sample chain runs as ONE Pallas kernel
        # over vocab tiles (ops/pallas/sample_epilogue.py) so the
        # [rows, V] logits never materialize in HBM.  Fused only when
        # the probe passes AND the draw is bit-identical to the XLA
        # oracle — today that is the greedy sampler over a float or
        # int8-"q" head on an unsharded (or placement-only) mesh; every
        # other combination keeps the final_logits+Sampler tail, which
        # remains the fallback/oracle everywhere ("off" forces it).
        self.sample_epilogue_mode = sample_epilogue
        self.epilogue_impl = "xla"
        if sample_epilogue != "off":
            from llm_np_cp_tpu.models.transformer import (
                epilogue_gate_error,
            )

            if self.mesh is not None and self.mesh_plan.model > 1:
                epi_err = ("model-sharded mesh (the kernel streams the "
                           "full lm head; a TP-aware epilogue is open "
                           "work)")
            else:
                t_probe = tracer.now_us() if tracer is not None else -1.0
                epi_err = epilogue_gate_error(
                    params, config, self.sampler.kind
                )
                if tracer is not None:
                    tracer.complete(
                        "probe.sample_epilogue", t_probe, cat="setup",
                        args={"ok": epi_err is None})
            if epi_err is None:
                self.epilogue_impl = "fused"
            elif sample_epilogue == "on":
                import logging

                logging.getLogger("llm_np_cp_tpu").warning(
                    "sample_epilogue='on' but the fused epilogue "
                    "cannot serve this engine (%s); using the XLA "
                    "logits tail", epi_err,
                )

        # dropless expert layers (unified tick only): their per-expert token
        # counts come back with the tick's one fetch
        self._n_expert_layers = len(config.expert_layers)
        self._expert_row_tiles: dict[int, int | None] = {}
        self._expert_rows_impls: dict[int, str] = {}
        if self._n_expert_layers:
            # the grouped matmul's verdict, asked here and not first while
            # the step is being traced (ops/moe.expert_row_tile asks there)
            t_probe = tracer.now_us() if tracer is not None else -1.0
            tile = self._expert_row_tile(max_slots)
            if tracer is not None:
                tracer.complete("probe.grouped_matmul", t_probe, cat="setup",
                                args={"ok": tile is not None})
        # which form advances a recurrent state by a row's first token
        # ("pallas": ops/pallas/ssm_state_update over the rows a tick
        # touches; "xla": ``ssm_chunk`` over all of a layer's rows) —
        # ``ssm_packed``'s own choice, asked here the way it asks and not
        # first while the step is being traced; None without such layers
        self.ssm_state_impl: str | None = None
        if config.ssm_layers:
            t_probe = tracer.now_us() if tracer is not None else -1.0
            self.ssm_state_impl = "xla" if ssm_ops.state_update_heads(
                self.pool.pages.state["ssm"],
                config.mamba_n_groups) is None else "pallas"
            if tracer is not None:
                tracer.complete(
                    "probe.ssm_state_update", t_probe, cat="setup",
                    args={"ok": self.ssm_state_impl == "pallas"})
        # ... and a delta-rule layer's matrix state likewise
        # (ops/pallas/kda_state_update / its twin: ``kda_packed``'s choice)
        self.kda_state_impl: str | None = None
        if config.kda_layers:
            t_probe = tracer.now_us() if tracer is not None else -1.0
            self.kda_state_impl = "pallas" if kda_ops.state_update_impl(
                self.pool.pages.state["kda"]) else "xla"
            if tracer is not None:
                tracer.complete(
                    "probe.kda_state_update", t_probe, cat="setup",
                    args={"ok": self.kda_state_impl == "pallas"})
        # ... and a power-retention layer's state likewise
        # (ops/pallas/retention_state_update / its twin)
        self.retention_state_impl: str | None = None
        if config.retention_layers:
            t_probe = tracer.now_us() if tracer is not None else -1.0
            self.retention_state_impl = (
                "pallas" if retention_ops.state_update_impl(
                    self.pool.pages.state["retention"]) else "xla")
            if tracer is not None:
                tracer.complete(
                    "probe.retention_state_update", t_probe, cat="setup",
                    args={"ok": self.retention_state_impl == "pallas"})
        # -- the tick: ONE jitted program, bucketed packed width —
        # prefill K/V goes straight into pool blocks and sampling
        # happens inside the mixed step.
        from llm_np_cp_tpu.ops.pallas.decode_attention import (
            RAGGED_Q_TILE,
        )

        # (no attention, no tile: a token is its own lane, and the tiled
        # width of a program is its dense width)
        self._q_tile = RAGGED_Q_TILE if self._paged else 1
        # lanes of the WIDE query tile a prompt chunk's tokens are laid
        # in where the pages have one (0: none), in the programs only a
        # tick with a chunk runs (``_wide_program``)
        self._wide_tile = self._resolve_wide_tile()
        # verify-lane width of the compiled step: every row carries
        # spec_k+1 sample slots ([R, W] last_idx/sample_pos operands
        # and an [R, W] token return) — plain rows use column 0 and
        # the rest are discarded host-side, so the shape is static
        # whatever each tick's draft widths turn out to be
        self._spec_w = self.spec_k + 1
        # what lays the step's packed operand out beside its two
        # widths (mixed_operand_layout)
        self._mixed_geometry = (
            self._q_tile, max_slots, self.max_blocks_per_seq,
            self._spec_w,
        ) + ((self.window_blocks,) if self.window_blocks else ())
        self.mixed_buckets = self._make_buckets(budget, max_slots)
        # the rung that holds ``max_slots`` one-tile rows: the widest a
        # decode-only tick runs (``_wide_program``)
        self._steady_lanes = min(
            t for t, _ in self.mixed_buckets if t >= max_slots * self._q_tile)
        # stated once a program: the packer looks its layout up
        self._mixed_layouts = {
            p: mixed_operand_layout(*p, *self._mixed_geometry)
            for p in self.mixed_buckets}
        self._mixed_step = self._make_mixed_step()
        # -- the layout the step reads each weight in, asked of the
        # compiler ONCE (``step_weight_formats``); the weights are put
        # there now, so no tick re-lays one out.  A rebuild of an engine
        # of this geometry (``clone_fresh``) takes that engine's compiled
        # step and, with it, the formats it was compiled for
        if (steps_from is not None
                and steps_from.ragged_attn_impl == self.ragged_attn_impl
                and steps_from.epilogue_impl == self.epilogue_impl):
            # same resolution → identical jaxpr; a runtime-degraded
            # process (disable_kernel) rebuilds on the XLA fallback
            # and compiles it once there, not per restart
            self._mixed_step = steps_from._mixed_step
            self._weight_formats = steps_from._weight_formats
        else:
            self._weight_formats = self._decide_weight_formats()
        # leaves and bytes this build moved (the ``engine_build`` span)
        self.weights_reput = self._lay_out_weights()
        # what a program still re-lays out an execution, in bytes, by
        # program ("512x64"; ``device_op_map`` reads it from the compiled
        # text; a ``/metrics`` gauge).  Empty until a recorder asked
        self.weight_relayout_bytes: dict[str, int] = {}
        # one-fetch ledger, initialized after the step builder: the
        # tick bumps it at its single packed host_sync transfer and the
        # tick trace args carry the per-tick count
        self.n_host_fetches = 0
        # mixed dispatches so far: the number a dispatch carries everywhere
        # (tick arg ``seq`` of its tick, metadata of its
        # ``serve.mixed_dispatch`` / ``serve.host_sync`` annotations), so a
        # profile's events join the recorder's ticks without a fitted clock
        self.n_mixed_dispatches = 0
        if tracer is not None:
            tracer.complete("engine_build", t_build, cat="setup", args={
                "tick": "unified",
                "buckets": len(self.mixed_buckets),
                # how the tick's layer loop holds the pool (1: flat over
                # (layer, block), written in place; 0: by layer slabs)
                # and what one page of it is
                "pool_carried": int(self.pool_carried),
                "pool_page_shape": self.pool_page_shape,
                # weight leaves (and their bytes) put into the layout the
                # step reads them in; 0 where they lay there already
                "weights_reput": self.weights_reput[0],
                "weights_reput_bytes": self.weights_reput[1],
                # what the slots carry besides K/V (0 without such layers)
                "state_bytes": int(sum(a.nbytes for a in jax.tree.leaves(
                    self.pool.pages.state))),
                "state_slots": max_slots,
                # what a token holds in the pool over all layers, as the
                # algorithm needs it (``ModelConfig.kv_token_shapes``) and
                # as the pool stores it; the routed experts held
                "page_bytes_per_token": config.kv_bytes_per_token(
                    self.cache_dtype.itemsize),
                "pool_bytes_per_token": self._block_nbytes // block_size,
                "experts_held": (config.experts_held
                                 if config.num_experts else 0),
                # a pool with a window class: what a token holds in each
                # (``page_bytes_per_token`` counts the window layers as
                # if they kept every token: what the class bounds), and
                # the ring a slot owns
                **({"pool_bytes_per_token_global": config.kv_bytes_per_token(
                        self.cache_dtype.itemsize, "global"),
                    "pool_bytes_per_token_window": config.kv_bytes_per_token(
                        self.cache_dtype.itemsize, "window"),
                    "window_blocks_per_slot": self.window_blocks,
                    # ... and the bytes each class holds on the device
                    "global_class_bytes": int(sum(
                        a.nbytes for a in self.pool.pages.pool_arrays())),
                    "window_class_bytes": int(sum(
                        a.nbytes for a in self.pool.pages.window))}
                   if self.window_blocks else {}),
            })

    def _make_buckets(
        self, budget: int, max_slots: int,
    ) -> tuple[tuple[int, int], ...]:
        """The mixed step's programs, ``(t_w, d_w)`` pairs in the order
        ``_pick_bucket`` searches: the width inside attention (tile
        lanes) and the width of the step's dense token axis.  Warm-up
        compiles exactly these, so the set is kept SMALL: a warm program
        still costs 0.7-1 s of every start (PERF.md §6, PR 30 / 31).

        The tile ladder is a doubling ladder of q-tile multiples capped
        by the worst aligned total (every planned token plus per-row
        tile padding): the ragged kernel's time follows its grid, so a
        tick gets the narrowest rung that holds its tiles.  Each rung
        has ONE dense width, its capacity — a tick holds no more tokens
        than its lanes, nor than the budget — because a finer dense
        ladder buys nothing: under about 240 rows a bf16 matmul on a v5e
        streams the same weights whatever its height.

        The one exception is the steady decode tick, ``max_slots`` rows
        of one tile each: at its rung's capacity (512 of 64 slots' 8-lane
        tiles) qkv, o_proj, the MLP and the K/V scatter ran eight lanes
        a token, compute-bound on lanes that hold nothing.  That rung
        gets a second program as wide as the rows' tokens,
        ``max_slots * (1 + spec_k)``."""
        qb = self._q_tile
        # each of up to max_slots segments wastes < qb lanes to alignment
        # (a rung of wide tiles is whole ones)
        a_max = _ceil_to(budget + max_slots * (qb - 1), self._wide_tile or qb)
        ladder = []
        # (the first rung: a tile, which where no layer has pages would be
        # one token; 8 tokens there)
        t = max(qb, 8)
        while t < a_max:
            ladder.append(t)
            t *= 2
        ladder.append(a_max)
        cap = _ceil_to(budget, qb)
        programs = [(t, min(t, cap)) for t in ladder]
        if not self._paged:
            # a program IS its dense width: the steady tick's rung holds
            # the rows already, and a second program of that rung would be
            # a second name for it
            return tuple(sorted(set(programs)))
        t_rows = next(t for t in ladder if t >= max_slots * qb)
        d_rows = _ceil_to(max_slots * self._spec_w, qb)
        # the operand's length is all the jitted step knows of its
        # program (mixed_operand_program): keep the lengths apart

        def length(program: tuple[int, int]) -> int:
            return mixed_operand_layout(*program, *self._mixed_geometry)[1]

        taken = {length(p) for p in programs}
        while d_rows < min(t_rows, cap) and length((t_rows, d_rows)) in taken:
            d_rows += qb
        if d_rows < min(t_rows, cap):
            programs.append((t_rows, d_rows))
        return tuple(sorted(set(programs)))

    def _resolve_wide_tile(self) -> int:
        """Lanes of the wide query tile this engine's packer lays a prompt
        chunk in (0: none): what ``ragged_wide_tile`` says of the pool's
        pages — of both classes where it has two, the narrower: the tile
        metadata is one for every layer.  A latent pool's kernel has no
        wide tile (PERF.md section 7)."""
        from llm_np_cp_tpu.ops.pallas.decode_attention import ragged_wide_tile
        from llm_np_cp_tpu.parallel.sharding import MODEL_AXIS

        pages, cfg = self.pool.pages, self.config
        if not self._paged or pages.latent or not pages.merged:
            return 0
        shards = self.mesh.shape[MODEL_AXIS] if self._kv_sharded else 1
        widths = []
        for kind in ("global", "window")[:1 + cfg.two_page_classes]:
            token = cfg.kv_token_shapes(kind)
            kh, d = token["k"]
            widths.append(ragged_wide_tile(
                kh // shards, cfg.num_attention_heads // kh, d,
                token["v"][1], pages.k.dtype, True))
        wide = min(widths)
        # (a budget no segment of which fills a wide tile has none)
        return wide if self.tick_token_budget >= wide else 0

    def _wide_program(self, t_w: int) -> int:
        """The wide tile of the program whose tiled axis is ``t_w`` lanes
        (0: its tiles are all ``RAGGED_Q_TILE`` lanes): the rungs past
        the one that holds ``max_slots`` one-tile rows, which only a
        tick with a prompt chunk needs — the programs a decode-only tick
        runs keep the attention call they have."""
        wide = self._wide_tile
        return wide if wide and t_w > self._steady_lanes \
            and t_w % wide == 0 else 0

    def _pick_bucket(self, n_lanes: int, n_tokens: int) -> tuple[int, int]:
        """The program for a tick of ``n_tokens`` tokens whose segments
        align to ``n_lanes`` tile lanes: the first, in ``(t_w, d_w)``
        order, that holds both — so the narrowest attention rung that
        holds the tiles, always, and on it the narrowest dense axis."""
        for t_w, d_w in self.mixed_buckets:
            if t_w >= n_lanes and d_w >= n_tokens:
                return t_w, d_w
        raise AssertionError(
            f"planner produced {n_tokens} tokens aligned to {n_lanes} "
            f"lanes > the largest program {self.mixed_buckets[-1]} — "
            "budget accounting is broken"
        )

    # ------------------------------------------------------------------
    # The layout the step reads each weight in
    # ------------------------------------------------------------------
    def step_weight_formats(self, params: Params, pages: PagedKV) -> Any:
        """The ``Format`` (layout and sharding) the step reads each weight
        leaf in when the COMPILER chooses: the pytree of ``params``, from
        ``compiled.input_formats`` of the step jitted with the weights'
        layouts left open (``Layout.AUTO``; the pool and the packed
        operand keep theirs).  ``params`` / ``pages``: arrays, or shapes
        that carry a sharding (tests/test_kernel_lowering.py compiles for
        a described chip).

        ``jax.jit`` hands a program every argument in the device's default
        layout and the compiler may not change an entry parameter's: where
        a projection's dot wants its weight's contracting axis minor (a
        result split into heads straight after the dot, which the compiler
        folds into it), it copied the WHOLE weight every execution —
        seven 100 MB ``q_proj`` a tick in one cell, 2.2 ms of 21 (PERF.md
        section 6, PR 54).  Asked instead, it says which layout each leaf
        should arrive in, and the engine puts it there once.

        ONE program decides: the one a decode-only tick with every slot
        live runs — most ticks of most traffic.  The other programs are
        compiled for the weights as they then lie (a plain ``jax.jit``
        compiles for the layout a committed argument has), so all agree
        and the engine holds one set of weights."""
        from jax.experimental.layout import Format, Layout

        program = self._pick_bucket(
            self.scheduler.max_slots * self._q_tile, self.scheduler.max_slots)

        def shape(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype)

        step = jax.jit(
            self._mixed_step.__wrapped__, donate_argnums=(1,),
            in_shardings=(
                jax.tree.map(lambda a: Format(Layout.AUTO, a.sharding),
                             params),
                jax.tree.map(lambda a: Format(None, a.sharding), pages),
                Format(None, pages.k.sharding)))
        return step.lower(
            jax.tree.map(shape, params), jax.tree.map(shape, pages),
            shape(self._dead_mixed_operands(*program)),
        ).compile().input_formats[0][0]

    def _decide_weight_formats(self) -> Any:
        """``step_weight_formats`` for this engine's weights and pool, or
        None where the weights stay as they come: under a mesh (no cell
        runs one; a sharded leaf's layout is the partitioner's business as
        much as the compiler's, and ``shard_params`` has just placed
        them), for an engine built on shapes alone, and on a device that
        keeps its arrays UNTILED (``Array.format``, as
        ``_pool_is_row_major`` asks it: the CPU).  There either axis
        order of a matrix is a stride away, the compiler asks for no
        other (every leaf of every tiny preset comes back as it is), and
        the question — a trace and a compile of the steady program, 1-4 s
        of every engine a test builds — is not put."""
        leaves = jax.tree.leaves(self.params)
        if self.mesh is not None or not all(
                isinstance(a, jax.Array) for a in leaves):
            return None
        # (the device's way with a matrix: one leaf of rank >= 2 says it)
        matrix = max(leaves, key=lambda a: a.ndim, default=None)
        if matrix is None or not getattr(matrix.format.layout, "tiling", None):
            return None
        return self.step_weight_formats(self.params, self.pool.pages)

    def _lay_out_weights(self) -> tuple[int, int]:
        """Put every weight leaf whose layout differs from the one the
        step reads it in (``_weight_formats``) into that layout, leaf by
        leaf: the engine then holds the new leaf and no reference to the
        old one, the caller's pytree is what it was, and a leaf that lies
        right stays the same buffer.  Returns (leaves, bytes) moved."""
        if self._weight_formats is None:
            return 0, 0
        leaves, tree = jax.tree.flatten(self.params)
        # (a leaf the step never reads has no format to ask for)
        turn = {i: fmt for i, (leaf, fmt) in enumerate(zip(
            leaves, jax.tree.leaves(self._weight_formats)))
            if fmt.layout is not None and leaf.format.layout != fmt.layout}
        if turn:
            # the copy is a program whose RESULT has the asked layout: the
            # one kind the persistent compile cache hands back wrong
            with compile_cache_bypassed():
                for i, fmt in turn.items():
                    leaves[i] = jax.device_put(leaves[i], fmt)
            self.params = jax.tree.unflatten(tree, leaves)
        return len(turn), sum(leaves[i].nbytes for i in turn)

    def weight_layout_gauges(self) -> dict[str, float]:
        """``/metrics``: the bytes an execution of each program spends
        re-laying out a weight (``step_weight_relayout_bytes{program=}``,
        0 expected; known once a recorder read the compiled programs:
        ``device_op_map``), and what this engine's build moved to get
        there."""
        out = {f'step_weight_relayout_bytes{{program="{program}"}}': float(n)
               for program, n in self.weight_relayout_bytes.items()}
        out["weights_reput"] = float(self.weights_reput[0])
        out["weights_reput_bytes"] = float(self.weights_reput[1])
        return out

    # ------------------------------------------------------------------
    # Mesh helpers (all no-ops on a single chip)
    # ------------------------------------------------------------------
    @property
    def mesh_desc(self) -> str | None:
        """Operator-readable mesh topology for the serve banner and
        ``/healthz`` (None on a single chip)."""
        if self.mesh is None:
            return None
        dev = next(iter(self.mesh.devices.flat))
        if self.mesh_plan.model == 1:
            # DP-without-TP placement mesh: one device, nothing sharded
            return f"pinned to {dev.platform} device {dev.id}"
        kv = "kv-sharded" if self._kv_sharded else "kv-replicated"
        return (f"tp={self.mesh_plan.model} over "
                f"{self.mesh_plan.num_devices} {dev.platform} devices "
                f"({kv})")

    @property
    def pool_page_shape(self) -> str:
        """One page of the pool as it is stored: ``64x2x128``, or
        ``64x512`` where the kv heads lie side by side (merged)."""
        return "x".join(str(n) for n in self.pool.pages.k.shape[2:])

    def pool_form_gauges(self) -> dict[str, float]:
        """``/metrics``: whether the tick's layer loop carries the pool
        flat and writes it in place (``pool_carried``), and the page's
        shape as a label."""
        out = {
            "pool_carried": float(self.pool_carried),
            f'pool_page_shape{{shape="{self.pool_page_shape}"}}': 1.0,
        }
        rings = self.pool.window
        if rings is not None:
            # both page classes, in blocks and in what a block holds over
            # the class's layers; the live context they serve
            item, bs = self.cache_dtype.itemsize, self.block_size
            out.update({
                "kv_global_blocks_in_use": float(
                    self.pool.free_list.num_allocated),
                "kv_window_blocks_in_use": float(rings.in_use),
                "kv_global_block_bytes": float(
                    bs * self.config.kv_bytes_per_token(item, "global")),
                "kv_window_block_bytes": float(
                    bs * self.config.kv_bytes_per_token(item, "window")),
                "window_blocks_recycled_total": float(rings.recycled_total),
                "context_tokens_live": float(sum(
                    r.cache_len - r.pad
                    for r in list(self.scheduler.running))),
            })
        return out

    def _put(self, a: Any) -> jnp.ndarray:
        """Per-tick operand placement.  Under a mesh every host-built
        operand (block tables, packed metadata, token ids) is committed
        FULLY REPLICATED, so each dispatch's in-avals — shardings
        included — are identical tick after tick: the zero-recompile
        contract extended to placement.  Replicated tables are also what
        keeps the scalar-prefetch kernels correct per shard: every
        device walks the same block ids over its head-slice of the
        slabs."""
        if self._rep_sharding is None:
            return jnp.asarray(a)
        return jax.device_put(a, self._rep_sharding)

    def _constrain_pages(self, pages: PagedKV) -> PagedKV:
        """Pin the slabs' sharding on a jitted step's OUTPUT (inside the
        jaxpr).  The pages a step returns re-enter the next dispatch, so
        their placement must be a fixed point of the program — GSPMD is
        free to choose output shardings otherwise, and a drifting choice
        would retrace every tick."""
        if self._pool_shardings is None:
            return pages
        # the conv state (placement meshes only: TP is refused for it)
        # is not paged and has no sharding of its own to pin
        return jax.tree.map(
            lax.with_sharding_constraint, pages._replace(state=None),
            self._pool_shardings)._replace(state=pages.state)

    def _shard_attn(self, fn: Callable, *, quantized: bool, n_meta: int,
                    q_head_axis: int) -> Callable:
        """Wrap a per-layer paged-attention callable for the mesh.

        With kv heads sharded, the Pallas scalar-prefetch kernels (and
        their XLA fallbacks) run UNMODIFIED inside ``shard_map`` over the
        model axis: each device sees its head-slice of q
        (``q_head_axis`` names the head dim) and of the pool slabs
        (+ int8 scale pages), while tables / lengths / pads / window
        metadata arrive replicated — GQA's kv-major head order makes the
        local group math identical to the global one.  Softmax is
        per-head, so no cross-shard collective is needed; check_vma is
        off because the kernel's gathers defeat replication inference.

        Off-mesh (or kv-replicated) the callable runs as-is.  Calling
        convention: ``wrapped(q, k_pages, v_pages, [k_scale, v_scale,]
        *meta)`` — scales positional only in quantized mode, so None
        never crosses a shard_map boundary."""
        if quantized:
            def call(q, kp, vp, ks, vs, *meta):
                return fn(q, kp, vp, *meta, k_scale=ks, v_scale=vs)
        else:
            def call(q, kp, vp, *meta):
                return fn(q, kp, vp, *meta, k_scale=None, v_scale=None)
        if self.mesh is None or not self._kv_sharded:
            return call
        from jax.sharding import PartitionSpec as P

        from llm_np_cp_tpu.parallel.sharding import MODEL_AXIS

        # no trailing Nones anywhere: unspecified trailing dims are
        # unsharded, and the normalized spelling is the one jit's
        # compile cache expects (tools/lint R1)
        qs = P(*([None] * q_head_axis), MODEL_AXIS)
        kvs = P(None, None, MODEL_AXIS)
        ss = P(None, None, MODEL_AXIS)
        rep = P()
        in_specs = (qs, kvs, kvs) + ((ss, ss) if quantized else ())
        in_specs += (rep,) * n_meta
        return jax.shard_map(call, mesh=self.mesh, in_specs=in_specs,
                             out_specs=qs, check_vma=False)

    # ------------------------------------------------------------------
    def _prefill_width(self, req: Request) -> int:
        """Left-padded prefill width: the request's content rounded up to
        a whole number of chunks (ONE compiled chunk program for every
        prompt length)."""
        return _ceil_to(req.total_len, self.prefill_chunk)

    def _prefill_plan(self, req: Request) -> tuple[list[int], int]:
        """Admission plan: ``(claimed shared block ids, fresh blocks
        needed)``.  With the prefix cache on, the prompt's fully-filled
        leading blocks are hashed and the longest registered chain is
        CLAIMED (one reference per block); the fresh need excludes them,
        so shared blocks don't double-count against pool capacity.  The
        shareable span is capped at ``width - prefill_chunk``: the LAST
        chunk always re-prefills because the first token's logits come
        out of it, and the cap also guarantees decode writes land
        strictly past every shared block.

        With the host tier attached, keys the device cache misses are
        looked up host-side as well: a hit above the measured
        restore-vs-recompute breakeven allocates ordinary pool blocks
        for the span NOW and stages the restore after admission (the
        plan only DECIDES — no restore job exists until the admission
        sticks, so a backed-off plan frees the blocks with nothing in
        flight to write into them).  Below breakeven the span
        re-prefills (counted)."""
        w = self._prefill_width(req)
        total = self.pool.blocks_for(w)
        cache = self.pool.prefix_cache
        # a backed-off admission freed its planned restore blocks; the
        # stale plan must not survive into this attempt
        req.extra.pop("tier_restore", None)
        if cache is None:
            return [], total
        unit = self._share_unit
        n_keys = ((w - self.prefill_chunk) // (unit * self.block_size)) * unit
        if n_keys <= 0:
            return [], total
        # a request stuck at the queue head is re-planned EVERY tick —
        # reuse the hashes while its content (hence width) is unchanged
        # instead of re-running SHA-256 over the prompt each attempt
        keys = req.extra.get("prefix_keys")
        if keys is None or req.extra.get("prefix_keys_width") != w:
            content = req.effective_prompt()
            keys = prefix_block_keys(
                content, w - content.size, self.block_size, n_keys
            )
            req.extra["prefix_keys"] = keys
            req.extra["prefix_keys_width"] = w
        # only whole prefill chunks can be skipped — truncate the match
        # to share-unit multiples before claiming
        n_shared = (len(cache.match(keys)) // unit) * unit
        shared = cache.claim(keys[:n_shared]) if n_shared else []
        restore_ids: list[int] = []
        if self.host_tier is not None and n_shared < len(keys):
            # combined coverage walk: LRU reclaim evicts a chain entry
            # at a time, so a prefix routinely ends up SPLIT — some
            # keys spilled host-side, some still registered device-side
            # (in either interleaving).  Each covered position is
            # either a host hit (restore into a fresh block) or a
            # device hit (claim in place); the walk stops at the first
            # key neither side holds, and the covered span truncates to
            # whole share units like the device match above.
            span: list[tuple[bytes, int | None]] = []
            for key in keys[n_shared:]:
                # device first: a dual-resident key (spilled copy still
                # host-side AND re-registered device-side — routine
                # after ship-spills and evict-restore cycles) claims in
                # place for free instead of paying a block alloc + a
                # host→device copy
                dev = cache.match([key])
                if dev:
                    span.append((key, dev[0]))
                    continue
                if self.host_tier.contains(key):
                    span.append((key, None))
                    continue
                break
            span = span[: (len(span) // unit) * unit]
            n_host = sum(1 for _, b in span if b is None)
            if n_host and self.host_tier.should_restore(
                n_host, self.block_size
            ):
                # claim the span's device entries FIRST: their increfs
                # pin them against the LRU reclaim the restore-target
                # allocs below may trigger (an evicted-then-reused id
                # would corrupt the span)
                for key, dev_blk in span:
                    if dev_blk is not None:
                        cache.claim([key])
                plan: list[tuple[bytes, int, bool]] = []
                ordered: list[int] = []
                complete = True
                for key, dev_blk in span:
                    if dev_blk is not None:
                        ordered.append(dev_blk)
                        plan.append((key, dev_blk, False))
                        continue
                    ids = self.pool.alloc(1)
                    if ids is None:
                        complete = False
                        break
                    ordered.append(ids[0])
                    plan.append((key, ids[0], True))
                if complete:
                    restore_ids = ordered
                    req.extra["tier_restore"] = plan
                else:
                    # roll the partial span back: decref the claimed
                    # device entries, free the allocated targets —
                    # nothing was enqueued, so nothing dangles
                    self.pool.free(ordered)
                    for key, dev_blk in span[len(ordered):]:
                        if dev_blk is not None:
                            self.pool.free([dev_blk])
            elif n_host:
                # measured breakeven says re-prefilling is cheaper than
                # restoring this span — fall back, visibly
                self.host_tier.note_skip(n_host)
        return shared + restore_ids, total - len(shared) - len(restore_ids)

    def compile_counts(self) -> dict[str, int]:
        """Compiled-program count per jitted step (the static-shape
        contract).  The tick is ONE step — ``mixed_step``, one compile
        per program of ``mixed_buckets`` and never one per tick.
        tools/compile_counter.py wraps this for the CI check."""

        def size(fn: Any) -> int:
            get = getattr(fn, "_cache_size", None)
            return int(get()) if get is not None else -1

        out = {"mixed_step": size(self._mixed_step)}
        if self._restore_block is not None:
            # the host tier's two programs: block id is traced and the
            # staged/sliced layout fixed, so each must stay at ONE
            # compile however many blocks spill or restore
            out["restore_block"] = size(self._restore_block)
            out["slice_block"] = size(self._slice_block)
        return out

    # ------------------------------------------------------------------
    # Jitted step builders
    # ------------------------------------------------------------------
    def _make_restore_block(self) -> Callable:
        """(pages, blk, k, v[, ks, vs]) → pages with one staged
        host-tier block written at pool block ``blk`` — the landing
        step of a restore.  ``blk`` arrives as a traced device scalar
        and the staged arrays have the block's fixed layout as the pool
        holds it ([L, BS, K, D]; merged [L, BS, K * D]), so the program
        compiles ONCE for the process however many blocks restore (the
        tier's zero-new-recompiles contract, compile_counter tiered
        section)."""
        quantized = self.cache_dtype == jnp.int8
        constrain_pages = self._constrain_pages

        if quantized:
            @partial(jax.jit, donate_argnums=(0,))
            def restore_block(pages: PagedKV, blk: jnp.ndarray,
                              k: jnp.ndarray, v: jnp.ndarray,
                              ks: jnp.ndarray, vs: jnp.ndarray):
                new = pages._replace(
                    k=pages.k.at[:, blk].set(k),
                    v=pages.v.at[:, blk].set(v),
                    k_scale=pages.k_scale.at[:, blk].set(ks),
                    v_scale=pages.v_scale.at[:, blk].set(vs),
                )
                return constrain_pages(new)
        else:
            @partial(jax.jit, donate_argnums=(0,))
            def restore_block(pages: PagedKV, blk: jnp.ndarray,
                              k: jnp.ndarray, v: jnp.ndarray):
                new = pages._replace(
                    k=pages.k.at[:, blk].set(k),
                    v=pages.v.at[:, blk].set(v),
                )
                return constrain_pages(new)
        return restore_block

    def _make_slice_block(self) -> Callable:
        """(pages, blk) → one block's per-layer K/V (+ scale pages) as
        standalone device arrays — the spill path's read.  The block id
        is a TRACED scalar: an eager ``pages.k[:, blk]`` would bake
        each Python-int index into its jaxpr and compile once per
        distinct block id as spills churn (caught by the
        compile-counter tiered section); this program compiles once,
        full stop.  NOT donated — the pool keeps its pages; the slices
        are the copies the tier's writer thread syncs to host."""
        quantized = self.cache_dtype == jnp.int8

        @jax.jit
        def slice_block(pages: PagedKV, blk: jnp.ndarray):
            def take(a):
                return lax.dynamic_index_in_dim(a, blk, axis=1,
                                                keepdims=False)

            out = (take(pages.k), take(pages.v))
            if quantized:
                out += (take(pages.k_scale), take(pages.v_scale))
            return out

        return slice_block

    # ------------------------------------------------------------------
    # Host-RAM KV tier (serve/host_tier.py)
    # ------------------------------------------------------------------
    def _on_prefix_reclaim(self, key: bytes, blk: int) -> None:
        """One prefix-cache entry is about to be LRU-reclaimed (its
        block returns to the free list).  Always counted and traced —
        reclaim used to be silent, so drop-vs-spill behavior was
        invisible on the scrape — and, with the host tier attached,
        the block's K/V is sliced for the writer thread BEFORE the id
        frees: the eager per-block slice is an async device op ordered
        ahead of any later overwrite, so the spill copy is race-free by
        dispatch order and the tick thread never blocks on it."""
        nbytes = self._block_nbytes
        spilled = False
        if self.host_tier is not None:
            # snapshot the pages: a supervisor rebuild yanks the dead
            # engine's slabs from ITS thread, and a zombie tick racing
            # that yank must degrade to plain drop, not crash inside
            # PrefixCache.release with the entry half-reclaimed
            pages = self.pool.pages
            if pages is not None:
                spilled = True
                try:
                    arrs = self._slice_block(
                        pages, self._put(np.int32(blk))
                    )
                except Exception:  # noqa: BLE001 — dead-pool slice = drop
                    spilled = False
                else:
                    # the tier dedupes resident AND queued keys; the
                    # LEDGERS count only blocks it actually accepted —
                    # a re-eviction or a ship-spill race moves no bytes
                    # and must not inflate the spill counters past the
                    # tier's own accounting
                    if self.host_tier.enqueue_spill(key, *arrs):
                        self._tier_spill_bytes += nbytes
                        self.metrics.on_tier_spill(blocks=1,
                                                   nbytes=nbytes)
        self.metrics.on_prefix_evicted(blocks=1, nbytes=nbytes)
        if self.tracer is not None:
            self.tracer.instant("prefix-evict", cat="kv_tier", args={
                "blocks": 1, "bytes": nbytes, "spilled": spilled,
            })

    def _enqueue_tier_restores(self, req: Request) -> None:
        """Stage the admission plan's host-tier hits: one writer-thread
        ``jax.device_put`` job per block (replicated under a mesh so
        the restore write's in-avals stay placement-stable).  Runs only
        AFTER the admission stuck — the planned blocks are now owned by
        ``req``, so a job can never target a free-listed id."""
        plan = req.extra.get("tier_restore")
        if not plan or self.host_tier is None:
            return
        req.extra["tier_tickets"] = [
            self.host_tier.enqueue_restore(key, blk, self._rep_sharding)
            for key, blk, is_restore in plan if is_restore
        ]

    def _apply_tier_restores(self, reqs: list[Request]) -> None:
        """Land staged restores as ordinary pool blocks BEFORE the
        covering dispatch (the planner pre-covered them, so they must
        hold real K/V by then; ``host_sync`` never waits on a tier
        transfer).  A miss — the host entry raced a capacity eviction,
        or staging failed — un-covers the tail of the span: those
        blocks stay allocated and ordinary prefill writes them, so the
        stream is correct either way, just slower.  Successful spans
        register in the device prefix cache immediately: they ARE valid
        registered prefix blocks again, so LATER admissions hit them
        device-side.  (Siblings admitted in the SAME admit() batch all
        planned before any registration landed, so each restores its
        own copy — wasteful for one batch but correct; deduping at plan
        time would make a sibling depend on a peer's not-yet-landed
        restore, whose failure path re-writes the block inside the very
        dispatch the sibling attends it in.)"""
        if self.host_tier is None:
            return
        for req in reqs:
            plan = req.extra.pop("tier_restore", None)
            tickets = req.extra.pop("tier_tickets", None)
            if not plan or tickets is None:
                continue
            results = iter(self.host_tier.take_restored(tickets))
            n_dev = req.n_shared_blocks - len(plan)
            quantized = self.cache_dtype == jnp.int8
            ok = 0
            n_restored = 0
            lat = 0.0
            pages = self.pool.pages
            for key, blk, is_restore in plan:
                if not is_restore:
                    ok += 1  # device-claimed in place: already valid
                    continue
                res = next(results)
                if res is None:
                    break  # coverage is prefix-contiguous: stop here
                _, staged, dt = res
                args = (staged.k, staged.v)
                if quantized:
                    args += (staged.k_scale, staged.v_scale)
                self.n_dispatches += 1
                pages = self._restore_block(
                    pages, self._put(np.int32(blk)), *args
                )
                ok += 1
                n_restored += 1
                lat = max(lat, dt)
            self.pool.pages = pages
            unit = self._share_unit
            ok = (ok // unit) * unit  # coverage in whole share units
            if ok < len(plan):
                # re-prefill the un-covered tail: shrink the covered
                # span; the tail blocks stay in req.block_ids and the
                # prefill writes them — a device-claimed block rounded
                # out of the span is rewritten with BIT-IDENTICAL
                # content (a slot's K/V depends only on its token and
                # position), so sharers are unaffected
                req.n_shared_blocks = n_dev + ok
                req.prefill_done = min(
                    req.prefill_done,
                    max(req.n_shared_blocks * self.block_size - req.pad,
                        0),
                )
            pc = self.pool.prefix_cache
            for key, blk, is_restore in plan[:ok]:
                # restored blocks ARE valid registered prefix blocks
                # again — register immediately so a same-tick sibling
                # admission hits them device-side (device-claimed
                # entries are registered already; register only
                # LRU-touches them)
                if is_restore and pc is not None:
                    pc.register([key], [blk])
            if n_restored:
                nbytes = n_restored * self._block_nbytes
                self._tier_restore_bytes += nbytes
                self._tier_restore_us += lat * 1e6
                self.metrics.on_tier_restore(
                    blocks=n_restored, nbytes=nbytes, latency_s=lat,
                )
                if self.tracer is not None:
                    self.tracer.request_instant(
                        req.req_id, "kv-restore", args=self._targs(
                            req, blocks=n_restored, bytes=nbytes,
                            restore_us=round(lat * 1e6, 1),
                        ))

    def spill_prefix_blocks(self, keys: list | None = None) -> int:
        """Ship registered prefix blocks into the host tier WITHOUT
        dropping them — the fleet's block-shipping primitive: a drain/
        re-home (or a router spill verdict) copies the source replica's
        prefix K/V host-side so the DESTINATION replica restores the
        prefix instead of re-prefilling it (serve/replica.py wires
        this into drain-to-peer, remove_replica and rolling upgrades).

        ``keys=None`` ships every registered entry (a draining
        replica's whole prefix set); passing a key chain ships just the
        matched prefix.  Safe from any thread: a REGISTERED full prefix
        block is never rewritten while registered (decode and suffix
        prefill write strictly past shared blocks), so the eager
        per-block device slices are stable whatever the tick thread is
        doing, and the tier's writer thread pays the actual copies.
        Returns the number of blocks enqueued."""
        if self.host_tier is None or self.pool.prefix_cache is None \
                or self.pool.pages is None:
            return 0
        if keys is None:
            pairs = self.pool.prefix_cache.items()
        else:
            ids = self.pool.prefix_cache.match(list(keys))
            pairs = list(zip(keys, ids))
        n = 0
        for key, blk in pairs:
            if self.host_tier.contains(key):
                continue  # fast path; the enqueue dedupe is authoritative
            pages = self.pool.pages
            if pages is None:
                break  # supervisor yanked the slabs mid-walk
            try:
                arrs = self._slice_block(pages, self._put(np.int32(blk)))
            except Exception:  # noqa: BLE001 — crashed-engine drains ship
                # what they can: a faulted donated dispatch may have
                # consumed the dead pool's buffers, in which case the
                # un-shipped prefixes just re-prefill (the tier-less
                # behavior), never break the drain itself
                break
            if self.host_tier.enqueue_spill(key, *arrs):
                self.metrics.on_tier_spill(blocks=1,
                                           nbytes=self._block_nbytes)
                n += 1
        return n

    def _make_mixed_step(self) -> Callable:
        """The unified-tick program: ONE dispatch runs a packed ragged
        batch of prefill slices (q_len up to the tick's token budget:
        ``Scheduler.plan_tick``) and decode rows (q_len 1) through the
        layer scan, scattering every token's K/V
        straight into its pool block and attending
        through the block tables.  The pool is the scan's carry,
        reshaped once to ``[L*NB, ...]`` (a bitcast) and written in
        place: layer ``l`` writes and attends pages ``l * NB + block``,
        and the donated buffer comes back as the result with nothing
        pool- or slab-sized copied (5 / 3.3 / 2.9 ms a tick of the
        1.5B cell went on that, PERF.md §6 PR 25) — in the dense stack's
        one scan and in every run of a hybrid stack's layers alike
        (``hybrid_layers``; PR 38).  The one exception is a pool the
        device does not keep row-major (``_pool_is_row_major``: int8
        pages and their scale pages), which goes through as ``xs`` /
        ``ys`` slabs (a hybrid stack: whole, written at ``[layer, block,
        slot]``).  Shared prefix blocks are read in place, and there is
        no separate sample dispatch (logits are gathered at each row's
        last packed token and sampled in-graph with keys derived from
        (seed, content position), so tokens are impl- and
        preemption-invariant).

        The step's token axis is DENSE: one lane a token, ``D`` wide
        from the embedding through qkv, the K/V scatter, o_proj and the
        MLP to the tail.  The ragged kernel wants a row's tokens in
        query tiles of their own, so the tile-aligned axis (``T`` lanes:
        a decode row is one token and seven dead lanes) exists only
        inside ``attn_fn``: a row gather spreads ``q`` over it, the
        kernel runs, a row gather brings each token's result back.  Run
        at ``T``, the matmuls of a full decode batch were compute-bound
        on dead lanes (PERF.md §6, PR 30 / 31).  The latent kernel has
        the tiles as metadata alone: it reads ``q`` and writes its
        result on the dense axis (PR 48).

        The host hands the step ONE operand, an int32 vector whose
        static slices are the packed batch (``mixed_operand_layout``):
        one transfer a tick, and its length names the program
        ``(T, D)``.  One compile per program, zero per tick
        (tools/compile_counter lint)."""
        from llm_np_cp_tpu.ops.pallas.decode_attention import (
            ragged_paged_attention,
            ragged_paged_attention_xla,
        )
        from llm_np_cp_tpu.ops.pallas.latent_attention import (
            ragged_latent_attention,
            ragged_latent_attention_xla,
        )
        from llm_np_cp_tpu.ops.pallas.sparse_index import (
            sparse_latent_attention,
            sparse_latent_attention_xla,
        )

        config, sampler = self.config, self.sampler
        quantized = self.cache_dtype == jnp.int8
        win = config.sliding_window
        num_layers = config.num_hidden_layers
        use_kernel = self.ragged_attn_impl == "pallas"
        use_epilogue = self.epilogue_impl == "fused"
        stop_tokens = self.stop_tokens
        big_win = jnp.int32(1 << 30)
        constrain_pages = self._constrain_pages
        # (a pool with no page class holds nothing a device could permute)
        carry_pool = self.pool_carried = (
            not self._paged or _pool_is_row_major(self.pool.pages))
        geometry, programs = self._mixed_geometry, self.mixed_buckets

        def attn_call_of(wide: int) -> Callable:
            """The layers' attention over the pages of the first class,
            in a program whose prompt chunks lie in tiles of ``wide``
            lanes (0: none does; ``_wide_program``)."""
            return self._shard_attn(
                partial(
                    ragged_paged_attention if use_kernel
                    else ragged_paged_attention_xla,
                    scale=config.attn_scale,
                    logit_softcap=config.attn_logit_softcapping,
                    # (the twin takes a token's metadata: no tile of any width)
                    **({"wide_tile": wide} if use_kernel else {}),
                ),
                quantized=quantized, n_meta=6, q_head_axis=1,
            )

        hybrid = config.is_hybrid
        q_tile, max_slots = geometry[:2]
        if (config.is_latent or config.two_page_classes) and not carry_pool:
            raise ValueError(
                "a latent pool, or one with a window class, is read where "
                "it lies (flat over layer and block); this device does not "
                f"keep {self.pool_page_shape} pages in the order of their "
                "shape")
        window_blocks = self.window_blocks
        # the scope of the bookkeeping every layer's state shares
        state_scope = (SCOPE_SSM_PROJ if config.ssm_layers else
                       SCOPE_KDA_PROJ if config.kda_layers else
                       SCOPE_RETENTION_PROJ if config.retention_layers else
                       SCOPE_CONV)

        @partial(jax.jit, donate_argnums=(1,))
        def mixed_step(
            params: Params,
            pages: PagedKV,  # the pool and, beside it, the conv state
            ops: jnp.ndarray,  # the packed operand (mixed_operand_layout)
        ):
            with jax.named_scope(SCOPE_EMBED):
                program = mixed_operand_program(
                    ops.shape[0], programs, *geometry)
                o = split_mixed_operands(ops, mixed_operand_layout(
                    *program, *geometry)[0])
                # (this program's tile shapes: a static of the trace)
                wide = self._wide_program(program[0])
                attn_call = attn_call_of(wide)
                tokens, pads = o["tokens"], o["pads"]
                tok_row, tok_live, seeds = o["tok_row"], o["tok_live"], o["seeds"]
                verify_len = o["verify_len"]
                last_idx, sample_pos = o["last_idx"], o["sample_pos"]
                # where a token lies in the pool and in attention's tiles
                # (absent from the operand of a pool with no page class,
                # whose stack has no layer that would read them)
                tables, tok_blk, tok_off, tok_slot = (
                    o.get(name) for name in
                    ("tables", "tok_blk", "tok_off", "tok_slot"))
                tile_row, tile_qpos0, tile_qlen, lane_tok, tok_lane = (
                    o.get(name) for name in
                    ("tile_row", "tile_qpos0", "tile_qlen", "lane_tok",
                     "tok_lane"))
                x = embed_inputs(params, tokens[None, :], config)  # [1, D, H]
                cos, sin = rope_cos_sin(
                    o["positions"][None, :], config, dtype=jnp.float32
                )
                # a window layer's own RoPE base, where it has one
                rope_window = (rope_cos_sin(
                    o["positions"][None, :], config, dtype=jnp.float32,
                    theta=config.swa_rope_theta)
                    if config.swa_rope_theta else (cos, sin))
            act = ACT2FN[config.hidden_act]
            is_sliding = jnp.array(
                [config.layer_is_sliding(i) for i in range(num_layers)],
                dtype=jnp.bool_,
            )

            # The pool is the scan's CARRY, flat over (layer, block): a
            # leading-dims reshape is a bitcast of the donated buffer, the
            # scatter below updates it in place, and a layer's blocks are
            # the ids ``layer * NB + block`` (pinned by
            # tests/test_kernel_lowering.py).  A pool the device does
            # not keep row-major would be relaid out WHOLE every tick
            # that way: its layers get their slabs as ``xs`` / ``ys``.
            nb = pages.k.shape[1]
            pools, state = pages.pool_arrays(), pages.state
            n_paged = pages.k.shape[0]  # layers that have pages
            layers = jnp.arange(n_paged, dtype=jnp.int32)

            def paged_hooks(kp, vp, scale_pages, base, layer=None,
                            written=None):
                """One layer's cache write and attention over the pages
                it is given: ``(kv_update, attn_fn)``.  The pages are a
                pool of blocks (one layer's slab, or the pool flat over
                layer and block with the layer's ``base``) — or, with
                ``layer``, the WHOLE pool ``[L, NB, ..]`` as the device
                keeps it: the write then lands in place at ``[layer,
                block, slot]``, the updated arrays are left in
                ``written``, and attention reads the layer's slab."""
                blk = base + tok_blk

                def put(pool, val):
                    # the fresh rows in the form the pool holds a token
                    # (a merged page: ``[tokens, K * D]``, 64 KB of a
                    # decode tick): the value is reshaped, never the pool
                    if layer is None:
                        return pool.at[blk, tok_off].set(
                            val.reshape(val.shape[:1] + pool.shape[2:]))
                    return pool.at[layer, blk, tok_off].set(
                        val.reshape(val.shape[:1] + pool.shape[3:]))

                def slab(pool):
                    return pool if layer is None else pool[layer]

                def kv_update(k, v):  # fresh projections [1, D, K, Dh]
                    # the few lanes that pad the dense axis all write
                    # (this layer's scratch block 0, slot 0) — duplicate
                    # scatter indices there are harmless
                    if quantized:
                        ksp, vsp = scale_pages
                        kq, ks = quantize_kv(k)
                        vq, vs = quantize_kv(v)
                        new = (put(kp, kq[0]), put(vp, vq[0]),
                               put(ksp, ks[0]), put(vsp, vs[0]))
                        if written is not None:
                            written.extend(new)
                        return ((slab(new[0]), slab(new[2])),
                                (slab(new[1]), slab(new[3])))
                    # explicit cast: f32 activations into a bf16 pool
                    # is the intended rounding, not an implicit promotion
                    new = (put(kp, k[0].astype(kp.dtype)),
                           put(vp, v[0].astype(vp.dtype)))
                    if written is not None:
                        written.extend(new)
                    return slab(new[0]), slab(new[1])

                def attn_fn(q, k_att, v_att, sliding_l):
                    if quantized:
                        (kp2, ksp2), (vp2, vsp2) = k_att, v_att
                    else:
                        kp2, vp2 = k_att, v_att
                        ksp2 = vsp2 = None
                    win_eff = (
                        jnp.where(sliding_l, jnp.int32(win), big_win)
                        if win is not None else big_win
                    )
                    scales = (ksp2, vsp2) if quantized else ()
                    # the attention callables take "a pool of pages and
                    # block ids": this layer's ids in the pool it is given
                    layer_tables = tables + base
                    # (a stack with a window class tells its two kinds of
                    # attention apart in a device profile)
                    with (jax.named_scope(SCOPE_ATTN_GLOBAL) if window_blocks
                          else _NULL_CTX):
                        if use_kernel:
                            # the one place the tile-aligned axis exists:
                            # spread the tokens over their tiles, attend,
                            # bring each token's row back
                            out = attn_call(
                                q[0][lane_tok], kp2, vp2, *scales,
                                layer_tables, tile_row, tile_qpos0,
                                tile_qlen, pads, win_eff,
                            )[tok_lane]
                        else:
                            out = attn_call(
                                q[0], kp2, vp2, *scales, layer_tables,
                                tok_row, tok_slot, tok_live, pads, win_eff,
                            )
                    return out[None]

                return kv_update, attn_fn

            def window_hooks(kp, vp, base, sink):
                """A window layer's cache write and attention over the
                WINDOW class's pages flat over (layer, block), the
                layer's blocks from ``base`` on: ``(kv_update, attn_fn)``
                as ``attention_block`` takes them.  A row's chain is the
                second table (``wtables``; column 0 is logical block
                ``wfirst[row]``): a token is written at the column of its
                slot's block, and the kernel is told where the table
                starts."""
                wtables, wfirst = o["wtables"], o["wfirst"]
                bs = kp.shape[1]
                col = jnp.clip(tok_slot // bs - wfirst[tok_row], 0,
                               window_blocks - 1)
                blk = base + jnp.where(tok_live, wtables[tok_row, col], 0)

                def kv_update(k, v):  # fresh projections [1, D, K, Dh]
                    def put(pool, val):
                        return pool.at[blk, tok_off].set(
                            val[0].reshape(val.shape[1], -1).astype(pool.dtype))

                    return put(kp, k), put(vp, v)

                def attn_fn(q, k_att, v_att, sliding_l):
                    kw = dict(scale=config.attn_scale, sink=sink,
                              block0=wfirst)
                    span = jnp.int32(win)
                    with jax.named_scope(SCOPE_ATTN_WINDOW):
                        if use_kernel:
                            out = ragged_paged_attention(
                                q[0][lane_tok], k_att, v_att, wtables + base,
                                tile_row, tile_qpos0, tile_qlen, pads, span,
                                wide_tile=wide, **kw)[tok_lane]
                        else:
                            out = ragged_paged_attention_xla(
                                q[0], k_att, v_att, wtables + base, tok_row,
                                tok_slot, tok_live, pads, span, **kw)
                    return out[None]

                return kv_update, attn_fn

            def widen_to(lp, a):
                """``a`` in a latent pool's dtype and row width (zeros past
                its own: block_pool.latent_page_width)."""
                return jnp.pad(a.astype(lp.dtype), (
                    (0, 0),) * (a.ndim - 1) + ((0, lp.shape[-1] - a.shape[-1]),))

            def latent_hooks(lp, base, ip=None):
                """A latent-attention layer's cache write and attention
                over the pool flat over (layer, block), the layer's
                blocks from ``base`` on: ``(kv_update, attn_fn)`` as
                ``latent_attention_block`` takes them.  A row is written
                in the width the pool stores it (zeros past ``rank +
                rope``: block_pool.latent_page_width) and the absorbed
                query padded likewise — the value, never the pool.
                ``ip``: the pool's index keys, flat likewise (a
                sparse-attention indexer): a token's key is written where
                its row is, and attention is score -> select -> attend
                over the same tables (ops/pallas/sparse_index.py)."""
                if ip is not None:
                    return sparse_hooks(lp, ip, base)
                blk = base + tok_blk
                widen = partial(widen_to, lp)

                def kv_update(row):  # fresh rows [1, D, rank + rope]
                    return lp.at[blk, tok_off].set(widen(row[0]))

                def attn_fn(q_lat, pool):  # [1, D, H, rank + rope]
                    layer_tables = tables + base
                    if use_kernel:
                        # the kernel reads and writes the DENSE axis: it
                        # is told each tile's first token (what the packer
                        # wrote for the tile's first lane) and moves a
                        # tile's live tokens itself
                        out = ragged_latent_attention(
                            widen(q_lat[0]), pool, layer_tables,
                            tile_row, tile_qpos0, tile_qlen,
                            lane_tok[::q_tile], pads,
                            scale=config.attn_scale,
                            rank=config.kv_lora_rank)
                    else:
                        out = ragged_latent_attention_xla(
                            q_lat[0].astype(lp.dtype), pool, layer_tables,
                            tok_row, tok_slot, tok_live, pads,
                            scale=config.attn_scale,
                            rank=config.kv_lora_rank)
                    return out[None].astype(q_lat.dtype)

                return kv_update, attn_fn

            def sparse_hooks(lp, ip, base):
                """``latent_hooks`` under an indexer (its ``ip``)."""
                blk = base + tok_blk
                widen = partial(widen_to, lp)
                kw = dict(scale=config.attn_scale, rank=config.kv_lora_rank,
                          topk=config.index_topk)

                def kv_update(row, key):  # [1, D, rank + rope], [1, D, dim_I]
                    return (lp.at[blk, tok_off].set(widen(row[0])),
                            ip.at[blk, tok_off].set(key[0].astype(ip.dtype)))

                def attn_fn(q_lat, pool, index):
                    q_idx, w_idx, keys = index
                    layer_tables = tables + base
                    if use_kernel:
                        out = sparse_latent_attention(
                            widen(q_lat[0]), pool, q_idx[0].astype(ip.dtype),
                            w_idx[0], keys, layer_tables, tile_row,
                            tile_qpos0, tile_qlen, lane_tok[::q_tile], pads,
                            **kw)
                    else:
                        out = sparse_latent_attention_xla(
                            q_lat[0].astype(lp.dtype), pool,
                            q_idx[0].astype(ip.dtype), w_idx[0], keys,
                            layer_tables, tok_row, tok_slot, tok_live, pads,
                            **kw)
                    return out[None].astype(q_lat.dtype)

                return kv_update, attn_fn

            def layer_step(carry: Any, xs: tuple) -> tuple:
                w, sliding, layer, *slabs = xs
                if carry_pool:
                    x, kp, vp, *scale_pages = carry
                    base = layer * nb
                else:
                    x, (kp, vp, *scale_pages), base = carry, slabs, 0
                kv_update, attn_fn = paged_hooks(kp, vp, scale_pages, base)
                x, kv_att, _, _ = run_decoder_layer(
                    w, x, config=config, act=act, cos=cos, sin=sin,
                    sliding=sliding, kv_update=kv_update, attn_fn=attn_fn,
                )
                if quantized:
                    (kp2, ksp2), (vp2, vsp2) = kv_att
                    kv_att = (kp2, vp2, ksp2, vsp2)
                return ((x, *kv_att), None) if carry_pool else (x, kv_att)

            loads = None
            if carry_pool:
                pools = tuple(a.reshape((n_paged * nb,) + a.shape[2:])
                              for a in pools)
            # the window class rides beside the first, flat likewise
            wpools = tuple(a.reshape((-1,) + a.shape[2:])
                           for a in pages.window or ())
            if hybrid:
                # every run of like layers carries the pool: flat like
                # the dense scan's, or (a pool the device permutes) whole
                # and written in place at [layer, block, slot]
                x, new_pools, wpools, new_state, loads = hybrid_layers(
                    params["layers"], x, pools, wpools, state,
                    paged_hooks=paged_hooks, latent_hooks=latent_hooks,
                    window_hooks=window_hooks, rope_window=rope_window,
                    act=act, cos=cos, sin=sin, layers=layers, ops=o, nb=nb)
            elif carry_pool:
                xs = (params["layers"], is_sliding, layers)
                (x, *new_pools), _ = lax.scan(
                    layer_step, (x, *pools), xs, unroll=scan_unroll(config))
                new_state = None
            else:
                xs = (params["layers"], is_sliding, layers)
                x, new_pools = lax.scan(layer_step, x, xs + pools,
                                        unroll=scan_unroll(config))
                new_state = None
            # (the arrays back in the pool's own shape: a bitcast again)
            new_pages = constrain_pages(pages._replace(
                **{name: a.reshape(p.shape) for name, a, p in zip(
                    PagedKV._fields, new_pools, pages.pool_arrays())},
                state=new_state))
            if pages.window is not None:
                new_pages = new_pages._replace(window=tuple(
                    a.reshape(p.shape) for a, p in zip(wpools, pages.window)))
            # sampling ONLY at each row's sample slots — [R, W] indices
            # into the dense axis: column 0 is the plain sample (decode
            # rows and completing prefill segments), columns 1..k' are a
            # speculating row's verify positions; unused slots point at
            # token 0 and their draw is discarded host-side.
            # Keys derive from (seed, content position) per slot, so a
            # verify sample at position p is BIT-IDENTICAL to the plain
            # decode draw at p — the accept walk's whole parity story.
            with jax.named_scope(SCOPE_TAIL):
                xr = x[0][last_idx]  # [R, W, H]
                r_rows, w_cols = xr.shape[0], xr.shape[1]
                if use_epilogue:
                    # fused tail: norm → lm_head → greedy sample streamed
                    # over vocab tiles — the [R, W, V] logits never exist
                    # (pinned by a jaxpr-inspection test).  Greedy ignores
                    # the RNG keys, so the draw is bit-identical to the
                    # oracle branch below.
                    from llm_np_cp_tpu.models.transformer import (
                        sample_epilogue_tail,
                    )

                    nxt = sample_epilogue_tail(
                        params, xr.reshape(r_rows * w_cols, -1), config
                    ).reshape(r_rows, w_cols)
                else:
                    logits = final_logits(params, xr, config)  # [R, W, V]
                    keys = jax.vmap(
                        lambda s, ps: jax.vmap(
                            lambda t: jax.random.fold_in(
                                jax.random.PRNGKey(s), t
                            )
                        )(ps)
                    )(seeds, sample_pos)
                    nxt = jax.vmap(
                        jax.vmap(lambda k, lg: sampler(k, lg[None])[0])
                    )(keys, logits)
                # in-graph accept walk + stop detection, so host_sync is ONE
                # packed transfer: a verify slice's draft tokens ARE the
                # packed input tokens at columns 1..k', so the longest
                # matching prefix is computable without a host round-trip
                drafts = tokens[last_idx[:, 1:]]  # [R, W-1]
                jpos = jnp.arange(
                    max(w_cols - 1, 0), dtype=jnp.int32
                )[None, :]
                live = jpos < (verify_len[:, None] - 1)
                lead = jnp.cumprod(
                    ((drafts == nxt[:, :-1]) & live).astype(jnp.int32),
                    axis=1,
                )
                accept = jnp.sum(lead, axis=1, dtype=jnp.int32)
                packed = _pack_sync(
                    nxt, _stop_hits(nxt, stop_tokens), accept
                )
                if loads is not None:
                    # every expert layer's per-expert token counts ride
                    # the tick's one fetch, behind the rows' outcome
                    packed = jnp.concatenate(
                        [packed.reshape(-1), loads.reshape(-1)])
            return packed, new_pages

        def hybrid_layers(groups, x, pools, wpools, state, *, paged_hooks,
                          latent_hooks, window_hooks, rope_window, act,
                          cos, sin, layers, ops, nb):
            """The layer loop of a stack of more than one kind of layer:
            each run of like layers (``config.layer_groups``) is one scan
            over its own stacked leaves, and every run carries the pool
            the way the dense scan does, FLAT over (layer, block): an
            attention layer scatters into its blocks ``layer * nb +
            block`` in place and hands the kernel the flat pool with its
            tables moved by that base — nothing pool- or slab-sized is
            copied (tests/test_kernel_lowering.py; the whole-pool carry
            it replaced handed the kernel ``pool[layer]``, a slab copy a
            layer: PERF.md section 6, PR 38).  Only a pool the device
            does not keep row-major (``carry_pool`` False: int8 pages and
            their scale pages on a TPU) is carried WHOLE ``[L, NB, ..]``
            instead, written at ``[layer, block, slot]`` and attended by
            its slab (``paged_hooks(layer=)``), since a flat reshape
            would relay all of it out.  What a sequence
            carries besides K/V (``state``: a convolution's history, a
            state-space mixer's recurrent state) is carried the same way,
            whole, and written in place at ``[layer, row]``: no run takes
            its layers' rows out or puts them back.  ``wpools``: the
            window class's pages (flat over ITS layers and blocks; empty
            where the pool has one class), which a window layer (``swa``)
            writes and attends through the second table.  Returns ``(x,
            pool, window pool, state, per-expert loads [expert layers, E]
            | None)``."""
            tok_row, tok_live = ops["tok_row"], ops["tok_live"]
            positions = ops["positions"]
            d_w = tok_row.shape[0]
            with jax.named_scope(state_scope):
                # where token i's predecessors in its own sequence are:
                # ``run`` of them are the packed tokens before it (a row's
                # tokens are consecutive on the dense axis), the rest the
                # slot's state
                idx = jnp.arange(d_w, dtype=jnp.int32)
                joined = jnp.concatenate([
                    jnp.zeros((1,), jnp.bool_),
                    (tok_row[1:] == tok_row[:-1]) & tok_live[1:]
                    & tok_live[:-1]])
                run = idx - lax.cummax(jnp.where(joined, 0, idx))
                ends = tok_live & ~jnp.concatenate(
                    [joined[1:], jnp.zeros((1,), jnp.bool_)])
                # a row's last token of the tick leaves the row's state
                row_out = jnp.where(ends, tok_row, max_slots)  # else: dropped
                if (config.ssm_layers or config.kda_layers
                        or config.retention_layers):
                    # the rows as the recurrence advances them: where a
                    # row's tokens start, how many it has, whether its
                    # sequence starts here (a slot's old state is never
                    # read by a new request, nor touched by a tick the
                    # row is not in)
                    start = jnp.zeros((max_slots,), jnp.int32).at[
                        jnp.where(tok_live & ~joined, tok_row, max_slots)
                    ].set(idx, mode="drop")
                    count = jnp.zeros((max_slots,), jnp.int32).at[
                        jnp.where(tok_live, tok_row, max_slots)
                    ].add(1, mode="drop")
                    fresh = (count > 0) & (positions[start] == 0)

            def conv_history(z, kept, layer):
                """``conv_block`` / ``ssm_block``'s hook over the packed
                axis: every token's predecessors, and ``kept`` (the whole
                history leaf) with the rows' new histories written in
                place at ``[layer, row]``."""
                taps = kept.shape[2] + 1
                zz = z[0]  # [D, C]
                rows = kept[layer, tok_row].astype(zz.dtype)  # [D, taps-1, C]
                hist = []
                for d in range(1, taps):
                    from_state = jnp.take_along_axis(
                        rows, jnp.clip(taps - 1 - d + run, 0, taps - 2)[
                            :, None, None], axis=1)[:, 0]
                    h_d = jnp.where((run >= d)[:, None],
                                    jnp.roll(zz, d, axis=0), from_state)
                    # before the sequence's start there is nothing: a
                    # slot's stale rows are never read by a new request
                    hist.append(jnp.where((positions >= d)[:, None], h_d,
                                          jnp.zeros_like(h_d)))
                new_rows = jnp.stack(hist[:taps - 2][::-1] + [zz], axis=1)
                return [h[None] for h in hist], kept.at[layer, row_out].set(
                    new_rows.astype(kept.dtype), mode="drop")

            loads = []
            a0 = c0 = w0 = 0
            # (a global layer may carry no positional encoding)
            g_cos, g_sin = (cos, sin) if config.global_rope else (None, None)
            # the window class's blocks a layer (``wpools``: its pages,
            # flat over its own layers and blocks; empty without one)
            nbw = 1 + max_slots * window_blocks
            # float32 between the blocks, as models.forward keeps it
            # (transformer._hybrid_stack says why)
            stream_dtype, x = x.dtype, x.astype(jnp.float32)
            for w_g, (op, ff, _, n) in zip(groups, config.layer_groups()):
                # a layer's place among the layers with pages / a state
                xs: dict[str, Any] = {}
                if op == "swa":
                    xs["paged"] = jnp.arange(w0, w0 + n, dtype=jnp.int32)
                    w0 += n
                elif op not in STATE_ONLY_OPS:
                    xs["paged"] = layers[a0:a0 + n]
                    a0 += n
                if op in STATE_ONLY_OPS or op == "attn_ssm":
                    xs["state"] = jnp.arange(c0, c0 + n, dtype=jnp.int32)
                    c0 += n

                def body(carry, layer, op=op, ff=ff):
                    x, pool, wpool, state = carry
                    w, at = layer
                    state = None if state is None else dict(state)
                    ys: dict[str, Any] = {}

                    def history(z):
                        hist, state["conv"] = conv_history(
                            z, state["conv"], at["state"])
                        return hist

                    if op == "conv":
                        x = conv_block(w, x, config=config, history=history)
                    elif op == "kda":
                        def scan(q, k, v, g, beta):
                            o, state["kda"] = kda_ops.kda_packed(
                                state["kda"], at["state"], q[0], k[0], v[0],
                                g[0], beta[0], tok_row=tok_row, start=start,
                                count=count, fresh=fresh,
                                chunk=kda_ops.CHUNK,
                                lower_bound=config.kda_lower_bound)
                            return o[None]

                        x = kda_block(w, x, config=config, history=history,
                                      scan=scan)
                    elif op == "retention":
                        def scan(q, k, v, log_g):
                            (o, state["retention"], state["retention_z"]
                             ) = retention_ops.retention_packed(
                                state["retention"], state["retention_z"],
                                at["state"], q[0], k[0], v[0], log_g[0],
                                tok_row=tok_row, start=start, count=count,
                                fresh=fresh, chunk=retention_ops.CHUNK)
                            return o[None]

                        x = retention_block(
                            w, x, config=config, cos=cos, sin=sin, scan=scan)
                    elif op == "latent":
                        # one array of rows, always carried flat
                        # (and, beside them, an indexer's keys)
                        kv_update, attn_fn = latent_hooks(
                            pool[0], at["paged"] * nb, *pool[1:])
                        x, rows = latent_attention_block(
                            w, x, config=config, cos=cos, sin=sin,
                            kv_update=kv_update, attn_fn=attn_fn)
                        pool = rows if config.has_indexer else (rows,)
                    elif op == "swa":
                        # the window class's pages, always carried flat
                        kv_update, attn_fn = window_hooks(
                            *wpool, at["paged"] * nbw, w.get("attn_sink"))
                        x, kv_att, _ = attention_block(
                            w, x, config=config, cos=rope_window[0],
                            sin=rope_window[1], kv_update=kv_update,
                            attn_fn=attn_fn)
                        wpool = tuple(kv_att)
                    else:
                        kp, vp, *scale_pages = pool
                        if carry_pool:
                            kv_update, attn_fn = paged_hooks(
                                kp, vp, scale_pages, at["paged"] * nb)
                        else:
                            written: list = []  # kv_update's writes
                            kv_update, attn_fn = paged_hooks(
                                kp, vp, scale_pages, 0, layer=at["paged"],
                                written=written)
                        normed = (input_norm(w, x, config)
                                  if op == "attn_ssm" else None)
                        mixed, kv_att, _ = attention_block(
                            w, x, config=config, cos=g_cos, sin=g_sin,
                            kv_update=kv_update, attn_fn=attn_fn,
                            normed=normed,
                        )
                        if not carry_pool:
                            pool = tuple(written)
                        elif quantized:
                            (kp2, ksp2), (vp2, vsp2) = kv_att
                            pool = (kp2, vp2, ksp2, vsp2)
                        else:
                            pool = tuple(kv_att)
                    if op == "attn_ssm":
                        def scan(xh, dt, a, bm, cm, d_skip):
                            y, state["ssm"] = ssm_ops.ssm_packed(
                                state["ssm"], at["state"], xh[0], dt[0], a,
                                bm[0], cm[0], d_skip, tok_row=tok_row,
                                start=start, count=count,
                                fresh=fresh, chunk=config.mamba_chunk_size)
                            return y[None]

                        x = x + (mixed + ssm_block(
                            w, normed, config=config, history=history,
                            scan=scan, out_dtype=x.dtype))
                    elif op == "attn":
                        x = mixed
                    if ff == "experts":
                        x, _, ys["load"] = experts_block(
                            w, x, config=config, act=act,
                            live=tok_live[None])
                    else:
                        x, _ = ff_block(w, x, config=config, act=act)
                    return (x, pool, wpool, state), ys

                (x, pools, wpools, state), ys = scan_group(
                    body, (x, tuple(pools), wpools, state), (w_g, xs), n)
                if "load" in ys:
                    loads.append(ys["load"])
            return (x.astype(stream_dtype), tuple(pools), wpools, state,
                    jnp.concatenate(loads, axis=0) if loads else None)

        return mixed_step

    # ------------------------------------------------------------------
    # Request lifecycle
    # ------------------------------------------------------------------
    def submit(
        self,
        prompt_ids: np.ndarray | list[int],
        max_new_tokens: int,
        *,
        request_id: int | None = None,
        seed: int = 0,
        callback: Callable[[Request, int, str | None], None] | None = None,
        on_event: Callable[[Request, str], None] | None = None,
        deadline_s: float | None = None,
        arrival_time: float | None = None,
        trace_id: str | None = None,
        speculative: bool = False,
        tenant: str = "default",
        received_time: float | None = None,
        enqueue_time: float | None = None,
        _recovered: bool = False,
    ) -> Request:
        """Queue one request.  ``received_time`` / ``enqueue_time`` are
        the HTTP layer's two stamps on this engine's clock (socket
        accept; the command put into the tick thread's inbox): the first
        two of the request's way to its first token
        (scheduler.TTFT_STAMPS), which this call continues with
        ``submit_time``."""
        prompt = np.asarray(prompt_ids, dtype=np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        # peak cache need over the request's lifetime (incl. re-prefills)
        worst = worst_case_slots(prompt.size, max_new_tokens,
                                 self.prefill_chunk)
        if worst > self.max_seq_len:
            raise ValueError(
                f"prompt ({prompt.size}) + max_new_tokens ({max_new_tokens}) "
                f"needs up to {worst} cache slots > max_seq_len "
                f"{self.max_seq_len}"
            )
        # worst-case ADMISSION need: a re-prefill after preemption can
        # carry up to max_new_tokens-1 already-generated tokens, and the
        # scheduler only admits with need + decode_reserve blocks free —
        # a request whose worst admission can never be satisfied would
        # sit at the queue head forever (strict FIFO), starving
        # everything behind it, so reject at submit
        need_max = self.pool.blocks_for(
            _ceil_to(prompt.size + max_new_tokens - 1, self.prefill_chunk)
        )
        headroom = need_max + self.scheduler.decode_reserve
        if headroom > self.pool.capacity:
            raise ValueError(
                f"request needs up to {need_max} blocks + "
                f"{self.scheduler.decode_reserve} reserve to admit "
                f"> pool capacity {self.pool.capacity}; grow num_blocks or "
                f"shrink the request"
            )
        if request_id is None:
            request_id = self._next_id
        self._next_id = max(self._next_id, request_id) + 1
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        # per-tenant in-flight cap: counted over the LIVE ledger (queued
        # + running), stateless so recovery replays and drains can never
        # leak a count.  Recovered work is exempt like the queue cap —
        # the cap must not orphan a request the engine already accepted.
        if self.tenants is not None and not _recovered:
            cap = self.tenants.max_inflight
            if cap is not None:
                n_live = sum(
                    1 for r in self._requests.values()
                    if r.tenant == tenant
                )
                if n_live >= cap:
                    self.tenants.on_throttle(tenant)
                    self.metrics.on_reject()
                    if self.tracer is not None:
                        self.tracer.instant(
                            "tenant-throttled", cat="request",
                            args={"tenant": tenant, "inflight": n_live,
                                  "cap": cap},
                        )
                    raise TenantThrottled(tenant, n_live, cap)
        req = Request(
            req_id=request_id,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            seed=seed,
            callback=callback,
            on_event=on_event,
            arrival_time=arrival_time if arrival_time is not None else 0.0,
            # the opt-in survives even on a non-spec engine (inert
            # there) so a journal replay onto a spec-enabled rebuild
            # resumes drafting
            speculative=bool(speculative),
            tenant=tenant,
            received_time=received_time,
            enqueue_time=enqueue_time,
        )
        req.submit_time = self.clock()
        if deadline_s is not None:
            req.deadline = req.submit_time + deadline_s
        # distributed trace identity: accept the caller's W3C trace id
        # (the HTTP layer parses/generates `traceparent`), else mint one
        # when some instrument will record it — with everything off this
        # stays a pair of is-None checks, no id is ever generated
        if trace_id is None and (
            self.tracer is not None or self.request_log is not None
        ):
            trace_id = gen_trace_id()
        if trace_id is not None:
            req.extra["trace"] = trace_id
        # the weight version serving this request, stamped at admission:
        # journal admission records and request-log lines carry it, so a
        # stream that survives a mid-roll drain still reports the ONE
        # version it was admitted under (recover() overrides the stamp
        # with the original admission's version)
        req.extra["weights_version"] = self.weights_version
        try:
            # supervisor replays of already-admitted work are exempt from
            # the queue cap, like preemption requeues — the cap must not
            # orphan a request the engine had already accepted
            self.scheduler.add(req, exempt_cap=_recovered)
        except QueueFull:
            # backpressure, not a client error: count the reject so the
            # 429s the HTTP layer returns are visible in /metrics
            self.metrics.on_reject()
            raise
        if _recovered:
            # counted at its ORIGINAL submit (the metrics object survives
            # the restart); record the recovery itself instead
            self.metrics.on_recover()
        else:
            self.metrics.on_submit(req)
        if self.tracer is not None:
            self.tracer.request_phase(req.req_id, "queued", args=self._targs(
                req, prompt_len=req.prompt_len,
                max_new_tokens=max_new_tokens,
            ))
            if _recovered:
                # the LINK instant: a replay/drain continues the same
                # trace id — merged timelines connect through it
                self.tracer.request_instant(
                    req.req_id, "recovery-replay", args=self._targs(req))
        self._requests[req.req_id] = req
        if self.journal is not None and not _recovered:
            # recovered resubmits are re-journaled from recover() AFTER
            # their teacher-forced tokens are seeded, so a second crash
            # replays from the latest full state
            self.journal.admit(req, now=self.clock())
        if self.tokenizer is not None:
            self._detok[req.req_id] = IncrementalDetok(self.tokenizer)
        return req

    def recover(
        self,
        prompt_ids: np.ndarray | list[int],
        max_new_tokens: int,
        *,
        request_id: int,
        seed: int = 0,
        generated: list[int] | tuple[int, ...] = (),
        callback: Callable[[Request, int, str | None], None] | None = None,
        on_event: Callable[[Request, str], None] | None = None,
        deadline_s: float | None = None,
        deadline_at: float | None = None,
        trace_id: str | None = None,
        lineage: dict | None = None,
        speculative: bool = False,
        tenant: str = "default",
        weights_version: int | None = None,
        stamps: dict | None = None,
    ) -> Request:
        """Resubmit a request that was in flight when a previous engine
        instance died, with its already-delivered tokens teacher-forced.

        ``stamps`` (``scheduler.first_stamps`` of the request's earlier
        life, taken on a clock this engine shares — ``clone_fresh``
        does) puts the FIRST stamps and tick counts of its way to the
        first token back, as a preemption requeue keeps them: the stages
        then cover the restart instead of starting anew.

        ``trace_id`` continues the request's ORIGINAL W3C trace (a
        replay is a link in the same trace, never a fresh one);
        ``lineage`` carries the survival counters the canonical request
        log reports (``replays`` — supervised-restart/journal
        recoveries including this one, ``drains`` — adoptions by a live
        peer after a replica died).

        This is the evict-requeue discipline applied across an engine
        rebuild: ``generated`` pre-seeds the request, so its first
        prefill runs over prompt+generated (``effective_prompt``) and the
        decode RNG keys derive from (seed, content position) — the
        continuation is token-identical to an uninterrupted run, and the
        pre-seeded tokens are NOT re-emitted through the callback.

        Deadlines resume the REMAINING budget: ``deadline_at`` is the
        original absolute deadline on the engine clock (clone_fresh
        shares the clock, so it stays comparable across rebuilds) — a
        request promised N seconds at submit is not silently granted a
        fresh window by every crash (a crash loop would otherwise make
        its deadline unenforceable).  A deadline that expired while the
        engine was down is swept (aborted) on the first tick, exactly as
        if the engine had lived.  ``deadline_s`` (a fresh window from
        now) remains for callers that genuinely want a restart.  The
        caller filters requests that were already terminal (``generated``
        at budget, or ending in a stop token) — those need only their
        lost finish event, not a resubmit.
        """
        if deadline_s is not None and deadline_at is not None:
            raise ValueError("pass deadline_s or deadline_at, not both")
        if len(generated) >= max_new_tokens:
            raise ValueError(
                f"request {request_id} already generated "
                f"{len(generated)}/{max_new_tokens} tokens; deliver its "
                "finish event instead of recovering it"
            )
        req = self.submit(
            prompt_ids, max_new_tokens, request_id=request_id, seed=seed,
            callback=callback, on_event=on_event, deadline_s=deadline_s,
            trace_id=trace_id, speculative=speculative, tenant=tenant,
            _recovered=True,
        )
        if deadline_at is not None:
            req.deadline = deadline_at
        for name, value in (stamps or {}).items():
            if name in TTFT_STAMPS or name in TTFT_COUNTS:
                setattr(req, name, value)
        req.generated = [int(t) for t in generated]
        if weights_version is not None:
            # the ORIGINAL admission's weight version, not this engine's:
            # a drain onto an already-rolled peer must keep reporting
            # the version the request was admitted (and served) under
            req.extra["weights_version"] = int(weights_version)
        if lineage:
            # before the journal re-admission below, so a SECOND crash
            # replays the lineage along with the token state
            req.extra.update({
                k: int(v) for k, v in lineage.items()
                if k in ("replays", "drains")
            })
        if self.journal is not None:
            self.journal.admit(req, now=self.clock())
        detok = self._detok.get(req.req_id)
        if detok is not None:
            # advance the detokenizer over the replayed tokens so the
            # next delta continues the client's text exactly; the deltas
            # themselves were already delivered pre-crash
            for tok in req.generated:
                detok.push(tok)
        return req

    def finish_recovered(
        self,
        prompt_ids: np.ndarray | list[int],
        max_new_tokens: int,
        *,
        request_id: int,
        generated: list[int] | tuple[int, ...],
        reason: str,
        trace_id: str | None = None,
        lineage: dict | None = None,
        tenant: str = "default",
        weights_version: int | None = None,
    ) -> str | None:
        """Terminal bookkeeping for a request that was recovered ALREADY
        complete (every token generated pre-crash; only its finish event
        was lost) or that recovery had to drop: counts the finish/abort
        in metrics — which survive the rebuild, so submitted must keep
        balancing finished+aborted+live — without re-running anything.
        Returns the detokenizer's held-back tail text (a fresh detok
        replayed over the tokens yields the same delta sequence the
        original emitted, so what its flush holds is exactly what the
        lost finish event would have carried) for the caller to deliver.
        The companion to ``recover`` for the supervisor's replay path."""
        req = Request(
            req_id=request_id,
            prompt=np.asarray(prompt_ids, dtype=np.int32).reshape(-1),
            max_new_tokens=max_new_tokens,
        )
        req.generated = [int(t) for t in generated]
        req.finish_reason = reason
        req.tenant = tenant
        if trace_id is not None:
            req.extra["trace"] = trace_id
        req.extra["weights_version"] = int(
            weights_version if weights_version is not None
            else self.weights_version
        )
        if lineage:
            req.extra.update({
                k: int(v) for k, v in lineage.items()
                if k in ("replays", "drains")
            })
        if self.journal is not None:
            self.journal.terminal(request_id, reason)
        if reason == "aborted":
            self.metrics.on_abort(req)
        else:
            self.metrics.on_finish(req)
        if self.tenants is not None:
            # the tenant's bill survives the crash too: the recovered
            # terminal charges whatever cost fields the replay carried
            # (usually zero — the device time died with the old process)
            self.tenants.on_terminal(req)
        # the canonical log still gets its line (phases empty — the
        # timestamps died with the old process; the SLO verdict reports
        # it untimed rather than guessing)
        self._log_request(req, reason)
        if self.tracer is not None:
            # close whatever span the pre-crash engine left open so the
            # span-vs-metrics parity (finish instants == terminal
            # counters) holds across recoveries too
            self.tracer.request_end(request_id, reason, args=self._targs(
                req, recovered_terminal=True))
        if self.tokenizer is None or not req.generated:
            return None
        detok = IncrementalDetok(self.tokenizer)
        for tok in req.generated:
            detok.push(tok)
        return detok.flush() or None

    def clone_fresh(self, *, params: Params | None = None,
                    weights_version: int | None = None) -> "ServeEngine":
        """A fresh engine with the same params/config/geometry and a
        zeroed block pool — what a supervisor restart rebuilds after a
        crash.  The compiled step programs are SHARED with this engine
        (identical geometry → identical jaxprs), so a restart never
        re-traces or recompiles (pinned by tools/compile_counter.py), and
        the metrics object carries over so operator counters survive.

        ``params``/``weights_version`` override the weights — the
        rolling-upgrade rebuild (serve/replica.py): the jitted steps
        take params as a call ARGUMENT, so a swap to same-shaped
        weights reuses every warm compile (the new engine's build puts
        them into the layouts the shared step was compiled for:
        ``_lay_out_weights``), and a swap that changes the
        param avals re-traces once per shared callable — once per
        FLEET, because rolled peers adopt the first rebuilt replica's
        callables via ``share_compiled_steps``."""
        eng = ServeEngine(
            params if params is not None else self.params, self.config,
            sampler=self.sampler,
            stop_tokens=self.stop_tokens,
            max_slots=self.scheduler.max_slots,
            num_blocks=self.pool.num_blocks,
            block_size=self.block_size,
            max_seq_len=self.max_seq_len,
            prefill_chunk=self.prefill_chunk,
            cache_dtype=self.cache_dtype,
            enable_prefix_cache=self.pool.prefix_cache is not None,
            max_queue=self.scheduler.max_queue,
            tokenizer=self.tokenizer,
            clock=self.clock,
            fault_injector=self.faults,
            tracer=self.tracer,
            sample_epilogue=self.sample_epilogue_mode,
            tick_token_budget=self.tick_token_budget or None,
            mesh_plan=self.mesh_plan,
            mesh_devices=self._mesh_devices,
            journal=self.journal,
            request_log=self.request_log,
            sentinel=self.sentinel,
            actions=self.actions,
            telemetry=self.telemetry,
            weights_version=(
                weights_version if weights_version is not None
                else self.weights_version
            ),
            host_tier=self.host_tier,
            # the ledger rides the rebuild like metrics: a restart is the
            # same replica, so tenant bills must keep accumulating
            tenants=self.tenants,
            spec_k=self.spec_k,
            spec_ngram=self.spec_ngram,
            spec_min_accept=self.spec_min_accept,
            spec_window=self.spec_window,
            # the compiled step and the formats it reads its weights in:
            # the build puts ``params`` (these, or a roll's new ones) there
            steps_from=self,
        )
        eng.metrics = self.metrics
        eng.decode_degraded = self.decode_degraded
        eng._next_id = self._next_id
        if self._restore_block is not None and eng._restore_block is not None:
            # the tier rides the rebuild (host entries survive the
            # crash — the zeroed pool restores instead of re-prefilling)
            # and identical geometry means identical tier jaxprs
            eng._restore_block = self._restore_block
            eng._slice_block = self._slice_block
        return eng

    def share_compiled_steps(self, src: "ServeEngine") -> None:
        """Adopt ``src``'s jitted step callables (geometry-identical
        engines only — the fleet's homogeneity check guarantees it).
        A rolling upgrade calls this on every rolled replica after the
        first, so new-weight avals are traced/compiled once per FLEET,
        not once per replica; an elastic ``add_replica`` clone uses it
        the same way.

        Placement-guarded: the step closures pin output shardings to
        the BUILDING engine's mesh (``_constrain_pages``), so engines
        on different device slices (DP placement meshes — one chip per
        replica) must keep their own callables; adopting a peer's
        would pin this replica's pages to the peer's devices and fault
        at dispatch.  Those fleets compile once per device slice —
        still once per set of identical placements, never per roll."""
        if not self._same_placement(src):
            return
        if self._restore_block is not None \
                and src._restore_block is not None:
            self._restore_block = src._restore_block
            self._slice_block = src._slice_block
        if self.ragged_attn_impl == src.ragged_attn_impl \
                and self.epilogue_impl == src.epilogue_impl:
            self._mixed_step = src._mixed_step
            # ... and this engine's weights go where THAT step reads them
            self._weight_formats = src._weight_formats
            self.weights_reput = tuple(map(
                sum, zip(self.weights_reput, self._lay_out_weights())))

    def _same_placement(self, src: "ServeEngine") -> bool:
        """Do both engines place params/pool/operands on the same
        device set?  (Sharing compiled steps across placements is a
        correctness error, not an optimization miss.)"""
        if self.mesh is None and src.mesh is None:
            return True
        if self.mesh is None or src.mesh is None:
            return False
        return list(self.mesh.devices.flat) == list(src.mesh.devices.flat)

    def _targs(self, req: Request, **kw: Any) -> dict:
        """Span args with the request's W3C trace id merged in (when it
        has one) — what lets ``summarize_trace --merge`` stitch the
        per-replica fragments of one request back together.  Callers
        hold the tracer is-None guard; with tracing off this never
        runs."""
        tid = req.extra.get("trace")
        if tid is not None:
            kw["trace"] = tid
        if req.tenant != "default":
            kw["tenant"] = req.tenant
        return kw

    def _log_request(self, req: Request, reason: str) -> None:
        """Emit the canonical wide-event line for a terminal request
        (enqueue only — the request-log writer thread does the IO)."""
        if self.request_log is None:
            return
        tracker = getattr(self.metrics, "slo", None)
        self.request_log.emit(request_record(
            req, reason=reason,
            policy=tracker.policy if tracker is not None else None,
            clock=self.clock,
        ))

    def _sentinel_observe(
        self, phases: tuple[tuple[str, float, float], ...],
    ) -> list[dict]:
        """Feed one tick's phase slices to the anomaly sentinel; an
        outlier stamps a trace instant naming the guilty phase and
        bumps the per-phase anomaly counter.  Returns the outliers —
        the tick's ``_actions_tick`` hands them to the ActionPolicy."""
        sent = self.sentinel
        if sent is None:
            return []
        outliers = sent.observe(phases)
        if not outliers:
            return []
        for o in outliers:
            self.metrics.on_anomaly(str(o["phase"]))
        guilty = outliers[0]
        if self.tracer is not None:
            self.tracer.instant("anomaly", cat="sentinel", args={
                "phase": guilty["phase"],
                "dur_us": round(float(guilty["dur_us"]), 1),
                "baseline_us": round(float(guilty["baseline_us"]), 1),
                "tick": sent.ticks,
            })
        return outliers

    def _phase_mark(self, name: str | None, **meta: int) -> float:
        """The boundary between two phases of the unified tick, with a
        tracer attached (every caller holds the ``tracer is not None``
        guard): close the tick thread's open ``serve.<phase>`` profiler
        annotation, stamp the recorder's clock, open ``name`` (None:
        nothing — the dispatch brings its own annotation, the tick's
        end opens none) with ``meta`` as the annotation's metadata (the
        dispatch's ``seq``; the name itself never changes).  A tick that
        died mid-phase leaves its annotation to the next mark."""
        ann = self._phase_ann
        if ann is not None:
            ann.__exit__(None, None, None)
            self._phase_ann = None
        now = self.tracer.now_us() if self.tracer is not None else -1.0
        if name is not None:
            self._phase_ann = ann = jax.profiler.TraceAnnotation(
                name, **meta)
            ann.__enter__()
        return now

    def _tick_budget(self) -> int:
        """This tick's token budget: the configured budget, capped by
        the ActionPolicy's shed-prefill verdict (decode rows are never
        shed — the floor is max_slots)."""
        if self.actions is None:
            return self.tick_token_budget
        return self.actions.plan_budget(
            self.tick_token_budget, self.scheduler.max_slots
        )

    def _actions_tick(self, outliers: list[dict]) -> None:
        """Feed one tick's sentinel verdicts + SLO burn to the
        ActionPolicy; count and trace every action flip (the
        ``llm_serve_lifecycle_actions_total{action=}`` series and the
        ``lifecycle-action`` trace instants the auto-action e2e reads).
        ``self.actions`` is re-read per hook like tracer/metrics — the
        supervisor mutes a zombie engine by clearing it."""
        if self.actions is None:
            return
        for action in self.actions.on_tick(
            outliers, getattr(self.metrics, "slo", None)
        ):
            self.metrics.on_lifecycle_action(action)
            if self.tracer is not None and self.actions is not None:
                self.tracer.instant(
                    "lifecycle-action", cat="lifecycle",
                    args={"action": action, **self.actions.state_args()},
                )

    # -- a token's way out, cut in two ---------------------------------
    # ``accept`` is what the NEXT plan reads (the token in
    # ``req.generated``, the timestamps, finish decided and the slot and
    # blocks released); ``publish`` is what the outside is handed
    # (metrics, detokenizer, callbacks, request log, tracer, journal).
    # The tick accepts tick N and publishes it behind tick N+1's
    # dispatch (``_accept`` → ``_owed`` → ``_publish``).

    def _accept_token(self, req: Request, token: int) -> None:
        req.generated.append(token)
        if req.first_token_time is None:
            req.first_token_time = self.clock()

    def _accept_finish(self, req: Request) -> str | None:
        """Decide finish on the request's newest token; when it ends the
        stream, release the request (slot and blocks are free for the
        next admission) and return the reason."""
        hit_stop = bool(
            self.stop_tokens and req.generated
            and req.generated[-1] in self.stop_tokens
        )
        if not (req.done or hit_stop):
            return None
        # a stop token on the last budgeted step still reports
        # "stop": the model chose to end, the budget merely agreed
        req.finish_reason = "stop" if hit_stop else "length"
        req.finish_time = self.clock()
        self.scheduler.finish(req)
        self._requests.pop(req.req_id, None)
        self._draft_states.pop(req.req_id, None)
        return req.finish_reason

    def _publish_token(self, req: Request, token: int) -> None:
        self.metrics.on_token(req)
        if req.callback is not None:
            delta = None
            detok = self._detok.get(req.req_id)
            if detok is not None:
                delta = detok.push(token)
            req.callback(req, token, delta)

    def _publish_finish(self, req: Request, reason: str) -> None:
        self._flush_detok(req)
        self.metrics.on_finish(req)
        if self.tenants is not None:
            self.tenants.on_terminal(req)
        if self.journal is not None:
            # flush the final delivery delta (the finishing tick's
            # token would otherwise be missed — the request left the
            # live set at accept), then mark terminal so the replay set
            # stays exact
            self.journal.end_tick((req,))
            self.journal.terminal(req.req_id, reason)
        self._log_request(req, reason)
        if self.tracer is not None:
            self.tracer.request_end(req.req_id, reason,
                                    args=self._targs(req))
        self._emit_event(req, reason)

    def _emit_event(self, req: Request, event: str) -> None:
        if req.on_event is not None:
            req.on_event(req, event)

    def _flush_detok(self, req: Request) -> None:
        """Pop the request's detokenizer and park any held-back tail text
        (mid-UTF-8 merge) in ``req.extra['final_text_delta']`` — terminal
        events carry it so streams don't lose their last characters."""
        detok = self._detok.pop(req.req_id, None)
        if detok is not None:
            tail = detok.flush()
            if tail:
                req.extra["final_text_delta"] = tail

    def _accept(self, req: Request, token: int,
                kind: int = _OWED_TOKEN) -> bool:
        """The unified tick's accept of one sampled token of a RUNNING
        row: into ``req.generated``, finish decided, and the token (then
        the terminal) owed to the outside.  True when the request
        finished on it."""
        self._accept_token(req, token)
        self._owed.append((kind, req, token))
        reason = self._accept_finish(req)
        if reason is None:
            return False
        self._owed.append((_OWED_FINISH, req, reason))
        return True

    def _publish(self, overlapped: bool, limit: int | None = None) -> int:
        """Hand out what ``accept`` owes, in emit order: per request
        every token once, then its terminal.  ``overlapped`` says
        whether a dispatch is in flight (the tick's ``deliver`` phase
        behind its dispatch) or the list is drained on the spot (no
        dispatch follows).  ``limit``: the first so many items alone
        (an aborted request's, ``_settle_owed``).  The journal's
        delivery watermark moves HERE, after the callbacks, so it never
        runs ahead of what they were handed.  A callback may abort any
        request: ``abort`` then drops that request's remaining items.
        Returns the number of items handed out."""
        owed = self._owed
        if not owed or self._publishing:
            return 0
        whole = limit is None
        if whole:
            limit = len(owed)
        self._publishing = True
        n = 0
        try:
            while owed and n < limit:
                kind, req, val = owed.popleft()
                n += 1
                if kind == _OWED_FINISH:
                    self._publish_finish(req, val)
                    continue
                t_emit = None
                if kind == _OWED_FIRST and req.first_emit_time is None:
                    # the emit, stamped BEFORE the callback: where the
                    # way to the first token ends on the tick thread
                    req.first_emit_time = t_emit = self.clock()
                self._publish_token(req, val)
                if (kind == _OWED_FIRST and self.tracer is not None
                        and req.state is RequestState.RUNNING):
                    # (not finished on it, not aborted from its callback,
                    # not preempted back into the queue since); ``decode``
                    # begins AT the emit, ahead of the frame it stands
                    # for (a re-prefill's: now)
                    self.tracer.request_phase(
                        req.req_id, "decode",
                        ts_us=(self.tracer.us_at(t_emit)
                               if t_emit is not None else None))
        finally:
            self._publishing = False
        if not whole:
            return n  # its abort writes the watermark with the terminal
        self.metrics.on_publish(overlapped)
        if self.journal is not None:
            # ONE delivery-watermark record for the whole tick (rows for
            # every live request whose count advanced) — batched per
            # tick, never per token.  The list is empty, so every live
            # request's ``generated`` is what its callback was handed; a
            # verify round's rows carry every ACCEPTED token: rejected
            # drafts never reach req.generated.  (Finished and aborted
            # requests wrote theirs with their terminal.)
            self.journal.end_tick(self._requests.values())
        return n

    def publish_owed(self) -> int:
        """Hand out what the last tick still owes, on the spot — for a
        caller that reads requests' ``generated`` between ticks as what
        was delivered (a direct-mode drain or restart, serve/replica)."""
        return self._publish(False)

    def _settle_owed(self, req: Request) -> None:
        """What an aborted request is still owed, settled before its
        ``aborted`` event.  Between ticks the tokens go out first.  From
        inside a token callback (a publish is running) they are dropped
        and taken back out of ``req.generated``: the ``aborted`` event
        follows the token whose callback asked for it.  Either way
        ``req.generated`` ends as exactly what the callback was
        handed."""
        owed = self._owed
        mine = [item for item in owed if item[1] is req]
        if not mine:
            return
        rest = [item for item in owed if item[1] is not req]
        owed.clear()
        if self._publishing:
            owed.extend(rest)
            del req.generated[-len(mine):]
        else:
            owed.extend(mine + rest)
            self._publish(False, limit=len(mine))

    def abort(self, request_id: int) -> bool:
        """Cancel a live request — queued, prefilled, or mid-decode.

        Its decode slot frees, its block references drop (refcounted
        decref: prefix blocks shared with other requests survive, and
        blocks this request registered in the prefix cache stay
        registered under the cache's own reference), and the terminal
        ``"aborted"`` event fires.  Returns False when the id is unknown
        or already terminal — an abort racing a natural finish is a
        no-op, not an error (the HTTP layer aborts on every client
        disconnect, including disconnects after [DONE]).

        Tokens of the request that the unified tick accepted and has not
        published yet are settled first (``_settle_owed``): handed out
        when the abort comes between ticks, dropped when it comes from
        inside a token callback — ``req.generated`` holds exactly what
        the callback was handed, and the ``aborted`` event follows it.
        A request that already finished at ``accept`` is terminal: its
        owed tokens and its own terminal still go out.

        NOT thread-safe, like every other engine entry point: callers
        off the tick thread go through the HTTP runner's command queue.
        """
        req = self._requests.pop(request_id, None)
        if req is None:
            return False
        self._draft_states.pop(request_id, None)
        self._settle_owed(req)
        self.scheduler.abort(req)
        req.finish_reason = "aborted"
        req.finish_time = self.clock()
        self._flush_detok(req)
        self.metrics.on_abort(req)
        if self.tenants is not None:
            # aborted work is still billed work: whatever device cost the
            # request accrued before cancellation lands on its tenant
            self.tenants.on_terminal(req)
        if self.journal is not None:
            self.journal.end_tick((req,))
            self.journal.terminal(req.req_id, "aborted")
        self._log_request(req, "aborted")
        if self.tracer is not None:
            self.tracer.request_end(req.req_id, "aborted",
                                    args=self._targs(req))
        self._emit_event(req, "aborted")
        if not self.scheduler.has_work:
            # no tick follows: what other requests are owed goes out now
            self._publish(False)
        return True

    def _sweep_deadlines(self) -> None:
        """Abort every live request past its deadline (checked once per
        tick — a deadline can overshoot by at most one tick)."""
        now = self.clock()
        expired = [
            r.req_id
            for r in self._requests.values()
            if r.deadline is not None and now >= r.deadline
        ]
        for rid in expired:
            self.abort(rid)

    def _fair_prefill_order(self, running: list[Request]) -> list[Request]:
        """Fair-share prefill ordering (``--tenant-fairness``): rank the
        running list by each tenant's accumulated cost share — terminal
        charges plus live work-so-far, byte-based when the telemetry
        roofline is attached, token-based otherwise — so the tick's
        prefill budget fills smallest-share-first.  The sort is STABLE
        over the scheduler's admission-ordered running list, so within a
        tenant requests stay oldest-first, and with one tenant (or the
        hook off) every key ties and the order is byte-identical to
        fairness-off.  Decode rows are untouched: ``plan_tick`` only
        consults this for the prefill fill, so running decodes are never
        starved by a cheaper tenant's arrivals."""
        if self.tenants is None:
            return running
        share = self.tenants.cost_shares(
            running, use_bytes=self.telemetry is not None,
        )
        return sorted(running, key=lambda r: share.get(r.tenant, 0.0))

    # ------------------------------------------------------------------
    # The tick (mixed_step)
    # ------------------------------------------------------------------
    def step(self) -> bool:
        """One scheduler tick (``_step_mixed``); returns True while work
        remains or a token is still owed to a callback.

        The contract with callbacks: the tokens and terminals a tick
        accepted reach ``callback`` / ``on_event`` during the NEXT
        ``step()`` (behind its dispatch), per request in order and
        exactly once — or before this ``step()`` returns when it
        dispatched nothing or leaves no work behind, so ``False`` means
        nothing is owed.  While it returns True, ``req.generated`` may
        be one tick ahead of what the callbacks were handed
        (``publish_owed`` closes the gap); inside a token callback it
        may hold the rest of that tick's tokens already."""
        return self._step_mixed()

    def _init_mixed_prefill(self, req: Request) -> None:
        """Admission bookkeeping for the unified tick: fix the request's
        left-pad and prefill target, pre-mark prefix-cache-covered
        content as done (covered chunks consume NO tick budget and are
        attended in place through the block table), and stash the
        teacher-forced content for the packer."""
        content = req.effective_prompt()
        w = self._prefill_width(req)
        req.pad = w - content.size
        shared_slots = req.n_shared_blocks * self.block_size
        req.prefill_target = int(content.size)
        req.prefill_done = max(shared_slots - req.pad, 0)
        req.prefilled = False
        req.extra["prefill_content"] = content

    def _pack_mixed(
        self,
        decode_rows: list[Request],
        prefill_segs: list[tuple[Request, int]],
    ) -> tuple[np.ndarray, tuple[int, int], int]:
        """Build the mixed step's packed operand from the planner's
        verdict: ONE host int32 array (``mixed_operand_layout``; the
        tick places it with one ``_put``), its program ``(t_w, d_w)``,
        and how many rows the array path filled.  The batch is laid out
        on TWO axes.  On the dense one — the step's token axis — each
        row's token segment lands at consecutive lanes with no
        alignment, decode rows first, and the only dead lanes are the
        tail up to ``d_w`` (they point at the scratch block).  On the
        tiled one — the ragged kernel's — every segment starts at a
        q-tile multiple, so a tile belongs to one row; ``lane_tok`` /
        ``tok_lane`` tie the two (dead tile lanes read token 0 and are
        masked by ``tile_qlen``).  The program is the first that holds
        the aligned total and the token total (``_pick_bucket``), so
        the dispatch reuses a warm compile whatever the prefill:decode
        mix.

        A decode row with no drafts is one token in one tile: all of
        them are written together (``_fill_decode_rows``).  Speculating
        rows and prefill chunks, a few a tick, go segment by segment
        (``_fill_segment``).

        In a program with WIDE tiles (``_wide_program``: the rungs only
        a tick with a prompt chunk runs) a prompt segment's tokens go
        ``wide`` to a tile as far as whole tiles reach — those tiles
        first on the tiled axis, each at a multiple of ``wide`` — and
        what is left of it in tiles of ``q_tile`` with everything else
        behind them: the same lanes in all (``wide`` is whole tiles), so
        the program is the one the totals pick either way."""
        qb = self._q_tile
        self._window_recycled_tick = 0
        self._prefill_tiles_tick = self._prefill_tile_tokens_tick = 0
        sizes = [1 + r.draft_len for r in decode_rows]
        sizes.extend(n for _, n in prefill_segs)
        dense = list(itertools.accumulate(sizes, initial=0))
        tiled = list(itertools.accumulate(
            (_ceil_to(n, qb) for n in sizes), initial=0))
        program = self._pick_bucket(tiled[-1], dense[-1])
        n_dec = len(decode_rows)
        wide = self._wide_program(program[0])
        wide_at: list[int] = []
        if wide:
            # the whole wide tiles of each prompt segment, then every
            # segment's tiles of ``q_tile`` (a prompt segment's: of what
            # is left of it)
            full = [n // wide * wide for _, n in prefill_segs]
            wide_at = list(itertools.accumulate(full, initial=0))
            tiled = list(itertools.accumulate(
                (_ceil_to(n - w, qb)
                 for n, w in zip(sizes, [0] * n_dec + full)),
                initial=wide_at[-1]))
        layout, size = self._mixed_layouts[program]
        ops = np.zeros(size, np.int32)
        sec = split_mixed_operands(ops, layout)
        plain = [(r, cur, lane)
                 for r, cur, lane in zip(decode_rows, dense, tiled)
                 if not r.draft_len]
        if plain:
            self._fill_decode_rows(sec, *zip(*plain))
        for r, cur, lane in zip(decode_rows, dense, tiled):
            if not r.draft_len:
                continue
            toks = [r.generated[-1]]
            toks.extend(int(t) for t in r.extra["spec_draft"][: r.draft_len])
            self._fill_segment(sec, r, np.asarray(toks, np.int32),
                               r.cache_len - 1, len(toks), cur, lane)
        for i, ((r, n), cur, lane) in enumerate(zip(
                prefill_segs, dense[n_dec:], tiled[n_dec:])):
            content = r.extra["prefill_content"]
            self._fill_segment(
                sec, r,
                np.asarray(content[r.prefill_done:r.prefill_done + n],
                           np.int32),
                r.pad + r.prefill_done,
                1 if r.prefill_done + n >= r.prefill_target else 0,
                cur, lane, *((wide, wide_at[i]) if wide else ()))
        return ops, program, len(plain)

    def _advance_window(self, sec: dict[str, np.ndarray], slot: Any,
                        start: Any, n: int) -> None:
        """The row in ``slot`` (or the rows: arrays) writes cache slots
        ``start .. start + n - 1`` this tick: move its chain of the
        window class (a block no query of this or a later tick can see
        goes back to the slot's ring: block_pool.WindowRings — host
        bookkeeping, no device work) and write the chain into the
        operand's second table."""
        rings = self.pool.window
        self._window_recycled_tick += rings.advance(slot, start, n)
        sec["wtables"][slot] = rings.table(slot)
        sec["wfirst"][slot] = rings.first[slot]

    def _fill_decode_rows(self, sec: dict[str, np.ndarray],
                          rows: Sequence[Request],
                          curs: Sequence[int],
                          lanes: Sequence[int]) -> None:
        """Plain decode rows — one token at dense index ``curs[i]``, one
        tile at lane ``lanes[i]``, one sample slot each — written into
        the operand's sections by whole-array assignments: what
        ``_fill_segment`` writes for each, without a numpy call per row
        and field.  The block table is the one write left per row."""
        bs = self.block_size
        tables = sec.get("tables")  # None: a pool with no page class
        slot, tok, sl, pad, seed, blk = [], [], [], [], [], []
        for r in rows:
            last = r.cache_len - 1
            if tables is not None:
                ids = r.block_ids
                tables[r.slot, :len(ids)] = ids
                blk.append(ids[last // bs])
            slot.append(r.slot)
            tok.append(r.generated[-1])
            sl.append(last)
            pad.append(r.pad)
            seed.append(r.seed)
        slot = np.asarray(slot, np.intp)
        sl = np.asarray(sl, np.int32)
        if self.window_blocks:
            self._advance_window(sec, slot, sl, 1)
        pos = sl - np.asarray(pad, np.int32)
        cur = np.asarray(curs, np.intp)
        sec["tokens"][cur] = tok
        sec["positions"][cur] = pos
        sec["tok_row"][cur] = slot
        sec["tok_live"][cur] = 1
        if tables is not None:
            lane = np.asarray(lanes, np.intp)
            tile = lane // self._q_tile
            sec["tok_blk"][cur] = blk
            sec["tok_off"][cur] = sl % bs
            sec["tok_slot"][cur] = sl
            sec["tok_lane"][cur] = lane
            sec["lane_tok"][lane] = cur
            sec["tile_row"][tile] = slot
            sec["tile_qpos0"][tile] = sl
            sec["tile_qlen"][tile] = 1
        sec["pads"][slot] = pad
        sec["seeds"][slot] = np.asarray(seed, np.uint32)
        sec["verify_len"][slot] = 1
        sec["last_idx"][slot, 0] = cur
        sec["sample_pos"][slot, 0] = pos

    def _fill_segment(self, sec: dict[str, np.ndarray], r: Request,
                      toks: np.ndarray, start_slot: int, n_verify: int,
                      cur: int, lane: int, wide: int = 0,
                      wide_lane: int = 0) -> None:
        """One row's token segment at dense index ``cur`` and tile lane
        ``lane`` (a q-tile multiple): its tokens occupy cache slots
        ``start_slot..`` and its LAST ``n_verify`` tokens are sampled —
        a speculating row samples its whole verify slice (input +
        drafts), a completing prefill 1 (its last token), a mid-prefill
        chunk 0.  ``wide``: the segment's first ``n // wide`` whole wide
        tiles lie from lane ``wide_lane`` on (a multiple of ``wide``;
        the first of a wide tile's ``wide / q_tile`` entries names it,
        the others stay dead) and only what is left of it from ``lane``
        on."""
        qb, bs = self._q_tile, self.block_size
        n = toks.size
        slot = r.slot
        if self.window_blocks:
            self._advance_window(sec, slot, start_slot, n)
        sec["pads"][slot] = r.pad
        sec["seeds"][slot] = np.uint32(r.seed)
        idx = np.arange(n, dtype=np.int32)
        sl = start_slot + idx
        sec["tokens"][cur:cur + n] = toks
        sec["positions"][cur:cur + n] = sl - r.pad
        sec["tok_row"][cur:cur + n] = slot
        sec["tok_live"][cur:cur + n] = 1
        if "tables" in sec:  # (absent: a pool with no page class)
            blocks = np.asarray(r.block_ids, np.int32)
            sec["tables"][slot, :blocks.size] = blocks
            sec["tok_blk"][cur:cur + n] = blocks[sl // bs]
            sec["tok_off"][cur:cur + n] = sl % bs
            sec["tok_slot"][cur:cur + n] = sl
            full = n // wide * wide if wide else 0
            lanes = lane + idx
            if full:
                lanes[:full] += wide_lane - lane
                lanes[full:] -= full
                w0 = np.arange(0, full, wide)  # each wide tile's first token
                tiles = (wide_lane + w0) // qb
                sec["tile_row"][tiles] = slot
                sec["tile_qpos0"][tiles] = start_slot + w0
                sec["tile_qlen"][tiles] = wide
            sec["tok_lane"][cur:cur + n] = lanes
            sec["lane_tok"][lanes] = cur + idx
            q0 = np.arange(full, n, qb)  # each tile's first token
            tiles = slice(lane // qb, lane // qb + q0.size)
            qlen = np.minimum(qb, n - q0)
            sec["tile_row"][tiles] = slot
            sec["tile_qpos0"][tiles] = start_slot + q0
            sec["tile_qlen"][tiles] = qlen
            # the tiles that hold more than one token, and the tokens in
            # them (``/metrics`` attn_prefill_tile_tokens)
            many = qlen > 1
            self._prefill_tiles_tick += full // wide if full else 0
            self._prefill_tiles_tick += int(many.sum())
            self._prefill_tile_tokens_tick += full + int(qlen[many].sum())
        if n_verify:
            first = n - n_verify  # verify slots = the last n_verify
            sec["verify_len"][slot] = n_verify
            sec["last_idx"][slot, :n_verify] = (
                cur + first + np.arange(n_verify))
            sec["sample_pos"][slot, :n_verify] = sl[first:] - r.pad

    def _finish_mixed_prefill(self, req: Request, tok: int) -> None:
        """A row's prefill reached its target this tick: register its
        prompt blocks with the prefix cache (they are already IN the
        pool — direct writes, nothing to copy) and accept the first
        token sampled by the same dispatch."""
        req.prefilled = True
        req.extra.pop("prefill_content", None)
        pc = self.pool.prefix_cache
        keys = req.extra.pop("prefix_keys", None)
        req.extra.pop("prefix_keys_width", None)
        if pc is not None and keys:
            pc.register(keys, req.block_ids[: len(keys)])
            self.metrics.on_prefix(
                requested=len(keys), hits=req.n_shared_blocks
            )
        first = req.first_token_time is None
        self._accept(req, tok, _OWED_FIRST)
        if first and self.tracer is not None:
            # the accept of the first token, with the number of the
            # dispatch that sampled it (the tick's and its two profiler
            # annotations' ``seq``)
            self.tracer.request_instant(
                req.req_id, "first_token",
                ts_us=self.tracer.us_at(req.first_token_time),
                args={"seq": self.n_mixed_dispatches})

    def _note_prefill_grants(
        self, prefill_segs: list[tuple[Request, int]],
    ) -> tuple[int, int, int]:
        """The plan's prompt grants, read for what they say of the lane:
        a row's fair share of a tick is ``min(prefill_chunk, remaining)``
        and what its grant holds beyond that is leftover of the prompt
        lane (``plan_tick``'s second pass).  Returns the leftover handed
        out, the rows that received it (0: a decode-only or
        fair-share-only tick) and the mid-prefill rows granted nothing.

        On the way it keeps each row's tick counts and stamps its
        ``lane_time`` / ``last_chunk_time`` (scheduler.TTFT_STAMPS) —
        ONE clock read a tick in which a row takes the lane or its last
        chunk, none otherwise; a row past its first token (a re-prefill
        after preemption) keeps what it has.  Per mid-prefill row, never
        per decode row: the starved walk runs only in a tick that
        granted some row nothing."""
        chunk = self.prefill_chunk
        now = None
        lane_tokens = lane_rows = 0
        for r, n in prefill_segs:
            left = r.prefill_target - r.prefill_done
            over = n - min(chunk, left)
            if over > 0:
                lane_tokens += over
                lane_rows += 1
            if r.first_token_time is not None:
                continue
            r.prefill_ticks += 1
            if over > 0:
                r.lane_ticks += 1
            if r.lane_time is None and (over > 0 or n >= left):
                r.lane_time = now = self.clock() if now is None else now
                if self.tracer is not None:
                    self.tracer.request_instant(
                        r.req_id, "lane", ts_us=self.tracer.us_at(now),
                        args={
                            # mid-prefill rows older than this one when
                            # it was admitted, and the prompt tokens it
                            # was granted before it got the lane
                            "rows_ahead": r.extra.pop("rows_ahead", 0),
                            "fair_tokens": r.prefill_done - max(
                                r.n_shared_blocks * self.block_size
                                - r.pad, 0),
                        })
            if n >= left and r.last_chunk_time is None:
                r.last_chunk_time = now = self.clock() if now is None else now
                if self.tracer is not None:
                    self.tracer.request_instant(
                        r.req_id, "last_chunk", ts_us=self.tracer.us_at(now),
                        args={"prefill_ticks": r.prefill_ticks,
                              "lane_ticks": r.lane_ticks,
                              "starved_ticks": r.starved_ticks,
                              # the dispatch this plan becomes
                              "seq": self.n_mixed_dispatches + 1})
        starved = self.scheduler.n_mid_prefill - len(prefill_segs)
        if starved > 0:
            granted = {id(r) for r, _ in prefill_segs}
            for r in self.scheduler.running:
                if (not r.prefilled and r.first_token_time is None
                        and id(r) not in granted):
                    r.starved_ticks += 1
        return lane_tokens, lane_rows, starved

    def _draft_tick(self) -> int:
        """Propose draft tokens for every speculating decode row —
        HOST-SIDE prompt lookup (serve/spec.DraftState), no device work,
        so the whole draft phase costs dictionary probes and the tick
        stays at ~1 dispatch.  Sets ``Request.draft_len`` (the verify
        width the planner budgets and growth covers) and stashes the
        tokens in ``extra['spec_draft']``; returns the proposed count
        for the trace args.  The cap keeps every verify write inside the
        request's cache ceiling and every possible accept inside its
        token budget."""
        if not self.spec_k:
            return 0
        from llm_np_cp_tpu.serve.spec import DraftState

        total = 0
        for r in self.scheduler.running:
            r.draft_len = 0
            if not (r.speculative and r.prefilled and r.generated):
                continue
            if r.extra.get("spec_off"):
                continue
            rem = r.max_new_tokens - len(r.generated)
            cap = min(self.spec_k, rem - 1,
                      self.max_seq_len - r.cache_len)
            if cap <= 0:
                continue
            st = self._draft_states.get(r.req_id)
            if st is None:
                # lazily built (recovery/preemption re-admissions land
                # here too): the stream is prompt + generated, exactly
                # what an uninterrupted request would have indexed
                st = DraftState(self.spec_ngram)
                st.extend(int(t) for t in r.prompt)
                self._draft_states[r.req_id] = st
            st.extend(r.generated[st.size - r.prompt_len:])
            draft = st.propose(cap)
            if draft:
                r.extra["spec_draft"] = draft
                r.draft_len = len(draft)
                total += len(draft)
        return total

    def _spec_feedback(self, req: Request, drafted: int,
                       accepted: int) -> None:
        """One verify round's accounting + the per-request fallback: a
        stream whose rolling acceptance collapses below
        ``spec_min_accept`` stops drafting (plain decode row from then
        on), so cold streams cost at most one wasted verify window —
        never a standing tax on the tick budget."""
        self.metrics.on_spec(drafted=drafted, accepted=accepted)
        st = req.extra.setdefault("spec_acc", [0, 0])
        st[0] += drafted
        st[1] += accepted
        if st[0] < self.spec_window:
            return
        if st[1] < self.spec_min_accept * st[0]:
            req.extra["spec_off"] = True
            self._draft_states.pop(req.req_id, None)
            if self.tracer is not None:
                self.tracer.request_instant(
                    req.req_id, "spec-fallback", args=self._targs(
                        req, drafted=st[0], accepted=st[1],
                    ))
        else:
            st[0] //= 2
            st[1] //= 2

    def _step_mixed(self) -> bool:
        """One unified tick: deadline sweep + admission, draft proposal,
        block growth, token-budget planning, then ONE mixed ragged
        dispatch covering every planned prefill chunk slice, plain
        decode row, and speculative verify slice.  The tokens of the
        PREVIOUS tick go out (``deliver``: ``_publish``) while this
        tick's program runs, between the dispatch and the fetch; after
        the fetch only ``accept`` stays on the device's critical path —
        what the next plan reads.  A tick that dispatches nothing, and a
        tick after which no work is left, hands out what is owed on the
        spot: a token never waits across an idle engine.  Phase slices
        (``admission`` / ``draft`` / ``grow`` / ``plan`` / ``pack`` /
        ``h2d`` / ``mixed_dispatch`` / ``deliver`` / ``host_sync`` /
        ``accept`` / ``account``, serve/tracing.MIXED_TICK_PHASES) keep the
        consecutive-timestamps sum-to-tick invariant, and each runs
        under a ``serve.<phase>`` profiler annotation (``_phase_mark``)
        so a device profile holds the host's phases on its own clock;
        the tick args additionally carry the prefill/decode token
        split, the dispatch's live context and packed width, the
        tick thread's own CPU time — and, on spec-enabled engines, the
        draft/accept token split — so tools/summarize_trace.py and the
        benchmark's readers can say where a tick went.
        ``self.tracer`` is re-read at EVERY hook (never cached in a
        local for the whole tick): a supervisor restart mutes the dead
        engine by clearing the attribute, and a watchdog-superseded but
        still-running zombie tick must stop writing into the shared
        recorder as soon as that mute lands.  Timestamps default to -1
        so a tick that STARTED untraced never emits a garbage span if a
        tracer is attached mid-tick."""
        t0 = (self._phase_mark("serve.admission")
              if self.tracer is not None else -1.0)
        cpu0 = time.thread_time_ns() if self.tracer is not None else 0
        fetches0 = self.n_host_fetches
        if self.host_tier is not None:
            self._tier_spill_bytes = 0
            self._tier_restore_bytes = 0
            self._tier_restore_us = 0.0
        self._sweep_deadlines()
        admitted = self.scheduler.admit()
        # mid-prefill rows older than the first of these admissions (the
        # request track's ``lane`` instant says how many stood ahead)
        rows_ahead = (
            sum(1 for r in self.scheduler.running if not r.prefilled)
            - len(admitted)
            if admitted and self.tracer is not None else 0)
        for req in admitted:
            if req.admit_time is None:
                req.admit_time = self.clock()
            # stage this admission's host-tier hits FIRST so the writer
            # thread's device_puts overlap the rest of the admission
            # loop; they land (_apply_tier_restores below) before any
            # growth/eviction could free a target block and before the
            # covering dispatch attends them
            self._enqueue_tier_restores(req)
            self._init_mixed_prefill(req)
            if self.tracer is not None:
                if req.lane_time is None:
                    req.extra["rows_ahead"] = rows_ahead
                rows_ahead += 1
                self.tracer.request_phase(
                    req.req_id, "prefill", args=self._targs(
                        req, shared_blocks=req.n_shared_blocks,
                        preemptions=req.n_preemptions,
                    ))
        self._apply_tier_restores(admitted)
        t1 = (self._phase_mark("serve.draft")
              if self.tracer is not None else -1.0)

        self._draft_tick()
        td = (self._phase_mark("serve.grow")
              if self.tracer is not None else -1.0)

        for req in self.scheduler.ensure_decode_blocks():
            if self.tracer is not None:
                self.tracer.request_instant(req.req_id, "evicted-requeued")
                self.tracer.request_phase(req.req_id, "queued")
            self._emit_event(req, "evicted-requeued")
        t2 = (self._phase_mark("serve.plan")
              if self.tracer is not None else -1.0)

        decode_rows, prefill_segs = self.scheduler.plan_tick(
            self._tick_budget(), self.prefill_chunk,
            prefill_order=(
                self._fair_prefill_order
                if self.tenants is not None and self.tenants.fairness
                else None
            ),
        )
        lane_tokens, lane_rows, starved_rows = (
            self._note_prefill_grants(prefill_segs)
            if self.scheduler.n_mid_prefill else (0, 0, 0))
        t3 = (self._phase_mark("serve.pack")
              if self.tracer is not None else -1.0)

        tp = th = t4 = t3
        tw = -1.0
        seq_meta: dict[str, int] = {}
        device_done = False
        cpu4 = cpu5 = 0
        ctx_tokens = array_rows = h2d_count = h2d_bytes = 0
        attn_pages = attn_grid_steps = attn_step_pages = 0
        attn_live_tiles = attn_decode_tiles = 0
        dsa: dict[str, int] | None = None
        packed_width = dense_width = 0
        n_prefill_tok = sum(n for _, n in prefill_segs)
        n_decode_tok = len(decode_rows)
        # drafts actually packed (post-trim) / accepted by the verifier
        n_spec_tok = sum(r.draft_len for r in decode_rows)
        n_spec_acc = 0
        tel = None
        cost = None
        expert_load = None
        td0 = 0.0
        out = None
        dispatched = bool(decode_rows or prefill_segs)
        if dispatched:
            if self.telemetry is not None:
                # the analytic byte/FLOP bill MUST run before the
                # accept walk below — verify lanes live in draft_len
                # only until then
                cost = self.telemetry.mixed_tick_cost(
                    self, decode_rows, prefill_segs
                )
            host_ops, (packed_width, dense_width), array_rows = (
                self._pack_mixed(decode_rows, prefill_segs))
            if self.config.has_indexer:
                # always counted (a scrape reads them without a recorder),
                # and read HERE like ``ctx_tokens`` below
                dsa = self._dsa_account(decode_rows, prefill_segs)
                # (the scores walk the pages the attention walks)
                dsa["index_pages"] = self._attn_page_account(
                    host_ops, packed_width, dense_width)[0]
            if self.tracer is not None:
                # what this dispatch attends: every row's live content
                # after its tokens land (left pad excluded), read HERE —
                # the deliver walks below advance and release the rows
                ctx_tokens = sum(
                    r.cache_len - r.pad + r.draft_len for r in decode_rows
                ) + sum(r.prefill_done + n for r, n in prefill_segs)
                (attn_pages, attn_grid_steps, attn_step_pages,
                 attn_live_tiles, attn_decode_tiles) = (
                    self._attn_page_account(
                        host_ops, packed_width, dense_width))
                h2d_count = 1
                h2d_bytes = host_ops.nbytes
            tp = (self._phase_mark("serve.h2d")
                  if self.tracer is not None else -1.0)
            ops = self._put(host_ops)
            # closes serve.h2d and opens nothing: the dispatch's own
            # annotation below is the one the harness aligns clocks on,
            # and it wraps the jitted call alone
            th = (self._phase_mark(None)
                  if self.tracer is not None else -1.0)
            td0 = self.clock()
            self.n_mixed_dispatches += 1
            # the NAME is what the harness counts ticks by; ``seq`` rides
            # as metadata and joins this event to the recorder's tick
            seq_meta = {"seq": self.n_mixed_dispatches}
            with (jax.profiler.TraceAnnotation(
                      "serve.mixed_dispatch", **seq_meta)
                  if self.tracer is not None else _NULL_CTX):
                out, self.pool.pages = self._dispatch_mixed(
                    ops, bool(prefill_segs)
                )
            t4 = (self._phase_mark("serve.deliver")
                  if self.tracer is not None else -1.0)
        # the PREVIOUS tick's tokens and terminals go out here: behind
        # this tick's dispatch — the program is queued, the device is
        # busy, and the interpreter is the event loop's while this
        # thread blocks in the fetch below — or on the spot when nothing
        # was dispatched (then the dispatch phases and host_sync are
        # empty, and deliver runs from the plan's end)
        publish_rows = self._publish(dispatched)
        tpub = t5 = (self._phase_mark("serve.host_sync", **seq_meta)
                     if self.tracer is not None else -1.0)
        if dispatched:
            cpu4 = time.thread_time_ns() if self.tracer is not None else 0
            if self.faults is not None:
                # injected host_sync regression: a real stall in the
                # host_sync phase window
                hang = self.faults.trip("host_sync")
                if hang is not None:
                    time.sleep(hang)
            # THE tick's one device→host transfer (lint R2 allows
            # exactly this fetch): the step packed samples + stop mask
            # + watermark + accept length into one int32 array; the
            # accept walk below reads the token + accept columns
            # host-side (see _pack_sync on the other two).  With a
            # recorder the fetch is cut where the work changes kind: had
            # the device finished before the host came to wait, the host
            # — not the step — set this tick's length (``is_ready`` asks,
            # it does not wait; its first call on a result costs 12 us on
            # a v5e, so it is the recorder's too); then the wait for the
            # program, a stamp, and the same one transfer — the copy and
            # the way back into the interpreter
            if self.tracer is not None:
                device_done = out.is_ready()
                jax.block_until_ready(out)
                tw = self.tracer.now_us()
            # lint: disable=R2 -- the recorder's block_until_ready above
            # waits and moves no data: this is still the tick's one fetch
            out_host = np.asarray(out)
            self.n_host_fetches += 1
            if self._n_expert_layers:
                # the same fetch carries every expert layer's per-expert
                # token counts behind the rows' outcome (_make_mixed_step)
                n_rows = out_host.size - (
                    self._n_expert_layers * self.config.experts_held)
                expert_load = out_host[n_rows:].reshape(
                    self._n_expert_layers, -1)
                out_host = out_host[:n_rows].reshape(
                    -1, self._spec_w + 3)
            nxt_host = out_host[:, : self._spec_w]
            accept_host = out_host[:, self._spec_w + 2]
            cpu5 = time.thread_time_ns() if self.tracer is not None else 0
            t5 = (self._phase_mark("serve.accept")
                  if self.tracer is not None else -1.0)
            if cost is not None and self.telemetry is not None:
                # attribution lands BEFORE the accept walks so a
                # finishing request's canonical log line carries its
                # final tick's cost
                tel = self.telemetry.finish(cost, self.clock() - td0)
                self.telemetry.attribute(cost, tel["device_time_s"])
                self.metrics.on_telemetry(tel)
            if n_prefill_tok:
                # per-request prefill time: the dispatch+sync wall split
                # by token share (the mixed analogue of Request.prefill_s)
                per_tok = (self.clock() - td0) / (
                    n_prefill_tok + n_decode_tok + n_spec_tok
                )
                for r, n in prefill_segs:
                    r.prefill_s += per_tok * n
                if self.host_tier is not None and per_tok > 0:
                    # the recompute side of the restore-vs-recompute
                    # breakeven: a MEASURED prefill token rate, refined
                    # every dispatching tick
                    self.host_tier.note_prefill_rate(1.0 / per_tok)
            # accept: only what the NEXT plan reads (``_accept``).  A row
            # whose request was aborted since the dispatch — from a
            # callback of the publish above — is skipped whole: its K/V
            # writes went to blocks no later program reads before they
            # are rewritten, like a rejected draft's.
            for r, n in prefill_segs:
                if r.state is not RequestState.RUNNING:
                    continue
                r.prefill_done += n
                if r.prefill_done >= r.prefill_target:
                    self._finish_mixed_prefill(r, int(nxt_host[r.slot, 0]))
            # the token column, read once for all decode rows
            first = nxt_host[[r.slot for r in decode_rows], 0].tolist()
            for r, tok in zip(decode_rows, first):
                if r.state is not RequestState.RUNNING:
                    r.draft_len = 0
                    continue
                if not r.draft_len:
                    self._accept(r, tok)
                    continue
                # the accept walk: the verifier sampled every position
                # of this row's slice with the SAME (seed, content-pos)
                # keys plain decode uses, so sample j is THE token the
                # stream emits at that position — walk while the drafts
                # match, stop at the first correction (which is itself
                # a verified emission), a stop token, or the budget.
                # The match count arrived IN the packed fetch (the step
                # compares its own draft inputs against its samples),
                # so the walk reads host-side slices — no recompare.
                # Rejected drafts' K/V writes sit past the new
                # cache_len and are overwritten before ever attended.
                r.extra.pop("spec_draft")
                n_match = int(accept_host[r.slot])
                acc = 0
                for j, tok in enumerate(
                        nxt_host[r.slot, : 1 + r.draft_len].tolist()):
                    done = self._accept(r, tok)
                    if j >= n_match:
                        # the correction or the bonus slot — the round
                        # is over either way
                        break
                    # the draft paid off even when this token ENDS the
                    # stream (a drafted stop token) — count it before
                    # the finish check, or accepted/rejected
                    # systematically misreport on short extractive
                    # completions
                    acc += 1
                    if done:
                        break  # stop token / budget
                n_spec_acc += acc
                drafted = r.draft_len
                r.draft_len = 0
                self._spec_feedback(r, drafted, acc)

        t6 = (self._phase_mark("serve.account")
              if self.tracer is not None else -1.0)
        drained_rows = 0
        if not self.scheduler.has_work:
            # no tick follows this one: what it accepted goes out now
            drained_rows = self._publish(False)
        if self.host_tier is not None and (
            self._tier_spill_bytes or self._tier_restore_bytes
        ):
            self.metrics.on_tier_gauge(
                resident_bytes=self.host_tier.resident_bytes,
                breakeven=self.host_tier.breakeven_ratio(self.block_size),
            )
        active = n_decode_tok + len(prefill_segs)
        self.metrics.on_tick(
            queue_depth=self.scheduler.queue_depth,
            occupancy=self.pool.occupancy,
            active_slots=active,
            preemptions_total=self.scheduler.n_preemptions,
            kv_bytes=(
                self._kv_bytes_tick_mixed(decode_rows, prefill_segs)
                if active and self._paged else 0
            ),
            prefill_tokens=n_prefill_tok,
            decode_tokens=n_decode_tok,
            prefill_rows=len(prefill_segs),
            dense_lanes=dense_width,
            host_bound=device_done,
            lane_tick=lane_rows > 0,
            starved_rows=starved_rows,
            prefill_tiles=self._prefill_tiles_tick if dispatched else 0,
            prefill_tile_tokens=(
                self._prefill_tile_tokens_tick if dispatched else 0),
        )
        if expert_load is not None:
            worst = expert_load[int(np.argmax(expert_load.max(axis=1)))]
            moe = {
                # experts that got a token, summed over the expert layers
                "experts_touched": int(np.count_nonzero(expert_load)),
                # tokens an expert, worst layer: the most and the mean
                # (over the experts HELD, as everything here)
                "expert_load_max": int(worst.max()),
                "expert_load_mean": round(float(worst.mean()), 3),
                # (token, expert) pairs whose expert is held, all layers:
                # every pair where all experts are, a share's share
                "pairs_held": int(expert_load.sum()),
                # ... of the pairs the routers made: the live tokens' k
                # choices in every expert layer.  held / routed is the
                # share of them whose rows this chip moves
                "pairs_routed": (
                    (n_prefill_tok + n_decode_tok)
                    * self.config.num_experts_per_tok * self._n_expert_layers),
                "state_slots_live": len(self.scheduler.running),
            }
            tm = self._expert_row_tile(dense_width)
            # who moves the rows: "kernel" — the grouped matmul's calls
            # gather a tile's tokens and add its weighted result to theirs,
            # the pairs held alone; "xla" — a gather of every laid row, an
            # un-sort and a sum over all the pairs routed (lax.ragged_dot's
            # path, rows that do not fit the calls)
            moe["expert_rows_impl"] = self._expert_rows_impl(dense_width)
            if tm is not None:
                # what the grouped matmul multiplied: each (layer, expert)
                # group in whole tiles of ``tm`` rows, so that tiles x tm
                # / pairs_held is the work over the pairs
                moe["expert_row_tile"] = tm
                moe["expert_row_tiles"] = int((-(-expert_load // tm)).sum())
            self.metrics.on_experts(
                touched=moe["experts_touched"],
                load_max=moe["expert_load_max"],
                load_mean=moe["expert_load_mean"],
                pairs_held=moe["pairs_held"],
                state_slots_live=moe["state_slots_live"])
        # rows whose recurrent state the dispatch read and wrote (every
        # layer's) — what the device moves under "pallas"; under "xla" it
        # moves every slot's row, these or not —, and the live tokens
        # through the recurrence: a state-space mixer's (``ssm_*``) or a
        # delta-rule layer's matrix state (``kda_*``) or a power-retention
        # layer's (``retention_*``)
        state_args: dict[str, Any] = {}
        for kind, layers, impl, report in (
                ("ssm", self.config.ssm_layers, self.ssm_state_impl,
                 self.metrics.on_ssm),
                ("kda", self.config.kda_layers, self.kda_state_impl,
                 self.metrics.on_kda),
                ("retention", self.config.retention_layers,
                 self.retention_state_impl, self.metrics.on_retention)):
            if layers and active:
                state_args.update({
                    f"{kind}_state_rows": active,
                    f"{kind}_scan_tokens": n_prefill_tok + n_decode_tok,
                    "state_slots_live": len(self.scheduler.running),
                    f"{kind}_state_impl": impl})
                report(rows=active, tokens=n_prefill_tok + n_decode_tok,
                       state_slots_live=len(self.scheduler.running),
                       kernel=impl == "pallas")
        if dsa is not None:
            state_args.update({f"dsa_{k}": v for k, v in dsa.items()})
            self.metrics.on_dsa(**dsa)
        outliers: list[dict] = []
        if self.tracer is not None and t0 >= 0.0:
            t7 = self._phase_mark(None)
            class_args: dict[str, int] = {}
            if self.window_blocks and dispatched:
                # a pool with a window class: what one layer of EACH kind
                # is asked to stream (a window layer's range starts
                # ``window - 1`` slots before the tile's first token), the
                # window blocks the rows hold and those this tick's pack
                # let go
                class_args = {
                    "attn_pages_global": attn_pages,
                    "attn_pages_window": self._attn_window_pages(host_ops, (
                        packed_width, dense_width)),
                    "window_blocks_live": self.pool.window.in_use,
                    "window_blocks_recycled": self._window_recycled_tick}
                self.metrics.on_page_classes(
                    pages_global=attn_pages,
                    pages_window=class_args["attn_pages_window"],
                    live_tiles=attn_live_tiles,
                    prefill_tiles=attn_live_tiles - attn_decode_tiles,
                    recycled=self._window_recycled_tick)
            targs = {
                "active_slots": active,
                "queue_depth": self.scheduler.queue_depth,
                "admitted": len(admitted),
                "prefill_tokens": n_prefill_tok,
                "decode_tokens": n_decode_tok,
                # the mid-prefill rows the prompt tokens went to (one
                # segment a row: plan_tick)
                "prefill_rows": len(prefill_segs),
                # the tick's kind: the leftover of the prompt lane handed
                # out beyond the rows' fair shares and the rows that got
                # it (0 = a decode-only or fair-share-only tick)
                "lane_tokens": lane_tokens,
                "lane_rows": lane_rows,
                # the dispatch as the device sees it: the live context
                # its rows attend (summed over rows), and the program —
                # the width inside attention (tile lanes) and the width
                # of the step's dense token axis
                "context_tokens": ctx_tokens,
                "packed_width": packed_width,
                "dense_width": dense_width,
                # what ONE layer's attention call is asked to stream:
                # the pages in every tile's visible range, summed over
                # tiles (a global layer's range), and the kv grid steps
                # the program takes for them — tiles x groups of
                # ``attn_pages_per_step`` pages
                "attn_pages": attn_pages,
                **class_args,
                "attn_grid_steps": attn_grid_steps,
                "attn_pages_per_step": attn_step_pages,
                # the dispatch's live query tiles, and those of them that
                # hold ONE live token (a decode row, a prefill segment's
                # one-token tail): the tiles whose kv steps attend that
                # token's score rows alone (ops/pallas/decode_attention,
                # latent_attention)
                "attn_live_tiles": attn_live_tiles,
                "attn_decode_tiles": attn_decode_tiles,
                # ... and those that hold more (a prefill chunk's tiles,
                # each of which streams its row's visible pages again)
                "attn_prefill_tiles": attn_live_tiles - attn_decode_tiles,
                # rows _pack_mixed wrote by whole-array assignments
                # (plain decode rows): how much of pack went the fast way
                "pack_array_rows": array_rows,
                # the tick-tail observables: host_sync wall (µs) and the
                # number of device→host transfers this tick — the
                # one-fetch contract says the latter is exactly 1 on
                # dispatching ticks (bench + tests pin it)
                "host_sync_us": round(max(t5 - tpub, 0.0), 1),
                "host_fetches": self.n_host_fetches - fetches0,
                # the publish of the previous tick's tokens: the items
                # handed out in ``deliver``, and whether a dispatch was
                # in flight meanwhile (1) or they went out on the spot
                # (0); ``publish_drained_rows`` is this tick's own items,
                # handed out at its end because no tick follows
                "publish_rows": publish_rows,
                "publish_overlapped": int(dispatched and publish_rows > 0),
                "publish_drained_rows": drained_rows,
                # the tick thread's own CPU time outside host_sync:
                # tick - host_sync - this = time it neither computed
                # nor waited for the device (the GIL, a blocking put)
                "thread_cpu_us": round(
                    (time.thread_time_ns() - cpu0 - (cpu5 - cpu4)) / 1e3, 1),
            }
            if dispatched:
                # the dispatch's number (the same one its two profiler
                # annotations carry), the part of host_sync spent waiting
                # for the program — the rest is the copy and the way back
                # into the interpreter — and whether the device had
                # already finished when the host came to wait
                targs.update(seq_meta)
                targs["device_wait_us"] = round(max(tw - tpub, 0.0), 1)
                targs["device_done_at_sync"] = int(device_done)
            if expert_load is not None:
                # the expert layers as the step counted them (the tick's
                # one fetch): summarize_trace's transfers section and
                # the benchmark's moe.* readers
                targs.update(moe)
            targs.update(state_args)
            if self.spec_k:
                # the draft/verify split for summarize_trace and the
                # sentinel: how many verify lanes rode this tick's
                # dispatch and how many paid off
                targs["spec_draft_tokens"] = n_spec_tok
                targs["spec_accept_tokens"] = n_spec_acc
            if self.host_tier is not None:
                # the tier's per-tick byte flow (what summarize_trace's
                # kv_tier section and a Perfetto tick click read)
                targs["tier_spill_bytes"] = self._tier_spill_bytes
                targs["tier_restore_bytes"] = self._tier_restore_bytes
                targs["tier_restore_us"] = round(self._tier_restore_us, 1)
            if tel is not None:
                targs.update(_roofline_targs(tel))
            self.tracer.tick(t0, (
                ("admission", t0, t1), ("draft", t1, td),
                ("grow", td, t2), ("plan", t2, t3),
                ("pack", t3, tp),
                ("h2d", tp, th, {"count": h2d_count, "bytes": h2d_bytes}),
                ("mixed_dispatch", th, t4), ("deliver", t4, tpub),
                ("host_sync", tpub, t5), ("accept", t5, t6),
                ("account", t6, t7),
            ), args=targs)
            if self.sentinel is not None:
                # same literal tuple as the tick() call above (R2's
                # exempt-span recovery reads the literal there); the
                # roofline deficit rides along as a pseudo-phase so a
                # persistent utilization regression pages like a
                # host_sync one
                outliers = self._sentinel_observe((
                    ("admission", t0, t1), ("draft", t1, td),
                    ("grow", td, t2), ("plan", t2, t3),
                    ("pack", t3, tp), ("h2d", tp, th),
                    ("mixed_dispatch", th, t4), ("deliver", t4, tpub),
                    ("host_sync", tpub, t5), ("accept", t5, t6),
                    ("account", t6, t7),
                ) + (
                    (("roofline_deficit", 0.0, tel["deficit_us"]),)
                    if tel is not None else ()
                ))
        self._actions_tick(outliers)
        # an owed list is work: the next tick hands it out
        return self.scheduler.has_work or bool(self._owed)

    def _dispatch_mixed(self, ops: jnp.ndarray, has_prefill: bool) -> tuple:
        """One mixed dispatch with runtime degradation: a ragged-kernel
        dispatch fault permanently falls back
        to the XLA ragged attention for the process and retries the same
        tick; on the XLA fallback there is nothing left to degrade to,
        so faults propagate to the supervisor.  Chaos sites: ``prefill``
        fires when the tick planned prefill tokens, ``decode`` at every
        dispatch (it IS the decode dispatch)."""
        faults = self.faults
        if faults is not None:
            if has_prefill and faults.trip("prefill") is not None:
                raise FaultInjected("prefill")
            if (
                faults.trip("decode") is not None
                and not self._degrade_mixed(
                    "chaos: injected mixed-dispatch fault"
                )
            ):
                raise FaultInjected("decode")
        self.n_dispatches += 1
        try:
            return self._mixed_step(self.params, self.pool.pages, ops)
        except Exception as e:  # noqa: BLE001 — any dispatch fault gates
            if not self._degrade_mixed(f"{type(e).__name__}: {e}"):
                raise
            self.n_dispatches += 1
            # lint: disable=R7 -- the step donated the pool pages: injected
            # faults fire BEFORE dispatch, so the chaos retry never sees
            # consumed pages; a real post-donation fault raises on the
            # deleted buffers here and the supervisor restart (which
            # rebuilds the pool) takes over
            return self._mixed_step(self.params, self.pool.pages, ops)

    def _degrade_mixed(self, reason: str) -> bool:
        """Pallas → XLA fallback for the tick, process-wide: a supervisor
        rebuild (``clone_fresh``) and any later engine in this process
        must not re-select the faulted kernel.  The tick is
        ONE program, so its Pallas kernels — ragged attention AND the
        fused sampling epilogue — degrade as a unit: the host cannot
        attribute a dispatch fault to one kernel inside the jaxpr, and
        each has its own XLA sibling.  Returns False when already fully
        on the fallback."""
        if self.ragged_attn_impl == "pallas" or self.epilogue_impl == "fused":
            from llm_np_cp_tpu.ops.pallas.support import (
                disable_kernel,
                epilogue_kernel_name,
                ragged_kernel_name,
            )

            if self.ragged_attn_impl == "pallas":
                disable_kernel(
                    ragged_kernel_name(
                        self.cache_dtype == jnp.int8,
                        latent=self.config.is_latent,
                        indexer=self.config.has_indexer),
                    reason,
                )
                self.ragged_attn_impl = "xla"
            if self.epilogue_impl == "fused":
                from llm_np_cp_tpu.models.transformer import (
                    head_quant_mode,
                )

                disable_kernel(
                    epilogue_kernel_name(
                        head_quant_mode(self.params, self.config)
                        == "int8"
                    ),
                    reason,
                )
                self.epilogue_impl = "xla"
            self.decode_degraded = reason
            self._mixed_step = self._make_mixed_step()
            return True
        return False

    def _kv_bytes_tick_mixed(
        self,
        decode_rows: list[Request],
        prefill_segs: list[tuple[Request, int]],
    ) -> int:
        """K/V bytes this mixed tick's attention touches.  The ragged
        kernel streams each q tile's visible blocks (window-aware per
        layer); the XLA fallback materializes every token's full padded
        row view, counted as such.  The math lives in serve/telemetry
        (which also yields the per-request split for cost attribution)
        so the metrics gauge and the roofline model can never drift;
        called post-accept-walk, draft_len is 0 and the numbers match
        the historical draft-free accounting exactly."""
        return int(mixed_tick_kv_read(self, decode_rows, prefill_segs,
                                      per_request=False)[0])

    def _attn_page_account(
        self, host_ops: np.ndarray, t_w: int, d_w: int,
    ) -> tuple[int, int, int, int, int]:
        """(pages, kv grid steps, P, live tiles, one-token tiles) of one
        layer's ragged attention call on this packed batch — the tracer's
        tick args ``attn_pages`` / ``attn_grid_steps`` /
        ``attn_pages_per_step`` / ``attn_live_tiles`` /
        ``attn_decode_tiles``.  Pages: over the live tiles, the blocks
        from the row's left pad to the tile's last token (what a global
        layer streams; a sliding layer starts later).  Steps: the
        program's tiles x ``ceil(max_blocks / P)``.  One-token tiles:
        the live tiles with ``tile_qlen == 1``, whose steps the kernel
        attends for that token alone."""
        from llm_np_cp_tpu.ops.pallas.decode_attention import (
            ragged_pages_per_step,
        )
        from llm_np_cp_tpu.ops.pallas.latent_attention import (
            latent_pages_per_step,
        )
        from llm_np_cp_tpu.parallel.sharding import MODEL_AXIS

        layout = self._mixed_layouts[t_w, d_w][0]
        if not self._paged:
            return 0, 0, 0, 0, 0  # no layer attends anything

        def section(name):
            off, shape = layout[name]
            return host_ops[off:off + shape[0]]

        qlen = section("tile_qlen")
        live = qlen > 0
        last = (section("tile_qpos0") + qlen - 1)[live] // self.block_size
        first = section("pads")[section("tile_row")[live]] // self.block_size
        pages = self.pool.pages
        # (the kv heads ONE chip holds: the kernel runs inside shard_map)
        shards = self.mesh.shape[MODEL_AXIS] if self._kv_sharded else 1
        per_step = latent_pages_per_step(
            self.max_blocks_per_seq, self.block_size, pages.k.shape[-1],
            pages.k.dtype,
        ) if pages.latent else ragged_pages_per_step(
            self.max_blocks_per_seq, self.block_size,
            pages.kv_heads // shards, pages.head_dim, pages.k.dtype,
            pages.quantized, merged=pages.merged,
            wide=bool(self._wide_program(t_w)))
        steps = (t_w // self._q_tile) * -(-self.max_blocks_per_seq // per_step)
        return (int((last - first + 1).sum()), steps, per_step,
                int(live.sum()), int((qlen == 1).sum()))

    def _dsa_account(self, decode_rows: list, prefill_segs: list) -> dict:
        """What ONE layer's indexer is asked for in this dispatch (tick
        args ``dsa_*``, ``Metrics.on_dsa``): over the dispatched tokens,
        the positions each may see (its own included), the ``min(..,
        index_topk)`` of them it attends, and the tokens that attend all
        they see."""
        topk = self.config.index_topk
        # a decode row's content after its token lands; a prompt slice's
        # tokens at positions ``prefill_done ..``
        sees = np.concatenate(
            [np.asarray([r.cache_len - r.pad for r in decode_rows], np.int64)]
            + [r.prefill_done + 1 + np.arange(n, dtype=np.int64)
               for r, n in prefill_segs])
        return dict(
            visible=int(sees.sum()),
            selected=int(np.minimum(sees, topk).sum()),
            dense_tokens=int((sees <= topk).sum()))

    def _attn_window_pages(self, host_ops: np.ndarray,
                           program: tuple[int, int]) -> int:
        """``_attn_page_account``'s pages for one WINDOW layer's call:
        over the live tiles, the blocks from ``window - 1`` slots before
        the tile's first token (or the row's left pad) to its last."""
        layout = self._mixed_layouts[program][0]

        def section(name):
            off, shape = layout[name]
            return host_ops[off:off + shape[0]]

        qlen, qpos0 = section("tile_qlen"), section("tile_qpos0")
        live = qlen > 0
        bs, win = self.block_size, self.config.sliding_window
        lo = np.maximum(section("pads")[section("tile_row")], qpos0 - win + 1)
        return int(((qpos0 + qlen - 1)[live] // bs - lo[live] // bs + 1).sum())

    def _dead_mixed_operands(self, t_w: int, d_w: int) -> np.ndarray:
        """The mixed step's operand for an all-dead batch of the program
        ``(t_w, d_w)`` (a host array): every lane points at the scratch
        block and is fully masked."""
        return np.zeros(self._mixed_layouts[t_w, d_w][1], np.int32)

    def _warm_mixed_bucket(self, t_w: int, d_w: int) -> None:
        """Compile one program with an all-dead batch, so the only effect
        is the compile (and a garbage write to scratch)."""
        out, self.pool.pages = self._mixed_step(
            self.params, self.pool.pages,
            self._put(self._dead_mixed_operands(t_w, d_w)),
        )
        np.asarray(out)  # block until the compile lands

    def device_op_map(self) -> dict:
        """What the operations of the unified step are, over its compiled
        buckets: a profile's name for an operation (``%copy.89
        bf16[28,1026,64,2,128]``) → [named scope, "pool" | "slab" | ""]
        (serve/opmap.py), read from the compiled modules' text.  Set-up
        work, done once after warm-up and only for a tracer: lowering a
        signature the warm step has run hands back the executable the
        tick runs, so nothing compiles (0.2 s for eight buckets on a v5e).

        The scopes are metadata, and the persistent compile cache keys a
        program WITHOUT its metadata unless told otherwise: a warm
        executable's text may say what another build's source said.
        Whoever attaches the recorder therefore sets
        ``jax_compilation_cache_include_metadata_in_key`` before warm-up
        (cli ``_build_serve_engine``); without a persistent cache the
        text is this process's own compile either way."""
        from llm_np_cp_tpu.models.transformer import STEP_SCOPES
        from llm_np_cp_tpu.serve import opmap

        # the K/V pool alone: what a sequence carries beside it (a
        # convolution's history, a recurrent state) is written by its own
        # mixer, under that mixer's scope, and is no pool move
        pool = opmap.pool_shapes(
            (a.dtype.name, a.sharding.shard_shape(a.shape))
            for a in self.pool.pages.all_arrays())
        maps = []
        for program in self.mixed_buckets:
            text = self._mixed_step.lower(
                self.params, self.pool.pages,
                self._put(self._dead_mixed_operands(*program)),
            ).compile().as_text()
            # the same text says what the program still re-lays out of
            # its weights an execution (0 expected: ``_lay_out_weights``)
            self.weight_relayout_bytes["%dx%d" % program] = sum(
                nbytes for _, _, nbytes, _ in opmap.weight_relayouts(text))
            maps.append(opmap.op_map_from_hlo(
                text, STEP_SCOPES, pool,
                # the expert layers' way out of the Pallas grouped matmul
                # (ops/moe.expert_row_tile: the probe refused, matrices
                # not whole lanes wide): lax.ragged_dot, which a TPU
                # compiles to custom calls of its own naming
                named=(("ragged-dot", SCOPE_MOE_EXPERTS),)))
        return opmap.merge(maps)

    def _expert_row_tile(self, dense_width: int) -> int | None:
        """The row tile the expert layers of the program ``dense_width``
        tokens wide multiply their pairs in — ``moe_dropless``'s own
        choice, asked the way it asks — or None where they run
        ``lax.ragged_dot``.  Asked once a width: a tick asks again."""
        if dense_width not in self._expert_row_tiles:
            self._expert_row_tiles[dense_width] = expert_row_tile(
                self._expert_weights(),
                dense_width * self.config.num_experts_per_tok,
                self.config.num_experts)
        return self._expert_row_tiles[dense_width]

    def _expert_rows_impl(self, dense_width: int) -> str:
        """Who moves the expert layers' rows in the program ``dense_width``
        tokens wide, ``moe_dropless``'s own choice asked the way it asks:
        "kernel" | "xla".  Asked once a width."""
        if dense_width not in self._expert_rows_impls:
            self._expert_rows_impls[dense_width] = (
                "kernel" if expert_rows_in_call(
                    self._expert_weights(), dense_width,
                    self.config.num_experts_per_tok,
                    self._expert_row_tile(dense_width)) else "xla")
        return self._expert_rows_impls[dense_width]

    def _expert_weights(self) -> jax.ShapeDtypeStruct:
        """One expert layer's gate weights ``[E_held, H, I]``, as the
        choices of ops/moe.py read them: shape and dtype."""
        w1 = next(run["w1"] for run in self.params["layers"] if "w1" in run)
        return jax.ShapeDtypeStruct(w1.shape[-3:], w1.dtype)

    def warmup(
        self, prompt_lens: list[int], max_new_tokens: int = 2,
    ) -> None:
        """Compile every program of the tick before measuring, then reset
        metrics — so a subsequent replay reports steady-state serving
        numbers, not first-compile stalls (on TPU a model compile is
        multi-second and would dominate TTFT p99).  One dummy request
        runs the loop end to end; every program of ``mixed_buckets`` its
        ticks did not pick is then compiled on an all-dead batch."""
        if not prompt_lens:
            return
        # chaos is suspended for the warmup pass: it is compile-only, so
        # its dispatches must not consume deterministic schedule hits
        # (shifting every site's firing point) and a scheduled fault must
        # not fire here, where no supervisor is watching yet.  The tracer
        # is suspended with it — warmup's dummy request is not part of
        # any measured timeline, like the metrics reset below.
        # the journal is suspended with them: warmup's dummy request is
        # compile-only and must not leave admission records a restart
        # would try to replay
        # ...and the request log: warmup's dummy request is not a real
        # terminal, so it must not leave a canonical log line
        faults, self.faults = self.faults, None
        tracer, self.tracer = self.tracer, None
        journal, self.journal = self.journal, None
        request_log, self.request_log = self.request_log, None
        # telemetry too: warmup ticks are compile-only, not device work
        # worth billing or baselining
        telemetry, self.telemetry = self.telemetry, None
        # ...and the host tier: the dummy request's blocks must not
        # spill into (or restore from) the shared host pool, and its
        # wall times must not seed the breakeven's prefill rate
        host_tier, self.host_tier = self.host_tier, None
        # ...and the tenant ledger: the dummy request is nobody's bill
        tenants, self.tenants = self.tenants, None
        # the SLO tracker is suspended the same way (the dummy request
        # must not count as a verdict) and survives _warmup_body's
        # metrics reset — the fresh ServeMetrics gets it back
        slo_tracker = getattr(self.metrics, "slo", None)
        self.metrics.slo = None
        t_warm = tracer.now_us() if tracer is not None else -1.0
        try:
            self._warmup_body(prompt_lens, max_new_tokens, tracer)
        finally:
            self.faults = faults
            self.tracer = tracer
            self.journal = journal
            self.request_log = request_log
            self.telemetry = telemetry
            self.host_tier = host_tier
            self.tenants = tenants
            self.metrics.slo = slo_tracker
        if tracer is not None:
            # set-up as spans: the warm-up whole (its buckets are its
            # children), then — outside it, the cost is tracing's own —
            # the device-side op map, once per recorder
            tracer.complete("warmup", t_warm, cat="setup")
            if tracer.get_other("op_map") is None:
                t_map = tracer.now_us()
                tracer.set_other("op_map", self.device_op_map())
                tracer.set_other("weight_relayout_bytes",
                                 dict(self.weight_relayout_bytes))
                tracer.complete("op_map", t_map, cat="setup", args={
                    "buckets": len(self.mixed_buckets),
                    "weight_relayout_bytes": sum(
                        self.weight_relayout_bytes.values())})

    def _warmup_body(self, prompt_lens: list[int], max_new_tokens: int,
                     tracer: TraceRecorder | None = None) -> None:
        t_req = tracer.now_us() if tracer is not None else -1.0
        self.submit(np.ones(min(prompt_lens), np.int32),
                    min(2, max_new_tokens))
        self.run_until_complete()
        if tracer is not None:
            tracer.complete("warmup.request", t_req, cat="setup")
        if self._restore_block is not None:
            # the host tier's one landing program: warm it against the
            # scratch block (garbage there is harmless by construction)
            # so the first mid-traffic restore never pays a compile
            shape = self.pool.pages.k.shape
            blk_shape = (shape[0],) + shape[2:]
            args = [self._put(jnp.zeros(blk_shape, self.cache_dtype))] * 2
            if self.pool.pages.quantized:
                args += [
                    self._put(jnp.zeros(blk_shape[:-1], jnp.float32))
                ] * 2
            self.pool.pages = self._restore_block(
                self.pool.pages, self._put(np.int32(0)), *args
            )
            # ...and the spill-path slicer (same traced-index contract)
            self._slice_block(self.pool.pages, self._put(np.int32(0)))
        # one compile per program — the dummy request covered
        # whichever its own ticks picked; warm the rest directly so
        # mid-traffic composition churn can never trigger a compile
        # stall
        for t_w, d_w in self.mixed_buckets:
            t_b = tracer.now_us() if tracer is not None else -1.0
            missed = tracer.compile_misses if tracer is not None else 0
            self._warm_mixed_bucket(t_w, d_w)
            if tracer is not None:
                # compiled: a backend compile ran (the dummy request
                # above, or the persistent cache, had not covered it)
                tracer.complete(
                    "warmup.bucket", t_b, cat="setup", args={
                        "width": t_w, "dense": d_w,
                        "compiled": tracer.compile_misses > missed,
                    })
        if self.pool.prefix_cache is not None:
            # drop the dummy request's registered blocks so the measured
            # span starts with a cold cache
            self.pool.prefix_cache.clear()
        # the dummy request is not part of any measured trace: drop it
        # from the finished ledger along with the metrics it produced
        self.scheduler.finished.clear()
        self.metrics = ServeMetrics(clock=self.clock)

    def run_until_complete(self, max_ticks: int = 100_000) -> None:
        for _ in range(max_ticks):
            if not self.step():
                return
        raise RuntimeError(f"serve loop did not drain within {max_ticks} ticks")

    # ------------------------------------------------------------------
    def replay_trace(
        self,
        trace: list[dict[str, Any]],
        *,
        realtime: bool = False,
        max_ticks: int = 100_000,
    ) -> dict[str, Any]:
        """Replay ``[{"arrival_s", "prompt", "max_new_tokens", "seed"?}]``.

        realtime=False (default, and what tests/bench use on CPU):
        arrivals are released by a virtual clock that advances to the
        next arrival whenever the engine is idle — the schedule stress
        is preserved without wall-clock sleeps.  realtime=True sleeps
        until each arrival (live serving simulation).  The loop itself
        is serve/trace.replay_arrivals, shared with ReplicaSet.
        """
        from llm_np_cp_tpu.serve.trace import replay_arrivals

        return replay_arrivals(
            self, trace, self.metrics.snapshot,
            realtime=realtime, max_ticks=max_ticks,
        )
