"""Paged KV cache: fixed-size blocks in one preallocated slab per layer.

A contiguous ``KVCache`` reserves ``max_seq_len`` slots per request up
front — at serving concurrency most of that is empty tail.  The pool
instead preallocates ONE slab of ``num_blocks`` fixed-size blocks per
layer and hands requests blocks on demand through a free list; a
request's cache is its *block table* (list of block ids), so fragments
left by finished requests are reusable immediately and admission control
reduces to counting free blocks.

Layout (the contiguous cache's [L, B, S, K, D] with S factored into
pages):

    k, v: [num_layers, num_blocks, block_size, kv_heads, head_dim]
    k, v: [num_layers, num_blocks, block_size, kv_heads * head_dim]   (merged)

A TPU lays an array out by its own rule, and a float page whose
``head_dim`` is not a whole row of 128 lanes (64: Llama-3.2-1B, LFM2) is
NOT kept in the order its shape lists: half of every tile would be
empty, so the device permutes the dimensions, and every program that
hands such a pool to a kernel relays it out on the way in and on the way
out (PERF.md section 6, PR 38).  The same page with its heads side by
side on the lanes, ``[BS, K * D]``, fills its tiles and lies as its shape
says: where ``K * D`` is a multiple of 128 such a pool is allocated
MERGED (``merges_pages``, from the shapes and the dtype alone).
``PagedKV`` says what a page is (``kv_heads``, ``head_dim``, ``merged``,
``token_shape``): a program that touches the pool asks it and reshapes
what it writes or what it gathered, never the pool.

Block 0 is RESERVED as a scratch block and never allocated: inactive
decode slots in the engine's fixed-width batch point their tables at it,
so the packed decode step can write unconditionally (no data-dependent
shapes) and garbage lands somewhere harmless.

A configuration whose sequences carry more than K/V between steps has
pages for its layers WITH K/V only (``L = len(config.attn_layers)``) and a
second, constant-size store beside them in the same manager, a small
pytree keyed by what the configuration's layers carry
(``config.state_shapes``, the one statement of these shapes):

    state["conv"]: [layers, slots, taps - 1, channels]     served dtype
    state["ssm"]:  [layers, slots, heads, d_head, d_state]  float32
    state["kda"]:  [layers, slots, heads, d, d]             float32
    state["retention"]:   [layers, slots, kv heads, rows, d]  float32
    state["retention_z"]: [layers, slots, kv heads, d, d]     float32

one row a slot, no blocks: the last inputs of a short convolution (a conv
layer's, or the one in front of a state-space mixer or of a delta-rule
layer's q, k and v), and the mixer's recurrent state or the delta-rule
layer's matrix state — float32 whatever is served, it is rounded once a
token for hundreds of tokens.  A stack may have BOTH pages and a state for
different layers: a delta-rule stack's one latent layer a group has pages
(``form.latent``), its other layers rows of the state.  The step carries the leaves and writes them in
place at ``[layer, row]`` like the pages (donated); a slot's row is never
read by a sequence's first token (a position-0 token's history and state
are zero), so admitting a request into a freed slot needs no clear.

A stack may also have a state and NO pages at all (``config.attn_layers``
empty: an attention-free model, every layer power retention).  Its pool has
no page class: ``k`` and ``v`` are arrays of no layer and no block, the
allocator is ``NoBlocks`` (capacity 0: nothing to hand out, nothing to run
out of), ``blocks_for`` is 0 for any length, and capacity is slots x state
and nothing else — a request is admitted by a free slot, never preempted for
a block, and its context is bounded by the model's positions alone.

A configuration whose WINDOW layers are a kind of their own
(``config.two_page_classes``) has TWO page classes in this one manager.  A
class is a KIND's, not a shape's: MiMo-V2's window layers have 8 kv heads
where its global layers have 4, AFMoE's (Trinity) the same 8 heads of 128
in both kinds, and both keep their window layers' pages bounded:

    k, v:       [global layers, num_blocks, BS, K * D] / [.., K * Dv]
    window k,v: [window layers, 1 + slots * W, BS, Kw * D] / [.., Kw * Dv]

Both classes are stored MERGED whatever their heads, so that the kernel
takes a head as a static slice of a page's lanes (toy heads that no rule
merges included).  AFMoE's 8 heads of 128 at a query group of 6 are merged by
``merges_pages``'s own rule, in one class or two: as ``[BS, K, D]`` pages the
kernel would attend all K heads in one score sheet of which a row keeps a
K-th, and the v5e compiler refuses it there.

The first is the class above: a request's chain of it grows with its
context, out of the free list.  The second is BOUNDED: a query of a
window layer sees only the last ``sliding_window`` positions, so a block
whose last token has left every later query's window is never read again.
Each decode slot owns a RING of ``W`` blocks of the window class
(``window_blocks_per_slot``: the window, the widest slice a tick writes,
and a block for the boundaries); logical block ``j`` of the slot's cache
lives in ring entry ``j % W``, and the slot's chain is the run of logical
blocks the coming tick can still see or writes — ``WindowRings.advance``
moves it as the row advances, host-side, with no device work: a block
that drops off the front is RECYCLED (its ring entry is the next one
written).  Held full-length, MiMo-V2.5's five window layers of a period
would be 25,600 of a token's 30,720 bytes; bounded, a token costs the two
global layers' 5,120 and a slot a constant.

int8 mode mirrors ``KVCache``'s quantized slabs: per-token-per-head
absmax scales (quant.quantize_kv layout) ride in parallel
``[L, NB, BS, K]`` f32 pages.

The allocator is host-side Python (a free list) — allocation happens at
scheduling time, between device steps, never under jit.  Blocks are
REFCOUNTED so prompt-prefix blocks can be shared across requests
(serve/prefix_cache.py): ``free`` is a decref and only a block's last
holder returns it to the free list.  The device-side pages are a pytree
(``PagedKV``) threaded through the engine's jitted steps and donated, so
slabs update in place.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from llm_np_cp_tpu.config import ModelConfig


class FreeList:
    """LIFO free-list allocator over block ids ``1..num_blocks-1``, with
    per-block refcounts for prefix sharing.

    Block 0 is the reserved scratch block (see module docstring).  LIFO
    reuse keeps recently-freed blocks hot (their slab pages are most
    likely still in cache on real hardware).  ``alloc`` hands out blocks
    at refcount 1; ``incref`` adds a sharer; ``free`` is a DECREF — a
    block returns to the free list only when its last reference drops,
    so a shared prefix block survives any one request's finish or
    eviction.  Pure Python so scheduler policies are testable without
    any device arrays.
    """

    def __init__(self, num_blocks: int) -> None:
        if num_blocks < 2:
            raise ValueError(
                f"need at least 2 blocks (1 reserved scratch), got {num_blocks}"
            )
        self.num_blocks = num_blocks
        self._free: list[int] = list(range(num_blocks - 1, 0, -1))
        self._ref: dict[int, int] = {}  # allocated block id → refcount

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocated(self) -> int:
        return len(self._ref)

    @property
    def capacity(self) -> int:
        """Allocatable blocks (excludes the reserved scratch block)."""
        return self.num_blocks - 1

    def refcount(self, block_id: int) -> int:
        """Current references on ``block_id`` (0 if free/unknown)."""
        return self._ref.get(block_id, 0)

    def alloc(self, n: int) -> list[int] | None:
        """Pop ``n`` blocks at refcount 1, or None (and no change) if
        not enough free."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        ids = [self._free.pop() for _ in range(n)]
        for i in ids:
            self._ref[i] = 1
        return ids

    def incref(self, ids: list[int]) -> None:
        """Add one reference per block (a new sharer of a prefix block).
        Only allocated blocks can gain references."""
        for i in ids:
            if i not in self._ref:
                raise ValueError(f"incref on unallocated block id {i}")
        for i in ids:
            self._ref[i] += 1

    def free(self, ids: list[int]) -> None:
        """Drop one reference per block; blocks whose count hits zero
        return to the free list.  Releasing a block with no references
        is still a hard error (double free)."""
        for i in ids:
            if i not in self._ref:
                raise ValueError(f"double free or foreign block id {i}")
            self._ref[i] -= 1
            if self._ref[i] == 0:
                del self._ref[i]
                self._free.append(i)


class NoBlocks:
    """``FreeList``'s interface over no blocks at all: the allocator of a
    pool with no page class (module docstring).  A request needs 0 blocks
    and gets them; nothing else can be asked of it."""

    num_blocks = num_free = num_allocated = capacity = 0

    def refcount(self, block_id: int) -> int:
        return 0

    def alloc(self, n: int) -> list[int] | None:
        return [] if n == 0 else None

    def incref(self, ids: list[int]) -> None:
        if ids:
            raise ValueError(f"incref on {ids}: the pool has no page class")

    def free(self, ids: list[int]) -> None:
        if ids:
            raise ValueError(f"free of {ids}: the pool has no page class")


def window_blocks_per_slot(window: int, widest_slice: int,
                           block_size: int) -> int:
    """Blocks of the window class a decode slot's ring holds: what one
    tick can need at once — the ``window - 1`` positions before the first
    query of the widest slice a tick writes (``widest_slice`` tokens: the
    tick's token budget, which the planner hands a lone prompt whole:
    ``Scheduler.plan_tick``), the slice itself,
    and one block more because the run starts anywhere inside a block.
    The ONE statement of the rule: the engine sizes the class by it and
    ``WindowRings.advance`` refuses a tick that would pass it."""
    return -(-(window - 1 + widest_slice) // block_size) + 1


class WindowRings:
    """The window class's allocator (module docstring): slot ``s`` owns
    blocks ``1 + s * W .. 1 + (s + 1) * W - 1`` (block 0 is the class's
    scratch block, as in the free list), and holds the logical blocks
    ``first[s] .. end[s] - 1`` of its request's cache live.  Pure Python /
    NumPy, like ``FreeList``: testable without a device."""

    def __init__(self, slots: int, per_slot: int, block_size: int,
                 window: int) -> None:
        self.slots, self.per_slot = slots, per_slot
        self.block_size, self.window = block_size, window
        self.first = np.zeros(slots, np.int64)
        self.end = np.zeros(slots, np.int64)
        self.recycled_total = 0

    @property
    def num_blocks(self) -> int:
        return 1 + self.slots * self.per_slot

    @property
    def in_use(self) -> int:
        return int((self.end - self.first).sum())

    def block(self, slot: int, logical: int) -> int:
        """The physical block that holds logical block ``logical`` of
        ``slot``'s cache while it is live."""
        return 1 + slot * self.per_slot + logical % self.per_slot

    def chain(self, slot: int) -> list[int]:
        """The slot's live blocks, oldest first."""
        return [self.block(slot, j)
                for j in range(int(self.first[slot]), int(self.end[slot]))]

    def advance(self, slots: Any, starts: Any, n: int) -> int:
        """The requests in ``slots`` (one, or an array of distinct ones)
        write cache slots ``starts .. starts + n - 1`` this tick: keep
        what their queries can see (from ``start - window + 1`` on), add
        what they write; returns how many blocks dropped off the front
        (recycled: their ring entries are written next)."""
        slots = np.atleast_1d(np.asarray(slots, np.intp))
        starts = np.atleast_1d(np.asarray(starts, np.int64))
        bs = self.block_size
        first = np.maximum(starts - self.window + 1, 0) // bs
        end = (starts + n - 1) // bs + 1
        if (end - first > self.per_slot).any():
            raise AssertionError(
                f"a tick of {n} tokens at slot {int(starts.max())} needs "
                f"{int((end - first).max())} window blocks, the ring holds "
                f"{self.per_slot}")
        recycled = int(np.maximum(
            np.minimum(first, self.end[slots]) - self.first[slots], 0).sum())
        self.first[slots], self.end[slots] = first, end
        self.recycled_total += recycled
        return recycled

    def table(self, slots: Any) -> np.ndarray:
        """``[per_slot]`` int32 (``[n, per_slot]`` for an array of slots):
        a slot's table for the coming tick — column ``c`` is logical
        block ``first + c`` (scratch 0 past the live run)."""
        at = np.asarray(slots, np.intp)
        cols = np.arange(self.per_slot)
        first, live = self.first[at], (self.end - self.first)[at]
        tab = 1 + at[..., None] * self.per_slot + (
            (first[..., None] + cols) % self.per_slot)
        return np.where(cols < live[..., None], tab, 0).astype(np.int32)

    def release(self, slot: int) -> None:
        """The slot's request left it (finished, aborted, preempted)."""
        self.first[slot] = self.end[slot] = 0


def merges_pages(kv_heads: int, head_dim: int, quantized: bool,
                 group: int | None = None) -> bool:
    """Whether a pool of such pages is allocated ``[L, NB, BS, K * D]``
    (module docstring): a float page whose ``[BS, K, D]`` form a TPU
    would not keep row-major (``head_dim`` short of a whole row of
    lanes — compiled for a described v5e, bf16 and float32 alike:
    tests/test_kernel_lowering.py) and whose heads side by side fill
    whole rows — or, where the caller says how many query heads share a
    kv head (``group``), heads of whole rows of lanes whose ``[BS, K,
    D]`` form the kernel cannot attend: it scores all ``K`` heads of
    such a page group in ONE sheet of ``K * 8 * G`` rows by ``512 * K``
    columns, a row keeping a ``K``-th of it (ops/pallas/decode_attention,
    "heads in rows"), and past ``K * K * G = 256`` (8 heads at a group
    of 4: 4 MiB of float32 scores) the v5e compiler refuses the kernel
    for its scoped VMEM (8 heads at a group of 6: 560 KB over; PERF.md
    section 6, PR 50).  Merged, a head is a static slice of a page's
    lanes and the sheet ``K`` times smaller.  int8 pages keep their form
    beside their scale pages."""
    if quantized:
        return False
    if head_dim % 128 != 0:
        return (kv_heads * head_dim) % 128 == 0
    return group is not None and kv_heads * kv_heads * group > 256


def latent_page_width(width: int) -> int:
    """Columns a pool gives a latent row ``[c' | k_pe]`` of ``width``
    values (latent attention: one row a token, no head axis): whole rows
    of 128 lanes, zeros past ``width`` (576 -> 640).  Compiled for a
    described v5e (tests/test_kernel_lowering.py): a ``[.., BS, 576]``
    array is NOT kept in the order of its shape (the block axis becomes
    the minor one), nor is ``k_pe`` as a ``[.., BS, 64]`` array of its
    own; ``[.., BS, 640]`` is, and a DMA cuts a page out of it."""
    return -(-width // 128) * 128


@jax.tree_util.register_static
@dataclasses.dataclass(frozen=True)
class PageForm:
    """What a page's shape no longer says (a merged page's ``head_dim``;
    a latent page's unpadded row width, as ``head_dim``, and that it is
    one): a leafless node of the ``PagedKV`` pytree, so it rides every
    jitted step as part of the tree's structure."""

    head_dim: int
    latent: bool = False


class PagedKV(NamedTuple):
    """Device-side pages: the pytree the engine's jitted steps thread
    through (and donate).  Scales are None for float pools."""

    # [L, NB, BS, K, D], or merged [L, NB, BS, K * D], or latent rows
    # [L, NB, BS, latent_page_width(rank + rope)] with no ``v`` beside
    # them (the rows' first ``rank`` columns are the values) — or, under
    # a sparse-attention indexer, the tokens' INDEX KEYS [L, NB, BS,
    # index_head_dim] in its place: written where the row is written,
    # read by the scores of the positions a token may see and no other
    k: jnp.ndarray
    v: jnp.ndarray | None
    k_scale: jnp.ndarray | None = None  # [L, NB, BS, K] f32 (int8 mode)
    v_scale: jnp.ndarray | None = None
    # what a sequence carries besides K/V (module docstring): not paged,
    # one row a slot, ``{"conv": .., "ssm": .., "kda": ..}`` as the
    # configuration's layers need; None for a stack of attention layers alone
    state: dict[str, jnp.ndarray] | None = None
    # set on a merged pool only; every other page says it by its shape
    form: PageForm | None = None
    # the WINDOW class's pages (module docstring), ``(k, v)`` stored
    # merged; None where the pool has one class
    window: tuple[jnp.ndarray, jnp.ndarray] | None = None

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def latent(self) -> bool:
        """Pages of latent rows: one array, no head axis, no ``v`` (an
        indexer's keys lie there instead)."""
        return self.form is not None and self.form.latent

    @property
    def merged(self) -> bool:
        """Pages stored ``[BS, K * D]``, the kv heads side by side."""
        return self.form is not None and not self.form.latent

    @property
    def head_dim(self) -> int:
        """A kv head's width (a latent page: its row's, unpadded)."""
        return self.form.head_dim if self.form else self.k.shape[-1]

    @property
    def kv_heads(self) -> int:
        """The kv heads of the array as the caller holds it (one shard's
        inside ``shard_map``)."""
        if self.latent:
            return 1
        if self.merged:
            return self.k.shape[-1] // self.head_dim
        return self.k.shape[-2]

    @property
    def token_shape(self) -> tuple[int, ...]:
        """One token's K (or V) as a page holds it: ``(K, D)`` or ``(K *
        D,)`` — what a value written into the pool is reshaped to."""
        return tuple(self.k.shape[3:])

    def pool_arrays(self) -> tuple[jnp.ndarray, ...]:
        """The paged arrays (``[L, NB, BS, ..]``) of the class whose
        chains grow, without the state."""
        return tuple(a for a in self[:4] if a is not None)

    def all_arrays(self) -> tuple[jnp.ndarray, ...]:
        """Every paged array: both classes'."""
        return self.pool_arrays() + (self.window or ())

    @property
    def num_blocks(self) -> int:
        return self.k.shape[1]

    @property
    def block_size(self) -> int:
        return self.k.shape[2]


class BlockPool:
    """Free-list allocator + the device slabs it allocates from.

    ``pages`` is rebound by the engine after every donated step; the
    pool object itself is host-side bookkeeping only.
    """

    def __init__(
        self,
        config: ModelConfig,
        num_blocks: int,
        block_size: int,
        dtype: jnp.dtype = jnp.bfloat16,
        enable_prefix_cache: bool = False,
        shardings: "PagedKV | None" = None,
        state_slots: int = 0,
        window_blocks: int = 0,
    ) -> None:
        if block_size < 8 or block_size % 8:
            # Mosaic's second-minor alignment rule for the decode kernels;
            # also keeps gathered views compatible with select_block_s
            raise ValueError(f"block_size must be a multiple of 8, got {block_size}")
        self.config = config
        self.block_size = block_size
        self.dtype = jnp.dtype(dtype)
        # a stack with no layer that has pages has no page class (module
        # docstring): whatever was asked for, there are no blocks
        self.paged = config.has_pages
        if not self.paged:
            num_blocks = 0
        self.free_list = FreeList(num_blocks) if self.paged else NoBlocks()
        if enable_prefix_cache:
            from llm_np_cp_tpu.serve.prefix_cache import PrefixCache

            self.prefix_cache: PrefixCache | None = PrefixCache(self.free_list)
        else:
            self.prefix_cache = None
        quantized = self.dtype == jnp.int8
        # what a token leaves a layer is the configuration's statement
        token = config.kv_token_shapes()
        latent = config.is_latent
        if latent and quantized:
            raise ValueError("an int8 pool of latent rows is not implemented")
        if latent:
            (d,), merged = token["k"], False
            page: tuple[int, ...] = (latent_page_width(d),)
            v_page = page
        else:
            kh, d = token["k"]
            # (a pool of two classes stores BOTH merged, whatever their
            # heads: module docstring)
            merged = merges_pages(
                kh, d, quantized, config.num_attention_heads // kh
            ) or config.two_page_classes
            page = (kh * d,) if merged else (kh, d)
            # (a value head may have another width than a key head)
            v_page = (kh * token["v"][1],) if merged else token["v"]
        lead = (len(config.global_layers), num_blocks, block_size)
        shape = lead + page
        # mesh-sharded mode: a PagedKV of NamedShardings (kv-head axis on
        # "model", see parallel/sharding.paged_kv_specs) commits the slabs
        # onto the mesh; the FREE LIST stays global — allocation is a
        # host-side decision and every shard holds the same block ids,
        # only a head-slice of each block's K/V.
        self.shardings = shardings
        where = shardings if shardings is not None else PagedKV(
            None, None, None, None)

        def zeros(shp: tuple, dt: Any, sharding: Any) -> jnp.ndarray:
            # Born ON its devices: each addressable shard is put from one
            # lazily-zeroed host buffer straight onto the device that
            # owns it.  What NOT to do, both measured on the four-chip
            # host (PR 21): zeros-then-device_put and
            # jnp.zeros(device=...) materialize a shard on the DEFAULT
            # device and copy it out (+304 MiB peak on chip 0 with four
            # replicas); a jitted zeros with a 2-device out_shardings
            # halted the second TP replica's cores ("Invalid logical z:
            # enhanced-barrier-parent-phase-1") at its first sync.
            if sharding is None:
                return jnp.zeros(shp, dt)
            host = np.zeros(sharding.shard_shape(shp), dt)
            return jax.make_array_from_callback(shp, sharding,
                                                lambda _index: host)

        if latent and config.has_indexer:
            # a sparse-attention indexer's key a token and layer: one more
            # array of the latent class (the rows' block ids and tables, the
            # rows' lifetime), 128 values wide at the published widths — a
            # whole row of lanes, kept in the order of its shape
            v_page = (config.index_head_dim,)
        self.pages = PagedKV(
            k=zeros(shape, dtype, where.k),
            v=(None if latent and not config.has_indexer
               else zeros(lead + v_page, dtype, where.v)),
            k_scale=(zeros(shape[:-1], jnp.float32, where.k_scale)
                     if quantized else None),
            v_scale=(zeros(shape[:-1], jnp.float32, where.v_scale)
                     if quantized else None),
            form=PageForm(d, latent) if merged or latent else None,
        )

        # the window class (module docstring): a ring of ``window_blocks``
        # blocks a slot, stored merged like the class above
        self.window: WindowRings | None = None
        if config.two_page_classes:
            if quantized or shardings is not None:
                raise ValueError(
                    "a pool with a window class is a float pool on one "
                    "device (int8 pages and a model axis have no rule for "
                    "two page classes)")
            if window_blocks < 2 or state_slots < 1:
                raise ValueError(
                    "a configuration whose window layers hold pages of "
                    "their own needs state_slots and window_blocks "
                    "(window_blocks_per_slot)")
            self.window = WindowRings(
                state_slots, window_blocks, block_size, config.sliding_window)
            wt = config.kv_token_shapes("window")
            w_lead = (len(config.window_layers), self.window.num_blocks,
                      block_size)
            self.pages = self.pages._replace(window=tuple(
                zeros(w_lead + (math.prod(wt[leaf]),), dtype, None)
                for leaf in ("k", "v")))

        # what a sequence carries besides K/V (module docstring): the
        # convolution history in the activations' dtype whatever the K/V
        # pages are quantized to, the recurrent state float32; None for
        # every other configuration
        shapes = config.state_shapes(
            state_slots, jnp.bfloat16 if quantized else dtype)
        if shapes:
            if state_slots < 1:
                raise ValueError(
                    "a configuration whose layers carry a state needs "
                    "state_slots (one state row a slot)")
            from jax.sharding import NamedSharding, PartitionSpec

            # a placement mesh pins the state beside the pages
            where_state = None if shardings is None else NamedSharding(
                shardings.k.mesh, PartitionSpec())
            self.pages = self.pages._replace(state={
                name: zeros(shape, jnp.dtype(dt), where_state)
                for name, (shape, dt) in shapes.items()})

    # -- accounting (delegates; the scheduler talks to these) ----------
    @property
    def num_blocks(self) -> int:
        return self.free_list.num_blocks

    @property
    def num_free(self) -> int:
        """Blocks available for allocation: the free list plus prefix-
        cache entries whose only reference is the cache's own (reclaimed
        on demand by ``alloc``) — shared blocks never double-count
        against pool capacity."""
        n = self.free_list.num_free
        if self.prefix_cache is not None:
            n += self.prefix_cache.n_reclaimable
        return n

    @property
    def capacity(self) -> int:
        return self.free_list.capacity

    @property
    def occupancy(self) -> float:
        """Fraction of allocatable blocks currently held by requests —
        the complement of ``num_free``, so cache-only (reclaimable)
        prefix blocks count as free here too, keeping the two admission
        metrics mutually consistent."""
        return (self.capacity - self.num_free) / max(self.capacity, 1)

    def blocks_for(self, n_tokens: int) -> int:
        """Blocks needed to hold ``n_tokens`` cache slots (none, of a
        pool with no page class)."""
        return -(-n_tokens // self.block_size) if self.paged else 0

    def stats(self) -> dict[str, int]:
        """Point-in-time accounting for scrapes and tests: raw free-list
        state plus the prefix-cache split (``cache_only`` blocks are held
        solely by the cache's own reference and are reclaimable on
        demand).  ``request_held = allocated - cache_only`` is the number
        of blocks live requests actually pin — the quantity abort tests
        assert returns to zero."""
        allocated = self.free_list.num_allocated
        cache_only = (
            self.prefix_cache.n_reclaimable
            if self.prefix_cache is not None else 0
        )
        out = {
            "capacity": self.capacity,
            "free": self.free_list.num_free,
            "allocated": allocated,
            "cache_only": cache_only,
            "request_held": allocated - cache_only,
        }
        if self.window is not None:
            out.update(
                window_blocks_capacity=self.window.num_blocks - 1,
                window_blocks_in_use=self.window.in_use,
                window_blocks_per_slot=self.window.per_slot,
                window_blocks_recycled_total=self.window.recycled_total)
        out.update(self.shard_stats())
        return out

    def shard_stats(self) -> dict[str, int]:
        """Per-shard KV slab accounting for scrapes and the serve banner.

        ``kv_bytes_shard`` is what ONE device holds (the whole slab when
        unsharded/replicated; a kv-head slice under TP); ``kv_shards`` is
        the number of distinct shards the slabs split into (1 when not
        sharded — replication is not a split).  Occupancy needs no
        per-shard variant: the free list is global and every shard holds
        the same block ids, so per-shard occupancy IS ``occupancy`` by
        construction — that invariant is the whole point of replicated
        block tables."""
        if self.pages is None:  # supervisor yanked the dead engine's slabs
            return {"kv_bytes_total": 0, "kv_bytes_shard": 0, "kv_shards": 1}
        arrs = self.pages.all_arrays()
        total = sum(a.nbytes for a in arrs)
        shard = 0
        for a in arrs:
            try:
                shape = a.sharding.shard_shape(a.shape)
            except (AttributeError, TypeError):
                shape = a.shape
            shard += math.prod(shape) * a.dtype.itemsize
        return {
            "kv_bytes_total": int(total),
            "kv_bytes_shard": int(shard),
            "kv_shards": max(int(round(total / shard)), 1) if shard else 1,
        }

    def alloc(self, n: int) -> list[int] | None:
        if (
            self.prefix_cache is not None
            and n > self.free_list.num_free
        ):
            # evict LRU cache-only entries to cover the shortfall
            self.prefix_cache.release(n - self.free_list.num_free)
        return self.free_list.alloc(n)

    def free(self, ids: list[int]) -> None:
        self.free_list.free(ids)
