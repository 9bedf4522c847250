"""Block-pool invariants: the allocator under the serving engine.

The free list is the admission-control ground truth — a bug here either
leaks pool capacity (throughput collapses under load) or double-books a
block (two requests silently corrupt each other's KV).  With refcounted
prefix sharing the stakes double: a premature free while another request
(or the prefix registry) still references a block is silent KV
corruption across requests.  Pure host-side tests; the device-slab
parity lives in test_serve_engine.py.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from llm_np_cp_tpu.config import tiny_config
from llm_np_cp_tpu.serve.block_pool import BlockPool, FreeList
from llm_np_cp_tpu.serve.prefix_cache import PrefixCache, prefix_block_keys


def test_freelist_alloc_free_roundtrip():
    fl = FreeList(8)
    assert fl.capacity == 7 and fl.num_free == 7
    ids = fl.alloc(3)
    assert ids is not None and len(ids) == 3 and len(set(ids)) == 3
    assert fl.num_free == 4 and fl.num_allocated == 3
    fl.free(ids)
    assert fl.num_free == 7 and fl.num_allocated == 0


def test_freelist_never_hands_out_scratch_block():
    fl = FreeList(8)
    ids = fl.alloc(7)  # drain the whole pool
    assert ids is not None and 0 not in ids
    assert sorted(ids) == list(range(1, 8))


def test_freelist_oversubscribe_returns_none_without_change():
    fl = FreeList(4)
    assert fl.alloc(4) is None  # capacity is 3 (block 0 reserved)
    assert fl.num_free == 3 and fl.num_allocated == 0
    got = fl.alloc(3)
    assert got is not None
    assert fl.alloc(1) is None
    assert fl.num_allocated == 3


def test_freelist_double_free_and_foreign_free_raise():
    fl = FreeList(4)
    ids = fl.alloc(1)
    fl.free(ids)
    with pytest.raises(ValueError):
        fl.free(ids)
    with pytest.raises(ValueError):
        fl.free([0])  # the scratch block is never allocated


def test_freelist_fragmentation_reuse():
    """Interleaved frees leave holes; any n <= num_free must remain
    allocatable (a paged pool has no external fragmentation by
    construction — this pins that the accounting agrees)."""
    fl = FreeList(16)
    held = [fl.alloc(1) for _ in range(15)]
    holes = held[::2]
    for h in holes:
        fl.free(h)
    assert fl.num_free == len(holes)
    again = fl.alloc(len(holes))
    assert again is not None
    assert sorted(again) == sorted(i for h in holes for i in h)


def test_freelist_lifo_reuse():
    """Most recently freed block is reallocated first (keeps hot pages
    hot on real hardware)."""
    fl = FreeList(8)
    a = fl.alloc(2)
    fl.free([a[1]])
    fl.free([a[0]])
    assert fl.alloc(1) == [a[0]]
    assert fl.alloc(1) == [a[1]]


def test_block_pool_shapes_and_occupancy():
    cfg = tiny_config("llama")
    pool = BlockPool(cfg, num_blocks=6, block_size=8, dtype=jnp.float32)
    assert pool.pages.k.shape == (
        cfg.num_hidden_layers, 6, 8, cfg.num_key_value_heads, cfg.head_dim
    )
    assert pool.pages.v.shape == pool.pages.k.shape
    assert not pool.pages.quantized
    assert pool.occupancy == 0.0
    ids = pool.alloc(2)
    assert pool.occupancy == pytest.approx(2 / 5)
    pool.free(ids)
    assert pool.occupancy == 0.0


def test_block_pool_blocks_for_rounds_up():
    cfg = tiny_config("llama")
    pool = BlockPool(cfg, num_blocks=4, block_size=8)
    assert pool.blocks_for(1) == 1
    assert pool.blocks_for(8) == 1
    assert pool.blocks_for(9) == 2
    assert pool.blocks_for(17) == 3


def test_block_pool_int8_pages_carry_scales():
    cfg = tiny_config("llama")
    pool = BlockPool(cfg, num_blocks=4, block_size=8, dtype=jnp.int8)
    assert pool.pages.quantized
    assert pool.pages.k.dtype == jnp.int8
    assert pool.pages.k_scale.shape == pool.pages.k.shape[:-1]
    assert pool.pages.k_scale.dtype == jnp.float32
    assert pool.pages.v_scale.shape == pool.pages.v.shape[:-1]


def test_block_pool_slabs_are_born_on_their_sharding():
    """N replicas each pin a pool to THEIR device: the slabs must be
    born there.  zeros-then-device_put — and ``jnp.zeros(device=...)``,
    which broadcasts one shard on the default device and copies it out —
    stage every replica's pool through device 0 (measured on the
    four-chip host: +304 MiB peak on chip 0).  The device-to-device
    transfer guard turns any such staging into an error."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from llm_np_cp_tpu.parallel.sharding import (
        MeshPlan, make_mesh, paged_kv_specs, to_shardings,
    )

    cfg = tiny_config("llama")
    far = jax.devices()[5]
    plan = MeshPlan()
    mesh = make_mesh(plan, [far])
    shardings = to_shardings(mesh, paged_kv_specs(cfg, plan, quantized=True))
    with jax.transfer_guard_device_to_device("disallow"):
        pool = BlockPool(cfg, num_blocks=4, block_size=8, dtype=jnp.int8,
                         shardings=shardings)
    assert pool.pages.state is None  # no conv layer, no state beside the pages
    for slab in pool.pages.pool_arrays():
        assert slab.devices() == {far}
        assert slab.sharding == NamedSharding(mesh, P())


def test_block_pool_rejects_bad_geometry():
    cfg = tiny_config("llama")
    with pytest.raises(ValueError):
        BlockPool(cfg, num_blocks=4, block_size=12)  # not a multiple of 8
    with pytest.raises(ValueError):
        BlockPool(cfg, num_blocks=4, block_size=4)  # below Mosaic minimum
    with pytest.raises(ValueError):
        FreeList(1)  # nothing allocatable beside the scratch block


# ---------------------------------------------------------------------------
# Refcounts: free is a decref; a block returns to the free list only when
# its LAST holder lets go.
# ---------------------------------------------------------------------------

def test_freelist_refcount_shared_block_survives_one_free():
    fl = FreeList(8)
    ids = fl.alloc(2)
    assert all(fl.refcount(i) == 1 for i in ids)
    fl.incref(ids)  # a second sharer
    assert all(fl.refcount(i) == 2 for i in ids)
    fl.free(ids)  # first sharer lets go — still allocated
    assert fl.num_allocated == 2 and fl.num_free == 5
    assert all(fl.refcount(i) == 1 for i in ids)
    fl.free(ids)  # last reference — now actually free
    assert fl.num_allocated == 0 and fl.num_free == 7
    assert all(fl.refcount(i) == 0 for i in ids)


def test_freelist_incref_on_free_block_raises():
    fl = FreeList(4)
    ids = fl.alloc(1)
    fl.free(ids)
    with pytest.raises(ValueError, match="unallocated"):
        fl.incref(ids)
    with pytest.raises(ValueError, match="unallocated"):
        fl.incref([0])  # scratch is never allocated


def test_freelist_over_free_still_raises_after_refcounts():
    """Decref below zero is still a hard double-free error — refcounts
    must not soften the corruption tripwire."""
    fl = FreeList(4)
    ids = fl.alloc(1)
    fl.incref(ids)
    fl.free(ids)
    fl.free(ids)
    with pytest.raises(ValueError):
        fl.free(ids)


# ---------------------------------------------------------------------------
# prefix_block_keys: the content→key mapping sharing correctness rests on.
# ---------------------------------------------------------------------------

def test_prefix_keys_chain_and_stop_at_partial_block():
    toks = np.arange(1, 40, dtype=np.int32)  # 39 tokens
    keys = prefix_block_keys(toks, pad=1, block_size=8, n_blocks=8)
    # pad+39 = 40 slots = 5 full blocks; block 5 would need slot 47 < 40
    assert len(keys) == 5
    assert len(set(keys)) == 5  # chained keys are distinct
    # same leading content → same leading keys; divergence at block 2
    other = toks.copy()
    other[20] += 1  # slot 21 (pad 1) → block 2
    keys2 = prefix_block_keys(other, pad=1, block_size=8, n_blocks=8)
    assert keys2[:2] == keys[:2]
    assert keys2[2:] != keys[2:]


def test_prefix_keys_pad_wider_than_block_hash_no_tail():
    """pad > block_size: the leading all-pad block's key must commit to
    NOTHING beyond the pad (a negative slice bound would wrap around and
    fold the prompt TAIL into key 0, silently defeating every prefix
    match under prefill_chunk > block_size layouts)."""
    a = np.arange(1, 30, dtype=np.int32)
    b = a.copy()
    b[10] += 1  # divergence at slot 30 (pad 20) — block 3, outside n_blocks
    ka = prefix_block_keys(a, pad=20, block_size=8, n_blocks=3)
    kb = prefix_block_keys(b, pad=20, block_size=8, n_blocks=3)
    # blocks 0-1 are pure pad, block 2 covers tokens 0..3 only — the
    # diverging token is in none of them, so ALL requested keys match
    assert len(ka) == len(kb) == 3
    assert ka == kb
    # and a divergence actually inside block 2 (token 0 at slot 20) breaks
    # keys from there on
    c = a.copy()
    c[0] += 1
    kc = prefix_block_keys(c, pad=20, block_size=8, n_blocks=3)
    assert kc[:2] == ka[:2] and kc[2] != ka[2]


def test_prefix_keys_depend_on_pad_and_block_size():
    toks = np.arange(1, 33, dtype=np.int32)
    a = prefix_block_keys(toks, pad=0, block_size=8, n_blocks=2)
    b = prefix_block_keys(toks, pad=8, block_size=8, n_blocks=2)
    c = prefix_block_keys(toks, pad=0, block_size=16, n_blocks=2)
    # pad shifts every slot's RoPE position; block size changes layout —
    # neither may collide even though block 1 of ``b`` holds the same
    # tokens as block 0 of ``a``
    assert not set(a) & set(b)
    assert not set(a) & set(c)


# ---------------------------------------------------------------------------
# PrefixCache: claim/register/release over the refcounted free list.
# ---------------------------------------------------------------------------

def _pool(num_blocks=10):
    cfg = tiny_config("llama")
    return BlockPool(cfg, num_blocks=num_blocks, block_size=8,
                     dtype=jnp.float32, enable_prefix_cache=True)


def test_prefix_cache_register_claim_roundtrip():
    pool = _pool()
    pc = pool.prefix_cache
    keys = [b"k0", b"k1", b"k2"]
    ids = pool.alloc(3)  # request A's prompt blocks
    pc.register(keys, ids)
    assert all(pool.free_list.refcount(i) == 2 for i in ids)  # A + cache
    # request B hits the full chain
    got = pc.claim(keys)
    assert got == ids
    assert all(pool.free_list.refcount(i) == 3 for i in ids)
    # a partial-chain claim stops at the first miss
    assert pc.claim([b"k0", b"MISS", b"k2"]) == ids[:1]
    pool.free(ids[:1])


def test_prefix_cache_match_is_pure():
    pool = _pool()
    pc = pool.prefix_cache
    ids = pool.alloc(2)
    pc.register([b"a", b"b"], ids)
    before = [pool.free_list.refcount(i) for i in ids]
    assert pc.match([b"a", b"b"]) == ids
    assert [pool.free_list.refcount(i) for i in ids] == before


def test_prefix_cache_release_skips_live_references():
    """Eviction can never free a block a live request references: only
    cache-only (refcount 1) entries are reclaimable, LRU first."""
    pool = _pool()
    pc = pool.prefix_cache
    a = pool.alloc(1)
    b = pool.alloc(1)
    pc.register([b"a"], a)
    pc.register([b"b"], b)
    pool.free(b)  # b's request finished — entry is now cache-only
    assert pc.n_reclaimable == 1
    freed = pc.release(2)  # asks for 2, but ``a`` is still live
    assert freed == 1
    assert pool.free_list.refcount(b[0]) == 0  # reclaimed
    assert pool.free_list.refcount(a[0]) == 2  # untouched (request + cache)
    assert pc.match([b"b"]) == []
    assert pc.match([b"a"]) == a
    pool.free(a)


def test_prefix_cache_lru_release_order():
    pool = _pool()
    pc = pool.prefix_cache
    a, b_, c = pool.alloc(1), pool.alloc(1), pool.alloc(1)
    pc.register([b"a"], a)
    pc.register([b"b"], b_)
    pc.register([b"c"], c)
    for ids in (a, b_, c):
        pool.free(ids)  # all cache-only now
    pc.claim([b"a"])  # LRU-touch a; release must take b first
    pool.free(a)  # drop the claim again
    assert pc.release(1) == 1
    assert pc.match([b"b"]) == [] and pc.match([b"a"]) == a


def test_pool_alloc_reclaims_cached_blocks_and_num_free_counts_them():
    """Shared blocks must not double-count against capacity: cache-only
    entries count as free for admission and are reclaimed by alloc on
    demand."""
    pool = _pool(num_blocks=6)  # 5 allocatable
    pc = pool.prefix_cache
    ids = pool.alloc(3)
    pc.register([b"a", b"b", b"c"], ids)
    pool.free(ids)  # request done — 3 cache-only blocks, 2 free
    assert pool.free_list.num_free == 2
    assert pool.num_free == 5  # reclaimable counted
    got = pool.alloc(4)  # needs a reclaim of 2
    assert got is not None and len(got) == 4
    assert pool.num_free == 1
    # the reclaim invalidated LRU entries; the survivor chain head is gone
    assert pc.match([b"a"]) == []


def test_prefix_cache_clear_drops_only_cache_references():
    pool = _pool()
    pc = pool.prefix_cache
    ids = pool.alloc(2)
    pc.register([b"a", b"b"], ids)
    pc.claim([b"a", b"b"])  # a live request shares them
    pc.clear()
    assert len(pc) == 0
    # live request's references survive the clear
    assert all(pool.free_list.refcount(i) == 2 for i in ids)
    pool.free(ids)
    pool.free(ids)
    assert pool.free_list.num_allocated == 0


def test_refcount_stress_invariants():
    """Randomized interleaving of alloc / share / register / release /
    free: every block is free xor allocated, counts always reconcile,
    and nothing double-frees."""
    rng = np.random.default_rng(0)
    fl = FreeList(24)
    pc = PrefixCache(fl)
    live: list[list[int]] = []  # per-"request" held ids (refs we own)
    registered: list[bytes] = []
    for step in range(2000):
        op = rng.integers(0, 5)
        if op == 0:  # alloc a fresh "request"
            n = int(rng.integers(1, 4))
            ids = fl.alloc(n)
            if ids is not None:
                live.append(ids)
        elif op == 1 and live:  # drop a request (decref all)
            ids = live.pop(int(rng.integers(0, len(live))))
            fl.free(ids)
        elif op == 2 and live:  # register a request's blocks
            ids = live[int(rng.integers(0, len(live)))]
            keys = [f"{step}:{i}".encode() for i in ids]
            pc.register(keys, ids)
            registered.extend(keys)
        elif op == 3 and registered:  # share: claim a registered key
            key = registered[int(rng.integers(0, len(registered)))]
            got = pc.claim([key])
            if got:
                live.append(got)
        else:  # reclaim pressure
            pc.release(int(rng.integers(1, 3)))
        # -- invariants -------------------------------------------------
        assert fl.num_free + fl.num_allocated == fl.capacity
        held = [i for ids in live for i in ids]
        for i in set(held):
            # every held reference is backed by the refcount (cache may
            # hold one more)
            assert fl.refcount(i) >= held.count(i)
        assert pc.n_reclaimable <= len(pc)
    for ids in live:
        fl.free(ids)
    pc.clear()
    assert fl.num_allocated == 0 and fl.num_free == fl.capacity
