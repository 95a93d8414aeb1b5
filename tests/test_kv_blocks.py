"""BlockAllocator unit tests: refcounts, prefix cache, CoW, eviction.

The allocator is pure host-side Python (the engine serializes it under
its own lock), so these tests pin its invariants without touching JAX
(the last test of the file, on the jitted programs' writes, apart):
a block leaves the free list only via alloc(), returns only at refcount
zero, cache retention counts as a reference, and the sha1-chained match
walk never covers the last prompt token (the prefill must compute the
last position's logits to sample the first output token).
"""

import numpy as np
import pytest

from dstack_tpu.workloads.kv_blocks import BlockAllocator, init_paged_state
from dstack_tpu.workloads.config import PRESETS

BS = 4  # block size used throughout; small so chains stay readable


def test_alloc_release_refcount_roundtrip():
    a = BlockAllocator(num_blocks=3, block_size=BS)
    b1, b2, b3 = a.alloc(), a.alloc(), a.alloc()
    assert sorted([b1, b2, b3]) == [0, 1, 2]
    assert a.in_use == 3
    assert a.alloc() is None  # exhausted, nothing cached to evict
    a.retain(b1)  # second holder
    a.release(b1)
    assert a.in_use == 3  # still held once
    a.release(b1)
    assert a.in_use == 2
    assert a.alloc() == b1  # freed block is reusable
    a.release(b2)
    with pytest.raises(AssertionError):  # double release must fail loudly
        a.release(b2)


def test_match_full_chain_and_partial_tail():
    a = BlockAllocator(num_blocks=8, block_size=BS)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]  # 2 full blocks + tail [9, 10]
    table = [a.alloc(), a.alloc(), a.alloc()]
    a.insert_full(prompt, table)
    assert a.cached == 2  # only complete blocks at finalize time
    a.insert_tail(prompt, table)
    assert a.cached == 3

    # Identical prompt: both full blocks match; the tail [9, 10] does NOT
    # because match leaves >=1 trailing token uncovered (limit=9 -> only a
    # 1-token tail [9] is searched, and the cached key is the 2-token tail).
    blocks, matched = a.match(prompt)
    assert blocks == table[:2] and matched == 8
    assert a.hits == 1 and a.tokens_reused == 8
    for b in blocks:
        a.release(b)  # matcher's retains

    # A longer prompt sharing the prefix matches full chain + cached tail.
    blocks, matched = a.match(prompt + [11, 12, 13])
    assert blocks == table and matched == 10
    for b in blocks:
        a.release(b)

    # Diverging first block: no match, miss counted.
    blocks, matched = a.match([99, 2, 3, 4, 5, 6, 7, 8])
    assert blocks == [] and matched == 0
    assert a.misses == 1


def test_match_never_covers_last_token():
    a = BlockAllocator(num_blocks=4, block_size=BS)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]  # exactly 2 full blocks
    table = [a.alloc(), a.alloc()]
    a.insert_full(prompt, table)
    # Same prompt again: limit = 7, so only the FIRST block may match —
    # the second would cover the final token whose logits prefill needs.
    blocks, matched = a.match(prompt)
    assert blocks == table[:1] and matched == 4


def test_ensure_writable_cow_semantics():
    a = BlockAllocator(num_blocks=3, block_size=BS)
    b = a.alloc()
    assert a.ensure_writable(b) == (b, False)  # private: write in place
    a.retain(b)  # now shared (e.g. matched by a second table)
    nb, needs_copy = a.ensure_writable(b)
    assert needs_copy and nb != b
    assert a.cow_copies == 1
    assert a._ref[b] == 1  # our share of the old block was released
    # Exhaustion during CoW: pool of 3 with all blocks held.
    a.retain(b)
    c = a.alloc()
    assert c is not None and a.in_use == 3
    assert a.ensure_writable(b) == (None, False)  # caller retries later


def test_lru_eviction_frees_cached_blocks_only_at_ref_zero():
    a = BlockAllocator(num_blocks=2, block_size=BS)
    p1, p2 = [1, 2, 3, 4, 9], [5, 6, 7, 8, 9]
    t1, t2 = [a.alloc()], [a.alloc()]
    a.insert_full(p1, t1)
    a.insert_full(p2, t2)
    assert a.alloc() is None  # cached but still table-held: not evictable
    for t in (t1, t2):
        a.release(t[0])  # tables retire; blocks now cache-held only
    assert a.in_use == 2 and a.cached == 2
    # p1's block is LRU (inserted first, never touched since): evicted.
    b = a.alloc()
    assert b == t1[0]
    assert a.evictions == 1 and a.cached == 1
    # p2's entry survived and still matches.
    blocks, matched = a.match(p2)
    assert blocks == t2 and matched == 4


def test_cache_disabled_is_inert():
    a = BlockAllocator(num_blocks=4, block_size=BS, cache=False)
    t = [a.alloc(), a.alloc()]
    a.insert_full([1, 2, 3, 4, 5, 6, 7, 8], t)
    a.insert_tail([1, 2, 3, 4, 5, 6], t)
    assert a.cached == 0
    assert a.match([1, 2, 3, 4, 5, 6, 7, 8]) == ([], 0)
    assert a.hits == 0 and a.misses == 0


def test_insert_full_dedups_against_existing_entries():
    a = BlockAllocator(num_blocks=4, block_size=BS)
    prompt = [1, 2, 3, 4, 5]
    t1 = [a.alloc(), a.alloc()]
    a.insert_full(prompt, t1)
    t2 = [a.alloc(), a.alloc()]
    a.insert_full(prompt, t2)  # same content: first entry wins
    assert a.cached == 1
    blocks, matched = a.match(prompt + [6, 7, 8])
    assert blocks == t1[:1] and matched == 4


def test_init_paged_state_validates_block_size():
    cfg = PRESETS["tiny"].with_(remat=False)
    with pytest.raises(ValueError, match="divide"):
        init_paged_state(cfg, batch=2, max_len=32, block_size=5,
                         num_blocks=16)
    st = init_paged_state(cfg, batch=2, max_len=32, block_size=8,
                          num_blocks=16)
    assert st.block_tables.shape == (2, 4)
    assert int(st.block_tables.min()) == 16  # pad sentinel == num_blocks


# -------------------------------------------- host-tier hooks (PR 16)


def test_spill_hook_fires_at_eviction_with_device_contents_intact():
    spilled = []
    a = BlockAllocator(num_blocks=2, block_size=BS,
                       spill=lambda key, b: spilled.append((key, b)))
    p1, p2 = [1, 2, 3, 4, 9], [5, 6, 7, 8, 9]
    t1, t2 = [a.alloc()], [a.alloc()]
    a.insert_full(p1, t1)
    a.insert_full(p2, t2)
    a.release(t1[0])
    a.release(t2[0])
    b = a.alloc()  # p1's block is LRU: evicted AND handed to the hook
    assert b == t1[0]
    assert len(spilled) == 1
    key, blk = spilled[0]
    assert blk == t1[0] and key[0] == "F"
    # The hook saw the block BEFORE it returned to the free list — by
    # the time alloc() hands it out it is no longer cache-indexed.
    assert blk not in a._block_key


def test_live_referenced_blocks_never_spill():
    """The spill invariant: a block any slot still references (ref > 1,
    cache hold + table hold) must not leave the device — alloc() returns
    None rather than spilling it."""
    spilled = []
    a = BlockAllocator(num_blocks=2, block_size=BS,
                       spill=lambda key, b: spilled.append(key))
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    table = [a.alloc(), a.alloc()]
    a.insert_full(prompt, table)  # both blocks: table ref + cache ref
    assert a.alloc() is None
    assert spilled == []
    a.release(table[0])  # first block now cache-held only
    assert a.alloc() == table[0]
    assert [k[0] for k in spilled] == ["F"]


def test_partial_tail_aliasing_full_chain_evicts_independently():
    """A partial-tail key shares its parent chain hash with the full
    blocks it extends. Eviction must treat the alias as its own LRU
    entry: touching the FULL chain via match() must not keep the tail
    alive, and spill keys must come out in true LRU order."""
    spilled = []
    a = BlockAllocator(num_blocks=3, block_size=BS,
                       spill=lambda key, b: spilled.append(key))
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]  # 2 full blocks + tail [9, 10]
    table = [a.alloc(), a.alloc(), a.alloc()]
    a.insert_full(prompt, table)
    a.insert_tail(prompt, table)
    for b in table:
        a.release(b)
    assert a.cached == 3
    # Longest-prefix match retains and LRU-bumps all three entries, tail
    # included; release the matcher's holds so everything is evictable.
    blocks, matched = a.match(prompt + [11, 12, 13])
    assert blocks == table and matched == 10
    for b in blocks:
        a.release(b)
    # Bump ONLY the full chain: a shorter probe never reaches the tail.
    blocks, matched = a.match(prompt[:8] + [99])
    assert matched == 8
    for b in blocks:
        a.release(b)
    # Drain the pool: the tail (now the true LRU) must evict FIRST even
    # though its parent hash equals the full chain's, then the full
    # blocks in chain order.
    assert [a.alloc() for _ in range(3)] == [table[2], table[0], table[1]]
    assert [k[0] for k in spilled] == ["P", "F", "F"]
    assert spilled[0][2] == (9, 10)  # the tail's token key rode along


def test_swap_in_hook_resurrects_chain_and_counts_host_hits():
    """A match() miss probes the swap_in hook; a resurrected block is
    republished under its key (hook's ref=1 becomes the cache hold) and
    the whole match counts as a host hit, not a device hit."""
    host = {}
    a = BlockAllocator(num_blocks=4, block_size=BS,
                       spill=lambda key, b: host.setdefault(key, b),
                       swap_in=None)
    # Wire swap_in after construction so the hook can reenter a.alloc().
    def swap_in(key):
        if key not in host:
            return None
        del host[key]
        return a.alloc()
    a._swap_in = swap_in
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9]
    table = [a.alloc(), a.alloc()]
    a.insert_full(prompt, table)
    a.release(table[0])
    a.release(table[1])
    # Evict both cached blocks into the fake host store.
    held = [a.alloc() for _ in range(4)]
    assert len(host) == 2
    for b in held:
        a.release(b)
    blocks, matched = a.match(prompt)
    assert matched == 8 and len(blocks) == 2
    assert a.hits == 1 and a.host_hits == 1
    assert host == {}  # both keys resurrected
    # Each resurrected block: cache hold + matcher hold.
    assert all(a._ref[b] == 2 for b in blocks)
    st = a.stats()
    assert st["host_hits"] == 1


def test_drop_cache_releases_cache_only_holds():
    """Weight refresh drops the whole prefix cache: cache-only blocks
    return to the free list, table-held blocks just lose their entry."""
    a = BlockAllocator(num_blocks=4, block_size=BS)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8]  # exactly 2 full blocks
    table = [a.alloc(), a.alloc()]
    a.insert_full(prompt, table)
    a.release(table[0])  # cache-only hold now
    # table[1] stays table-held (a live request still points at it).
    assert a.cached == 2
    dropped = a.drop_cache()
    assert dropped == 2
    assert a.cached == 0
    assert a._ref[table[0]] == 0  # returned to the free list
    assert a._ref[table[1]] == 1  # the live hold survives
    # Post-drop, the same prompt must MISS — stale KV never grafts.
    blocks, matched = a.match(prompt + [9, 10])
    assert blocks == [] and matched == 0
    # And the freed block is allocatable again.
    assert a.alloc() is not None


def test_drop_cache_empty_is_noop():
    a = BlockAllocator(num_blocks=2, block_size=BS)
    assert a.drop_cache() == 0
    assert a.drop_cache() == 0  # idempotent


# -- the jitted programs write rows, not slabs ---------------------------------


def _random_pool_state(cfg, slots, max_len, block, seed):
    """A paged state whose pool holds random bits in every layer, so an
    untouched block is one that still holds exactly those bits."""
    import jax
    import jax.numpy as jnp

    nb = slots * (max_len // block)
    st = init_paged_state(cfg, slots, max_len, block, nb)
    kk, kv = jax.random.split(jax.random.PRNGKey(seed))
    return st._replace(
        k=jax.random.normal(kk, st.k.shape, jnp.float32).astype(st.k.dtype),
        v=jax.random.normal(kv, st.v.shape, jnp.float32).astype(st.v.dtype),
    )


def _assert_only_rows_written(before, after, rows):
    """Every layer of the pool is bit-identical outside `rows`, a list of
    (block, offset), and differs in each of them."""
    before, after = np.asarray(before), np.asarray(after)
    written = np.zeros(before.shape[:3], bool)
    for blk, off in rows:
        written[:, blk, off] = True
    changed = (before != after).any(axis=(3, 4))
    assert (changed == written).all(), (
        "layers/blocks/rows that differ from the rows written: "
        f"{np.argwhere(changed != written)[:8].tolist()}"
    )


@pytest.mark.parametrize("program", ["decode_step", "prefill_chunk"])
def test_paged_program_writes_only_its_rows_in_every_layer(program):
    """The pool is carried through the layer loop and written at
    [layer, block, offset]: a lane pointed at the sentinel block drops
    (it must not spill into block 0 of layer l+1, which is where
    sentinel + l * num_blocks would land on a flattened pool), and no
    layer's other blocks change by a bit."""
    import jax
    import jax.numpy as jnp

    from dstack_tpu.workloads.kv_blocks import (
        make_chunk_prefill,
        make_paged_decode_step,
    )
    from dstack_tpu.workloads.transformer import init_params

    cfg = PRESETS["tiny"].with_(remat=False, n_layers=3)
    slots, max_len, block = 3, 32, 8
    mb = max_len // block
    st = _random_pool_state(cfg, slots, max_len, block, seed=1)
    nb = st.k.shape[1]
    params = init_params(cfg, jax.random.PRNGKey(0))
    k0, v0 = np.asarray(st.k), np.asarray(st.v)

    if program == "decode_step":
        # Slot 0 writes row 10 = block 7, offset 2; slot 1 is inactive
        # with a STALE table row (its lane must drop); slot 2 is active
        # with a full cache (lengths == max_len: the write is refused).
        tables = np.full((slots, mb), nb, np.int32)
        tables[0, :2] = [5, 7]
        tables[1, :2] = [0, 1]
        tables[2] = [2, 3, 4, 6]
        st = st._replace(
            block_tables=jnp.asarray(tables),
            lengths=jnp.asarray([10, 9, max_len], jnp.int32),
            last_token=jnp.asarray([3, 4, 5], jnp.int32),
            active=jnp.asarray([True, False, True]),
            remaining=jnp.asarray([4, 4, 4], jnp.int32),
        )
        step = make_paged_decode_step(cfg, steps=1)
        out, _, _ = step(params, st, jax.random.PRNGKey(2))
        rows = [(7, 2)]
    else:
        # A chunk of 8 lanes, 5 real, at positions 6..10: rows 6, 7 of
        # block 9 and rows 0..2 of block 4; three padded lanes drop.
        row = np.full((mb,), nb, np.int32)
        row[:2] = [9, 4]
        chunk = make_chunk_prefill(cfg, 8)
        out, _ = chunk(
            params, st, jnp.int32(1), jnp.asarray(row),
            jnp.asarray([[7, 8, 9, 10, 11, 0, 0, 0]], jnp.int32),
            jnp.int32(5), jnp.int32(6), jnp.int32(4), jnp.float32(0.0),
            jnp.float32(1.0), jax.random.PRNGKey(2), jnp.bool_(True),
        )
        rows = [(9, 6), (9, 7), (4, 0), (4, 1), (4, 2)]

    _assert_only_rows_written(k0, out.k, rows)
    _assert_only_rows_written(v0, out.v, rows)
