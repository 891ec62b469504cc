"""Property suite for the paged-KV block allocator.

Model-based testing: random interleavings of the allocator's whole API
(admission-style alloc+publish, free, fork, copy-on-write, prefix match)
against a shadow model of table→block references.  After every op:

  * ``assert_consistent`` — free / cached / live partition the pool, the
    prefix index points only at live-or-cached blocks, the sentinel is
    never handed out;
  * every LIVE block's refcount equals the number of table references the
    shadow model holds (so alloc/free/fork can never double-free or leak);
  * freed blocks are reusable: draining every table returns the pool to
    full capacity.

Needs ``hypothesis`` (requirements-dev.txt).
"""

from collections import Counter

import pytest
from hypothesis import given, settings, strategies as st

from repro.serve.blocks import (BlockAllocator, NoFreeBlocks, SENTINEL)


# ---------------------------------------------------------------------------
# deterministic unit coverage
# ---------------------------------------------------------------------------


def test_rejects_degenerate_pools():
    with pytest.raises(ValueError):
        BlockAllocator(1, 4)            # sentinel only
    with pytest.raises(ValueError):
        BlockAllocator(4, 0)


def test_sentinel_never_allocated():
    a = BlockAllocator(5, 4)
    got = [a.alloc() for _ in range(a.capacity)]
    assert SENTINEL not in got
    assert sorted(got) == [1, 2, 3, 4]
    with pytest.raises(NoFreeBlocks):
        a.alloc()


def test_double_free_raises():
    a = BlockAllocator(4, 4)
    b = a.alloc()
    assert a.decref(b)
    with pytest.raises(RuntimeError, match="double free"):
        a.decref(b)
    a.assert_consistent()


def test_freed_blocks_are_reusable():
    a = BlockAllocator(3, 2)
    b1, b2 = a.alloc(), a.alloc()
    with pytest.raises(NoFreeBlocks):
        a.alloc()
    a.decref(b1)
    b3 = a.alloc()              # the freed block comes back
    assert b3 == b1
    a.free_blocks([b2, b3])
    assert a.num_free == a.capacity and a.num_used == 0


def test_fork_shares_and_free_unwinds():
    a = BlockAllocator(6, 2)
    blocks = [a.alloc(), a.alloc()]
    forked = a.fork(blocks)
    assert forked == blocks
    assert all(a.refcount(b) == 2 for b in blocks)
    a.free_blocks(forked)
    assert all(a.refcount(b) == 1 for b in blocks)
    a.free_blocks(blocks)
    assert a.num_used == 0 and a.num_free == a.capacity
    a.assert_consistent()


def test_cow_private_is_noop_shared_copies():
    a = BlockAllocator(6, 2)
    b = a.alloc()
    assert a.cow(b) == (b, False)           # refcount 1: already writable
    a.incref(b)
    nb, copied = a.cow(b)
    assert copied and nb != b
    assert a.refcount(b) == 1 and a.refcount(nb) == 1
    a.free_blocks([b, nb])
    a.assert_consistent()


def test_cow_pool_dry_leaves_state_intact():
    a = BlockAllocator(2, 2)                # one usable block
    b = a.alloc()
    a.incref(b)
    with pytest.raises(NoFreeBlocks):
        a.cow(b)
    assert a.refcount(b) == 2               # nothing half-done
    a.assert_consistent()


def test_publish_match_and_retention():
    a = BlockAllocator(8, 2)
    prompt = [1, 2, 3, 4, 5]                # 2 full blocks + a tail token
    keys = a.prefix_keys(prompt)
    assert keys == [(1, 2), (1, 2, 3, 4)]
    blocks = [a.alloc() for _ in range(3)]
    for b, k in zip(blocks, keys):
        assert a.publish(b, k)
    # concurrent identical prompt: shares the two published blocks
    m = a.match_prefix(prompt)
    assert m == blocks[:2]
    assert all(a.refcount(b) == 2 for b in m)
    a.free_blocks(m)
    # retention: freeing the ORIGINAL keeps published blocks cached and
    # revivable — a later identical prompt still hits
    a.free_blocks(blocks)
    assert a.num_used == 0 and a.num_cached == 2
    assert a.num_free == a.capacity         # cached blocks are allocatable
    m2 = a.match_prefix(prompt)
    assert m2 == blocks[:2] and all(a.refcount(b) == 1 for b in m2)
    a.free_blocks(m2)
    a.assert_consistent()


def test_publish_first_writer_wins():
    a = BlockAllocator(6, 2)
    b1, b2 = a.alloc(), a.alloc()
    assert a.publish(b1, (7, 8))
    assert not a.publish(b2, (7, 8))        # key taken: b2 stays private
    a.free_blocks([b1, b2])
    assert a.num_cached == 1                # only the published one retained
    a.assert_consistent()


def test_eviction_unpublishes_oldest_cached():
    a = BlockAllocator(3, 2)                # two usable blocks
    b1, b2 = a.alloc(), a.alloc()
    a.publish(b1, (1, 1))
    a.publish(b2, (2, 2))
    a.free_blocks([b1, b2])                 # both cached, b1 older
    c1 = a.alloc()                          # evicts b1 (FIFO)
    assert c1 == b1
    assert a.match_prefix([1, 1]) == []     # b1's entry is gone
    m = a.match_prefix([2, 2])              # b2 still revivable
    assert m == [b2]
    a.free_blocks([c1] + m)
    a.assert_consistent()


def test_blocks_for():
    a = BlockAllocator(4, 8)
    assert a.blocks_for(1) == 1
    assert a.blocks_for(8) == 1
    assert a.blocks_for(9) == 2
    assert a.blocks_for(17) == 3


# ---------------------------------------------------------------------------
# property suite: random op interleavings vs a shadow reference model
# ---------------------------------------------------------------------------


def _check_refcounts(alloc, tables):
    """Every live block's refcount must equal the table references held."""
    refs = Counter(b for blocks, _ in tables for b in blocks)
    for b, n in refs.items():
        assert alloc.refcount(b) == n, f"block {b}: {alloc.refcount(b)} != {n}"
    live = alloc.num_used
    assert live == len(refs), f"{live} live blocks but {len(refs)} referenced"


@settings(max_examples=25, deadline=None)
@given(st.data())
def test_allocator_random_ops_maintain_invariants(data):
    nb = data.draw(st.integers(min_value=3, max_value=20), label="nb")
    bs = data.draw(st.integers(min_value=1, max_value=4), label="bs")
    a = BlockAllocator(nb, bs)
    tables = []     # shadow model: (blocks, prompt) pairs we hold refs on
    n_ops = data.draw(st.integers(min_value=1, max_value=50), label="n_ops")
    for _ in range(n_ops):
        op = data.draw(st.sampled_from(
            ["admit", "free", "fork", "cow", "probe"]), label="op")
        if op == "admit":
            # admission flow: match the prefix cache, allocate the tail,
            # publish the full prompt blocks (tiny alphabet → collisions)
            plen = data.draw(st.integers(min_value=1, max_value=3 * bs))
            prompt = [data.draw(st.integers(min_value=0, max_value=2))
                      for _ in range(plen)]
            matched = a.match_prefix(prompt)
            fresh = a.blocks_for(plen) - len(matched)
            if fresh > a.num_free:
                a.free_blocks(matched)          # deferred admission
            else:
                blocks = matched + [a.alloc() for _ in range(fresh)]
                for blk, key in zip(blocks, a.prefix_keys(prompt)):
                    a.publish(blk, key)
                tables.append((blocks, prompt))
        elif op == "free" and tables:
            i = data.draw(st.integers(min_value=0, max_value=len(tables) - 1))
            blocks, _ = tables.pop(i)
            a.free_blocks(blocks)
        elif op == "fork" and tables:
            i = data.draw(st.integers(min_value=0, max_value=len(tables) - 1))
            blocks, prompt = tables[i]
            tables.append((a.fork(blocks), prompt))
        elif op == "cow" and tables:
            i = data.draw(st.integers(min_value=0, max_value=len(tables) - 1))
            blocks, prompt = tables[i]
            if blocks:
                j = data.draw(st.integers(min_value=0,
                                          max_value=len(blocks) - 1))
                try:
                    nb_, _copied = a.cow(blocks[j])
                    blocks[j] = nb_
                except NoFreeBlocks:
                    pass                        # state must stay intact
        elif op == "probe":
            # a lookup the caller abandons must be reference-neutral
            plen = data.draw(st.integers(min_value=1, max_value=2 * bs))
            prompt = [data.draw(st.integers(min_value=0, max_value=2))
                      for _ in range(plen)]
            a.free_blocks(a.match_prefix(prompt))
        a.assert_consistent()
        _check_refcounts(a, tables)

    # drain: every freed block is reusable, nothing leaks
    for blocks, _ in tables:
        a.free_blocks(blocks)
    a.assert_consistent()
    assert a.num_used == 0
    assert a.num_free == a.capacity
    got = sorted(a.alloc() for _ in range(a.capacity))
    assert got == list(range(1, nb))            # every block came back
