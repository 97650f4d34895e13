"""Property-based allocator invariants for PagePool / PrefixTrie.

Random interleavings of the full host-side cache lifecycle — admit,
publish, decode-page materialization, speculative rollback, trie eviction,
slot free, plus the resilience fault actions (watchdog quarantine-free,
deadline abort, degradation-ladder trie flush) — must preserve the
refcount algebra at every step:

* conservation: ``free_count + allocated_count == n_pages - 1`` (the null
  page is permanently pinned and never counted);
* refs == holders: every page's refcount equals the number of block-table
  entries naming it plus one if the trie caches it — no leaked pages, no
  double-free;
* reservation accounting: ``cache.reserved`` equals the sum of per-slot
  reservations, and full teardown (free every slot, drain the trie)
  returns every page to the free list.

Runs under real ``hypothesis`` when installed, or the deterministic
fallback installed by the repo-root ``conftest.py`` otherwise.
"""

import functools

import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.models import ModelConfig, build
from repro.serve.cache import (NULL_PAGE, PagedCache, PagePool, PrefixTrie,
                               publish_prefix_shared, share_trie)

PAGE = 4
ALPHABET = 6          # tiny vocab so random prompts actually share prefixes


@functools.lru_cache(maxsize=None)
def _tiny_model():
    cfg = ModelConfig(name="tiny", n_layers=1, d_model=32, n_heads=2,
                      n_kv_heads=2, d_ff=64, vocab=32, mpd_c=4)
    return build(cfg)


def _mk_cache(slack=0):
    return PagedCache(_tiny_model(), n_slots=3, max_len=24,
                      page_size=PAGE, slack_tokens=slack)


def _check_invariants(caches, live):
    """``live``: slot -> (prompt, kv_len) per active request (same slots in
    every cache)."""
    for cache in caches:
        pool = cache.pool
        assert pool.free_count + pool.allocated_count == pool.n_pages - 1
        assert len(set(pool._free)) == len(pool._free), "double-free"
        assert all(pool.ref[p] == 0 for p in pool._free)
        expect = np.zeros(pool.n_pages, np.int64)
        expect[NULL_PAGE] = 1
        for slot in live:
            row = cache.block_tables[slot]
            for pid in row[row != NULL_PAGE]:
                expect[pid] += 1
        for value in cache.trie.nodes.values():
            expect[cache._own_pid(value)] += 1
        assert (pool.ref == expect).all(), \
            (pool.ref.tolist(), expect.tolist())
        assert cache.reserved == sum(cache._slot_reserved)
        assert cache.reserved >= 0


def _run_ops(ops, caches, slack):
    """Interpret a random op sequence against one or more caches driven in
    lockstep (the shared-trie configuration drives two)."""
    shared = len(caches) > 1
    live = {}                             # slot -> [prompt, kv_len, max_new]
    for seed in ops:
        rng = np.random.default_rng(seed)
        op = int(rng.integers(9))
        if op == 0 and len(live) < caches[0].n_slots:        # admit
            slot = next(s for s in range(caches[0].n_slots) if s not in live)
            prompt = rng.integers(0, ALPHABET,
                                  int(rng.integers(2, 17))).astype(np.int32)
            max_new = int(rng.integers(1, 8))
            if all(c.can_admit(len(prompt), max_new, prompt) for c in caches):
                matched = [c.admit_request(slot, prompt, max_new)
                           for c in caches]
                assert len(set(matched)) == 1, matched
                live[slot] = [prompt, len(prompt), max_new]
        elif op == 1 and live:                               # publish
            slot = int(rng.choice(sorted(live)))
            prompt = live[slot][0]
            if shared:
                publish_prefix_shared(caches, prompt, slot, len(prompt))
            else:
                caches[0].publish_prefix(prompt, slot, len(prompt))
        elif op == 2 and live:                               # decode page
            slot = int(rng.choice(sorted(live)))
            prompt, kv, max_new = live[slot]
            if kv < len(prompt) + max_new + slack:  # inside the reservation
                for c in caches:
                    c.ensure_decode_page(slot, kv)
                live[slot][1] = kv + 1
        elif op == 3 and live:                               # rollback
            slot = int(rng.choice(sorted(live)))
            prompt, kv, _ = live[slot]
            keep = int(rng.integers(len(prompt), kv + 1))
            for c in caches:
                c.rollback(slot, keep)
            live[slot][1] = keep
        elif op == 4:                                        # trie evict
            caches[0].trie.evict_one()
        elif op == 5 and live:                               # free slot
            slot = int(rng.choice(sorted(live)))
            for c in caches:
                c.free_slot(slot)
            del live[slot]
        elif op == 6 and live:               # fault: quarantine-free a slot
            # the engine's watchdog path — preempt_slot drops exactly the
            # request's refs; trie-published pages survive for the retry
            slot = int(rng.choice(sorted(live)))
            for c in caches:
                c.preempt_slot(slot)
            del live[slot]
        elif op == 7 and live:               # fault: deadline abort
            # _fail_request frees the slot mid-flight like a finish
            slot = int(rng.choice(sorted(live)))
            for c in caches:
                c.free_slot(slot)
            del live[slot]
        elif op == 8:                        # fault: degradation trie flush
            # stage-2 ladder action: cascade-evict every reclaimable node
            # (a shared trie drains both pools); live refs are untouched
            caches[0].flush_trie()
        _check_invariants(caches, live)

    # teardown: every page must come home
    for slot in list(live):
        for c in caches:
            c.free_slot(slot)
    while caches[0].trie.evict_one() is not None:
        pass
    for c in caches:
        assert c.pool.free_count == c.pool.n_pages - 1
        assert c.pool.allocated_count == 0
        assert c.reserved == 0


@settings(max_examples=30, deadline=None)  # first example builds a PagedCache (~0.3 s)
@given(st.lists(st.integers(0, 1 << 30), min_size=10, max_size=60),
       st.integers(0, 4))
def test_paged_cache_refcount_invariants(ops, slack):
    _run_ops(ops, [_mk_cache(slack=slack)], slack)


@settings(max_examples=20, deadline=None)
@given(st.lists(st.integers(0, 1 << 30), min_size=10, max_size=60),
       st.integers(0, 4))
def test_shared_trie_refcount_invariants(ops, slack):
    """Two pools behind one trie (the speculative-decoding layout): joint
    nodes retain and release in both pools atomically."""
    target, draft = _mk_cache(slack=slack), _mk_cache(slack=slack)
    trie = share_trie([target, draft])
    assert trie is target.trie and trie is draft.trie
    _run_ops(ops, [target, draft], slack)


@settings(max_examples=40)
@given(st.lists(st.integers(0, 1 << 30), min_size=5, max_size=40))
def test_page_pool_alloc_release(ops):
    """Bare pool churn: alloc/retain/release in random order never breaks
    conservation and teardown frees everything."""
    pool = PagePool(9)
    held = []                                   # multiset of held refs
    for seed in ops:
        rng = np.random.default_rng(seed)
        op = int(rng.integers(3))
        if op == 0 and pool.free_count:
            held.append(pool.alloc())
        elif op == 1 and held:
            pid = held[int(rng.integers(len(held)))]
            pool.retain(pid)
            held.append(pid)
        elif op == 2 and held:
            pid = held.pop(int(rng.integers(len(held))))
            pool.release(pid)
        assert pool.free_count + pool.allocated_count == pool.n_pages - 1
        for pid in set(held):
            assert pool.ref[pid] == held.count(pid)
    for pid in held:
        pool.release(pid)
    assert pool.free_count == pool.n_pages - 1


def test_shared_trie_unit():
    """Joint nodes: insert takes a ref in every pool, eviction frees every
    pool, and a node is reclaimable only when *all* pools are trie-only."""
    a, b = PagePool(4), PagePool(4)
    trie = PrefixTrie([a, b], 2)
    prompt = np.array([1, 2, 3, 4], np.int32)
    pa, pb = a.alloc(), b.alloc()
    assert trie.insert(prompt, 0, (pa, pb))
    assert a.ref[pa] == 2 and b.ref[pb] == 2
    a.release(pa), b.release(pb)                # trie is now sole holder
    assert trie.is_reclaimable((pa, pb))
    b.retain(pb)                                # one pool pinned -> not
    assert not trie.is_reclaimable((pa, pb))
    assert trie.evict_one() is None
    b.release(pb)
    assert trie.evict_one() == (pa, pb)
    assert a.free_count == a.n_pages - 1 and b.free_count == b.n_pages - 1
