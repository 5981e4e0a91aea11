"""Page-pool allocator invariants (serving/pages.py, DESIGN.md §11).

Property-tested with hypothesis: random alloc/retain/release/writable
sequences must conserve pages, keep refcounts consistent, and never
leave a page simultaneously free and referenced.
"""
import random

import pytest
from _hypo import given, settings, st

from repro.serving.pages import PagePool, PrefixCache


# ----------------------------------------------------------------------
# PagePool
# ----------------------------------------------------------------------

@settings(max_examples=20)
@given(num_pages=st.integers(1, 12), seed=st.integers(0, 10_000),
       steps=st.integers(1, 120))
def test_pool_random_ops_keep_invariants(num_pages, seed, steps):
    rng = random.Random(seed)
    pool = PagePool(num_pages, page_size=4)
    held = []                      # one entry per reference we hold
    for _ in range(steps):
        op = rng.random()
        if op < 0.4:
            pid = pool.alloc()
            if pid is None:
                assert pool.free_pages == 0
            else:
                assert pool.refcount(pid) == 1
                held.append(pid)
        elif op < 0.6 and held:
            pid = rng.choice(held)
            pool.retain(pid)
            held.append(pid)
        elif op < 0.85 and held:
            pid = held.pop(rng.randrange(len(held)))
            pool.release(pid)
        elif held:
            pid = rng.choice(held)
            new_pid, copied = pool.writable(pid)
            if new_pid is None:
                assert pool.refcount(pid) > 1 and pool.free_pages == 0
            else:
                assert pool.refcount(new_pid) >= 1
                if copied:
                    assert new_pid != pid
                    held.remove(pid)
                    held.append(new_pid)
                else:
                    assert new_pid == pid and pool.refcount(pid) == 1
        pool.check_invariants()
    # every reference we hold maps to a live page; full drain frees all
    for pid in held:
        pool.release(pid)
    pool.check_invariants()
    assert pool.free_pages == num_pages
    assert pool.in_use == 0


def test_pool_exhaustion_and_alloc_n():
    pool = PagePool(3, page_size=8)
    pages = pool.alloc_n(3)
    assert sorted(pages) == [0, 1, 2]
    assert pool.alloc() is None
    assert pool.alloc_n(1) is None
    assert pool.metrics.alloc_failures == 2
    pool.release(pages[1])
    assert pool.alloc() == 1       # LIFO free list reuses the freed page
    pool.check_invariants()


def test_pool_writable_cow_semantics():
    pool = PagePool(4, page_size=8)
    a = pool.alloc()
    same, copied = pool.writable(a)
    assert (same, copied) == (a, False)       # exclusive: no copy
    pool.retain(a)                            # now shared
    fresh, copied = pool.writable(a)
    assert copied and fresh != a
    assert pool.refcount(fresh) == 1
    assert pool.refcount(a) == 1              # the other holder remains
    assert pool.metrics.cow_copies == 1
    pool.check_invariants()


def test_pool_refcount_errors():
    pool = PagePool(2, page_size=4)
    with pytest.raises(ValueError):
        pool.release(0)
    with pytest.raises(ValueError):
        pool.retain(1)


# ----------------------------------------------------------------------
# PrefixCache
# ----------------------------------------------------------------------

def test_prefix_cache_full_and_partial_match():
    pool = PagePool(8, page_size=4)
    cache = PrefixCache(pool)
    prompt = [5, 9, 3, 7, 2, 8]               # page0 full, page1 covers 2
    pages = pool.alloc_n(2)
    cache.register(prompt, pages)
    # full-page + partial sub-length entries, each holding a reference
    assert pool.refcount(pages[0]) == 2
    assert pool.refcount(pages[1]) == 3       # c=1 and c=2 entries

    # identical prompt: shares both pages, stops at the partial page
    shared, n = cache.match(list(prompt))
    assert n == 6 and [p for p, _ in shared] == pages
    assert [c for _, c in shared] == [4, 2]
    for pid, _ in shared:
        pool.release(pid)

    # divergence mid-page: shares up to the divergence point only
    shared, n = cache.match([5, 9, 3, 7, 2, 99, 1])
    assert n == 5 and [(p, c) for p, c in shared] == [(pages[0], 4),
                                                      (pages[1], 1)]
    for pid, _ in shared:
        pool.release(pid)

    # divergence inside the first page: nothing shareable
    shared, n = cache.match([5, 1, 3, 7])
    assert (shared, n) == ([], 0)
    assert pool.metrics.prefix_hits == 4


def test_prefix_cache_eviction_returns_pages():
    pool = PagePool(4, page_size=4)
    cache = PrefixCache(pool)
    prompt = [1, 2, 3, 4]
    (pid,) = pool.alloc_n(1)
    cache.register(prompt, [pid])
    pool.release(pid)              # only the cache holds it now
    assert pool.free_pages == 3
    assert cache.evict(1) == 1     # entry dropped, page back in the pool
    assert pool.free_pages == 4
    assert len(cache) == 0
    pool.check_invariants()


def test_prefix_cache_eviction_skips_shared_holders():
    pool = PagePool(4, page_size=4)
    cache = PrefixCache(pool)
    (pid,) = pool.alloc_n(1)
    cache.register([1, 2, 3, 4], [pid])
    # the request still holds the page: eviction frees nothing but the
    # cache entry is gone and the request's reference survives
    assert cache.evict(1) == 0
    assert len(cache) == 0
    assert pool.refcount(pid) == 1
    pool.check_invariants()
