"""Paged-cache unit tests: family-aware eviction, COW partial-block
matching, the digest-advertisement cap, and the allocator page-state
invariant under a randomized admit/decode/preempt/evict storm
(RTPU_DEBUG_ALLOCATOR asserts it after every op).

Host-side structures only — nothing is prefilled or decoded, the engine
of the last section is driven by hand with no weights — so these run in
milliseconds and pin the eviction-policy semantics the serving bench
depends on.  The batched `PrefixCache.evict` is held, page for page,
against the one-block-a-call walk it replaced, kept below as the plain
reference.
"""

import copy
import random

import pytest

from ray_tpu.llm.paged_cache import (CacheConfig, PageAllocator, PrefixCache,
                                     init_cache)


def _insert_chain(alloc, cache, tokens):
    """Simulate a finished sequence: allocate, register, release — its
    full pages end CACHED-RESIDENT.  Returns the pages."""
    n_pages = len(tokens) // cache.page_size
    pages = alloc.allocate(n_pages)
    alloc.mark_cached(cache.insert(tokens, pages))
    alloc.free(pages)
    return pages


@pytest.fixture(autouse=True)
def _debug_allocator(monkeypatch):
    monkeypatch.setenv("RTPU_DEBUG_ALLOCATOR", "1")


# ------------------------------------------------- family-aware eviction


def test_evicts_cold_family_before_hot():
    alloc = PageAllocator(32)
    cache = PrefixCache(4)
    hot = list(range(1, 13))       # 3 blocks
    cold = list(range(50, 62))     # 3 blocks, different family
    hot_pages = _insert_chain(alloc, cache, hot)
    cold_pages = _insert_chain(alloc, cache, cold)
    # heat the hot family: match() records family reuse
    cache.match(hot + [99])
    # ALL of the cold family drains before any hot block goes
    for _ in range(3):
        page, klass = cache.evict_one(alloc.refcount)
        assert klass == "cold_family"
        assert page in cold_pages
        alloc.reclaim(page)
    page, _ = cache.evict_one(alloc.refcount)
    assert page in hot_pages


def test_eviction_is_leaf_first_within_a_family():
    alloc = PageAllocator(32)
    cache = PrefixCache(4)
    pages = _insert_chain(alloc, cache, list(range(1, 13)))  # one chain
    # the chain must be cut from the tip: block 2, then 1, then the root —
    # never a block whose child is still resident
    for expect in reversed(pages):
        page, klass = cache.evict_one(alloc.refcount)
        assert (page, klass) == (expect, "cold_family")
        alloc.reclaim(page)
    assert cache.evict_one(alloc.refcount) is None


def test_hot_root_forced_when_leaves_are_pinned():
    alloc = PageAllocator(32)
    cache = PrefixCache(4)
    pages = _insert_chain(alloc, cache, list(range(1, 13)))
    # a live sequence pins the leaf (refcount > 0): leaf-first finds no
    # candidate, so the chain is cut at an interior block and the
    # eviction is classified as forced
    alloc.retain([pages[-1]])
    page, klass = cache.evict_one(alloc.refcount)
    assert klass == "hot_root_forced"
    assert page in pages[:-1]
    alloc.reclaim(page)
    st = cache.stats()
    assert st["evictions_hot_root_forced"] == 1
    alloc.free([pages[-1]])


def test_never_hit_family_is_coldest():
    alloc = PageAllocator(32)
    cache = PrefixCache(4)
    a = _insert_chain(alloc, cache, list(range(1, 9)))
    cache.match(list(range(1, 9)) + [99])  # family A has one hit
    b = _insert_chain(alloc, cache, list(range(60, 68)))  # never hit
    # B was inserted LAST (warmer in pure LRU terms) but has never been
    # hit — family heat must rank it colder than A
    page, _ = cache.evict_one(alloc.refcount)
    assert page in b
    alloc.reclaim(page)
    del a


def test_junk_tails_drain_before_any_family_spine():
    alloc = PageAllocator(32)
    cache = PrefixCache(4)
    a_base = list(range(1, 9))
    b_base = list(range(51, 59))
    a1 = _insert_chain(alloc, cache, a_base + [11, 12, 13, 14])
    a2 = _insert_chain(alloc, cache, a_base + [21, 22, 23, 24])
    b1 = _insert_chain(alloc, cache, b_base + [61, 62, 63, 64])
    b2 = _insert_chain(alloc, cache, b_base + [71, 72, 73, 74])
    cache.match(a_base + [99])  # family A is hot, B never hit
    junk = {a1[2], a2[2], b1[2], b2[2]}
    # all four never-reused tails drain first — B's (coldest) before
    # A's — and neither family's shared spine goes while junk remains
    got = []
    for _ in range(4):
        page, klass = cache.evict_one(alloc.refcount)
        assert klass == "cold_family"
        got.append(page)
        alloc.reclaim(page)
    assert set(got) == junk
    assert set(got[:2]) == {b1[2], b2[2]}
    # only now is a spine block cut, from the coldest family (B)
    page, _ = cache.evict_one(alloc.refcount)
    assert page == b1[1]
    alloc.reclaim(page)


# ------------------------------------------------------- COW boundary


def test_match_cow_finds_partial_block():
    alloc = PageAllocator(32)
    cache = PrefixCache(4)
    toks = list(range(1, 13))
    pages = _insert_chain(alloc, cache, toks)
    # diverge INSIDE block 2 after sharing its first 2 tokens
    pages_m, src, m = cache.match_cow(toks[:8] + [9, 10, 77, 78, 79])
    assert pages_m == pages[:2]
    assert src == pages[2]
    assert m == 2
    assert cache.stats()["cow_hits"] == 1


def test_match_cow_leaves_one_suffix_token():
    alloc = PageAllocator(32)
    cache = PrefixCache(4)
    toks = list(range(1, 13))
    _insert_chain(alloc, cache, toks)
    # prompt identical to a cached chain: the boundary share is capped so
    # at least one token remains to prefill (it seeds decode's logits)
    pages_m, src, m = cache.match_cow(toks)
    assert len(pages_m) == 2
    assert src is not None and m == 3  # 3 of block 2's 4 tokens


def test_peek_does_not_refresh_lru():
    alloc = PageAllocator(32)
    cache = PrefixCache(4)
    a = _insert_chain(alloc, cache, list(range(1, 9)))
    b = _insert_chain(alloc, cache, list(range(60, 68)))
    before = cache.digests(limit=64)
    got = cache.peek_match_tokens(list(range(1, 9)) + [99])
    assert got == 8  # 1 full block + 3 boundary tokens... see below
    assert cache.digests(limit=64) == before  # no reordering
    del a, b


def test_peek_match_tokens_counts_partial():
    alloc = PageAllocator(32)
    cache = PrefixCache(4)
    _insert_chain(alloc, cache, list(range(1, 13)))
    # 2 full blocks + 2 boundary tokens, no LRU/heat side effects
    n = cache.peek_match_tokens([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 77, 78])
    assert n == 10
    assert cache.stats()["cow_hits"] == 0


# ------------------------------------------------- digest advertisement


def test_digest_cap_from_flag(monkeypatch):
    monkeypatch.setenv("RTPU_PREFIX_DIGESTS", "2")
    alloc = PageAllocator(64)
    cache = PrefixCache(4)
    _insert_chain(alloc, cache, list(range(1, 25)))  # 6 blocks
    assert cache.digest_limit == 2
    assert len(cache.digests()) == 2
    assert len(cache.digests(limit=64)) == 6  # explicit override wins
    assert cache.stats()["digest_limit"] == 2


# --------------------------------------------- allocator invariant storm


def test_allocator_invariant_storm():
    """Randomized admit/finish/preempt/hit/evict storm with the debug
    partition invariant asserted inside EVERY allocator op: every page is
    exactly one of {free, refcounted, cached-resident} at all times, and
    a full drain returns the pool to pristine — the refcount-leak class
    ordinary tests can't see."""
    rng = random.Random(7)
    alloc = PageAllocator(32)
    cache = PrefixCache(4)
    live = []  # page lists held by simulated running sequences
    for _ in range(3000):
        op = rng.randrange(5)
        if op == 0 and alloc.num_free() >= 3:  # admit fresh
            live.append(alloc.allocate(rng.randrange(1, 4)))
        elif op == 1 and live:  # finish: register full pages, release
            pages = live.pop(rng.randrange(len(live)))
            toks = [rng.randrange(6) for _ in range(len(pages) * 4)]
            alloc.mark_cached(cache.insert(toks, pages))
            alloc.free(pages)
        elif op == 2 and live:  # abort/preempt without caching
            alloc.free(live.pop(rng.randrange(len(live))))
        elif op == 3:  # admission prefix hit: pin matched pages
            toks = [rng.randrange(6) for _ in range(13)]
            matched = cache.match(toks)
            if matched:
                alloc.retain(matched)
                live.append(matched)
        else:  # pool pressure: evict one cached block
            hit = cache.evict_one(alloc.refcount)
            if hit is not None:
                alloc.reclaim(hit[0])
        # the counter is the sum it replaced (`_check` asserts it too)
        assert alloc.num_resident() == sum(
            1 for p in alloc._cached if alloc.refcount(p) <= 0)
    for pages in live:
        alloc.free(pages)
    while True:
        hit = cache.evict_one(alloc.refcount)
        if hit is None:
            break
        alloc.reclaim(hit[0])
    assert alloc.num_free() == 31  # every page home again (0 is null)
    assert alloc.num_resident() == 0


# ------------------------------- batched eviction against the reference


def reference_evict_one(cache, refcount):
    """The walk `PrefixCache.evict` replaced (PR 30), kept as the plain
    reference: EVERY resident block is visited to give back ONE page."""
    for spine_ok in (False, True):
        best = None
        best_heat = None
        for d, blk in cache._blocks.items():  # oldest-first = LRU
            if refcount(blk.page) > 0 or not cache._is_leaf(d):
                continue
            if blk.was_hit and not spine_ok:
                continue
            fam = cache._families.get(blk.root)
            heat = ((fam.last_hit, fam.hits) if fam is not None
                    else (0.0, 0))
            if best_heat is None or heat < best_heat:
                best, best_heat = blk, heat
        if best is not None:
            cache._remove(best)
            cache.evictions_cold_family += 1
            return best.page, "cold_family"
    for d, blk in list(cache._blocks.items()):
        if refcount(blk.page) <= 0:
            cache._remove(blk)
            cache.evictions_hot_root_forced += 1
            return blk.page, "hot_root_forced"
    return None


def _fresh(rng, n):
    return [rng.randrange(1000, 1_000_000) for _ in range(n)]


def _unshared_chains(rng):
    """What the benchmark's serving cells leave: never-hit chains, each a
    family of its own, the pool full of them."""
    alloc, cache = PageAllocator(512), PrefixCache(4)
    for _ in range(rng.randrange(8, 20)):
        _insert_chain(alloc, cache, _fresh(rng, 4 * rng.randrange(1, 12)))
    return alloc, cache


def _shared_spines(rng, pin=False):
    """Families with a reused spine and unique tails; some families hit
    again later (heat), so the spine pass has an order to get right."""
    alloc, cache = PageAllocator(512), PrefixCache(4)
    spines = [_fresh(rng, 4 * rng.randrange(1, 5))
              for _ in range(rng.randrange(3, 7))]
    tails = []
    for _ in range(rng.randrange(10, 24)):
        toks = rng.choice(spines) + _fresh(rng, 4 * rng.randrange(1, 5))
        tails.append(_insert_chain(alloc, cache, toks))
    for spine in rng.sample(spines, len(spines) // 2):
        cache.match(spine + [7])
    if pin:
        # live sequences: some hold every block they registered (the
        # whole chain, for a family's first request), some only the tip
        for pages in rng.sample(tails, len(tails) // 2):
            held = ([p for p in pages if alloc.is_cached(p)]
                    if rng.random() < 0.5 else pages[-1:])
            alloc.retain(held)
    return alloc, cache


def _family_empties(rng):
    """One- and two-block families among long ones: a batch takes the
    last block of several families (their heat rows go) on its way."""
    alloc, cache = PageAllocator(512), PrefixCache(4)
    for _ in range(rng.randrange(6, 12)):
        _insert_chain(alloc, cache, _fresh(rng, 4 * rng.randrange(1, 3)))
        _insert_chain(alloc, cache, _fresh(rng, 4 * rng.randrange(6, 10)))
    return alloc, cache


def _only_interior(rng):
    """Every leaf is pinned, so chains are cut at interior blocks, oldest
    first; shorter re-matches put some parents AFTER their children in
    the LRU order, so a forced cut can free a parent as a leaf."""
    alloc, cache = PageAllocator(512), PrefixCache(4)
    for _ in range(rng.randrange(4, 9)):
        toks = _fresh(rng, 4 * rng.randrange(3, 8))
        pages = _insert_chain(alloc, cache, toks)
        alloc.retain(pages[-1:])
        if rng.random() < 0.6:
            cache.match(toks[:4 * rng.randrange(1, len(pages))] + [7])
    return alloc, cache


def _never_hit_parent(rng):
    """A reused block under a parent that was cut and came back never
    hit: the parent jumps the spine queue the moment its child goes."""
    alloc, cache = PageAllocator(512), PrefixCache(4)
    for _ in range(rng.randrange(3, 7)):
        toks = _fresh(rng, 12)
        _, b, _ = _insert_chain(alloc, cache, toks)
        cache.match(toks + [7])
        # cut at b, as a pool whose every other block is pinned would
        assert cache.evict(lambda p: int(p != b), 1) == [
            (b, "hot_root_forced")]
        alloc.reclaim(b)
        _insert_chain(alloc, cache, toks)  # b's block is back, never hit
        _insert_chain(alloc, cache, _fresh(rng, 8))
    return alloc, cache


def _storm(rng):
    """Whatever a random admit/finish/hit/evict history leaves behind."""
    alloc, cache = PageAllocator(96), PrefixCache(4)
    live = []
    for _ in range(rng.randrange(300, 900)):
        op = rng.randrange(5)
        if op == 0 and alloc.num_free() >= 4:
            live.append(alloc.allocate(rng.randrange(1, 5)))
        elif op == 1 and live:
            pages = live.pop(rng.randrange(len(live)))
            toks = [rng.randrange(3) for _ in range(len(pages) * 4)]
            alloc.mark_cached(cache.insert(toks, pages))
            alloc.free(pages)
        elif op in (2, 3):
            matched = cache.match([rng.randrange(3) for _ in range(17)])
            if matched:
                alloc.retain(matched)
                live.append(matched)
        elif alloc.num_free() < 8:
            for page, _ in cache.evict(alloc.refcount, rng.randrange(1, 6)):
                alloc.reclaim(page)
    rng.shuffle(live)
    for pages in live[len(live) // 3:]:  # most sequences end, some stay
        alloc.free(pages)
    return alloc, cache


def _cache_state(cache):
    """The index and the counters; not what `evict` examined, which the
    reference walk neither counts nor is held to."""
    stats = cache.stats()
    del stats["eviction_blocks_examined"]
    return (list(cache._blocks), dict(cache._by_page),
            {k: set(v) for k, v in cache._children.items()},
            {r: (f.hits, f.blocks, f.last_hit)
             for r, f in cache._families.items()},
            stats)


SHAPES = {"unshared_chains": _unshared_chains,
          "shared_spines": _shared_spines,
          "pinned_leaves": lambda rng: _shared_spines(rng, pin=True),
          "family_empties_mid_batch": _family_empties,
          "only_interior_evictable": _only_interior,
          "never_hit_parent": _never_hit_parent,
          "storm": _storm}


# Histories: MANY `evict` calls on ONE cache, which keeps its order between
# them, with everything that changes a key or a leaf in between.


class _Twins:
    """A cache driven through `evict`, and its twin driven through the
    reference walk, one operation at a time; after every operation both
    must have answered alike and hold the same index, counters, refcounts
    and free list.  The clock that family heat reads stands still inside
    an operation and moves between two, so both sides read the same."""

    def __init__(self, monkeypatch, num_pages, page_size=4):
        from ray_tpu.llm import paged_cache

        self.now = 100.0
        monkeypatch.setattr(
            paged_cache, "time",
            type("Clock", (), {"monotonic": lambda _: self.now})())
        self.sides = [(PageAllocator(num_pages), PrefixCache(page_size))
                      for _ in "gw"]
        self.evicted = 0

    @property
    def alloc(self):
        return self.sides[0][0]

    @property
    def cache(self):
        return self.sides[0][1]

    def both(self, op):
        self.now += 1.0
        got, want = (op(alloc, cache) for alloc, cache in self.sides)
        assert got == want
        self._same()
        return got

    def evict(self, n):
        self.now += 1.0
        (alloc, cache), (ref_alloc, ref_cache) = self.sides
        got = cache.evict(alloc.refcount, n)
        want = []
        for _ in range(n):
            hit = reference_evict_one(ref_cache, ref_alloc.refcount)
            if hit is None:
                break
            want.append(hit)
        assert got == want
        for page, _ in got:
            alloc.reclaim(page)
            ref_alloc.reclaim(page)
        self._same()
        self.evicted += len(got)
        return got

    def _same(self):
        (alloc, cache), (ref_alloc, ref_cache) = self.sides
        assert _cache_state(cache) == _cache_state(ref_cache)
        assert (alloc._free, alloc._rc, alloc._cached) == (
            ref_alloc._free, ref_alloc._rc, ref_alloc._cached)
        # an entry a block, and every evictable leaf has one
        queued = [e[-1] for e in cache._order]
        ids = {id(b) for b in queued}
        assert len(queued) == len(ids)
        assert all(b.queued for b in queued)
        for d, blk in cache._blocks.items():
            if cache._is_leaf(d):
                assert blk.queued and id(blk) in ids

    # what an engine does to the pair of them

    def admit(self, tokens, register=True):
        """match_cow, pin, take the rest; the prompt's full pages are
        registered while the sequence lives (its tip a pinned leaf).
        Returns its pages, or None when the pool cannot hold it."""
        def op(alloc, cache):
            matched, src, _ = cache.match_cow(tokens)
            need = len(tokens) // cache.page_size + 1 - len(matched)
            if alloc.num_free() < need:
                return None
            alloc.retain(matched)
            pages = matched + alloc.allocate(need)
            if register:
                alloc.mark_cached(cache.insert(tokens, pages))
            return pages, src
        got = self.both(op)
        return got and got[0]

    def finish(self, tokens, pages):
        def op(alloc, cache):
            alloc.mark_cached(cache.insert(tokens, pages))
            alloc.free(pages)
        self.both(op)

    def make_room(self, rng, short):
        """Evict as `_reserve` would, a random number of pages over."""
        if short > 0:
            self.evict(short + rng.randrange(0, 4))


def _history_unshared(tw, rng):
    """The serving cells' traffic: unshared prompts, answers that extend
    them (a prompt's tip gains children at the finish), a pool that is
    full from early on, so nearly every admission and growth evicts."""
    live = []
    for _ in range(rng.randrange(120, 200)):
        if live and rng.random() < 0.45:
            toks, pages = live.pop(rng.randrange(len(live)))
            grown = toks + _fresh(rng, 4 * rng.randrange(0, 4))
            extra = len(grown) // 4 + 1 - len(pages)
            tw.make_room(rng, extra - tw.alloc.num_free())
            if tw.alloc.num_free() >= extra:
                pages = pages + tw.both(lambda a, c: a.allocate(extra))
                tw.finish(grown, pages)
            else:
                tw.both(lambda a, c: a.free(pages))  # preempted
        else:
            toks = _fresh(rng, rng.randrange(5, 40))
            tw.make_room(rng, len(toks) // 4 + 1 - tw.alloc.num_free())
            pages = tw.admit(toks, register=rng.random() < 0.8)
            if pages is not None:
                live.append((toks, pages))


def _history_shared(tw, rng):
    """Families with a spine: hits re-stamp spines and heat families long
    after their leaves were keyed; sessions extend their own tips; a COW
    source is refreshed; peeks (which must change nothing) in between."""
    spines = [_fresh(rng, 4 * rng.randrange(1, 5)) for _ in range(5)]
    live, done = [], []
    for _ in range(rng.randrange(150, 250)):
        op = rng.randrange(8)
        if op <= 2:
            base = rng.choice(done)[:rng.randrange(4, 40)] \
                if done and rng.random() < 0.3 else rng.choice(spines)
            toks = base + _fresh(rng, rng.randrange(1, 18))
            tw.make_room(rng, len(toks) // 4 + 1 - tw.alloc.num_free())
            pages = tw.admit(toks, register=rng.random() < 0.7)
            if pages is not None:
                live.append((toks, pages))
        elif op <= 4 and live:
            toks, pages = live.pop(rng.randrange(len(live)))
            tw.finish(toks, pages)
            done.append(toks)
        elif op == 5:
            toks = rng.choice(spines) + [7]
            before = _cache_state(tw.cache), [
                e[:4] for e in tw.cache._order]
            tw.both(lambda a, c: c.peek_match_tokens(toks))
            assert before == (_cache_state(tw.cache),
                              [e[:4] for e in tw.cache._order])
        elif op == 6:
            toks = rng.choice(spines) + [7]
            tw.both(lambda a, c: c.match(toks))
        else:
            tw.evict(rng.randrange(1, 7))


def _history_storm(tw, rng):
    """A small alphabet on a small pool: chains share, diverge inside
    blocks, are pinned through their middles (forced cuts) and come back
    under digests that were cut."""
    live = []
    for _ in range(rng.randrange(400, 700)):
        op = rng.randrange(6)
        if op == 0 and tw.alloc.num_free() >= 4:
            n = rng.randrange(1, 5)
            live.append(tw.both(lambda a, c: a.allocate(n)))
        elif op == 1 and live:
            pages = live.pop(rng.randrange(len(live)))
            toks = [rng.randrange(3) for _ in range(len(pages) * 4)]
            tw.finish(toks, pages)
        elif op in (2, 3):
            toks = [rng.randrange(3) for _ in range(17)]
            how = rng.choice(["match", "match_cow", "peek_match_tokens"])
            got = tw.both(lambda a, c: getattr(c, how)(toks))
            matched = got if how == "match" else got[0] \
                if how == "match_cow" else []
            if matched:
                tw.both(lambda a, c: a.retain(matched))
                live.append(matched)
        elif op == 4 and live:
            pages = live.pop(rng.randrange(len(live)))
            tw.both(lambda a, c: a.free(pages))
        elif tw.alloc.num_free() < 12:
            tw.evict(rng.randrange(1, 6))


def _history_leaf_gains_a_child_and_is_bared(tw, rng):
    """A tip is extended (its entry goes stale), hit while it is interior
    (its key grows unseen), and bared again by the eviction of what
    extended it: it must come out where its key of the moment puts it."""
    chains = []
    for _ in range(rng.randrange(4, 8)):
        toks = _fresh(rng, 4 * rng.randrange(1, 4))
        tw.finish(toks, tw.admit(toks, register=False))
        chains.append(toks)
    for toks in rng.sample(chains, len(chains) - 1):
        longer = toks + _fresh(rng, 4 * rng.randrange(1, 4))
        tw.finish(longer, tw.admit(longer, register=False))
        if rng.random() < 0.5:  # the old tip is hit while interior
            tw.both(lambda a, c: c.match(toks + [7]))
    assert tw.cache._stale > 0
    while tw.evict(rng.randrange(1, 4)):  # tails first, then the bared
        if rng.random() < 0.3:
            toks = rng.choice(chains) + [7]
            tw.both(lambda a, c: c.match(toks))
    assert len(tw.cache) == 0 and not tw.cache._order


def _history_tip_unpinned_between_calls(tw, rng):
    """Live sequences hold their tips while older and younger chains are
    evicted round them (set aside, call after call); then they end, one
    by one, and their tips come out in their place in the order."""
    held = []
    for i in range(rng.randrange(12, 20)):
        toks = _fresh(rng, 4 * rng.randrange(1, 5) + 1)
        pages = tw.admit(toks)
        if i % 3:
            tw.finish(toks, pages)
        else:
            held.append(pages)  # registered, its tip a pinned leaf
    pinned = len(held)
    tw.evict(3)
    assert len(tw.cache._order) >= pinned  # set aside, and back
    while held:
        pages = held.pop(rng.randrange(len(held)))
        tw.both(lambda a, c: a.free(pages))
        tw.evict(rng.randrange(1, 5))
    while tw.evict(4):
        pass
    assert len(tw.cache) == 0 and tw.alloc.num_resident() == 0


HISTORIES = {
    "interleaved_unshared": (_history_unshared, 64),
    "interleaved_shared_spines": (_history_shared, 96),
    "interleaved_storm": (_history_storm, 64),
    "leaf_gains_a_child_and_is_bared":
        (_history_leaf_gains_a_child_and_is_bared, 256),
    "tip_unpinned_between_calls":
        (_history_tip_unpinned_between_calls, 256)}


@pytest.mark.parametrize("seed", [11, 2147485001, 30303])
@pytest.mark.parametrize("shape", [*SHAPES, "n_beyond_evictable",
                                   *HISTORIES])
def test_evict_n_is_n_reference_calls(shape, seed, monkeypatch):
    """`evict(refcount, n)` gives the pages, in the order, with the
    classes and the counters of n calls of the one-block walk, and leaves
    the index as they leave it — on every shape, also when n asks for more
    than can go, and call after call on ONE cache through every history
    (there the twin is compared after every operation)."""
    rng = random.Random(f"{shape}/{seed}")
    if shape in HISTORIES:
        history, num_pages = HISTORIES[shape]
        tw = _Twins(monkeypatch, num_pages)
        history(tw, rng)
        assert tw.evicted >= 10
        return
    beyond = shape == "n_beyond_evictable"
    build = rng.choice(list(SHAPES.values())) if beyond else SHAPES[shape]
    alloc, cache = build(rng)
    evictable = sum(1 for b in cache._blocks.values()
                    if alloc.refcount(b.page) <= 0)
    assert evictable >= 2
    for n in ([evictable + 5] if beyond
              else [1, rng.randrange(2, evictable), evictable]):
        ref_alloc, ref_cache = copy.deepcopy((alloc, cache))
        want = []
        for _ in range(n):
            hit = reference_evict_one(ref_cache, ref_alloc.refcount)
            if hit is None:
                break
            want.append(hit)
        got_alloc, got_cache = copy.deepcopy((alloc, cache))
        got = got_cache.evict(got_alloc.refcount, n)
        assert got == want
        assert len(got) == min(n, evictable)
        assert _cache_state(got_cache) == _cache_state(ref_cache)
    assert cache.evict(alloc.refcount, 0) == []


# ------------------------------- the engine's one pass (counts, not times)


def _engine(num_pages, max_slots=32):
    import jax

    from ray_tpu.llm.engine import EngineConfig, LLMEngine
    from ray_tpu.models import llama

    model = llama.LlamaConfig(vocab_size=64, d_model=16, n_layers=1,
                              n_heads=2, n_kv_heads=1, d_ff=32,
                              max_seq_len=512, dtype="float32", remat=False)
    # nothing here prefills or decodes; the engine lays its weights out for
    # serving as it is built, so it is handed some
    return LLMEngine(
        llama.init(model, jax.random.PRNGKey(0)), model,
        EngineConfig(max_slots=max_slots, num_pages=num_pages, page_size=4,
                     max_seq_len=512, prefill_buckets=(512,)))


def _fill_pool(engine, rng):
    """Finished requests' chains until the pool holds nothing else."""
    alloc, cache = engine.allocator, engine.prefix_cache
    while alloc.num_free():
        n = min(rng.randrange(3, 12), alloc.num_free())
        _insert_chain(alloc, cache, _fresh(rng, 4 * n))


def _decoding_slots(engine, rng, n_slots):
    """Slots one token short of a page boundary, as a burst finds them:
    every one needs a page (some two) for its next 8 writes."""
    from ray_tpu.llm.engine import SamplingParams, _Request, _Slot

    for i in range(n_slots):
        n_pages = rng.randrange(2, 6)
        toks = _fresh(rng, 4 * n_pages - rng.randrange(0, 3))
        req = _Request(request_id=f"r{i}", prompt_tokens=toks[:5],
                       params=SamplingParams(max_tokens=256),
                       submitted_at=float(i))
        engine._slots[i] = _Slot(
            request=req, pages=engine.allocator.allocate(n_pages),
            num_tokens=len(toks), last_token=1, generated=toks[5:])


def test_reserve_is_one_scan_for_all_its_pages():
    engine = _engine(num_pages=400)
    _fill_pool(engine, random.Random(5))
    assert engine.allocator.num_free() == 0
    assert engine._reserve(37)
    st = engine.stats()
    assert st["page_evictions"] == 37
    assert st["eviction_scans"] <= 2
    assert st["free_pages"] == 37
    assert st["prefix_cache"]["evictions_cold_family"] == 37


def test_reserve_keeps_partial_reclaims_when_the_pool_cannot_cover():
    engine = _engine(num_pages=64)
    engine.allocator.allocate(40)  # live sequences
    _fill_pool(engine, random.Random(6))
    assert not engine._reserve(37)
    st = engine.stats()
    assert st["page_evictions"] == st["free_pages"] == 63 - 40
    assert st["eviction_scans"] == 1
    assert len(engine.prefix_cache) == 0


def test_a_burst_over_32_slots_is_one_scan():
    rng = random.Random(8)
    engine = _engine(num_pages=600)
    _decoding_slots(engine, rng, 32)
    _fill_pool(engine, rng)
    before = [len(s.pages) for s in engine._slots]
    engine._ensure_capacity(8)
    grown = [len(s.pages) - b for s, b in zip(engine._slots, before)]
    assert all(1 <= g <= 2 for g in grown)
    st = engine.stats()
    assert st["page_evictions"] == sum(grown) >= 32
    assert st["eviction_scans"] <= 2
    assert st["preempted"] == 0


def _serve_unshared(engine, rng, live_slots):
    """A pool filled as the serving cells fill theirs, pages of 4 for
    pages of 16: `live_slots` sequences decode (their prompts' full pages
    registered at admission, so every tip is a pinned leaf, and OLDER than
    most of what lies round it); every other page is what finished
    requests left, each a family of its own whose answer extended its
    prompt's tip at the finish."""
    from ray_tpu.llm.engine import SamplingParams, _Request, _Slot

    alloc = engine.allocator
    for i in range(live_slots):
        prompt = _fresh(rng, rng.randrange(32, 192))
        pages = alloc.allocate(len(prompt) // 4 + 1)
        engine._register_blocks(prompt, pages)
        answer = _fresh(rng, 4 * rng.randrange(1, 24) - len(prompt) % 4 - 1)
        pages += alloc.allocate((len(prompt) + len(answer)) // 4 + 1
                                - len(pages))
        req = _Request(request_id=f"r{i}", prompt_tokens=prompt,
                       params=SamplingParams(max_tokens=512),
                       submitted_at=float(i))
        engine._slots[i] = _Slot(
            request=req, pages=pages, num_tokens=len(prompt) + len(answer),
            last_token=1, generated=answer)
    while alloc.num_free() >= 80:
        prompt = _fresh(rng, rng.randrange(32, 192))
        seq = prompt + _fresh(rng, rng.randrange(64, 128))
        pages = alloc.allocate(len(seq) // 4 + 1)
        engine._register_blocks(prompt, pages)  # admitted
        engine._register_blocks(seq, pages)  # finished
        alloc.free(pages)
    alloc.allocate(alloc.num_free())  # the rest is held by somebody


@pytest.mark.parametrize("num_pages, live_slots", [(3072, 32), (12288, 28)])
def test_a_phase_examines_what_it_takes_not_what_is_resident(
        num_pages, live_slots, monkeypatch):
    """Counts, not times: with the pool full, an admission's `_reserve(14)`
    and a burst's `_ensure_capacity(8)` pop the pages they take, the live
    slots' pinned tips and a few stale entries, whatever the pool holds
    (a walk of the index examines every resident block a call: ~1,700 and
    ~9,900 on these two pools)."""
    monkeypatch.setenv("RTPU_DEBUG_ALLOCATOR", "0")  # O(pool) an operation
    engine = _engine(num_pages, max_slots=64)
    _serve_unshared(engine, random.Random(num_pages), live_slots)
    cache = engine.prefix_cache
    assert engine.allocator.num_free() == 0
    resident = engine.allocator.num_resident()
    assert resident > num_pages // 2
    assert resident == sum(1 for p in engine.allocator._cached
                           if engine.allocator.refcount(p) <= 0)
    assert len(cache._order) < len(cache) // 10  # leaves, not blocks

    assert engine._reserve(14)
    st = engine.stats()
    assert st["page_evictions"] == 14 and st["eviction_scans"] == 1
    assert 14 <= st["eviction_blocks_examined"] < 14 + live_slots + 8
    assert st["prefix_cache"]["eviction_blocks_examined"] \
        == st["eviction_blocks_examined"]
    engine.allocator.allocate(14)  # the admitted prompt's

    before = [len(s.pages) for s in engine._slots if s is not None]
    engine._ensure_capacity(8)
    grown = sum(len(s.pages) for s in engine._slots if s is not None) \
        - sum(before)
    st = engine.stats()
    assert grown >= live_slots // 2 and st["preempted"] == 0
    assert st["page_evictions"] == 14 + grown and st["eviction_scans"] == 2
    assert st["eviction_blocks_examined"] < (14 + live_slots + 8) + (
        grown + live_slots + 8)
    assert engine.allocator.num_resident() == resident - 14 - grown


def test_the_order_holds_an_entry_a_leaf_however_chains_are_extended(
        monkeypatch):
    """Sessions that extend their own tip turn after turn, and hits on
    their spines between: a tip that gains a child leaves a stale entry,
    a hit leaves none, and the stale ones are swept before they outnumber
    the leaves by more than the slack."""
    monkeypatch.setenv("RTPU_DEBUG_ALLOCATOR", "0")  # O(pool) an operation
    rng = random.Random(3)
    alloc, cache = PageAllocator(8192), PrefixCache(4)
    sessions = [_fresh(rng, 8) for _ in range(40)]
    for turn in range(30):
        for i, toks in enumerate(sessions):
            sessions[i] = toks = toks + _fresh(rng, 8)
            matched = cache.match(toks)
            alloc.retain(matched)
            pages = matched + alloc.allocate(len(toks) // 4 - len(matched))
            alloc.mark_cached(cache.insert(toks, pages))
            alloc.free(pages)
            leaves = sum(1 for d in cache._blocks if cache._is_leaf(d))
            assert leaves == 40 if turn else leaves == i + 1
            assert len(cache._order) <= 2 * leaves + 32
    assert len(cache) == 40 * 62
    # and the order is still the walk's
    ref_alloc, ref_cache = copy.deepcopy((alloc, cache))
    want = [reference_evict_one(ref_cache, ref_alloc.refcount)
            for _ in range(500)]
    assert cache.evict(alloc.refcount, 500) == want
    assert _cache_state(cache) == _cache_state(ref_cache)


def _reference_ensure_capacity(engine, steps):
    """`_ensure_capacity` as it was: every slot reserves for itself, one
    reference walk a page."""
    def reserve(n):
        while engine.allocator.num_free() < n:
            hit = reference_evict_one(engine.prefix_cache,
                                      engine.allocator.refcount)
            if hit is None:
                return False
            engine.allocator.reclaim(hit[0])
        return True

    ps = engine.cfg.page_size
    order = sorted(
        ((i, s) for i, s in enumerate(engine._slots) if s is not None),
        key=lambda t: t[1].request.submitted_at)
    for i, s in order:
        while engine._slots[i] is s:
            sp = s.request.params
            remaining = max(1, sp.max_tokens - s.request.produced)
            k = min(steps, remaining)
            need = min((s.num_tokens + k - 1) // ps + 1,
                       engine.max_pages_per_seq)
            delta = need - len(s.pages)
            if delta <= 0:
                break
            if reserve(delta):
                s.pages.extend(engine.allocator.allocate(delta))
                break
            victim = min(
                ((j, t) for j, t in enumerate(engine._slots)
                 if t is not None),
                key=lambda t: (engine._shared_pages(t[1]),
                               -t[1].request.submitted_at))
            engine._preempt(*victim)


@pytest.mark.parametrize("cached_pages", [0, 5, 11, 200])
def test_a_burst_preempts_as_the_per_slot_path_did(cached_pages):
    """A pool that cannot cover the burst's sum (and one that can): the
    same slots grow by as many pages, the same victims are preempted in
    the same order, and the index and the free list's length end the same.
    (Not the ids: the free list is in page order, so what the evictions
    gave back does not come out in their order, and one reserve for the
    burst takes other ids than a reserve a slot.)"""
    def build():
        rng = random.Random(9)
        engine = _engine(num_pages=300, max_slots=16)
        _decoding_slots(engine, rng, 16)
        # what is not the slots' or cached is held by nobody we preempt
        spare = engine.allocator.num_free() - cached_pages
        if spare > 0:
            engine.allocator.allocate(spare)
        _fill_pool(engine, rng)
        return engine

    got = build()
    got._ensure_capacity(8)
    want = build()
    _reference_ensure_capacity(want, 8)

    def outcome(e):
        held = [p for s in e._slots if s for p in s.pages]
        assert len(set(held)) == len(held)
        assert not set(held) & set(e.allocator._free)
        return ([(s.request.request_id, len(s.pages)) if s else None
                 for s in e._slots],
                [r.request_id for r in e._waiting.queue],
                len(e.allocator._free), _cache_state(e.prefix_cache))

    assert outcome(got) == outcome(want)
    preempted = got.stats()["preempted"]
    assert (preempted > 0) == (cached_pages < 16)
    # one pass for the burst; past it only a reserve that fails, or that
    # takes a preempted slot's pages, scans again: never one a page
    scans = got.stats()["eviction_scans"]
    assert scans == 1 if not preempted else scans <= 1 + 16 + preempted


# -- pools by layer type ------------------------------------------------------

WINDOWED = dict(n_layers=2, n_kv_heads=2, head_dim=8, window_layers=6,
                window=64, page_size=16, num_pages=40, window_pages=24,
                dtype="float32")


def test_pools_by_layer_type_are_two_of_each():
    k, v = init_cache(CacheConfig(**WINDOWED))
    assert set(k) == set(v) == {"full", "window"}
    assert k["full"].shape == v["full"].shape == (2, 40, 16, 2, 8)
    assert k["window"].shape == v["window"].shape == (6, 24, 16, 2, 8)
    plain, _ = init_cache(CacheConfig(n_layers=2, n_kv_heads=2, head_dim=8))
    assert plain.shape == (2, 256, 16, 2, 8)  # one kind: an array, as ever


@pytest.mark.parametrize("context, kept", [
    (1, 1), (16, 1), (63, 4), (64, 4), (65, 5), (79, 4), (80, 4),
    (81, 5), (1000, 5)])
def test_a_window_layer_keeps_the_pages_its_window_reaches(context, kept):
    """``bytes_per_token_at``: a full layer holds every page of a sequence,
    a window layer those from the page of ``context - window + 1`` on, the
    position the NEXT query still sees."""
    cc = CacheConfig(**WINDOWED)
    pages = -(-context // 16)
    row = 2 * 2 * 8 * 4
    assert cc.bytes_per_token_at(context) == pytest.approx(
        (2 * pages + 6 * kept) * 16 * row / context)
    assert kept <= cc.window_pages_per_seq(16) == 6


@pytest.mark.parametrize("wrong", [
    dict(window=0), dict(window_layers=0), dict(window_pages=1),
    dict(n_kv_heads=0, head_dim=0, latent_dim=128)])
def test_window_layers_come_whole_or_not_at_all(wrong):
    with pytest.raises(ValueError):
        CacheConfig(**{**WINDOWED, **wrong})


@pytest.mark.parametrize("scan_chunk", [0, -64])
def test_state_layers_come_with_their_scan_chunk(scan_chunk):
    """The engine counts ``scan_chunks`` by it: a family that declares
    state layers and forgets it is told at construction, not by a division
    in the middle of its first prefill."""
    rows = {"S": (2, (4, 8, 8), "float32")}
    with pytest.raises(ValueError, match="scan_chunk"):
        CacheConfig(n_layers=1, n_kv_heads=2, head_dim=8, state_layers=2,
                    state_rows=rows, scan_chunk=scan_chunk)
    assert CacheConfig(n_layers=1, n_kv_heads=2, head_dim=8, state_layers=2,
                       state_rows=rows, scan_chunk=128).scan_chunk == 128


def test_a_window_pool_is_allocated_like_any_other():
    """The window layers' allocator is a ``PageAllocator``: pages given
    back while a sequence lives rejoin the free list in page order BEHIND
    where the allocator stands, so another sequence takes untouched pages
    first and a given-back page's rows stay readable until the list comes
    round."""
    alloc = PageAllocator(8)
    held = alloc.allocate(4)
    alloc.free(held[:2])  # behind the window
    assert alloc.allocate(3) == [5, 6, 7] and alloc.num_free() == 2
    assert alloc.allocate(2) == held[:2]


# ------------------------------------------- the free list, in page order

class _FifoAllocator(PageAllocator):
    """The rule the free list had before it was kept in page order, kept
    here to hold the new one against: ``allocate`` takes the list's head,
    ``free`` and ``reclaim`` append."""

    def allocate(self, n):
        if n > len(self._free):
            raise MemoryError(f"needs {n} pages, {len(self._free)} free")
        out, self._free = self._free[:n], self._free[n:]
        for p in out:
            self._rc[p] = 1
        return out

    def free(self, pages):
        for p in pages:
            if p and self._rc.pop(p, None) is not None:
                self._free.append(p)

    def _check(self):  # (its list is in no order)
        pass


def _in_runs_of_four(pages):
    groups = [pages[i:i + 4] for i in range(0, len(pages) - 3, 4)]
    return sum(all(b - a == 1 for a, b in zip(g, g[1:]))
               for g in groups) / len(groups)


def _serve_rounds(alloc, rng, rounds, live=9):
    """Requests as a long-document cell's: a prompt in chunks of 64 pages,
    then pages one at a time, taken in turn by the live sequences, and
    everything a sequence holds given back when it ends (in a shuffled
    order).  Yields, a round (one finished sequence), the share of the
    fresh 64-page chunks since the round before that lay in runs of four
    (the last reading again where a round took no chunk)."""
    slots = []
    shares = [1.0]
    done = 0
    while done < rounds:
        while len(slots) < live:
            slots.append({"pages": [], "prompt": 64 * rng.randint(2, 4),
                          "decode": rng.randint(20, 50)})
        for s in list(slots):
            if len(s["pages"]) < s["prompt"]:
                chunk = alloc.allocate(64)
                shares.append(_in_runs_of_four(chunk))
                s["pages"] += chunk
            elif s["decode"]:
                s["pages"] += alloc.allocate(1)
                s["decode"] -= 1
            else:
                rng.shuffle(s["pages"])
                alloc.free(s["pages"])
                slots.remove(s)
                done += 1
                yield sum(shares) / len(shares)
                shares = shares[-1:]


@pytest.mark.parametrize("num_pages", [3072, 8448])
def test_chunks_go_on_coming_out_in_runs_however_long_the_pool_is_used(
        num_pages, monkeypatch):
    """After a hundred rounds of chunk allocations, single allocations and
    frees in any order the free list is in page order (``_check`` holds it
    after every call) and a fresh 64-page chunk lies in runs of four as it
    did in the first round; on the first-in-first-out list the share falls
    to nearly nothing, which is why the kernels' merged copies
    (ops/paged_attention.py) come with this order."""
    shares = list(_serve_rounds(PageAllocator(num_pages), random.Random(5),
                                100))
    first, last = sum(shares[:10]) / 10, sum(shares[-10:]) / 10
    assert first > 0.9 and last > 0.9 and last > first - 0.05
    monkeypatch.setenv("RTPU_DEBUG_ALLOCATOR", "0")
    fifo = list(_serve_rounds(_FifoAllocator(num_pages), random.Random(5),
                              100))
    assert sum(fifo[:3]) / 3 > 0.8 and sum(fifo[-10:]) / 10 < 0.2


@pytest.mark.parametrize("seed", [1, 2147485001])
def test_the_free_list_stays_in_page_order_and_goes_round(seed):
    """Interleaved allocations of every size, frees and reclaims in any
    order: the list is sorted after each (``_check``), what an allocation
    hands out are the free ids that FOLLOW the last one handed out, in page
    order and round the end (so a run wherever free neighbours exist), and
    a page given back is not handed out again before the allocator has
    gone round the list."""
    rng = random.Random(seed)
    alloc = PageAllocator(400)
    held = []
    for _ in range(600):
        if held and (rng.random() < 0.45 or alloc.num_free() < 70):
            rng.shuffle(held)
            gone = [held.pop() for _ in range(min(len(held),
                                                  rng.choice((1, 1, 5, 80))))]
            if rng.random() < 0.3:
                for p in gone:
                    alloc.free([p])
            else:
                alloc.free(gone)
            continue
        n = rng.choice((1, 1, 1, 4, 64))
        free_before, after = sorted(alloc._free), alloc._next
        out = alloc.allocate(n)
        ahead = [p for p in free_before if p >= after]
        behind = [p for p in free_before if p < after]
        assert out == (ahead + behind)[:n]
        held += out
    assert alloc._free == sorted(alloc._free)
    # a page given back comes out again only after every page that was free
    # ahead of the allocator
    alloc = PageAllocator(16)
    first = alloc.allocate(6)
    alloc.free(first[:3])
    assert alloc.allocate(9) == list(range(7, 16))
    assert alloc.allocate(3) == first[:3]
