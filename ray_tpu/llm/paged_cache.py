"""Paged KV cache: fixed-shape page pool + host-side page allocator.

TPU-native replacement for the paged attention the reference delegates to
vLLM (/root/reference/python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_engine.py:181 — engine kwargs `block_size`, `gpu_memory_utilization`):
KV lives in a static [n_layers, num_pages, page_size, n_kv, head_dim] pool
so every decode step has one compiled shape regardless of sequence lengths;
sequences map to pages through an integer page table.  The allocator is a
trivial host-side free list — allocation happens at admission time, never
inside the jitted step.

Prefix caching (ISSUE 10) layers two host-side structures on top:

- the allocator grows refcounts and a "cached-resident" set, so a page whose
  sequence finished can stay resident (its KV intact) until the pool needs
  it back, and a page shared by several sequences is only truly freed when
  the last one releases it;
- `PrefixCache` is a vLLM-style block index: a chain hash over FULL prompt
  pages maps token-block digests to resident pages, so a new request whose
  prompt shares a page-aligned prefix with earlier traffic skips
  recomputing (and re-storing) that prefix's KV.

Converting locality into throughput (ISSUE 14) adds:

- per-family heat: every block belongs to the family of its chain's root
  digest; families track hit count, resident-block count, and last-hit
  time, and `evict` reclaims leaf-first inside the COLDEST family
  instead of walking a global LRU — a burst of unique traffic can no
  longer shred a hot shared root that queued requests are about to hit.
  `evict(refcount, n)` takes all the pages a phase of the engine loop
  needs from an ORDER the cache keeps between calls (a heap over the
  leaves, entered when a block becomes one, re-keyed when popped), so a
  call costs what it takes and what it sets aside, not a pass over the
  resident blocks;
- partial-block (copy-on-write) matching: blocks remember their token
  content, so a prompt that diverges INSIDE a cached block still reuses
  the shared slots — the engine copies that single page and prefills only
  from the divergence point (`match_cow`).

Pools by LAYER TYPE (models/afmoe.py: window and full layers in one stack):
a model whose ``cache_layout()`` names ``window_layers`` and a ``window``
gets TWO pools of K/V pages, each with its own `PageAllocator` and its own
page table a sequence, and a page is ``page_size`` tokens of every layer
OF ITS KIND.

- ``"full"``: ``n_layers`` layers x ``num_pages``.  A sequence holds a page
  for every ``page_size`` positions it has, for as long as it lives, as the
  one pool of every other model.
- ``"window"``: ``window_layers`` layers x ``window_pages``.  A query at
  position i sees keys ``max(0, i - window + 1) .. i``, so a sequence holds
  here only the pages that reach into the last ``window`` positions of
  where it stands, and the chunk or burst about to be written: at most
  ``ceil((window + largest chunk) / page_size) + 1`` at any instant
  (``window_pages_per_seq``).  Which of the two forms the issue offered: A
  TABLE WITH NULL ENTRIES BEHIND THE WINDOW, not a ring.  The window
  layers' page table is indexed by ABSOLUTE page (position // page_size),
  as the full layers' is; the engine gives a page back to the window
  allocator once it lies wholly behind ``next position - window + 1``
  (after every chunk of a prefill and before every decode burst,
  ``engine._trim_window``) and puts the null page 0 in its place.  Why the
  table: write coordinates, the decode kernel's walk and the suffix
  prefill's gather stay functions of the absolute position, the SAME for
  both kinds (a ring would give a window layer its own position
  arithmetic in three programs); what a null entry costs is an int32 of
  host memory.  Nothing reads a null entry: the decode kernel begins its
  walk at the page that holds the window's first position
  (ops/paged_attention.py ``window``), and the suffix prefill gathers only
  the ``window_reach_pages`` pages that the chunk and its window reach.
"""

from __future__ import annotations

import bisect
import hashlib
import heapq
import os
import time
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

_FALSY = ("", "0", "false", "no", "off")


@dataclass
class CacheConfig:
    """What a model caches, as its configuration declares it
    (``cfg.cache_layout()``), at an engine's sizes.  Three kinds.  Pages of K/V:
    ``n_layers`` pools of ``n_kv_heads`` x ``head_dim`` (the layers that
    attend, the heads as the pool holds them), K and V one shape.  LATENT
    pages (``latent_dim`` > 0, then no heads): ``n_layers`` pools of ONE
    row of ``latent_dim`` a token, key and value both, and no V pool
    (models/glm_moe_lite.py ``cache_layout`` says what lies in the row).
    The page table, ``PageAllocator`` and ``PrefixCache`` are the same for
    both: a page is ``page_size`` tokens of every layer.  And
    ``state_rows``, by name the (count, shape, dtype) of the rows that each
    of ``max_slots`` slots holds for the ``state_layers`` recurrent layers
    between them: fixed in size, beside the pages and not in them,
    meaningless once the slot is released (``scan_chunk``: the tokens a
    step of those layers' prefill scan takes, for the engine's count of
    the steps that ran).  A fourth, beside K/V pages
    only: ``window_layers`` of the attending layers see the last ``window``
    positions alone and have a pool of their own, ``window_pages`` pages a
    layer (the module docstring says how a sequence holds them);
    ``n_layers`` then counts the FULL layers.  A fifth, beside K/V pages
    too: ``page_rows``, by name the (layers, shape, dtype) of a row that
    every PAGE holds beside its K and V (models/minicpm_sala.py: the pooled
    keys its sparse layers choose blocks by).  Such a row is found through
    the page table as the page is, lives and dies with the page, and is
    allocated with the state rows (``init_state``: [layers, num_pages,
    *shape]).  Every page row has a TWIN IN SLOT ORDER beside it, under
    ``<name>_by_slot``: [layers, max_slots, max_pages_per_seq, *shape], row
    j of slot s the row of the page at entry j of s's page table.  A
    program that wants a slot's rows EVERY step (the decode step's choice
    of blocks) reads them there where they lie, where through the table
    they are a gather of a row an index (13.8 ns a row on the chip, 0.71 ms
    a layer a step at 32 x 1,600: PERF.md section 6, PRs 50 and 52).
    Whoever writes a page row writes its twin, the same value at the same
    place in the program; a twin's row means something as far as its
    slot's context has completed rows and nothing past it or once the slot
    is released (the rule of the state rows: the prefill that admits a
    sequence to a slot writes from row 0)."""

    n_layers: int
    n_kv_heads: int = 0
    head_dim: int = 0
    num_pages: int = 256
    page_size: int = 16
    dtype: str = "bfloat16"
    state_layers: int = 0
    state_rows: Optional[dict] = None
    scan_chunk: int = 0  # tokens a step of a state layer's prefill scan takes
    max_slots: int = 0
    latent_dim: int = 0
    window_layers: int = 0
    window: int = 0
    window_pages: int = 0
    page_rows: Optional[dict] = None
    max_pages_per_seq: int = 0  # entries of a sequence's page table

    def __post_init__(self):
        if bool(self.latent_dim) == bool(self.n_kv_heads * self.head_dim):
            raise ValueError(
                f"a page holds K and V of n_kv_heads x head_dim "
                f"({self.n_kv_heads} x {self.head_dim}) or one latent row "
                f"of latent_dim ({self.latent_dim}), one of the two")
        if bool(self.window_layers) != bool(self.window) or (
                self.window_layers and (self.latent_dim
                                        or self.window_pages < 2)):
            raise ValueError(
                f"window layers ({self.window_layers}) come with a window "
                f"({self.window}), K/V pages and a pool of their own "
                f"(window_pages {self.window_pages}), or not at all")
        if self.state_layers and self.scan_chunk <= 0:
            raise ValueError(
                f"{self.state_layers} state layers come with the tokens a "
                f"step of their prefill scan takes (scan_chunk "
                f"{self.scan_chunk}): cache_layout() declares it beside "
                f"state_rows, and the engine counts scan_chunks by it")

    @property
    def tokens_capacity(self) -> int:
        return self.num_pages * self.page_size

    @property
    def _row_bytes(self) -> int:
        row = self.latent_dim or 2 * self.n_kv_heads * self.head_dim
        return row * jnp.dtype(self.dtype).itemsize

    @property
    def bytes_per_token(self) -> int:
        """Page bytes a cached token takes, all layers.  With window layers
        that is no one number: ``bytes_per_token_at`` a context length."""
        if self.window_layers:
            raise ValueError(
                f"{self.window_layers} window layers keep {self.window} "
                f"tokens of a sequence and {self.n_layers} full layers all "
                f"of it: ask bytes_per_token_at(context_tokens)")
        return self.n_layers * self._row_bytes

    def bytes_per_token_at(self, context_tokens: int) -> float:
        """Page bytes a token of a sequence ``context_tokens`` long takes,
        all layers, whole pages counted: a full layer keeps every page of
        the sequence, a window layer the pages that reach into its last
        ``window`` positions."""
        ps = self.page_size
        pages = -(-context_tokens // ps)
        kept = pages - max(0, context_tokens - self.window + 1) // ps \
            if self.window_layers else 0
        return ((self.n_layers * pages + self.window_layers * kept) * ps
                * self._row_bytes / max(1, context_tokens))

    def window_pages_per_seq(self, largest_chunk: int) -> int:
        """The most pages of the window pool one sequence holds at any
        instant: the window and the chunk (or burst) being written, and
        one more for a window that begins inside a page."""
        return -(-(self.window + largest_chunk) // self.page_size) + 1


def init_cache(cfg: CacheConfig):
    """(cache_k, cache_v) zeros; for latent pages (the one pool, None); for
    pools by layer type each of the two a dict ``{"full": ..., "window":
    ...}`` of one pool a kind."""
    dt = jnp.dtype(cfg.dtype)
    if cfg.latent_dim:
        return jnp.zeros((cfg.n_layers, cfg.num_pages, cfg.page_size,
                          cfg.latent_dim), dt), None
    shape = (cfg.n_layers, cfg.num_pages, cfg.page_size,
             cfg.n_kv_heads, cfg.head_dim)
    if cfg.window_layers:
        shapes = {"full": shape, "window": (
            cfg.window_layers, cfg.window_pages, *shape[2:])}
        return tuple({kind: jnp.zeros(s, dt) for kind, s in shapes.items()}
                     for _ in "kv")
    return jnp.zeros(shape, dt), jnp.zeros(shape, dt)


def init_state(cfg: CacheConfig):
    """The state rows [count, max_slots, *row], the page rows [layers,
    num_pages, *row] and each page row's twin in slot order [layers,
    max_slots, max_pages_per_seq, *row] (``<name>_by_slot``) by name, zeros;
    None for a model that declares neither."""
    if not cfg.state_rows and not cfg.page_rows:
        return None
    page_rows = (cfg.page_rows or {}).items()
    return {**{name: jnp.zeros((count, cfg.max_slots, *shape), dt)
               for name, (count, shape, dt) in (cfg.state_rows or {}).items()},
            **{name: jnp.zeros((layers, cfg.num_pages, *shape), dt)
               for name, (layers, shape, dt) in page_rows},
            **{f"{name}_by_slot": jnp.zeros((layers, cfg.max_slots,
                                         cfg.max_pages_per_seq, *shape), dt)
               for name, (layers, shape, dt) in page_rows}}


class PageAllocator:
    """Host-side free list (reference analogue: vLLM's BlockManager).

    Three page states: FREE (on the free list), IN USE (refcount >= 1),
    and CACHED-RESIDENT (refcount 0 but registered in a PrefixCache —
    KV intact, reclaimable on demand).  allocate/free keep their original
    one-owner semantics when retain/mark_cached are never called, so code
    (and tests) that predate prefix caching see the old behavior.
    """

    def __init__(self, num_pages: int):
        # page 0 is reserved as the "null" page that padded page-table
        # entries point at; attention masks it out by position.
        # THE FREE LIST IS KEPT IN PAGE ORDER and `allocate` goes ROUND it:
        # it takes the lowest free ids past the last page it handed out
        # (`_next`), and begins again at the list's head when they run out.
        # Freed neighbours rejoin, so a prompt's pages go on coming out as
        # ids in a row however long the pool has been in use (the paged
        # kernels move such a row in one copy, ops/paged_attention.py
        # `_run_copies`; on a first-in-first-out list every page a decode
        # burst takes singly splits a row for good, and taking the LOWEST
        # ids would fill the holes single pages leave with the next
        # prompt's).  And, as on the first-in-first-out list, a page given
        # back is not written again before the list has come round.
        # No other code reads anything into the ids a sequence gets.
        self._free: List[int] = list(range(1, num_pages))
        self._next = 0
        self._rc: Dict[int, int] = {}
        self._cached: Set[int] = set()
        # cached pages nobody references, counted where a page changes
        # state (`_check` holds it to the sum over `_cached`)
        self._resident = 0
        self.num_pages = num_pages
        # RTPU_DEBUG_ALLOCATOR: assert the page-state partition invariant
        # after every op (O(num_pages) — test/chaos runs only)
        self._debug = os.environ.get(
            "RTPU_DEBUG_ALLOCATOR", "").strip().lower() not in _FALSY

    def _check(self) -> None:
        """Every page is exactly one of {free-list, refcounted,
        cached-resident}: the free list is duplicate-free and disjoint
        from the other two states, refcount entries are strictly
        positive, and no page is lost (unreachable from all three) —
        the refcount-leak class ordinary tests can't see."""
        if not self._debug:
            return
        fs = set(self._free)
        assert len(fs) == len(self._free), \
            f"duplicate pages on the free list: {sorted(self._free)}"
        assert all(a < b for a, b in zip(self._free, self._free[1:])), \
            f"the free list is out of page order: {self._free}"
        assert 0 not in fs, "null page 0 on the free list"
        for p, rc in self._rc.items():
            assert rc >= 1, f"page {p} holds refcount {rc} (should be gone)"
            assert p not in fs, f"page {p} is both free and refcounted"
        for p in self._cached:
            assert p not in fs, f"page {p} is both free and cached-resident"
        for p in range(1, self.num_pages):
            assert p in fs or self._rc.get(p, 0) > 0 or p in self._cached, \
                f"page {p} leaked: not free, not referenced, not cached"
        resident = sum(1 for p in self._cached if self._rc.get(p, 0) <= 0)
        assert self._resident == resident, \
            f"{self._resident} pages counted resident, {resident} are"

    def num_free(self) -> int:
        return len(self._free)

    def num_resident(self) -> int:
        """Cached pages with no live owner (reclaimable without preempting)."""
        return self._resident

    def can_allocate(self, n: int) -> bool:
        return len(self._free) >= n

    def allocate(self, n: int) -> List[int]:
        if n > len(self._free):
            raise MemoryError(f"needs {n} pages, {len(self._free)} free")
        at = bisect.bisect_left(self._free, self._next)
        out = self._free[at:at + n]
        del self._free[at:at + n]
        if len(out) < n:  # round the end of the list
            rest = n - len(out)
            out += self._free[:rest]
            del self._free[:rest]
        if out:
            self._next = out[-1] + 1
        for p in out:
            self._rc[p] = 1
        self._check()
        return out

    def retain(self, pages: List[int]) -> None:
        """Add a reference to already-resident pages (prefix-cache hit)."""
        for p in pages:
            if p != 0:
                rc = self._rc.get(p, 0)
                self._rc[p] = rc + 1
                if rc <= 0 and p in self._cached:
                    self._resident -= 1
        self._check()

    def refcount(self, page: int) -> int:
        return self._rc.get(page, 0)

    def is_cached(self, page: int) -> bool:
        return page in self._cached

    def free(self, pages: List[int]) -> None:
        """Release one reference; a page returns to the free list only when
        nothing references it AND it is not cached-resident."""
        back = []
        for p in pages:
            if p == 0:
                continue
            rc = self._rc.get(p, 1) - 1
            if rc > 0:
                self._rc[p] = rc
                continue
            held = self._rc.pop(p, None) is not None
            if p not in self._cached:
                back.append(p)
            elif held:
                self._resident += 1
        if len(back) > 8:
            # a finished sequence's: two sorted stretches, or nearly, so a
            # merge and not a sort from nothing (~0.1 ms at 32,768 pages)
            self._free.extend(back)
            self._free.sort()
        else:  # a page behind a window, a COW source
            for p in back:
                bisect.insort(self._free, p)
        self._check()

    def mark_cached(self, pages: List[int]) -> None:
        for p in pages:
            if p != 0 and p not in self._cached:
                self._cached.add(p)
                if self._rc.get(p, 0) <= 0:
                    self._resident += 1
        self._check()

    def reclaim(self, page: int) -> None:
        """Cache eviction: drop residency; back to the free list if idle."""
        idle = self._rc.get(page, 0) <= 0
        if page in self._cached:
            self._cached.remove(page)
            if idle:
                self._resident -= 1
        if idle:
            self._rc.pop(page, None)
            at = bisect.bisect_left(self._free, page)
            if at == len(self._free) or self._free[at] != page:
                self._free.insert(at, page)
        self._check()


@dataclass
class _Block:
    digest: bytes
    page: int
    parent: bytes = b""   # digest of the previous block (b"" for roots)
    root: bytes = b""     # family identity: digest of the chain's block 0
    tokens: tuple = ()    # block content, for partial (COW) matching
    # ever reused after insertion (matched by a later lookup, or walked
    # through by a sibling chain's insert): True marks the shared SPINE
    # of a family; False marks a never-reused block (a request's unique
    # tail) — the junk eviction should drain first
    was_hit: bool = False
    # LRU position: taken from the cache's clock when the block enters
    # `_blocks` and at every move_to_end, so stamps order as `_blocks` does
    stamp: int = 0
    # the eviction order holds an entry for this block (at most one)
    queued: bool = False


@dataclass
class _Family:
    """Per-family heat: one entry per resident root digest."""

    hits: int = 0          # admissions that reused at least one block
    blocks: int = 0        # resident blocks in this family
    last_hit: float = 0.0  # monotonic ts of the last reuse (0 = never)


class PrefixCache:
    """Chain-hashed index of full prompt pages resident in the KV pool.

    Digest of block k = blake2b(digest of block k-1 || tokens of block k),
    so a digest identifies the entire prefix up to and including its page —
    matching is a walk from the root, never a per-page comparison (vLLM's
    block hash scheme).  Eviction is driven by the allocator owner (engine)
    when the pool runs dry and is FAMILY-aware: drain never-reused leaves
    (unique request tails) coldest-family-first across the whole pool,
    then reclaim leaf-first within the family least recently hit, never
    a block whose child blocks are still resident — so unique traffic
    drains cold chains from the tip instead of cutting hot shared roots
    out from under queued requests.

    THE EVICTION ORDER is kept between calls, not found by a walk of the
    index at every call: `_order` is a heap of `_evict_key` entries over
    the blocks that are LEAVES, referenced or not.  A block enters when it
    becomes a leaf (`insert` of a tip, `_remove` baring a parent) and goes
    when it is evicted.  Everything that changes a key makes it LARGER
    (`was_hit` False -> True, a family's `last_hit` and `hits`, the LRU
    stamp at a move_to_end), so nothing is re-keyed when a block or its
    family is used: an entry's stored key is a lower bound, `evict`
    compares it with the block's key of the moment when it pops it and
    puts it back under that one if it grew, and the first entry popped
    whose key is current is the least of all (every other entry's current
    key is at least its stored one).  Memory: a block has at most ONE
    entry (`_Block.queued`), whatever happens to its key, so hits add
    none; a leaf that gains a child keeps its entry as a stale one, counted
    in `_stale` and dropped when popped (or taken up again if the block is
    bared first), and when the stale ones outnumber the rest by 32 the heap
    is rebuilt without them (`_compact`: amortised against the insertions
    that made them stale), so `_order` holds under 2 x the resident leaves
    + 32 entries however many turns extend a chain or hit a spine.
    """

    def __init__(self, page_size: int):
        self.page_size = page_size
        self._blocks: "OrderedDict[bytes, _Block]" = OrderedDict()
        self._by_page: Dict[int, bytes] = {}
        # parent digest -> digests of its RESIDENT children (b"" = roots);
        # maintained on insert/evict, so the leaf test is one dict lookup
        self._children: Dict[bytes, Set[bytes]] = {}
        self._families: Dict[bytes, _Family] = {}
        # resident-digest advertisement cap (the router's exact-digest hit
        # path degrades to the n-gram tree past it)
        self.digest_limit = int(
            os.environ.get("RTPU_PREFIX_DIGESTS", "16") or 16)
        self.hit_tokens = 0
        self.lookup_tokens = 0
        self.evictions = 0
        self.evictions_cold_family = 0
        self.evictions_hot_root_forced = 0
        self.cow_hits = 0
        # the eviction order (class docstring) and what `evict` had to look
        # at to take its pages: entries popped (stale, re-keyed and pinned
        # ones too) and blocks a forced cut walked
        self._order: List[tuple] = []
        self._stale = 0
        self._clock = 0
        self.eviction_blocks_examined = 0

    # ------------------------- hashing -------------------------------

    @staticmethod
    def _chain(prev: bytes, tokens) -> bytes:
        h = hashlib.blake2b(prev, digest_size=8)
        h.update(np.asarray(tokens, np.int32).tobytes())
        return h.digest()

    @classmethod
    def digest_for(cls, tokens: List[int], page_size: int) -> Optional[str]:
        """Digest of the longest cacheable prefix of `tokens` (the P/D
        residency hint: two processes computing it agree byte-for-byte)."""
        n = len(tokens)
        blocks = max(0, (n - 1) // page_size)
        if blocks == 0:
            return None
        d = b""
        for k in range(blocks):
            d = cls._chain(d, tokens[k * page_size:(k + 1) * page_size])
        return d.hex()

    @classmethod
    def root_digest_for(cls, tokens: List[int],
                        page_size: int) -> Optional[str]:
        """Digest of `tokens`' FIRST full block — the family identity the
        KV tier addresses spine objects by (same chain hash as
        digest_for, so every process derives the same address)."""
        if len(tokens) < page_size:
            return None
        return cls._chain(b"", tokens[:page_size]).hex()

    # ------------------------- index ops -----------------------------

    def __len__(self) -> int:
        return len(self._blocks)

    def _walk(self, tokens: List[int],
              refresh: bool = True) -> Tuple[List[int], bytes, int]:
        """Longest chain of cached FULL pages covering a proper prefix;
        returns (pages, digest of the last matched block or b"", blocks
        matched).  Capped at (n-1)//page_size blocks so at least one
        suffix token is always left to prefill (the logits that seed
        decode)."""
        ps = self.page_size
        n = len(tokens)
        pages: List[int] = []
        d = b""
        for k in range(max(0, (n - 1) // ps)):
            nd = self._chain(d, tokens[k * ps:(k + 1) * ps])
            blk = self._blocks.get(nd)
            if blk is None:
                break
            if refresh:
                self._refresh(blk)
            d = nd
            pages.append(blk.page)
        return pages, d, len(pages)

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _refresh(self, blk: _Block) -> None:
        """A reuse: `blk` is the most recently used block and a hit one."""
        self._blocks.move_to_end(blk.digest)
        blk.stamp = self._tick()
        blk.was_hit = True

    def _touch_family(self, d: bytes) -> None:
        """Record a reuse on the family owning block `d` (heat signal for
        eviction — updated at match time, unlike the hit/lookup counters
        the caller commits only on successful admission, because queued
        retries for a family ARE demand for its pages)."""
        blk = self._blocks.get(d)
        if blk is None:
            return
        fam = self._families.get(blk.root)
        if fam is not None:
            fam.hits += 1
            fam.last_hit = time.monotonic()

    def match(self, tokens: List[int]) -> List[int]:
        """Full-page prefix match (LRU refresh + family heat only; the
        hit/lookup counters are committed by the caller on admission, so a
        request bouncing off a full pool doesn't inflate the hit rate)."""
        pages, d, _ = self._walk(tokens)
        if pages:
            self._touch_family(d)
        return pages

    def match_cow(self, tokens: List[int]) -> Tuple[List[int],
                                                    Optional[int], int]:
        """Full-page match PLUS the copy-on-write boundary: returns
        (pages, cow_src_page, cow_len).  When the first uncovered block of
        `tokens` shares its leading cow_len tokens with a resident child
        block of the matched chain, cow_src_page is that child's page —
        the engine copies it into a fresh page and prefills only from the
        divergence point, instead of recomputing the whole block."""
        pages, d, k = self._walk(tokens)
        if pages:
            self._touch_family(d)
        ps = self.page_size
        want = tokens[k * ps:(k + 1) * ps]
        # at least one suffix token must remain to prefill
        limit = min(len(want), len(tokens) - 1 - k * ps)
        if limit <= 0:
            return pages, None, 0
        best_src, best_m = None, 0
        for cd in self._children.get(d, ()):
            blk = self._blocks.get(cd)
            if blk is None:
                continue
            m = 0
            for a, b in zip(blk.tokens[:limit], want[:limit]):
                if a != b:
                    break
                m += 1
            if m > best_m:
                best_src, best_m = blk, m
        if best_src is None or best_m <= 0:
            return pages, None, 0
        self._refresh(best_src)
        self._touch_family(best_src.digest)
        self.cow_hits += 1
        return pages, best_src.page, best_m

    def peek_match_tokens(self, tokens: List[int]) -> int:
        """Matched-token count WITHOUT LRU refresh or heat updates — the
        hit-aware admission ranking signal (scanning the waiting queue
        must not reorder eviction)."""
        pages, d, k = self._walk(tokens, refresh=False)
        ps = self.page_size
        want = tokens[k * ps:(k + 1) * ps]
        limit = min(len(want), len(tokens) - 1 - k * ps)
        best_m = 0
        if limit > 0:
            for cd in self._children.get(d, ()):
                blk = self._blocks.get(cd)
                if blk is None:
                    continue
                m = 0
                for a, b in zip(blk.tokens[:limit], want[:limit]):
                    if a != b:
                        break
                    m += 1
                best_m = max(best_m, m)
        return k * ps + best_m

    def note_lookup(self, lookup_tokens: int, hit_tokens: int) -> None:
        self.lookup_tokens += lookup_tokens
        self.hit_tokens += hit_tokens

    def insert(self, tokens: List[int], pages: List[int]) -> List[int]:
        """Register every full page of `tokens` held in `pages`; returns the
        pages newly added to the index (callers mark those cached-resident).
        A digest that already maps to some other resident page keeps the
        existing mapping — identical content, and the old page may be
        shared by live sequences."""
        ps = self.page_size
        full = min(len(tokens) // ps, len(pages))
        d = b""
        root = b""
        fresh: List[_Block] = []
        for k in range(full):
            prev = d
            d = self._chain(d, tokens[k * ps:(k + 1) * ps])
            if k == 0:
                root = d
            blk = self._blocks.get(d)
            if blk is not None:
                self._refresh(blk)  # a sibling chain runs through it
                continue
            page = pages[k]
            if page == 0 or page in self._by_page:
                continue
            blk = self._blocks[d] = _Block(
                d, page, parent=prev, root=root,
                tokens=tuple(int(t) for t in tokens[k * ps:(k + 1) * ps]),
                stamp=self._tick())
            self._by_page[page] = d
            sibs = self._children.setdefault(prev, set())
            was_leaf = not sibs  # `prev`, where it is resident
            sibs.add(d)
            self._families.setdefault(root, _Family()).blocks += 1
            fresh.append(blk)
            if was_leaf and (parent := self._blocks.get(prev)) is not None \
                    and parent.queued:
                self._note_stale()
        for blk in fresh:  # the tip; more where a page was passed over
            if self._is_leaf(blk.digest):
                self._enqueue(blk)
        return [blk.page for blk in fresh]

    def _remove(self, blk: _Block) -> None:
        del self._blocks[blk.digest]
        del self._by_page[blk.page]
        sibs = self._children.get(blk.parent)
        if sibs is not None:
            sibs.discard(blk.digest)
            if not sibs:
                del self._children[blk.parent]
                parent = self._blocks.get(blk.parent)
                if parent is not None:  # bared: a leaf (again)
                    if parent.queued:
                        self._stale -= 1
                    else:
                        self._enqueue(parent)
        fam = self._families.get(blk.root)
        if fam is not None:
            fam.blocks -= 1
            if fam.blocks <= 0:
                del self._families[blk.root]
        self.evictions += 1
        if blk.queued and self._is_leaf(blk.digest):
            self._note_stale()  # gone by another way than its entry's pop

    def _is_leaf(self, d: bytes) -> bool:
        return not self._children.get(d)

    # ------------------------- the eviction order --------------------

    def _enqueue(self, blk: _Block) -> None:
        blk.queued = True
        heapq.heappush(self._order, self._evict_key(blk))

    def _note_stale(self) -> None:
        """One more entry whose block is no resident leaf; rebuild the heap
        without them once they outnumber the rest."""
        self._stale += 1
        if 2 * self._stale > len(self._order) + 32:
            self._compact()

    def _compact(self) -> None:
        live = []
        for entry in self._order:
            blk = entry[-1]
            if self._is_resident_leaf(blk):
                live.append(entry)
            else:
                blk.queued = False
        self._order[:] = live
        heapq.heapify(self._order)
        self._stale = 0

    def _is_resident_leaf(self, blk: _Block) -> bool:
        return self._blocks.get(blk.digest) is blk \
            and self._is_leaf(blk.digest)

    def _pop_leaf(self, refcount: Callable[[int], int],
                  pinned: List[_Block]) -> Optional[_Block]:
        """The unreferenced leaf that sorts first, taken off the order; None
        when every leaf is referenced (those go to `pinned`, for the caller
        to put back)."""
        order = self._order
        while order:
            entry = heapq.heappop(order)
            self.eviction_blocks_examined += 1
            blk = entry[-1]
            if not self._is_resident_leaf(blk):
                blk.queued = False
                self._stale -= 1
                continue
            key = self._evict_key(blk)
            if key[:4] != entry[:4]:  # used since it was keyed: grown
                heapq.heappush(order, key)
            elif refcount(blk.page) > 0:
                pinned.append(blk)
            else:
                blk.queued = False
                return blk
        return None

    def evict(self, refcount: Callable[[int], int], n: int
              ) -> List[Tuple[int, str]]:
        """Reclaim up to `n` blocks; returns (page, class) per block in
        eviction order, fewer than `n` when every remaining block is
        pinned.

        Candidates are unreferenced blocks with no resident children;
        among them the family least recently hit loses a block (never-hit
        families sort before any family with a hit), LRU within ties —
        class "cold_family".  NEVER-REUSED leaves (a request's unique
        tail: no later lookup or sibling insert ever touched the block)
        are drained across ALL families before any reused spine block is
        cut — otherwise the momentarily-coldest hot family loses spine
        pages while hotter families sit on piles of junk.  Only when
        every evictable block still has resident children (its leaves are
        all pinned) is a chain cut at an interior block, oldest first —
        class "hot_root_forced", the event the bench counts as throwing
        locality away.

        The leaves come off the order the cache keeps (class docstring),
        each under its key of the moment (never-hit before reused, family
        heat, LRU stamp).  A leaf whose page is referenced (the tip of a
        live sequence) is set aside and goes back when the call ends;
        evicting a block can turn its parent into a leaf, which then joins
        the order under its own key (a chain drains from its tip).  So the
        pages, their order and their classes are those of `n` successive
        walks of the whole index for one block each: no match happens
        inside a call, hence neither heat, `was_hit`, refcounts nor the
        relative LRU order of the survivors can change under it.  The cost
        is O((n + pinned + stale or re-keyed entries) log leaves),
        `eviction_blocks_examined` counts it; only the forced cuts still
        walk the index (once a call that reaches them, oldest first)."""
        out: List[Tuple[int, str]] = []
        pinned: List[_Block] = []
        oldest = None  # the forced cuts' walk, begun when first needed
        while len(out) < n:
            blk = self._pop_leaf(refcount, pinned)
            if blk is not None:
                klass = "cold_family"
                self.evictions_cold_family += 1
            else:
                if oldest is None:
                    oldest = iter(list(self._blocks.values()))
                for blk in oldest:
                    self.eviction_blocks_examined += 1
                    if self._blocks.get(blk.digest) is blk \
                            and refcount(blk.page) <= 0:
                        break
                else:
                    break
                klass = "hot_root_forced"
                self.evictions_hot_root_forced += 1
            self._remove(blk)
            out.append((blk.page, klass))
        for blk in pinned:
            heapq.heappush(self._order, self._evict_key(blk))
        return out

    def _evict_key(self, blk: _Block) -> tuple:
        fam = self._families.get(blk.root)
        heat = (fam.last_hit, fam.hits) if fam is not None else (0.0, 0)
        return (blk.was_hit, *heat, blk.stamp, blk)

    def evict_one(self, refcount: Callable[[int], int]
                  ) -> Optional[Tuple[int, str]]:
        """`evict` for a single block: (page, class), or None if every
        block is pinned."""
        got = self.evict(refcount, 1)
        return got[0] if got else None

    def digests(self, limit: Optional[int] = None) -> List[str]:
        """Most-recently-used block digests (hex) — the resident-prefix
        advertisement the request router matches P/D hints against.
        Default cap: ``RTPU_PREFIX_DIGESTS`` (pools with more hot blocks
        than the cap degrade the router to its n-gram tree)."""
        if limit is None:
            limit = self.digest_limit
        out = []
        for d in reversed(self._blocks):
            out.append(d.hex())
            if len(out) >= limit:
                break
        return out

    def family_hits(self, root: bytes) -> int:
        """Hit count of the family rooted at `root`, -1 when the family
        has no resident blocks (the KV tier's seal gate)."""
        fam = self._families.get(root)
        return fam.hits if fam is not None else -1

    def spine(self, root: bytes) -> Tuple[List[int], List[int]]:
        """The family's shared spine: from the root block down while
        exactly ONE resident child was ever reused (was_hit) — the pages
        later requests actually re-walk, and exactly what a KV-tier seal
        captures.  Unique tails (was_hit=False) and fork points (two hot
        children — the shared prefix ends where tails diverge) stop the
        walk.  Returns (tokens, pages); empty when the root is gone."""
        blk = self._blocks.get(root)
        if blk is None:
            return [], []
        toks: List[int] = list(blk.tokens)
        pages: List[int] = [blk.page]
        d = root
        while True:
            hot = [cd for cd in self._children.get(d, ())
                   if (b := self._blocks.get(cd)) is not None and b.was_hit]
            if len(hot) != 1:
                break
            d = hot[0]
            b = self._blocks[d]
            toks.extend(b.tokens)
            pages.append(b.page)
        return toks, pages

    def family_stats(self) -> List[dict]:
        """Per-family heat rows, hottest first (debug/CLI view)."""
        rows = [{"root": root.hex(), "blocks": fam.blocks,
                 "hits": fam.hits,
                 "last_hit_age_s": round(
                     time.monotonic() - fam.last_hit, 3)
                 if fam.last_hit else None}
                for root, fam in self._families.items()]
        rows.sort(key=lambda r: (r["last_hit_age_s"] is None,
                                 r["last_hit_age_s"] or 0.0))
        return rows

    def stats(self) -> dict:
        return {
            "blocks": len(self._blocks),
            "families": len(self._families),
            "hit_tokens": self.hit_tokens,
            "lookup_tokens": self.lookup_tokens,
            "evictions": self.evictions,
            "evictions_cold_family": self.evictions_cold_family,
            "evictions_hot_root_forced": self.evictions_hot_root_forced,
            "cow_hits": self.cow_hits,
            "eviction_blocks_examined": self.eviction_blocks_examined,
            "digest_limit": self.digest_limit,
            "hit_rate": round(self.hit_tokens / self.lookup_tokens, 4)
            if self.lookup_tokens else 0.0,
        }
