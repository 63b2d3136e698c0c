"""Request-router subsystem tests (serve/request_router/).

Unit coverage: pow-2 load preference, the prefix tree (insert / deepest
match / LRU eviction), imbalance fallback, digest-hit routing, stats
staleness, and the process-wide registry (multi-handle agreement).  The
integration test at the bottom drives two real LLM engines through both
policies and asserts prefix-aware routing earns a strictly higher
prefix-cache hit rate than pow-2 on shared-prefix traffic.
"""

import random

import pytest

from ray_tpu.serve.request_router import (
    Pow2Router,
    PrefixAwareRouter,
    PrefixTree,
    get_router,
)
from ray_tpu.serve.request_router.base import _REGISTRY


class FakeReplica:
    def __init__(self, rid: bytes):
        self.actor_id = rid

    def __repr__(self):
        return f"FakeReplica({self.actor_id!r})"


@pytest.fixture(autouse=True)
def _clear_registry():
    _REGISTRY.clear()
    yield
    _REGISTRY.clear()


# ---------------------------------------------------------------- pow-2


def test_pow2_prefers_shorter_queue():
    random.seed(0)
    router = Pow2Router("app", "d")
    r1, r2 = FakeReplica(b"r1"), FakeReplica(b"r2")
    router.update_replicas([r1, r2])
    for _ in range(3):
        router.on_send(r1.actor_id)
    # with two replicas the sample is always {r1, r2}; the pick must be
    # the unloaded one every time
    for _ in range(20):
        assert router.choose() is r2


def test_pow2_single_replica_short_circuits():
    router = Pow2Router("app", "d")
    r1 = FakeReplica(b"r1")
    router.update_replicas([r1])
    assert router.choose() is r1
    assert router._decisions["single"] == 1


def test_router_raises_without_replicas():
    router = Pow2Router("app", "d")
    with pytest.raises(RuntimeError, match="no running replicas"):
        router.choose()


# ---------------------------------------------------------- prefix tree


def test_prefix_tree_insert_and_deepest_match():
    tree = PrefixTree(block=4, cap=64)
    tree.insert("aaaabbbbcccc", b"r1")
    tree.insert("aaaabbbb", b"r2")  # shares the first two levels
    live = {b"r1", b"r2"}
    # full hint: r1 owns the deepest (3-block) node
    rid, depth = tree.match("aaaabbbbcccc", live)
    assert (rid, depth) == (b"r1", 3)
    # 2-block hint: r2 inserted later, so it is the most recent there
    rid, depth = tree.match("aaaabbbb", live)
    assert (rid, depth) == (b"r2", 2)
    # no match at all
    assert tree.match("zzzz", live) == (None, 0)
    # dead replicas never match
    rid, _ = tree.match("aaaabbbbcccc", {b"r2"})
    assert rid == b"r2"


def test_prefix_tree_lru_eviction():
    tree = PrefixTree(block=4, cap=3)
    tree.insert("aaaabbbbcccc", b"r1")  # 3 nodes, at cap
    assert len(tree) == 3
    tree.insert("zzzz", b"r2")  # evicts the coldest node ("aaaa")
    assert len(tree) == 3
    assert tree.evictions == 1
    # the walk stops at the evicted depth-1 node (trie semantics: a cut
    # path no longer matches), so the hint now misses...
    assert tree.match("aaaabbbbcccc", {b"r1", b"r2"}) == (None, 0)
    assert tree.match("zzzz", {b"r2"}) == (b"r2", 1)
    # ...and re-inserting it restores the match while evicting the
    # coldest remaining nodes
    tree.insert("aaaabbbbcccc", b"r1")
    assert len(tree) == 3
    assert tree.match("aaaabbbbcccc", {b"r1"}) == (b"r1", 3)
    assert tree.match("zzzz", {b"r2"}) == (None, 0)


def test_prefix_tree_forget_replica():
    tree = PrefixTree(block=4, cap=16)
    tree.insert("aaaa", b"r1")
    tree.forget(b"r1")
    assert tree.match("aaaa", {b"r1"}) == (None, 0)


# --------------------------------------------------- prefix-aware router


def _aware(reps):
    router = PrefixAwareRouter("app", "d")
    router.update_replicas(reps)
    return router


def test_prefix_affinity_sticks():
    random.seed(1)
    r1, r2 = FakeReplica(b"r1"), FakeReplica(b"r2")
    router = _aware([r1, r2])
    hint = "system-prompt-alpha:" + "x" * 64
    first = router.choose(hint)
    # every subsequent request with the hint lands on the same replica
    for _ in range(20):
        assert router.choose(hint) is first
    assert router._decisions["prefix_hit"] >= 20


def test_imbalance_falls_back_to_pow2():
    random.seed(2)
    r1, r2 = FakeReplica(b"r1"), FakeReplica(b"r2")
    router = _aware([r1, r2])
    router.imbalance = 4.0
    hint = "shared-prefix:" + "y" * 64
    home = router.choose(hint)
    other = r2 if home is r1 else r1
    # overload the home replica past min + imbalance
    for _ in range(6):
        router.on_send(home.actor_id)
    assert router.choose(hint) is other
    assert router._decisions["fallback_imbalanced"] >= 1
    # the shed did NOT migrate the prefix home: a transient spike spills
    # requests but the family's pages live on `home`, and once the spike
    # drains traffic returns to them instead of rebuilding on `other`
    for _ in range(6):
        router.on_done(home.actor_id)
    assert router.choose(hint) is home


def test_new_prefixes_home_to_smallest_footprint():
    """First-touch homing balances the resident working set: unhomed
    prefixes go to the replica with the fewest homed tree nodes, so N
    prefix families split N/2-N/2 instead of binomially."""
    random.seed(4)
    r1, r2 = FakeReplica(b"r1"), FakeReplica(b"r2")
    router = _aware([r1, r2])
    homes = {b"r1": 0, b"r2": 0}
    for i in range(10):
        rep = router.choose(f"family-{i:02d}:" + "z" * 48)
        homes[rep.actor_id] += 1
    assert homes[b"r1"] == homes[b"r2"] == 5


def test_digest_hit_routes_to_page_holder():
    random.seed(3)
    r1, r2 = FakeReplica(b"r1"), FakeReplica(b"r2")
    router = _aware([r1, r2])
    digest = "deadbeefcafef00d"
    router.update_stats({r2.actor_id: {
        "queue_len": 0,
        "engine": {"prefix_digests": [digest]}}})
    for _ in range(5):
        assert router.choose(digest) is r2
    assert router._decisions["digest_hit"] == 5


def test_departed_replica_forgotten():
    random.seed(4)
    r1, r2 = FakeReplica(b"r1"), FakeReplica(b"r2")
    router = _aware([r1, r2])
    hint = "sticky:" + "z" * 64
    home = router.choose(hint)
    survivor = r2 if home is r1 else r1
    router.update_replicas([survivor])
    assert router.choose(hint) is survivor


def test_purge_dead_evicts_stats_tree_and_routing():
    """Replica DEATH (vs scale-down): purge_dead must drop the corpse's
    stats sample, its prefix-tree homes, and the replica itself — a
    fresh-looking digest sample would otherwise keep winning digest-hit
    routing and pin requests to the corpse for up to RTPU_ROUTER_STALE_S
    (update_replicas only prunes on a list refresh, which the handle's
    cached replica set delays)."""
    random.seed(5)
    r1, r2 = FakeReplica(b"r1"), FakeReplica(b"r2")
    router = _aware([r1, r2])
    digest = "feedfacecafebeef"
    hint = "doomed:" + "q" * 64
    router.update_stats({r1.actor_id: {
        "queue_len": 0, "engine": {"prefix_digests": [digest]}}})
    router.tree.insert(hint, r1.actor_id)
    assert router.choose(digest) is r1  # sanity: r1 owns both signals
    assert router.choose(hint) is r1

    router.purge_dead([r1.actor_id])

    assert router.stats_for(r1.actor_id) is None
    assert router.tree.count_for(r1.actor_id) == 0
    # every signal that pointed at the corpse now lands on the survivor
    for h in (digest, hint, None):
        assert router.choose(h) is r2
    # idle in-flight accounting dropped too; settled entries never go
    # negative for a replica that no longer exists
    assert r1.actor_id not in router._inflight


# ------------------------------------------------------- stats staleness


def test_stale_stats_ignored():
    router = Pow2Router("app", "d")
    r1 = FakeReplica(b"r1")
    router.update_replicas([r1])
    router.update_stats({r1.actor_id: {"queue_len": 50, "age_s": 0.0}})
    assert router.load(r1.actor_id) == 50
    # a sample backdated past RTPU_ROUTER_STALE_S contributes nothing
    router.update_stats({r1.actor_id: {"queue_len": 50, "age_s": 999.0}})
    assert router.stats_for(r1.actor_id) is None
    assert router.load(r1.actor_id) == 0


def test_load_is_max_of_local_and_reported():
    router = Pow2Router("app", "d")
    r1 = FakeReplica(b"r1")
    router.update_replicas([r1])
    router.update_stats({r1.actor_id: {"queue_len": 2, "age_s": 0.0}})
    for _ in range(5):
        router.on_send(r1.actor_id)
    assert router.load(r1.actor_id) == 5  # local dominates
    for _ in range(4):
        router.on_done(r1.actor_id)
    assert router.load(r1.actor_id) == 2  # report dominates


def test_stale_home_stats_count_as_loaded():
    """Overload-gate boundary (the mid-rung TTFT cliff): when the home
    replica's stats sample ages out while ANOTHER replica reports fresh
    ones, the gate must treat the silent replica as loaded — its queue
    depth is exactly what we can no longer see."""
    random.seed(6)
    r1, r2 = FakeReplica(b"r1"), FakeReplica(b"r2")
    router = _aware([r1, r2])
    hint = "stale-gate:" + "s" * 64
    home = router.choose(hint)
    other = r2 if home is r1 else r1
    # both fresh: affinity holds
    router.update_stats({
        home.actor_id: {"queue_len": 0, "age_s": 0.0},
        other.actor_id: {"queue_len": 0, "age_s": 0.0}})
    assert router.choose(hint) is home
    assert router._overloaded(home.actor_id, [r1, r2]) is None
    # the home's sample ages past RTPU_ROUTER_STALE_S, the other stays
    # fresh: the affinity match is abandoned (and pow-2 sees the home's
    # one in-flight request, so the re-home is deterministic)
    router.update_stats({
        home.actor_id: {"queue_len": 0, "age_s": 999.0},
        other.actor_id: {"queue_len": 0, "age_s": 0.0}})
    assert router._overloaded(home.actor_id, [r1, r2]) == "stale"
    router.on_send(home.actor_id)
    assert router.choose(hint) is other
    assert router._decisions["fallback_stale"] >= 1


def test_stale_gate_stays_open_without_any_fresh_stats():
    """When NO replica has fresh stats (controller warmup, or a handle
    that never receives the piggyback) the stale gate must NOT trip —
    local in-flight counts are the only signal and they already feed
    load().  Regression guard for single-process routing."""
    random.seed(7)
    r1, r2 = FakeReplica(b"r1"), FakeReplica(b"r2")
    router = _aware([r1, r2])
    hint = "no-stats:" + "n" * 64
    home = router.choose(hint)
    assert router._overloaded(home.actor_id, [r1, r2]) is None
    for _ in range(10):
        assert router.choose(hint) is home


# ------------------------------------------- registry / handle agreement


def test_get_router_shared_across_handles():
    a = get_router("app", "dep", "pow2")
    b = get_router("app", "dep", "pow2")
    assert a is b
    # routing state is shared: a send through one handle's router is
    # visible to the other (the old per-handle home-map divergence)
    a.on_send(b"r1")
    assert b._inflight[b"r1"] == 1
    assert get_router("app", "other", "pow2") is not a


def test_policy_swap_carries_inflight():
    a = get_router("app", "dep", "pow2")
    a.on_send(b"r1")
    b = get_router("app", "dep", "prefix_aware")
    assert b is not a
    assert isinstance(b, PrefixAwareRouter)
    assert b._inflight[b"r1"] == 1  # settled responses still decrement
    assert get_router("app", "dep", "prefix_aware") is b


# ------------------------------------------------------------ snapshots


def test_snapshot_shape():
    random.seed(5)
    r1, r2 = FakeReplica(b"r1"), FakeReplica(b"r2")
    router = _aware([r1, r2])
    router.choose("hinted:" + "w" * 40)
    snap = router.snapshot()
    assert snap["policy"] == "prefix_aware"
    assert snap["replicas"] == 2
    assert sum(snap["decisions"].values()) == 1
    assert "prefix_tree" in snap and snap["prefix_tree"]["nodes"] >= 1


# ------------------------------------------------ engine integration


@pytest.fixture(scope="module")
def tiny_model():
    jax = pytest.importorskip("jax")
    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, dtype="float32", remat=False)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return params, cfg


def _run_policy(tiny_model, router_cls, seed, families, num_pages):
    """Two real engines behind a router; shared-prefix traffic, ten
    requests a family in turn; returns the aggregate prefix-cache hit
    rate across both engines, each engine's page evictions and the
    prefill tokens the cache saved."""
    from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams

    params, cfg = tiny_model
    engines = {}
    reps = []
    for name in (b"e1", b"e2"):
        eng = LLMEngine(params, cfg, EngineConfig(
            max_slots=4, num_pages=num_pages, page_size=8,
            max_seq_len=256, prefill_buckets=(16, 32, 64)))
        engines[name] = eng
        reps.append(FakeReplica(name))
    router = router_cls(
        "app", f"bench-{router_cls.__name__}-{seed}-{families}")
    router.update_replicas(reps)
    random.seed(seed)
    rng = random.Random(seed)
    groups = [[1 + g, 2 + g, 3 + g, 4 + g] * 6 for g in range(families)]
    try:
        for i in range(10 * families):
            g = i % families
            prompt = groups[g] + [rng.randrange(1, 128) for _ in range(4)]
            hint = f"group-{g}:" + "p" * 48
            rep = router.choose(hint)
            router.on_send(rep.actor_id)
            engines[rep.actor_id].generate(
                prompt, SamplingParams(max_tokens=4))
            router.on_done(rep.actor_id)
            router.update_stats({
                rid: {"queue_len": 0, "age_s": 0.0,
                      "engine": e.stats()}
                for rid, e in engines.items()})
        stats = [e.stats() for e in engines.values()]
        hits = sum(s["prefix_cache"]["hit_tokens"] for s in stats)
        lookups = sum(s["prefix_cache"]["lookup_tokens"] for s in stats)
        return {"hit_rate": hits / max(lookups, 1),
                "evictions": [s["page_evictions"] for s in stats],
                "saved": sum(s["prefill_tokens_saved"] for s in stats)}
    finally:
        for e in engines.values():
            e.stop()


# A family's prefix is three pages and a request holds a fourth.  Roomy:
# every family stays resident wherever it lands.  Pressed: a prefix-aware
# home holds three of six families (nine pages) in a pool of ten, so both
# policies evict prefix pages for the whole run.
@pytest.mark.parametrize("families,num_pages,pressed",
                         [(3, 64, False), (6, 10, True)],
                         ids=["roomy", "pool_below_family_set"])
def test_prefix_aware_beats_pow2_hit_rate(tiny_model, families, num_pages,
                                          pressed):
    aware = _run_policy(tiny_model, PrefixAwareRouter, 11, families,
                        num_pages)
    pow2 = _run_policy(tiny_model, Pow2Router, 11, families, num_pages)
    # same traffic, same engines: KV-locality routing must convert more
    # lookups into warm-page hits than blind load balancing
    assert aware["hit_rate"] > pow2["hit_rate"], (aware, pow2)
    assert aware["saved"] > 0, aware
    evictions = aware["evictions"] + pow2["evictions"]
    if pressed:
        # the scene is under the load it claims: every engine evicted
        assert all(evictions), (aware, pow2)
    else:
        assert not any(evictions), (aware, pow2)
        # sticky homes make most prefixes warm
        assert aware["hit_rate"] >= 0.5, aware


# ------------------------------------- cache/COW byte-identical decode


def _drain(req):
    out = []
    while True:
        item = req.out_queue.get(timeout=300)
        if item is None:
            return out
        if isinstance(item, Exception):
            raise item
        out.append(item)


def _family_decode(tiny_model, monkeypatch, cache_on):
    """Greedy-decode a family of prefix-sharing prompts twice: first
    sequentially (full-page hits + COW boundary copies), then
    concurrently against a pool too small for all of them (forced
    preemption + resume).  Returns (sequential outputs, concurrent
    outputs, engine stats)."""
    monkeypatch.setenv("RTPU_PREFIX_CACHE", "1" if cache_on else "0")
    monkeypatch.setenv("RTPU_DEBUG_ALLOCATOR", "1")
    from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams

    params, cfg = tiny_model
    eng = LLMEngine(params, cfg, EngineConfig(
        max_slots=4, num_pages=24, page_size=8, max_seq_len=128,
        prefill_buckets=(8, 16, 32, 64)))
    fam = [3, 1, 4, 1, 5] * 4  # 20 shared tokens: 2 full pages + 4 in a
    #                            partial boundary block (the COW case)
    prompts = [fam + [20 + i, 30 + i, 40 + i] for i in range(6)]
    try:
        seq = [eng.generate(p, SamplingParams(max_tokens=8))
               for p in prompts]
        # 4 concurrent slots x 8 pages each (2 of them shared family
        # pages) vs 23 allocatable: decode growth must preempt and
        # resume mid-stream
        reqs = [eng.submit(p, SamplingParams(max_tokens=40))
                for p in prompts]
        conc = [_drain(r) for r in reqs]
        return seq, conc, eng.stats()
    finally:
        eng.stop()


def test_cache_cow_decode_byte_identical(tiny_model, monkeypatch):
    """Prefix cache + COW + family eviction + preemption resume must be
    invisible in the output stream: greedy decode with the cache on is
    byte-identical, token for token, to decode with the cache off —
    including sequences resumed after a forced preemption."""
    on_seq, on_conc, st = _family_decode(tiny_model, monkeypatch, True)
    off_seq, off_conc, st_off = _family_decode(tiny_model, monkeypatch,
                                               False)
    assert on_seq == off_seq
    assert on_conc == off_conc
    # the run actually exercised what it claims to: COW copies fired and
    # the concurrent phase preempted at least one sequence
    assert st["cow_copies"] > 0
    assert st["preempted"] > 0
    assert st["prefill_tokens_saved"] > 0
    assert st_off["prefix_cache"] is None
