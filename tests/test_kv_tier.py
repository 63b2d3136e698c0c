"""Store-backed KV page tier (ISSUE 16): seal/pull correctness, typed
pull-failure fallback, store-daemon chaos, and kill/recover failover.

The core invariants, mirroring the P/D handoff tests in shape:

1. a decode running on PULL-HYDRATED pages is byte-identical to one on
   locally-prefilled pages (the tier is lossless);
2. every pull failure degrades to a cold prefill with a counted,
   reasoned fallback — never a wedged or wrong request;
3. after a replica kill, a survivor sharing the store tier recovers the
   dead replica's hot families by pulling, not recomputing.
"""

import os
import signal
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.llm.kv_tier import (  # noqa: E402
    InProcessStore,
    KVPullError,
    KVTier,
    LocalDirectory,
    decode_spine,
    encode_spine,
)
from ray_tpu.models import llama  # noqa: E402


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, dtype="float32", remat=False)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return params, cfg


def _engine(tiny_model, tier=None):
    params, cfg = tiny_model
    return LLMEngine(params, cfg, EngineConfig(
        max_slots=4, num_pages=64, page_size=8, max_seq_len=256,
        prefill_buckets=(16, 32, 64, 128)), kv_tier=tier)


def _prompt(seed: int, n: int = 40):
    return list(int(t) for t in
                np.random.RandomState(seed).randint(1, 128, size=n))


# ------------------------------------------------------------ blob codec


def test_spine_blob_roundtrip():
    tokens = list(range(16))
    kv_k = np.arange(2 * 2 * 8 * 2 * 4, dtype=np.float32).reshape(
        2, 2, 8, 2, 4)
    kv_v = kv_k * 2 + 1
    blob = encode_spine(tokens, kv_k, kv_v, page_size=8)
    t2, k2, v2, hdr = decode_spine(blob)
    assert t2 == tokens
    np.testing.assert_array_equal(k2, kv_k)
    np.testing.assert_array_equal(v2, kv_v)
    assert hdr["blocks"] == 2 and hdr["page_size"] == 8
    assert hdr["dtype"] == "float32"


def test_spine_blob_typed_damage():
    tokens = list(range(8))
    kv = np.ones((1, 1, 8, 2, 4), dtype=np.float32)
    blob = encode_spine(tokens, kv, kv, page_size=8)
    with pytest.raises(KVPullError) as ei:
        decode_spine(b"JUNK" + blob[4:])
    assert ei.value.reason == "corrupt"
    with pytest.raises(KVPullError) as ei:
        decode_spine(blob[:len(blob) // 2])  # torn stripe
    assert ei.value.reason == "truncated"
    with pytest.raises(KVPullError) as ei:
        decode_spine(blob[:10])  # header cut short
    assert ei.value.reason == "truncated"
    with pytest.raises(KVPullError) as ei:
        decode_spine(blob[:6])  # can't even read the preamble
    assert ei.value.reason == "corrupt"


def test_oid_is_depth_versioned():
    root = "aa" * 8
    assert KVTier.oid_for(root, 2) != KVTier.oid_for(root, 3)
    assert KVTier.oid_for(root, 2) == KVTier.oid_for(root, 2)
    assert len(KVTier.oid_for(root, 2)) == 20


def test_directory_never_shadows_deeper_spine():
    d = LocalDirectory()
    d.publish("r", {"oid": "aa", "blocks": 4, "hits": 9})
    d.publish("r", {"oid": "bb", "blocks": 2, "hits": 20})
    rec = d.lookup("r")
    # the shallower reseal keeps the deeper blob's address but may
    # refresh the heat
    assert rec["oid"] == "aa" and rec["blocks"] == 4
    assert d.hottest(1) == ["r"]


# ------------------------------------------------- seal -> pull -> decode


def test_pull_hydrated_decode_byte_identical(tiny_model):
    """A second engine that never saw the prompt decodes byte-identically
    after pulling the family spine sealed by the first (the whole point:
    failover pays a pull, not a recompute, and loses nothing)."""
    store, dirx = InProcessStore(), LocalDirectory()
    prompt = _prompt(0)
    sp = SamplingParams(max_tokens=10, temperature=0.0)

    e1 = _engine(tiny_model, KVTier(store, dirx, seal_min_hits=1))
    expected = e1.generate(list(prompt), sp)
    assert e1.generate(list(prompt), sp) == expected  # 2nd run heats + seals
    assert e1.stats()["kv_seals"] >= 1
    e1.stop()

    e2 = _engine(tiny_model, KVTier(store, dirx, seal_min_hits=1))
    got = e2.generate(list(prompt), sp)
    st = e2.stats()
    e2.stop()
    assert got == expected, (got, expected)
    assert st["kv_pulls"] >= 1 and st["kv_pull_pages"] >= 4
    assert st["kv_pull_fallbacks"] == 0
    # the hydrated spine registered as REAL prefix-cache hits
    assert st["prefix_cache"]["hit_tokens"] >= 32


def test_warm_restart_prehydrates_hottest(tiny_model):
    """kv_prehydrate (the controller's replication push / a restarted
    replica's warm-up) loads a family before any request references it."""
    store, dirx = InProcessStore(), LocalDirectory()
    prompt = _prompt(1)
    sp = SamplingParams(max_tokens=8, temperature=0.0)

    tier1 = KVTier(store, dirx, seal_min_hits=1)
    e1 = _engine(tiny_model, tier1)
    expected = e1.generate(list(prompt), sp)
    e1.generate(list(prompt), sp)
    e1.stop()

    tier2 = KVTier(store, dirx, seal_min_hits=1)
    e2 = _engine(tiny_model, tier2)
    roots = tier2.hottest(8)
    assert roots, "sealed family missing from directory heat index"
    e2.kv_prehydrate(roots)
    deadline = time.monotonic() + 10
    while e2.stats()["kv_pulls"] < 1:
        assert time.monotonic() < deadline, "prehydrate never pulled"
        time.sleep(0.05)
    st = e2.stats()
    assert st["kv_pull_pages"] >= 4
    # the family is now resident BEFORE its first request arrives
    assert e2.generate(list(prompt), sp) == expected
    assert e2.stats()["prefix_cache"]["hit_tokens"] >= 32
    e2.stop()


# ---------------------------------------------------- fallback paths


class _FlakyStore(InProcessStore):
    """Store whose reads fail with a store-client-shaped exception."""

    def __init__(self, exc):
        super().__init__()
        self._exc = exc
        self.failing = False

    def get_bytes(self, oid, timeout_ms=0):
        if self.failing:
            raise self._exc
        return super().get_bytes(oid, timeout_ms)


def test_pull_failure_falls_back_to_cold_prefill(tiny_model):
    """Typed pull failure (daemon died mid-pull): the request cold-
    prefills, output stays byte-identical, and the fallback is counted
    under its reason — never an error surfaced to the caller."""
    from ray_tpu.exceptions import StoreDiedError

    store = _FlakyStore(StoreDiedError("daemon gone"))
    dirx = LocalDirectory()
    prompt = _prompt(2)
    sp = SamplingParams(max_tokens=10, temperature=0.0)

    e1 = _engine(tiny_model, KVTier(store, dirx, seal_min_hits=1))
    expected = e1.generate(list(prompt), sp)
    e1.generate(list(prompt), sp)
    assert e1.stats()["kv_seals"] >= 1
    e1.stop()

    store.failing = True
    e2 = _engine(tiny_model, KVTier(store, dirx, seal_min_hits=1))
    got = e2.generate(list(prompt), sp)
    st = e2.stats()
    e2.stop()
    assert got == expected
    assert st["kv_pulls"] == 0
    assert st["kv_pull_fallbacks"] >= 1
    assert st["prefix_cache"]["hit_tokens"] == 0  # genuinely cold


def test_truncated_blob_falls_back(tiny_model):
    """A torn stripe (truncated blob bytes in the store) is a typed
    'truncated' fallback, not a crash."""
    store, dirx = InProcessStore(), LocalDirectory()
    prompt = _prompt(3)
    sp = SamplingParams(max_tokens=8, temperature=0.0)

    e1 = _engine(tiny_model, KVTier(store, dirx, seal_min_hits=1))
    expected = e1.generate(list(prompt), sp)
    e1.generate(list(prompt), sp)
    e1.stop()

    with store._lock:  # tear every sealed blob in half
        for oid in list(store._objs):
            store._objs[oid] = store._objs[oid][:len(store._objs[oid]) // 2]

    e2 = _engine(tiny_model, KVTier(store, dirx, seal_min_hits=1))
    got = e2.generate(list(prompt), sp)
    st = e2.stats()
    e2.stop()
    assert got == expected
    assert st["kv_pull_fallbacks"] >= 1


def test_store_chaos_daemon_death_falls_back(tiny_model, tmp_path,
                                             monkeypatch):
    """Against the REAL shm store daemon: seal a family, SIGKILL the
    daemon (as RTPU_TESTING_STORE_FAILURE kill chaos does, but
    deterministically), and the next engine's pull degrades to a counted
    'store_died' cold prefill with byte-identical output."""
    from ray_tpu.core import store_client as sc
    from ray_tpu.core.store_client import StoreClient, StoreServer

    srv = StoreServer(str(tmp_path / "kv.sock"),
                      f"rtpu_kvt_{os.getpid()}", 1 << 24)
    client = StoreClient(srv.socket_path, srv.shm_name, srv.capacity)
    dirx = LocalDirectory()
    prompt = _prompt(4)
    sp = SamplingParams(max_tokens=10, temperature=0.0)
    try:
        e1 = _engine(tiny_model, KVTier(client, dirx, seal_min_hits=1))
        expected = e1.generate(list(prompt), sp)
        e1.generate(list(prompt), sp)
        assert e1.stats()["kv_seals"] >= 1
        e1.stop()

        # sanity: a fresh engine CAN pull from the live daemon
        e2 = _engine(tiny_model, KVTier(client, dirx, seal_min_hits=1))
        assert e2.generate(list(prompt), sp) == expected
        assert e2.stats()["kv_pulls"] >= 1
        e2.stop()

        # daemon dies; retries must give up inside the test budget
        monkeypatch.setattr(sc, "_RETRY_BUDGET_S", 0.5)
        os.kill(srv._proc.pid, signal.SIGKILL)
        deadline = time.monotonic() + 5
        while srv.poll() is None:
            assert time.monotonic() < deadline, "daemon ignored SIGKILL"
            time.sleep(0.02)

        e3 = _engine(tiny_model, KVTier(client, dirx, seal_min_hits=1))
        got = e3.generate(list(prompt), sp)
        st = e3.stats()
        e3.stop()
        assert got == expected
        assert st["kv_pulls"] == 0
        assert st["kv_pull_fallbacks"] >= 1
    finally:
        client.close()
        srv.shutdown()


# ------------------------------------------------- kill / recover


def _kill_scene(tiny_model, tier_on):
    """Two engines behind a prefix-aware router, with or without a shared
    store tier; e1 dies after a warm phase and the same burst runs again
    on the survivor.  Every request is held to the first answer its family
    got.  Returns the pre-kill hit rate and, of the failed-over burst, the
    survivor's pulls, hit tokens and looked-up tokens."""
    from ray_tpu.serve.request_router.prefix_aware import PrefixAwareRouter

    store, dirx = InProcessStore(), LocalDirectory()
    e1, e2 = (_engine(tiny_model, KVTier(store, dirx, seal_min_hits=1)
                      if tier_on else None) for _ in range(2))

    class Rep:
        def __init__(self, rid, engine):
            self.actor_id = rid
            self.engine = engine

    r1, r2 = Rep(b"e1", e1), Rep(b"e2", e2)
    router = PrefixAwareRouter("app", f"kv-{tier_on}")
    router.update_replicas([r1, r2])
    families = [_prompt(10 + f, 40) for f in range(4)]
    sp = SamplingParams(max_tokens=6, temperature=0.0)

    def run(i):
        fam = families[i % len(families)]
        hint = ",".join(str(t) for t in fam[:16])
        rep = router.choose(hint)
        router.on_send(rep.actor_id)
        try:
            return rep.engine.generate(list(fam), sp)
        finally:
            router.on_done(rep.actor_id)

    baseline = {i: run(i) for i in range(len(families))}
    for i in range(16):  # warm phase: homes form, families heat, seals
        assert run(i) == baseline[i % len(families)]
    pre = max(e.stats()["prefix_cache"]["hit_rate"] for e in (e1, e2))
    assert pre > 0.5, "warm phase never got hot"
    if tier_on:
        assert len(dirx.hottest(8)) >= 1, \
            "no family sealed during warm phase"

    # mid-burst kill: e1 vanishes; router purges the corpse
    e1.stop()
    router.purge_dead([r1.actor_id])
    router.update_replicas([r2])

    s0 = e2.stats()
    for i in range(16):  # failed-over burst, all on the survivor
        assert run(i) == baseline[i % len(families)]
    s1 = e2.stats()
    e2.stop()
    pc0, pc1 = s0["prefix_cache"], s1["prefix_cache"]
    return {"pre": pre, "pulls": s1["kv_pulls"] - s0["kv_pulls"],
            "hit": pc1["hit_tokens"] - pc0["hit_tokens"],
            "lookup": pc1["lookup_tokens"] - pc0["lookup_tokens"]}


def test_kill_recover_hit_rate(tiny_model):
    """The survivor recovers the hit rate by PULLING the dead engine's
    sealed spines from the shared store tier instead of cold-prefilling
    every family from scratch: it prefills fewer tokens after the kill
    than the same scene pays without the tier."""
    on = _kill_scene(tiny_model, tier_on=True)
    off = _kill_scene(tiny_model, tier_on=False)
    assert on["pulls"] > 0, \
        "survivor never pulled the dead engine's families"
    assert off["pulls"] == 0
    assert on["hit"] / max(1, on["lookup"]) >= 0.8 * on["pre"], on
    assert on["lookup"] == off["lookup"]  # the same burst
    assert on["lookup"] - on["hit"] < off["lookup"] - off["hit"], (on, off)
