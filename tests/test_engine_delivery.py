"""Delivery behind the next dispatch (ISSUE 35).

A replay changes slot state and leaves what the streams are to receive
pending; the engine loop puts it once the next device program is dispatched
(or, where an iteration dispatches none, before that iteration ends).  What
comes off a request's ``out_queue`` is what came off it before: the tokens
in order, then ``None``.

The recorder below puts the programs' calls (``lm.prefill`` ...), the
loop's fetch phases and the queues' puts on ONE list, in the order they
happened: all of them happen on the engine thread.
"""

import queue
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sdar_moe as sdar_reference
from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm import model as lm
from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.llm.paged_cache import PageAllocator
from ray_tpu.models import llama, sdar_moe
from test_block_diffusion import VOCAB as SDAR_VOCAB
from test_block_diffusion import _file as file_config
from test_block_diffusion import _prompts as block_prompts

PROGRAMS = ("prefill", "prefill_with_prefix", "decode_step",
            "decode_step_greedy", "decode_step_greedy_chained", "block_step")
PROMPT = [3, 14, 15, 92, 65, 35]


@pytest.fixture(scope="module")
def dense():
    cfg = llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, dtype="float32", remat=False)
    return llama.init(cfg, jax.random.PRNGKey(0)), cfg


@pytest.fixture(scope="module")
def routed():
    cfg = sdar_moe.SDARMoEConfig.tiny(SDAR_VOCAB)
    return sdar_moe.init(cfg, jax.random.PRNGKey(0)), cfg


def _engine(model, **kw):
    params, cfg = model
    return LLMEngine(params, cfg, EngineConfig(**{**dict(
        max_slots=4, num_pages=64, page_size=8, max_seq_len=256,
        prefill_buckets=(16, 32, 64, 128)), **kw}))


@pytest.fixture(scope="module")
def full(dense):
    """40 greedy tokens after PROMPT by the training forward, no cache
    (padded to one length: a causal model does not look ahead)."""
    params, cfg = dense
    toks = list(PROMPT)
    for _ in range(40):
        padded = jnp.asarray([toks + [0] * (64 - len(toks))])
        toks.append(int(jnp.argmax(
            llama.apply(params, padded, cfg)[0, len(toks) - 1])))
    return toks[len(PROMPT):]


def _items(req, timeout=120):
    """Everything that comes off the request's queue, terminator included;
    nothing may follow the terminator."""
    out = []
    while not out or out[-1] is not None:
        out.append(req.out_queue.get(timeout=timeout))
    assert req.out_queue.empty()
    return out


class Recorder:
    """Dispatches, fetches and puts of one engine, on one list."""

    def __init__(self, monkeypatch, engine):
        self.events = events = []  # (kind, what, counters or item, time)
        self.engine = engine

        def note(kind, what, value):
            events.append((kind, what, value, time.monotonic()))

        for name in PROGRAMS:
            def program(*a, _run=getattr(lm, name), _name=name, **kw):
                note("dispatch", _name, dict(engine._stats))
                return _run(*a, **kw)
            monkeypatch.setattr(lm, name, program)
        begin = engine_mod._LoopPhases.begin

        def recording_begin(ph, name, *a, **kw):
            if ph is engine._ph and name.endswith("_fetch"):
                note("fetch", name, dict(engine._stats))
            return begin(ph, name, *a, **kw)
        monkeypatch.setattr(engine_mod._LoopPhases, "begin", recording_begin)
        self._note = note

    def submit(self, tag, prompt, **params):
        """Before ``engine.start()``: the request's queue is tapped before
        the loop can reach it."""
        req = self.engine.submit(prompt, SamplingParams(**params))
        put = req.out_queue.put

        def recording_put(item):
            self._note("put", tag, item)
            put(item)
        req.out_queue.put = recording_put
        return req

    def check_order(self):
        """No put before the first dispatch that follows the fetch its
        tokens came back with (unless the engine never dispatched again),
        and every token replayed before a fetch has been put by then."""
        kinds = [e[0] for e in self.events]
        dispatches = [k for k, kind in enumerate(kinds) if kind == "dispatch"]
        fetch, token_puts, behind = None, 0, 0
        for k, (kind, _, value, _) in enumerate(self.events):
            if kind == "fetch":
                fetch = k
                # what was replayed before this fetch has all been put
                assert token_puts == value["tokens_generated"], (k, value)
            elif kind == "put":
                assert fetch is not None, "a put before any fetch"
                if any(fetch < d < k for d in dispatches):
                    behind += 1
                else:  # the engine went idle: it dispatched nothing more
                    assert not any(d > k for d in dispatches), self.events[k]
                token_puts += value is not None
        assert behind
        return behind


# -- (a) what comes off the queue --------------------------------------------

@pytest.fixture(scope="module")
def mix(dense, full):
    """One engine, four requests at once that end in four ways, and what
    each should receive."""
    # a stop token whose FIRST occurrence is inside a burst (the prefill
    # yields token 0, the bursts of 8 tokens 1-8, 9-16, ...)
    stop_at = next(i for i in range(2, 16) if full[i] not in full[:i]
                   and (i - 1) % 8 not in (0, 7))
    want = {"stop_mid_burst": (dict(max_tokens=40,
                                    stop_token_ids=(full[stop_at],)),
                               full[:stop_at]),
            "max_tokens_mid_burst": (dict(max_tokens=12), full[:12]),
            "max_tokens_1": (dict(max_tokens=1), full[:1]),
            "runs_on": (dict(max_tokens=40), full)}
    engine = _engine(dense)
    reqs = {k: engine.submit(PROMPT, SamplingParams(**p))
            for k, (p, _) in want.items()}
    engine.start()
    try:
        got = {k: _items(r) for k, r in reqs.items()}
    finally:
        engine.stop()
    return got, {k: toks + [None] for k, (_, toks) in want.items()}, \
        engine.stats()


@pytest.mark.parametrize("case", ["stop_mid_burst", "max_tokens_mid_burst",
                                  "max_tokens_1", "runs_on"])
def test_a_request_receives_its_tokens_then_the_terminator(mix, case):
    got, want, stats = mix
    assert got[case] == want[case]
    assert all(type(t) is int for t in got[case][:-1])
    # what was generated was delivered, and nothing is left behind
    assert stats["tokens_generated"] == sum(len(v) - 1 for v in got.values())


def test_a_preempted_request_receives_each_token_once(dense, full):
    """A pool too small for its requests preempts and resumes them: the
    tokens delivered before the preemption are not delivered again, and
    the stream is the solo run's."""
    engine = _engine(dense)
    engine.cfg.num_pages = 16  # 15 allocatable; a request grows to 6
    engine.allocator = PageAllocator(16)
    reqs = [engine.submit(PROMPT, SamplingParams(max_tokens=40))
            for _ in range(4)]
    engine.start()
    try:
        got = [_items(r) for r in reqs]
    finally:
        engine.stop()
    assert engine.stats()["preempted"] >= 1
    assert got == [full + [None]] * 4


def test_a_block_diffusion_request_receives_its_blocks(routed, monkeypatch):
    params, cfg = routed
    prompts = block_prompts((7, 18))
    cands, _ = sdar_reference.greedy(file_config(cfg), params, prompts, 14, 96)
    want = [[c[0] for c in row] for row in cands]
    engine = _engine(routed, max_seq_len=128, prefill_buckets=(16, 32, 64))
    rec = Recorder(monkeypatch, engine)
    # 14 is not a whole number of blocks: max_tokens cuts inside one
    reqs = [rec.submit(k, p, max_tokens=14) for k, p in enumerate(prompts)]
    engine.start()
    try:
        got = [_items(r) for r in reqs]
    finally:
        engine.stop()
    assert got == [w + [None] for w in want]
    assert {e[1] for e in rec.events if e[0] == "dispatch"} >= {"block_step"}
    rec.check_order()


def test_a_loop_exception_follows_the_tokens_already_replayed(
        dense, full, monkeypatch):
    """The third burst fails as it is dispatched: the request receives the
    two bursts replayed before it (the second still pending then), the
    error, the terminator; and the engine serves the next request."""
    calls = []
    step = lm.decode_step_greedy_chained

    def failing(*a, **kw):
        calls.append(1)
        if len(calls) == 17:
            raise RuntimeError("the device fell over")
        return step(*a, **kw)
    monkeypatch.setattr(lm, "decode_step_greedy_chained", failing)
    engine = _engine(dense)
    req = engine.submit(PROMPT, SamplingParams(max_tokens=40))
    engine.start()
    try:
        got = _items(req)
        assert got[:17] == full[:17]
        assert isinstance(got[17], RuntimeError) and got[18:] == [None]
        assert engine.generate(PROMPT, SamplingParams(max_tokens=5)) \
            == full[:5]
    finally:
        engine.stop()


# -- (b) when it is put -------------------------------------------------------

@pytest.mark.parametrize("temperature", [0.0, 0.7],
                         ids=["greedy_bursts", "sampled_steps"])
def test_puts_follow_the_next_dispatch_and_precede_the_next_fetch(
        dense, monkeypatch, temperature):
    engine = _engine(dense, max_slots=2)
    rec = Recorder(monkeypatch, engine)
    # two slots and three requests: the third is admitted (a prefill is
    # dispatched) when the first ends, between two bursts
    reqs = [rec.submit(k, PROMPT + [k], max_tokens=n,
                       temperature=temperature, seed=k)
            for k, n in enumerate((11, 30, 21))]
    engine.start()
    try:
        got = [_items(r) for r in reqs]
    finally:
        engine.stop()
    assert [len(g) - 1 for g in got] == [11, 30, 21]
    behind = rec.check_order()
    programs = {e[1] for e in rec.events if e[0] == "dispatch"}
    # (the third prompt shares a boundary page: a copy and a suffix prefill)
    assert programs - {"prefill_with_prefix"} == {
        "prefill", "decode_step_greedy_chained" if temperature == 0
        else "decode_step"}
    # the first token of a prefill takes the same road: it is put behind
    # the dispatch that follows its prefill_fetch
    first_put = next(k for k, e in enumerate(rec.events) if e[0] == "put")
    assert [e[0] for e in rec.events[:first_put]].count("dispatch") == 2
    # all but what the idle engine delivered at the end went behind one
    assert behind >= sum(len(g) for g in got) - 9


# -- (c) nothing stranded, (d) the counters ----------------------------------

def test_the_last_request_is_delivered_without_a_dispatch(dense, full,
                                                          monkeypatch):
    engine = _engine(dense)
    rec = Recorder(monkeypatch, engine)
    req = rec.submit("only", PROMPT, max_tokens=20)
    engine.start()
    try:
        assert _items(req, timeout=30) == full[:20] + [None]
        returned = time.monotonic()
        stats = engine.stats()
        assert engine.generate(PROMPT, SamplingParams(max_tokens=1),
                               timeout_s=30) == full[:1]
    finally:
        engine.stop()
    # the last burst's tokens and the terminator left after the last fetch
    # with nothing dispatched behind it, at once
    last_fetch = max(k for k, e in enumerate(rec.events) if e[0] == "fetch"
                     and e[3] < returned)
    tail = [e for e in rec.events[last_fetch + 1:] if e[3] <= returned]
    assert {e[0] for e in tail} == {"put"} and tail[-1][2] is None
    assert returned - rec.events[last_fetch][3] < 1.0
    # in the steady run every delivery went behind a dispatch; the last one
    # could not
    at_dispatch = [e[2] for e in rec.events
                   if e[0] == "dispatch" and e[3] < returned]
    assert all(c["deliveries"] == c["deliveries_behind_dispatch"]
               for c in at_dispatch)
    assert at_dispatch[-1]["deliveries"] >= 2
    assert stats["deliveries"] == stats["deliveries_behind_dispatch"] + 1
    assert stats["tokens_generated"] == 20


def test_a_greedy_burst_is_its_steps_and_one_fetch(dense, full, monkeypatch):
    """Between two fetches a greedy burst launches its steps and nothing
    else of llm/model.py's, every one the chained program, and what comes
    back from the device is ONE array; ``stats()`` says the same."""
    engine = _engine(dense)
    rec = Recorder(monkeypatch, engine)

    class Numpy:  # the engine's numpy, noting what it takes off the device
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *a, **kw):
            if isinstance(x, jax.Array):
                rec._note("get", "np.asarray", x.shape)
            return np.asarray(x, *a, **kw)

    def device_get(x):
        rec._note("get", "jax.device_get", len(jax.tree.leaves(x)))
        return get(x)
    get = jax.device_get
    monkeypatch.setattr(engine_mod, "np", Numpy())
    monkeypatch.setattr(jax, "device_get", device_get)
    req = rec.submit("only", PROMPT, max_tokens=20)
    engine.start()
    try:
        assert _items(req, timeout=60) == full[:20] + [None]
    finally:
        engine.stop()
    events = [e for e in rec.events if e[0] != "put"]
    fetches = [k for k, e in enumerate(events) if e[0] == "fetch"]
    bursts = []
    for k, nxt in zip(fetches, fetches[1:] + [len(events)]):
        if events[k][1] != engine_mod.P_DECODE_FETCH:
            continue
        # what ran since the fetch before, and what came back after this one
        before = max(j for j in fetches if j < k)
        launched = [e[1] for e in events[before + 1:k]
                    if e[0] == "dispatch"]
        assert set(launched) == {"decode_step_greedy_chained"}, launched
        got = [e for e in events[k + 1:nxt] if e[0] == "get"]
        assert [e[1] for e in got] == ["np.asarray"], got
        assert got[0][2][0] == lm.BURST_ROWS
        bursts.append(len(launched))
    assert bursts == [8, 8, 8]  # 19 tokens after the prefill's
    stats = engine.stats()
    assert stats["decode_programs"] == stats["decode_steps"] == sum(bursts)
    assert stats["decode_fetches"] == len(bursts)


def test_stop_delivers_what_the_last_replay_left(dense):
    engine = _engine(dense)
    req = engine.submit(PROMPT, SamplingParams(max_tokens=240))
    engine.start()
    got = [req.out_queue.get(timeout=60) for _ in range(10)]
    engine.stop()
    assert not engine._thread.is_alive() and engine._undelivered == []
    while True:
        try:
            got.append(req.out_queue.get_nowait())
        except queue.Empty:
            break
    # cut mid-stream: every token the engine counted is with the caller,
    # and no terminator
    assert None not in got and 10 <= len(got) < 240
    assert len(got) == req.emitted == engine.stats()["tokens_generated"]
