"""Engine-loop anatomy (ISSUE 24): what the scheduler thread was doing.

The loop's phases as banked spans (ordered, disjoint, covering an
iteration; one idle span per idle stretch; a prefill's phases naming their
request; compilations naming the phase they interrupted), the rotation of
the loop's trace id, the stall event, and the off path: with
``RTPU_TRACE_SAMPLE=0`` the loop reaches no span or event code and the
tokens are what they were.

And the other direction (ISSUE 51): what the loop was doing while a
request stood, by class of phase, on the request's own ``llm.queue``,
``llm.admission`` and ``llm.decode`` spans.
"""

import threading
import time

import jax
import pytest

from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.models import llama
from ray_tpu.util import events as events_mod
from ray_tpu.util import tracing

PROMPTS = ([5, 6, 7, 8, 9], list(range(20, 42)))
LOOP_PHASES = {getattr(engine_mod, n) for n in dir(engine_mod)
               if n.startswith("P_")}


def _engine(model):
    params, cfg = model
    return LLMEngine(params, cfg, EngineConfig(
        max_slots=2, num_pages=64, page_size=8, max_seq_len=256,
        prefill_buckets=(16, 32)))


@pytest.fixture(scope="module")
def model():
    # a vocabulary no other test file uses: the programs compile here, so
    # the sampled run has compilations to put on its timeline
    cfg = llama.LlamaConfig(
        vocab_size=131, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, dtype="float32", remat=False)
    return llama.init(cfg, jax.random.PRNGKey(0)), cfg


@pytest.fixture(scope="module")
def sampled_run(model):
    """One sampled engine: two requests with 0.3 s of nothing between
    them.  Returns (records banked by the loop, the tokens, the stats)."""
    recs = []
    mp = pytest.MonkeyPatch()
    mp.setenv("RTPU_TRACE_SAMPLE", "1.0")
    orig = tracing._record
    mp.setattr(tracing, "_record", lambda r: (recs.append(r), orig(r))[1])
    eng = _engine(model)
    try:
        eng.start()
        outs = [eng.generate(PROMPTS[0], SamplingParams(max_tokens=12))]
        time.sleep(0.3)
        outs.append(eng.generate(PROMPTS[1], SamplingParams(max_tokens=20)))
        stats = eng.stats()
    finally:
        eng.stop()
        mp.undo()
    return recs, outs, stats


def _by_iteration(recs):
    its = {}
    for r in recs:
        if r["name"] in LOOP_PHASES and r["name"] != engine_mod.P_IDLE:
            its.setdefault(r["args"]["it"], []).append(r)
    return its


def test_phases_are_ordered_disjoint_and_cover_their_iteration(sampled_run):
    recs, _, _ = sampled_run
    loops = {r["args"]["it"]: r for r in recs
             if r["name"] == engine_mod.S_LOOP}
    its = _by_iteration(recs)
    assert len(loops) >= 4 and set(loops) == set(its)
    for it, phases in its.items():
        loop = loops[it]
        assert all(p["parent_id"] == loop["span_id"]
                   and p["trace_id"] == loop["trace_id"] for p in phases)
        # banked in the order they ran, each starting where the last ended
        for a, b in zip(phases, phases[1:]):
            assert a["end_ts"] <= b["start_ts"] + 1e-9, (a, b)
        covered = sum(p["end_ts"] - p["start_ts"] for p in phases)
        assert abs(covered - (loop["end_ts"] - loop["start_ts"])) < 1e-3
        assert phases[0]["start_ts"] == loop["start_ts"]
        assert phases[-1]["end_ts"] == loop["end_ts"]
    # a decode iteration runs host -> dispatch -> fetch -> emit, and what
    # the burst before it replayed is delivered (a decode_emit of its own)
    # once the burst's first step is dispatched
    last_burst, after = sorted(its)[-2:]
    # (the gauges' refresh, every 0.25 s, may close any working iteration)
    burst = [p for p in its[last_burst] if p["name"] != engine_mod.P_GAUGES]
    names = [p["name"].rsplit(".", 1)[1] for p in burst]
    assert names[-6:] == ["decode_host", "decode_dispatch", "decode_emit",
                          "decode_dispatch", "decode_fetch", "decode_emit"]
    delivery, replay = (burst[k]["args"] for k in (-4, -1))
    assert set(delivery) == {"delivered", "it"} and delivery["delivered"] == 8
    assert replay["slots_released"] == 1 and replay["tokens"] >= 1
    assert "delivered" not in replay
    # the request's last tokens and its terminator leave in the iteration
    # that finds nothing to dispatch, before it ends
    names = [p["name"].rsplit(".", 1)[1] for p in its[after]]
    assert names[:2] == ["admit", "decode_emit"]
    assert its[after][1]["args"] == {
        "delivered": replay["tokens"] + 1, "it": after}
    # every token reached a stream through a delivery, with a terminator a
    # request
    delivered = sum(p["args"].get("delivered", 0)
                    for ps in its.values() for p in ps)
    assert delivered == sum(len(o) for o in sampled_run[1]) + 2


def test_an_idle_stretch_is_one_span(sampled_run):
    recs, _, _ = sampled_run
    first_end = max(r["end_ts"] for r in recs if r["args"].get(
        "request_id") and r["args"]["it"] == min(_by_iteration(recs)))
    # the stretch between the two requests (stop() may cut another, after
    # the last working iteration)
    last_work = max(r["start_ts"] for r in recs
                    if r["name"] == engine_mod.S_LOOP)
    idle = [r for r in recs if r["name"] == engine_mod.P_IDLE
            and first_end <= r["start_ts"] < last_work]
    assert len(idle) == 1, idle
    assert 0.29 <= idle[0]["end_ts"] - idle[0]["start_ts"] < 2.0
    # one span for ~150 sleeps of 2 ms, and it says so
    assert idle[0]["args"]["iterations"] >= 20


def test_a_prefills_phases_name_their_request(sampled_run):
    recs, _, _ = sampled_run
    prefill = [r for r in recs if ".prefill_" in r["name"]]
    ids = {r["args"]["request_id"] for r in prefill}
    assert len(ids) == 2
    for rid in ids:
        mine = [r["name"].rsplit(".", 1)[1] for r in prefill
                if r["args"]["request_id"] == rid]
        assert mine == ["prefill_host", "prefill_dispatch",
                        "prefill_fetch", "prefill_emit"]
    host = next(r for r in prefill if r["name"].endswith("prefill_host"))
    assert host["args"]["bucket"] in (16, 32)
    assert host["args"]["prefix_len"] == 0
    admits = [r["args"] for r in recs if r["name"] == engine_mod.P_ADMIT]
    assert {a["request_id"] for a in admits
            if a["outcome"] == "admitted"} == ids
    assert any(a["outcome"] == "none_waiting" for a in admits)


def test_a_compilation_names_the_phase_it_interrupted(model, sampled_run,
                                                      monkeypatch):
    # through jax.monitoring itself, on a thread that plays the engine's
    recs = []
    monkeypatch.setattr(tracing, "_record", recs.append)
    eng = _engine(model)
    ph = eng._ph
    ph.sampled = True
    req = engine_mod._Request("req-c0ffee", [1, 2, 3], SamplingParams())
    eng._watch_compiles()
    try:
        ph.it = 41
        ph.begin(engine_mod.P_PREFILL_DISPATCH, req)
        jax.monitoring.record_event_duration_secs(
            engine_mod._COMPILE_EVENT, 0.25, fun_name="jit(prefill)")
        jax.monitoring.record_event_duration_secs(
            "/jax/core/compile/jaxpr_trace_duration", 0.1, fun_name="x")
        ph.finish_iteration(True)
    finally:
        engine_mod._sampled_loops.pop(threading.get_ident())
    loop, compile_, phase = recs
    assert (loop["name"], phase["name"]) == (
        engine_mod.S_LOOP, engine_mod.P_PREFILL_DISPATCH)
    assert compile_["name"] == engine_mod.S_COMPILE
    assert compile_["args"] == {
        "seconds": 0.25, "phase": engine_mod.P_PREFILL_DISPATCH,
        "program": "jit(prefill)", "it": 41, "request_id": "req-c0ffee"}
    assert compile_["end_ts"] - compile_["start_ts"] == pytest.approx(0.25)
    assert compile_["parent_id"] == loop["span_id"]
    # another thread's compilation is not this loop's
    t = threading.Thread(
        target=jax.monitoring.record_event_duration_secs,
        args=(engine_mod._COMPILE_EVENT, 0.5))
    t.start()
    t.join(10)
    assert len(recs) == 3 and not ph._done
    # and what the real engine compiled (nothing, if the persistent cache
    # held its programs) interrupted a dispatch or a fetch
    for c in (r for r in sampled_run[0]
              if r["name"] == engine_mod.S_COMPILE):
        assert c["args"]["phase"].rsplit("_", 1)[1] in ("dispatch", "fetch")
        assert c["args"]["it"] >= 1 and c["args"]["seconds"] > 0


def test_off_path_reaches_no_span_code_and_tokens_are_the_same(
        model, sampled_run, monkeypatch):
    _, sampled_outs, _ = sampled_run

    def boom(*a, **k):
        raise AssertionError("the unsampled loop reached span/event code")

    monkeypatch.setenv("RTPU_TRACE_SAMPLE", "0")
    for target, name in ((tracing, "record_span"), (tracing, "_record"),
                         (tracing, "_ensure_flusher"),
                         (tracing, "new_span_id"), (events_mod, "emit")):
        monkeypatch.setattr(target, name, boom)
    before = set(threading.enumerate())
    eng = _engine(model)
    try:
        eng.start()
        outs = [eng.generate(PROMPTS[0], SamplingParams(max_tokens=12))]
        time.sleep(0.05)
        outs.append(eng.generate(PROMPTS[1], SamplingParams(max_tokens=20)))
        started = set(threading.enumerate()) - before
        # the loop itself and nothing else: no heartbeat, flusher or timer
        assert started == {eng._thread}
        assert threading.get_ident() not in engine_mod._sampled_loops
        assert eng._thread.ident not in engine_mod._sampled_loops
    finally:
        eng.stop()
    assert outs == sampled_outs  # greedy, token for token
    assert not eng._ph.sampled and eng._ph._done == []
    assert eng._ph.it > 4


def _iterate(ph, n_phases=7):
    ph.it += 1
    for _ in range(n_phases):
        ph.begin(engine_mod.P_DECODE_FETCH)
    ph.finish_iteration(True)


def test_the_loops_trace_id_rotates_under_the_cap(monkeypatch):
    recs = []
    monkeypatch.setattr(tracing, "_record", recs.append)
    ph = engine_mod._LoopPhases([])
    ph.sampled = True
    for _ in range(600):  # ten minutes of full-occupancy decode
        _iterate(ph, n_phases=13)  # two admissions and a burst
    per_trace = {}
    for r in recs:
        per_trace[r["trace_id"]] = per_trace.get(r["trace_id"], 0) + 1
    assert len(recs) == 600 * 14
    assert len(per_trace) <= 2 and max(per_trace.values()) <= 9_000
    # an iteration's spans stay together, in one trace
    for r in recs:
        if r["name"] != engine_mod.S_LOOP:
            assert r["parent_id"] and r["trace_id"] in per_trace
    # the rotation itself, at a small budget
    t = tracing.LoopTrace(budget=10)
    ids = [t.take(4) for _ in range(5)]
    assert ids[0] == ids[1] != ids[2] == ids[3] != ids[4]


def test_a_phase_stretched_past_two_seconds_is_one_stall_event(monkeypatch):
    now = [100.0]
    emitted = []
    monkeypatch.setattr(engine_mod, "_mono", lambda: now[0])
    monkeypatch.setattr(tracing, "_record", lambda r: None)
    monkeypatch.setattr(events_mod, "emit",
                        lambda kind, **kw: emitted.append((kind, kw)))
    ph = engine_mod._LoopPhases([object()] * 3 + [None])
    ph.sampled = True
    ph.it = 7
    ph.begin(engine_mod.P_DECODE_HOST)
    now[0] += 0.9
    ph.begin(engine_mod.P_DECODE_FETCH)  # a normal burst: no event
    now[0] += 1.9
    ph.begin(engine_mod.P_DECODE_EMIT)
    now[0] += 3.5  # the whole process stood still here
    ph.finish_iteration(True)
    for _ in range(1200):  # 2.4 s with nothing to do is not a stall
        ph.it += 1
        ph.begin(engine_mod.P_ADMIT)
        now[0] += 0.002
        ph.finish_iteration(False)
    assert [k for k, _ in emitted] == ["llm.loop_stall"]
    data = emitted[0][1]["data"]
    assert data == {"phase": engine_mod.P_DECODE_EMIT, "seconds": 3.5,
                    "it": 7, "active_slots": 3}
    # unsampled, the same stretch is not even looked at
    ph.sampled = False
    ph.begin(engine_mod.P_DECODE_EMIT)
    now[0] += 5.0
    ph.end()
    assert len(emitted) == 1


def test_stats_serves_its_counters_without_the_rings(sampled_run, model):
    _, outs, stats = sampled_run
    assert stats["prefills"] == 2 and stats["admitted"] == 2
    assert stats["tokens_generated"] == sum(len(o) for o in outs) == 32
    assert stats["decode_steps"] >= 30
    assert {"active_slots", "free_pages", "waiting", "prefix_cache",
            "resident_pages", "kv_families"} <= set(stats)
    assert not [k for k in stats if k.startswith(("p50_", "p90_"))]
    eng = _engine(model)
    assert not hasattr(eng, "_queue_waits")
    assert not hasattr(eng, "_prefill_times")
    assert eng.stats()["tokens_generated"] == 0  # before any loop ran


# -- a request's time by what the loop was doing (ISSUE 51) ------------------

WAITED = tracing.WAIT_ATTRS
REQUEST_SPANS = ("llm.queue", "llm.admission", "llm.decode")


def _step(eng, between=None):
    """One iteration of the engine's loop on this thread (``between``:
    called after the admission, before the burst)."""
    ph = eng._ph
    ph.it += 1
    admitted = eng._admit()
    if between is not None:
        between()
    stepped = eng._decode_all()
    if not (admitted or stepped):
        eng._deliver(False)
    ph.finish_iteration(True)


def _busy(eng):
    return any(s is not None for s in eng._slots) or eng._waiting.qsize()


@pytest.fixture(scope="module")
def stepped_run(model):
    """A sampled loop stepped by hand, so that who waits behind what is
    known: A decodes; B, a prompt of three chunks, is submitted before a
    burst of A's and admitted between A's bursts; once both are done C is
    preempted mid-answer and resumed; then the loop's own thread serves D,
    submitted from this one.  Returns {request: its spans, oldest first}."""
    recs = []
    mp = pytest.MonkeyPatch()
    mp.setenv("RTPU_TRACE_SAMPLE", "1.0")
    orig = tracing._record
    mp.setattr(tracing, "_record", lambda r: (recs.append(r), orig(r))[1])
    eng = _engine(model)
    eng._ph.sampled = True  # (start() reads the flag; nothing started yet)

    def submit(name, prompt, n):
        with tracing.use_context((name * 32, None)):
            return eng.submit(prompt, SamplingParams(max_tokens=n))

    try:
        submit("a", PROMPTS[0], 48)
        _step(eng, between=lambda: submit("b", list(range(10, 90)), 12))
        while _busy(eng):
            _step(eng)
        submit("c", PROMPTS[1], 30)
        _step(eng)
        _step(eng)
        (i, slot), = [(i, s) for i, s in enumerate(eng._slots) if s]
        eng._preempt(i, slot)
        while _busy(eng):
            _step(eng)
        eng.start()
        with tracing.use_context(("d" * 32, None)):
            eng.generate(PROMPTS[1], SamplingParams(max_tokens=20))
    finally:
        eng.stop()
        mp.undo()
    return {name: [r for r in recs if r["trace_id"] == name * 32]
            for name in "abcd"}


def _named(spans, name):
    return [s for s in spans if s["name"] == name]


def test_the_five_are_on_every_request_span_and_sum_to_its_length(
        stepped_run):
    seen = 0
    for spans in stepped_run.values():
        for s in spans:
            if s["name"] in REQUEST_SPANS:
                five = [s["args"][k] for k in WAITED]
                assert min(five) >= 0.0, s
                assert abs(sum(five) - (s["end_ts"] - s["start_ts"])) \
                    < 1e-3, s
                seen += 1
    assert seen == 4 * 3 + 2  # C's second wait and second admission
    # a request served by the loop's thread, submitted from another:
    # behind nobody, its wait is the loop's idling and its own admit
    queue, = _named(stepped_run["d"], "llm.queue")
    assert queue["args"]["step_s"] == queue["args"]["other_prefill_s"] == 0


def test_a_decoding_request_is_told_whose_prefill_it_stood_behind(
        stepped_run):
    a, b = stepped_run["a"], stepped_run["b"]
    (a_decode,), (b_queue,), (b_adm,) = (
        _named(a, "llm.decode"), _named(b, "llm.queue"),
        _named(b, "llm.admission"))
    # every prefill phase of the loop while A decoded carried B: B's admit
    # (inside its wait) and its programs (its admission, but for the few
    # statements between its first token and the next phase)
    # (A's own: what was left of its last prefill phase at its first token)
    assert a_decode["args"]["own_prefill_s"] < 1e-3
    assert a_decode["args"]["other_prefill_s"] == pytest.approx(
        b_queue["args"]["own_prefill_s"] + b_adm["args"]["own_prefill_s"],
        abs=1e-3)
    assert b_adm["args"]["own_prefill_s"] > 0
    # B was submitted before a burst of A's and waited it out
    assert b_queue["args"]["step_s"] > 0
    assert b_queue["args"]["other_prefill_s"] == 0.0


def test_a_prompt_in_chunks_has_one_admission_with_the_bursts_between(
        stepped_run):
    b = stepped_run["b"]
    adm, = _named(b, "llm.admission")
    chunks = _named(b, "llm.prefill")
    assert [c["args"]["chunk"] for c in chunks] == [0, 1, 2]
    assert adm["args"]["chunks"] == len(chunks) == 3
    assert adm["args"]["tokens"] == 80 and adm["args"]["resumed"] is False
    assert adm["start_ts"] <= chunks[0]["start_ts"] + 1e-3
    assert adm["end_ts"] >= chunks[-1]["end_ts"] - 1e-3
    # A's bursts ran between B's chunks, and that is said on B's span
    assert adm["args"]["step_s"] > 0
    # (a chunk's last phase runs on past its span, to the burst's first;
    # a delivery made behind a chunk's dispatch is the host's)
    assert adm["args"]["own_prefill_s"] + adm["args"]["host_s"] >= sum(
        c["end_ts"] - c["start_ts"] for c in chunks) - 1e-3
    assert adm["args"]["own_prefill_s"] > 10 * adm["args"]["host_s"]
    # it overlaps its chunks' spans: no phase of the SLO burn's attribution
    from ray_tpu._private import slo

    assert "llm.admission" not in slo._PHASE_BY_SPAN
    # a prompt of one program: as long as its prefill
    adm, = _named(stepped_run["a"], "llm.admission")
    prefill, = _named(stepped_run["a"], "llm.prefill")
    assert adm["args"]["chunks"] == 1
    assert adm["end_ts"] - adm["start_ts"] == pytest.approx(
        prefill["end_ts"] - prefill["start_ts"], abs=2e-3)


def test_a_resumed_request_has_a_second_wait_and_admission(stepped_run):
    c = stepped_run["c"]
    names = [s["name"] for s in c if s["name"] != "llm.prefill"]
    assert names == ["llm.queue", "llm.admission", "llm.preempt",
                     "llm.queue", "llm.admission", "llm.decode",
                     "llm.request"]
    first, second = _named(c, "llm.admission")
    assert (first["args"]["resumed"], second["args"]["resumed"]) \
        == (False, True)
    assert [q["args"]["resumed"] for q in _named(c, "llm.queue")] \
        == [False, True]
    preempt, = _named(c, "llm.preempt")
    waited, resumed = _named(c, "llm.queue")[1], second
    assert waited["start_ts"] == pytest.approx(preempt["start_ts"], abs=1e-3)
    assert resumed["start_ts"] == pytest.approx(waited["end_ts"], abs=1e-3)
    # the answer's span runs from the FIRST first token to the end, over
    # the second wait and admission, whose prefill is its own
    decode, = _named(c, "llm.decode")
    assert decode["args"]["preempts"] == 1 and decode["args"]["tokens"] == 30
    assert decode["start_ts"] == pytest.approx(first["end_ts"], abs=1e-3)
    assert decode["args"]["own_prefill_s"] >= second["args"]["own_prefill_s"]
    assert decode["args"]["other_prefill_s"] == 0.0


def test_a_reading_counts_the_phase_that_is_open(monkeypatch):
    now = [100.0]
    monkeypatch.setattr(engine_mod, "_mono", lambda: now[0])
    monkeypatch.setattr(tracing, "_record", lambda r: None)
    ph = engine_mod._LoopPhases([])
    req, other = (engine_mod._Request(rid, [1], SamplingParams())
                  for rid in ("req-a", "req-b"))
    assert ph.reading(req) is None and engine_mod._waited(None, None) == {}
    ph.sampled = True
    r0 = ph.reading(req)
    ph.begin(engine_mod.P_DECODE_FETCH)
    now[0] += 0.5  # the whole process could stand still here
    r1 = ph.reading(req)
    assert engine_mod._waited(r0, r1) == dict(zip(WAITED, (0.5, 0, 0, 0, 0)))
    ph.begin(engine_mod.P_ADMIT)
    now[0] += 0.125
    ph.req = req  # no slot: the loop's own time
    assert engine_mod._waited(r1, ph.reading(req))["host_s"] == 0.125
    ph.vals = ("admitted",)  # ... or the request's, once it is admitted
    assert engine_mod._waited(r1, ph.reading(req))["own_prefill_s"] == 0.125
    ph.begin(engine_mod.P_PREFILL_FETCH, req)
    now[0] += 0.25
    r2 = ph.reading(req)
    assert engine_mod._waited(r1, r2) == dict(
        zip(WAITED, (0, 0.375, 0, 0, 0)))
    assert engine_mod._waited(r1, ph.reading(other)) == dict(
        zip(WAITED, (0, 0, 0.375, 0, 0)))  # another request's prompt
    assert req.own_s == 0.125  # (closed phases only; the reading adds)
    ph.finish_iteration(True)
    now[0] += 0.0625  # between two iterations: the loop's own time
    ph.begin(engine_mod.P_ADMIT)
    ph.finish_iteration(False)
    now[0] += 2.0  # nothing to do, asleep
    r3 = ph.reading(req)
    assert engine_mod._waited(r2, r3) == dict(
        zip(WAITED, (0, 0, 0, 0.0625, 2.0)))
    assert req.own_s == 0.375
    assert sum(engine_mod._waited(r0, r3).values()) == pytest.approx(
        now[0] - 100.0)
