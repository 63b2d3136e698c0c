"""Per-request serving anatomy: router→engine trace spans, exemplar-linked
histograms, SLO burn attribution.

Covers the serving trace plane end to end: trace context propagates
through ``handle.options(routing_hint=...)`` into the replica and engine
(one connected tree), the P/D prefill→decode handoff links spans across
two engines, exemplar trace ids survive the Histogram → metrics_push →
TSDB pipeline (and the p99 picker answers with them), the
``RTPU_TRACE_SAMPLE`` head sampler gates serving roots, preemption events
carry request identity, and ``attribute_burn`` decomposes banked spans
into phase shares with a dominant-phase verdict.
"""

import collections
import time
import types

import pytest


# ---------------------------------------------------------------------------
# exemplars: Histogram -> snapshot -> TSDB -> quantile-walk picker


def _hist_snapshot_doc(snap):
    """Wrap one metric snapshot in the minimal metrics_snapshot shape
    TSDB.ingest consumes."""
    return {"runtime": {"node_id": b"\x01" * 16},
            "app": [[snap]], "app_sources": ["w1"]}


def test_exemplar_survives_push_into_tsdb():
    from ray_tpu._private.tsdb import TSDB
    from ray_tpu.util.metrics import Histogram

    h = Histogram("t_exemplar_lat_s", "test latency",
                  boundaries=(0.01, 0.1, 1.0))
    tsdb = TSDB()
    # two scrapes so the window holds a real delta (first point is the
    # counter baseline, as in the sampler's steady state)
    h.observe(0.005, exemplar="trace-fast")
    h.observe(0.5, exemplar="trace-slow")
    tsdb.ingest(_hist_snapshot_doc(h._snapshot()), ts=50.0)
    h.observe(0.004, exemplar="trace-fast")
    h.observe(0.5, exemplar="trace-slow")
    snap = h._snapshot()
    assert snap.get("exemplars"), snap
    tsdb.ingest(_hist_snapshot_doc(snap), ts=100.0)
    series = tsdb.query("t_exemplar_lat_s", window_s=60.0, now=100.0)
    assert series and series[0]["exemplars"], series
    banked = series[0]["exemplars"]
    assert "trace-slow" in banked.values(), banked
    # the p99 of this window sits in the 0.5 observation's bucket: the
    # picker must answer with that request's trace id
    assert tsdb.exemplar("t_exemplar_lat_s", 0.99, 60.0,
                         now=100.0) == "trace-slow"
    # p01 walks to the fast bucket
    assert tsdb.exemplar("t_exemplar_lat_s", 0.01, 60.0,
                         now=100.0) == "trace-fast"


def test_exemplar_ambient_pickup_from_trace_context():
    """An observe() inside a traced request links the bucket without the
    call site threading ids."""
    from ray_tpu.util import tracing
    from ray_tpu.util.metrics import Histogram

    h = Histogram("t_ambient_lat_s", "test latency")
    tracing.enable_tracing()
    try:
        with tracing.trace_span("req") as sp:
            h.observe(0.02)
    finally:
        tracing.disable_tracing()
    snap = h._snapshot()
    assert sp is not None
    banked = snap.get("exemplars") or {}
    assert any(sp.trace_id in by_bucket.values()
               for by_bucket in banked.values()), snap


# ---------------------------------------------------------------------------
# RTPU_TRACE_SAMPLE head sampling


def test_trace_sample_flag_gates_serving_roots(monkeypatch):
    from ray_tpu.util import tracing

    tracing.disable_tracing()
    monkeypatch.setenv("RTPU_TRACE_SAMPLE", "0")
    with tracing.serving_span("openai.request", path="/v1/x") as sp:
        assert sp is None
        assert tracing.current_context() is None
    monkeypatch.setenv("RTPU_TRACE_SAMPLE", "1.0")
    with tracing.serving_span("openai.request", path="/v1/x") as sp:
        # sampled: a root is minted even with tracing globally off, and
        # nested spans inherit its context end to end
        assert sp is not None
        ctx = tracing.current_context()
        assert ctx is not None and ctx[0] == sp.trace_id
        with tracing.trace_span("nested") as child:
            assert child is not None
            assert child.trace_id == sp.trace_id
    assert tracing.current_context() is None


def test_sampled_out_request_still_serves(monkeypatch):
    """A sampled-out request must not lose the response path — only the
    span."""
    from ray_tpu.util import tracing

    tracing.disable_tracing()
    monkeypatch.setenv("RTPU_TRACE_SAMPLE", "0")
    with tracing.serving_span("pd.request") as sp:
        out = {"ok": True}
    assert sp is None and out["ok"]


# ---------------------------------------------------------------------------
# preemption carries request identity


def test_preempt_event_carries_request_identity(monkeypatch):
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.util import events as events_mod

    emitted = {}

    def fake_emit(kind, message="", severity="info", data=None,
                  trace_id=None, **kw):
        emitted.update(kind=kind, message=message, data=data,
                       trace_id=trace_id)

    monkeypatch.setattr(events_mod, "emit", fake_emit)

    spans = []
    req = engine_mod._Request(
        request_id="req-abc123", prompt_tokens=[1, 2, 3],
        params=engine_mod.SamplingParams(max_tokens=4))
    req.trace_ctx = ("t" * 32, "p" * 16)
    req.produced = 2
    slot = types.SimpleNamespace(request=req, generated=[7, 8],
                                 num_tokens=5, pages=[1, 2])
    fake = types.SimpleNamespace(
        _register_blocks=lambda seq, pages: None,
        allocator=types.SimpleNamespace(free=lambda pages: None),
        _free_pages=lambda s: None,  # (pages of either kind, since PR 46)
        _slots=[object()],
        _stats=collections.defaultdict(int),
        _m={"preempted": types.SimpleNamespace(inc=lambda *a, **k: None)},
        _span=lambda r, name, t0, t1, ok=True, **attrs:
            spans.append((name, ok, attrs)),
        _waiting=types.SimpleNamespace(queue=collections.deque()),
        # (its second wait begins at a reading of the loop's sums: none of
        # a loop that is not sampled, since ISSUE 51)
        _ph=types.SimpleNamespace(reading=lambda r: None),
    )
    engine_mod.LLMEngine._preempt(fake, 0, slot)

    assert emitted["kind"] == "llm.preempt"
    assert emitted["data"]["request_id"] == "req-abc123"
    assert "req-abc123" in emitted["message"]
    assert emitted["trace_id"] == "t" * 32
    assert req.preempts == 1
    assert spans and spans[0][0] == "llm.preempt" and spans[0][1] is False
    assert fake._waiting.queue[0] is req  # requeued at the front


# ---------------------------------------------------------------------------
# burn attribution (pure function over banked spans)


def _mk_span(trace_id, name, dur):
    return {"trace_id": trace_id, "name": name, "start_ts": 0.0,
            "end_ts": dur, "run_s": dur}


def test_attribute_burn_phase_shares_and_verdict():
    from ray_tpu._private import slo as slo_mod

    spans = [
        _mk_span("t1", "llm.queue", 0.1),
        _mk_span("t1", "llm.kv_pull", 0.05),
        _mk_span("t1", "llm.prefill", 0.6),
        _mk_span("t1", "llm.decode", 0.25),
        _mk_span("t2", "llm.queue", 0.02),
        _mk_span("t2", "llm.prefill", 0.9),
        _mk_span("t2", "llm.request", 99.0),  # umbrella: not a phase
    ]
    attr = slo_mod.attribute_burn(spans)
    assert attr is not None
    assert attr["verdict"] == "cold_prefill"
    assert abs(sum(attr["phases"].values()) - 1.0) < 0.01, attr
    assert attr["phases"]["prefill"] > attr["phases"]["decode"]
    assert attr["traces"] == 2
    # exemplars ranked by pre-decode time: t2 (0.92) before t1 (0.75)
    assert attr["exemplar_trace_ids"] == ["t2", "t1"]


def test_attribute_burn_no_phase_spans():
    from ray_tpu._private import slo as slo_mod

    assert slo_mod.attribute_burn([]) is None
    assert slo_mod.attribute_burn(
        [_mk_span("t1", "serve.route", 1.0)]) is None


def test_slo_status_carries_attribution():
    from ray_tpu._private import slo as slo_mod

    eng = slo_mod.SLOEngine(
        rules=[slo_mod.Rule("r1: p90(llm_ttft_s, 15s) < 0.1")])
    attr = {"phases": {"queue": 1.0}, "verdict": "queue_bound",
            "exemplar_trace_ids": ["tx"], "traces": 1}
    eng.note_attribution("r1", attr)
    row = eng.status()["rules"][0]
    assert row["attribution"] == attr


# ---------------------------------------------------------------------------
# cluster tests: propagation across the routed handle path and P/D linking

jax = pytest.importorskip("jax")

from ray_tpu.llm.engine import EngineConfig, SamplingParams  # noqa: E402
from ray_tpu.models import llama  # noqa: E402


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, dtype="float32", remat=False)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return params, cfg


def test_trace_propagates_through_routing_hint(ray_cluster):
    """handle.options(routing_hint=...).remote() must carry the caller's
    trace context into the replica, and the decision span must record the
    router's policy/outcome — one connected tree."""
    import ray_tpu.serve as serve
    from ray_tpu.util import state, tracing

    tracing.enable_tracing()

    @serve.deployment(num_replicas=2, request_router_policy="prefix_aware")
    class Echo:
        def __call__(self, x):
            from ray_tpu.util import tracing as t

            return {"x": x, "ctx": t.current_context()}

    serve.run(Echo.bind(), name="trace_app", route_prefix="/trace-app")
    try:
        with tracing.trace_span("client-root") as root:
            out = serve.get_app_handle("trace_app").options(
                routing_hint="prefix-T").remote(7).result(timeout_s=60)
        assert out["x"] == 7
        # the replica saw THIS trace, not a fresh one
        assert out["ctx"] is not None and out["ctx"][0] == root.trace_id

        deadline = time.monotonic() + 20
        names, trace = set(), None
        while time.monotonic() < deadline:
            trace = state.get_trace(root.trace_id)
            names = {sp["name"] for sp in trace["spans"]}
            if {"serve.route", "replica.handle"} <= names:
                break
            time.sleep(0.25)
        assert {"client-root", "serve.route", "replica.handle"} <= names, \
            names
        assert len(trace["tree"]) == 1, [t["name"] for t in trace["tree"]]
        assert trace["tree"][0]["name"] == "client-root"
        route = next(sp for sp in trace["spans"]
                     if sp["name"] == "serve.route")
        args = route.get("args") or {}
        assert args.get("policy") == "prefix_aware", args
        assert args.get("hinted") is True, args
        assert args.get("replica"), args
        assert args.get("outcome"), args
    finally:
        serve.delete("trace_app")
        tracing.disable_tracing()


def test_pd_handoff_links_decode_under_prefill(tiny_model, monkeypatch):
    """The decode hop re-establishes the prefill span as its parent: the
    cross-engine handoff renders as one connected tree."""
    from ray_tpu.llm.pd_disagg import DecodeServer, PrefillServer
    from ray_tpu.llm.server import LLMConfig
    from ray_tpu.util import tracing

    params, cfg = tiny_model

    def loader(params=params, cfg=cfg):
        return params, cfg

    recs = []
    orig_record = tracing._record
    monkeypatch.setattr(
        tracing, "_record",
        lambda rec: (recs.append(rec), orig_record(rec))[1])

    llm_config = LLMConfig(
        model_id="tiny-pd-trace", model_loader=loader,
        engine_config=EngineConfig(max_slots=2, num_pages=64, page_size=8,
                                   max_seq_len=256,
                                   prefill_buckets=(16, 32)),
        default_max_tokens=6)
    tracing.enable_tracing()
    ps = ds = None
    try:
        ps = PrefillServer(llm_config)
        ds = DecodeServer(llm_config)
        pre = ps.prefill("hello world", {"max_tokens": 4})
        assert pre.get("trace_id") and pre.get("prefill_span_id"), pre
        out = ds.decode(pre, {"max_tokens": 4})
        assert out["tokens"], out
    finally:
        tracing.disable_tracing()
        if ps is not None:
            ps._engine.stop()
        if ds is not None:
            ds._engine.stop()

    pd_prefill = next(r for r in recs if r["name"] == "pd.prefill")
    pd_decode = next(r for r in recs if r["name"] == "pd.decode")
    assert pd_prefill["trace_id"] == pre["trace_id"]
    assert pd_prefill["span_id"] == pre["prefill_span_id"]
    # the link: decode's span lives in the SAME trace, parented under the
    # prefill span recorded by the other engine
    assert pd_decode["trace_id"] == pre["trace_id"]
    assert pd_decode["parent_id"] == pre["prefill_span_id"]
    assert pd_decode["args"].get("handoff") in ("tier", "host")
    # engine anatomy rode along in the same trace
    engine_names = {r["name"] for r in recs
                    if r["trace_id"] == pre["trace_id"]}
    assert "llm.request" in engine_names, engine_names
