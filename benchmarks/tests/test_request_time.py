"""The readers of a request's time by what the engine loop was doing
(``layer_metrics/_request_time.py``) and of the split of the idle label
nobody owned (``layer_metrics/_idle_launch.py``), over hand-made spans and
planes: the values, the slowest-fifth rule, and None on a program whose
spans lack the attributes (the parent of the PR that added them)."""

import pytest

from benchmarks import common
from benchmarks.layer_metrics import _idle_launch, _request_time

SPAN_METRICS = ("engine_tpot_p90_ms", "tpot_tail_step_ms",
                "tpot_tail_other_prefill_ms", "tpot_tail_host_ms",
                "admission_p90_ms", "admission_between_chunks_share",
                "entry_delivery_p50_ms")


def _span(name, s, e, trace="t", **args):
    return {"name": name, "start_ts": s, "end_ts": e, "trace_id": trace,
            "args": args}


def _five(step=0.0, own=0.0, other=0.0, host=0.0, idle=0.0):
    return dict(zip(_request_time.FIVE, (step, own, other, host, idle)))


def _ctx(spans, records=()):
    return {"window": {"t0_wall": 100.0}, "seconds": 50.0, "spans": spans,
            "notes": [], "schedule_mode": "open", "records": list(records)}


def _read(name, ctx):
    return common.module("layer_metrics", name).read(ctx)


def _decodes():
    """Ten requests of 11 tokens each: request i takes 10 + i ms a token,
    of which 10 in steps, i behind other prompts' chunks... and the two
    slowest also 1 ms of host and 0.5 idle a token."""
    out = []
    for i in range(10):
        slow = i >= 8
        per = 0.010 + 0.001 * i + (0.0015 if slow else 0.0)
        out.append(_span(
            "llm.decode", 101.0, 101.0 + 10 * per, trace=f"r{i}", tokens=11,
            **_five(step=0.1, other=0.01 * i,
                    host=0.01 if slow else 0.0,
                    idle=0.004 if slow else 0.0,
                    own=0.001 if slow else 0.0)))
    return out


def test_a_tail_token_is_the_slowest_fifth_and_its_parts_sum():
    spans = _decodes() + [
        # one token: no time a token; ended before the window: not this run's
        _span("llm.decode", 101.0, 101.0, tokens=1, **_five()),
        _span("llm.decode", 90.0, 99.0, tokens=11, **_five(step=9.0))]
    ctx = _ctx(spans)
    # nearest rank over ten: the p90 is the ninth, the p80 the eighth
    assert _read("engine_tpot_p90_ms", ctx) == pytest.approx(19.5)
    tail = _request_time.tail(ctx)
    assert (tail["requests"], tail["of"]) == (3, 10)  # at or above 17 ms
    assert _read("tpot_tail_step_ms", ctx) == pytest.approx(10.0)
    assert _read("tpot_tail_other_prefill_ms", ctx) == pytest.approx(
        (7 + 8 + 9) / 3)
    # host + idle + the request's own prefill, two of the three requests
    assert _read("tpot_tail_host_ms", ctx) == pytest.approx(1.5 * 2 / 3)
    assert tail["ms_a_token"] == pytest.approx((17 + 19.5 + 20.5) / 3)
    assert (tail["step"] + tail["other_prefill"] + tail["host"]
            == pytest.approx(tail["ms_a_token"]))
    notes = [n for n in ctx["notes"] if n.startswith("a tail token")]
    assert len(notes) == 1 and "slowest 3 of 10 requests" in notes[0]


def test_an_admission_is_one_span_however_many_chunks():
    spans = [
        # three chunks of 80 ms with two bursts of 100 ms between them
        _span("llm.queue", 100.5, 101.0, trace="a", wait_s=0.5,
              **_five(step=0.4, other=0.1)),
        _span("llm.admission", 101.0, 101.44, trace="a", chunks=3,
              tokens=6000, resumed=False, **_five(step=0.2, own=0.24)),
        _span("llm.prefill", 101.0, 101.08, trace="a"),
        # one program
        _span("llm.queue", 102.0, 102.02, trace="b", wait_s=0.02,
              **_five(host=0.02)),
        _span("llm.admission", 102.02, 102.08, trace="b", chunks=1,
              tokens=700, resumed=False, **_five(own=0.06)),
        # its admission is still open at the window's end: left out
        _span("llm.queue", 149.0, 149.5, trace="c", wait_s=0.5,
              **_five(step=0.5))]
    records = [{"due": 1.0, "sent": 1.0, "first": 1.0 + ttft, "ok": True}
               for ttft in (0.97, 0.11)] + [
        {"due": 60.0, "sent": 60.0, "first": 61.0, "ok": True}]
    ctx = _ctx(spans, records)
    assert _read("admission_p90_ms", ctx) == pytest.approx(440.0)
    assert _read("admission_between_chunks_share", ctx) == pytest.approx(
        100.0 * 0.2 / 0.5)
    # medians: client 110 ms (nearest rank of two), engine 80 ms (b; a
    # took 940 and c, whose admission has not ended, is not counted)
    assert _read("entry_delivery_p50_ms", ctx) == pytest.approx(30.0)


def test_spans_without_the_attributes_say_nothing():
    """The parent banks ``llm.queue`` and ``llm.decode`` without the five
    and no ``llm.admission``: no reader raises, each returns None."""
    spans = [_span("llm.queue", 100.5, 101.0, wait_s=0.5),
             _span("llm.prefill", 101.0, 101.1, tokens=700, prefix_len=0),
             _span("llm.decode", 101.1, 103.0, tokens=100, preempts=0)]
    records = [{"due": 1.0, "sent": 1.0, "first": 1.2, "ok": True}]
    for ctx in (_ctx(spans, records), _ctx([], records), _ctx([])):
        for name in SPAN_METRICS:
            assert _read(name, ctx) is None, name
        assert ctx["notes"] == []
    ctx = {"notes": []}  # and no trace, no planes
    assert _read("idle_dispatch_share", ctx) is None
    assert _read("idle_fetch_share", dict(ctx)) is None


def test_the_unowned_idle_is_split_by_dispatch_and_fetch():
    # test_host_phases' slice: device busy 0-4, 5-9, 9.5-10 of 0-10 s
    extracted = {"busy": [[0.0, 4.0], [5.0, 9.0], [9.5, 10.0]],
                 "annotations": [
        ["decode_fetch", 1, 0.0, 4.05], ["decode_emit", 1, 4.05, 4.30],
        ["admit", 2, 4.30, 4.40], ["prefill_host", 2, 4.40, 4.90],
        ["prefill_dispatch", 2, 4.90, 5.2], ["prefill_fetch", 2, 5.2, 9.0],
        ["idle", 3, 9.0, 9.25]]}
    # 4.0-4.05 under a fetch, 4.9-5.0 under a dispatch, 9.25-9.5 under none
    assert _idle_launch.split(extracted, 0.0, 10.0) == {
        "dispatch": pytest.approx(1.0), "fetch": pytest.approx(0.5)}
    assert _idle_launch.split({"busy": [], "annotations": []}, 0, 10) is None
    ctx = {"notes": [], "_host_phase_planes": extracted,
           "device_trace": {"t_lo_s": 0.0, "t_hi_s": 10.0}}
    assert _read("idle_dispatch_share", ctx) == pytest.approx(1.0)
    assert _read("idle_fetch_share", ctx) == pytest.approx(0.5)
    assert _read("idle_unattributed_share", ctx) == pytest.approx(4.0)
    note, = [n for n in ctx["notes"] if n.startswith("idle_unattributed")]
    assert note.endswith("under no annotation 2.500")


def test_cpu_rehearsal_yields_every_new_metric_and_the_parts_sum(tmp_path):
    """The tiny open-loop cell through the real runner, on the CPU: the
    engine's spans carry the five, every new reader finds them, and the
    three parts of a tail token sum to its engine-side time."""
    import json

    from benchmarks.tests import test_harness

    root, _ = test_harness._temp_copy(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    b["workloads"].append({"name": "tiny_open", "config": "tiny_serve",
                           "traffic": "tiny_open", "chips": 1,
                           "why": "test-only"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "serve_long_prompt" in m.get("workloads", []):
            m["workloads"].append("tiny_open")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    done = test_harness._run(
        root, "--workload", "tiny_open", "--seed", "2147483761",
        "--seconds", "4", "--trace", "1", env={"BENCH_REHEARSE": "1"})
    assert done.returncode == 0, done.stderr[-3000:]
    got = json.loads(done.stdout.strip().splitlines()[-1])["metrics"]
    names = SPAN_METRICS + ("idle_dispatch_share", "idle_fetch_share")
    assert set(names) <= set(got), sorted(set(names) - set(got))
    note, = [line for line in done.stdout.splitlines()
             if line.startswith("# a tail token by what")]
    total = float(note.split("window): ")[1].split(" = ")[0])
    assert total == pytest.approx(sum(
        got[k]["value"] for k in ("tpot_tail_step_ms", "tpot_tail_host_ms",
                                  "tpot_tail_other_prefill_ms")), abs=0.05)
    assert got["engine_tpot_p90_ms"]["value"] > 0
    assert 0.0 <= got["admission_between_chunks_share"]["value"] <= 100.0
    assert (got["idle_dispatch_share"]["value"]
            + got["idle_fetch_share"]["value"]
            <= got["idle_unattributed_share"]["value"] + 1e-6)
    assert "# idle_unattributed_share" in done.stdout
