"""The engine-loop readers (``trace/host_phases.py``): the idle split and the
clock skew on synthetic planes, the queue-wait split and the host costs on
synthetic spans, the extraction on a small trace recorded on the chip with
the loop's annotations in it, and a CPU rehearsal of both serving shapes
that yields every metric this reader feeds."""

import json
import os

import pytest

from benchmarks import common
from benchmarks.tests import test_harness
from benchmarks.trace import host_phases as hp

RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "loop_phases.xplane.pb")
BOTH = ("idle_admit_host_share", "idle_decode_host_share",
        "idle_no_work_share", "idle_unattributed_share",
        "decode_host_ms_per_step", "engine_loop_longest_phase_ms",
        "trace_clock_skew_ms")
OPEN_ONLY = ("queue_wait_decode_share", "queue_wait_prefill_share",
             "prefill_host_ms_p50")


def _ann(name, it, s, e):
    return [name, it, s, e]


def test_idle_shares_sum_to_the_idle_share():
    # device busy 0-4, 5-9, 9.5-10 of a slice 0-10 s: idle 4-5 and 9-9.5
    extracted = {"busy": [[0.0, 4.0], [5.0, 9.0], [9.5, 10.0]],
                 "annotations": [
        _ann("decode_fetch", 1, 0.0, 4.05),    # host awaits the device
        _ann("decode_emit", 1, 4.05, 4.30),    # tokens out: decode host
        _ann("admit", 2, 4.30, 4.40),
        _ann("prefill_host", 2, 4.40, 4.90),   # the page_rows loop
        _ann("prefill_dispatch", 2, 4.90, 5.2),
        _ann("prefill_fetch", 2, 5.2, 9.0),
        _ann("idle", 3, 9.0, 9.25)]}           # then nothing, unannotated
    split = hp.idle_split(extracted, 0.0, 10.0)
    assert split["idle"] == pytest.approx(15.0)
    assert split["decode_host"] == pytest.approx(2.5)
    assert split["admit_host"] == pytest.approx(6.0)
    assert split["no_work"] == pytest.approx(2.5)
    # 4.0-4.05 under a fetch, 4.9-5.0 under a dispatch, 9.25-9.5 under none
    assert split["unattributed"] == pytest.approx(4.0)
    assert sum(split[k] for k in ("admit_host", "decode_host", "no_work",
                                  "unattributed")) \
        == pytest.approx(split["idle"])
    # a program without the annotations: nothing to say
    assert hp.idle_split({"busy": [[0, 1]], "annotations": []}, 0, 10) is None
    assert hp.intersect([[0, 4], [6, 9]], [[3, 7]]) == [[3, 4], [6, 7]]


def _span(name, s, e, **args):
    return {"name": name, "start_ts": s, "end_ts": e, "trace_id": "t",
            "args": args}


def test_skew_is_recovered_from_shifted_annotations():
    zero, skew = 1000.0, 0.0235  # the wall clock's guess is 23.5 ms late
    annotations, spans = [], []
    for it in range(1, 8):
        t = it * 1.0
        for k, name in enumerate(("admit", "prefill_host", "admit",
                                  "decode_host")):  # admit twice an iteration
            s = t + 0.1 * k
            annotations.append(_ann(name, it, s, s + 0.05))
            spans.append(_span("llm.loop." + name, zero + skew + s,
                               zero + skew + s + 0.05, it=it))
    spans.append(_span("llm.queue", zero, zero + 3.0, request_id="r"))
    spans.append(_span("llm.loop", zero + 1, zero + 2, it=1))
    # the session's first iteration lost its first annotation: left out
    got = hp.clock_skew_ms({"annotations": annotations[1:]}, spans, zero)
    assert got == pytest.approx(23.5, abs=1e-6)
    assert hp.clock_skew_ms({"annotations": []}, spans, zero) is None
    assert hp.clock_skew_ms({"annotations": annotations}, [], zero) is None


def _ctx(spans, **kw):
    return {"window": {"t0_wall": 100.0}, "seconds": 50.0, "spans": spans,
            "counters": {"decode_steps": 10}, "notes": [], **kw}


def test_queue_wait_is_split_by_what_the_thread_was_doing():
    spans = [
        # request a waits 100-101: 0.6 s of a burst, 0.3 s of b's prefill
        _span("llm.queue", 100.0, 101.0, request_id="a"),
        _span("llm.loop.decode_fetch", 99.8, 100.6, it=1),
        _span("llm.loop.prefill_dispatch", 100.6, 100.9, it=2,
              request_id="b"),
        _span("llm.loop.admit", 100.9, 101.0, it=2, request_id="a",
              outcome="admitted"),
        # its own prefill lies after its queue span and never counts
        _span("llm.loop.prefill_host", 101.0, 101.02, it=2, request_id="a"),
        _span("llm.loop.prefill_emit", 101.3, 101.31, it=2, request_id="a"),
        _span("llm.loop.decode_host", 101.31, 101.33, it=2),
        _span("llm.loop.decode_dispatch", 101.33, 101.34, it=2),
        _span("llm.loop.decode_emit", 102.2, 102.21, it=2),
        _span("llm.loop.idle", 102.3, 110.0, it=3, iterations=9),
        # ended before the window: not this run's
        _span("llm.queue", 90.0, 99.0, request_id="z"),
    ]
    ctx = _ctx(spans)
    assert hp.queue_wait_share(ctx, "decode_") == pytest.approx(60.0)
    assert hp.queue_wait_share(ctx, "prefill_") == pytest.approx(30.0)
    # host cost of a decode step: host + dispatch + emit, over the counter
    assert hp.decode_host_ms_per_step(ctx) == pytest.approx(
        (0.02 + 0.01 + 0.01) * 1e3 / 10)
    # host cost of a's admission: admit + prefill_host + prefill_emit
    assert hp.admission_host_ms(ctx) == [pytest.approx(130.0)]
    longest = hp.longest_phase(ctx)  # never the idle span
    assert longest["name"] == "llm.loop.decode_fetch"
    reader = common.module("layer_metrics", "engine_loop_longest_phase_ms")
    assert reader.read(ctx) == pytest.approx(800.0)
    assert "llm.loop.decode_fetch 800.0 ms, iteration 1" in ctx["notes"][-1]
    # the parent's program banks no phases: every reader says nothing
    bare = _ctx([s for s in spans if s["name"] == "llm.queue"])
    for name in BOTH + OPEN_ONLY:
        assert common.module("layer_metrics", name).read(bare) is None, name


def test_recorded_chip_trace_carries_the_phases_beside_the_device():
    """A cut (0.53 s: the end of one burst, an admission, the start of the
    next burst) of a traced ``serve_long_output`` run on a TPU v5e (PR 24):
    the engine thread's ``llm.loop.*`` annotations on the host plane, the
    device's ``XLA Modules`` and ``XLA Ops`` lines; event texts truncated
    to 120 characters, operations' stats dropped."""
    ex = hp.extract(RECORDED)
    names = {a[0] for a in ex["annotations"]}
    assert {"admit", "decode_host", "decode_dispatch", "decode_fetch",
            "decode_emit"} <= names
    assert all(a[1] is not None and a[3] >= a[2] for a in ex["annotations"])
    # the thread's phases never overlap
    for a, b in zip(ex["annotations"], ex["annotations"][1:]):
        assert a[3] <= b[2] + 1e-6, (a, b)
    steps = sorted(m[1] for m in ex["modules"]
                   if m[0] == "jit_decode_step_greedy")
    dispatches = [a for a in ex["annotations"] if a[0] == "decode_dispatch"]
    assert steps and dispatches
    fetches = {a[1]: a for a in ex["annotations"] if a[0] == "decode_fetch"}
    for d in dispatches:
        # each dispatch precedes its burst's first run on the device, which
        # starts before the host has finished waiting for the burst
        after = [s for s in steps if s >= d[2]]
        if after and d[1] in fetches:
            assert d[2] <= after[0] <= fetches[d[1]][3]
    # the admission in the cut: its dispatch precedes the prefill's run,
    # which ends before the host has the logits
    (pd,) = [a for a in ex["annotations"] if a[0] == "prefill_dispatch"]
    (pf,) = [a for a in ex["annotations"] if a[0] == "prefill_fetch"]
    (run,) = [m for m in ex["modules"] if m[0] == "jit_prefill"]
    assert pd[2] <= run[1] and run[2] <= pf[3] and pd[1] == pf[1] == 531
    lo, hi = ex["busy"][0][0], ex["busy"][-1][1]
    split = hp.idle_split(ex, lo, hi)
    assert split["idle"] == pytest.approx(4.134, abs=0.01)
    assert split["admit_host"] == pytest.approx(1.226, abs=0.01)
    assert split["decode_host"] == pytest.approx(1.641, abs=0.01)
    assert sum(split[k] for k in ("admit_host", "decode_host", "no_work",
                                  "unattributed")) \
        == pytest.approx(split["idle"])
    assert split["no_work"] == 0.0


@pytest.mark.parametrize("cell,like,names", [
    ("tiny_closed", "serve_long_output", BOTH),
    ("tiny_open", "serve_long_prompt", BOTH + OPEN_ONLY)])
def test_cpu_rehearsal_yields_every_new_metric(tmp_path, cell, like, names):
    root, _ = test_harness._temp_copy(tmp_path)
    b = json.loads((root / "BENCHMARK.json").read_text())
    if cell not in [w["name"] for w in b["workloads"]]:
        b["workloads"].append({"name": cell, "config": "tiny_serve",
                               "traffic": cell, "chips": 1,
                               "why": "test-only"})
    for m in b["end_to_end"] + b["per_layer"]:
        if like in m.get("workloads", []) and cell not in m["workloads"]:
            m["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    done = test_harness._run(
        root, "--workload", cell, "--seed", "2147483659", "--seconds", "4",
        "--trace", "1", env={"BENCH_REHEARSE": "1"})
    assert done.returncode == 0, done.stderr[-3000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["device"]["platform"] == "cpu"  # never taken for a chip's
    got = line["metrics"]
    assert set(names) <= set(got), sorted(set(names) - set(got))
    parts = sum(got[k]["value"] for k in names if k.startswith("idle_"))
    assert parts == pytest.approx(got["device_idle_share"]["value"],
                                  abs=0.2)
    assert got["decode_host_ms_per_step"]["value"] > 0
    assert 0 < got["engine_loop_longest_phase_ms"]["value"] < 2000
    assert abs(got["trace_clock_skew_ms"]["value"]) < 100
    if cell == "tiny_open":
        shares = (got["queue_wait_decode_share"]["value"]
                  + got["queue_wait_prefill_share"]["value"])
        assert 0.0 <= shares <= 100.0
        assert got["prefill_host_ms_p50"]["value"] > 0
    assert "device idle by engine phase" in done.stdout
    assert "longest engine-loop phase: llm.loop." in done.stdout
