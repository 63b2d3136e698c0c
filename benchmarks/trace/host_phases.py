"""What the engine's scheduler thread was doing, read two ways.

The engine cuts every iteration of its loop into phases
(``llm.loop.admit`` ... ``llm.loop.decode_emit``, ``llm.loop.idle``;
``ray_tpu/llm/engine.py``).  Each phase is a ``jax.profiler.TraceAnnotation``,
so in a traced run it lies on the host plane of the ``.xplane.pb``, ON THE
PROFILER'S CLOCK, beside the device's operations; and, the loop being
sampled in a traced run, each is also a span banked on the wall clock,
which ``ctx["spans"]`` holds.

From the first: device idle time split by the phase that overlaps it.  From
the second: the requests' queue wait split by phase, and the host's cost of
a decode step and of an admission.  From both, matched by (name,
iteration): how far the wall clock's guess at the trace's zero
(``common.trace_zero``) lies from the profiler's own.

The driver never imports JAX, and ``ProfileData`` is JAX: ``planes()`` has a
child process with ``JAX_PLATFORMS=cpu`` parse the file (this module run as
a script) and hand back JSON.  A program without the phases (the parent of
the PR that added them) has neither annotations nor spans: every function
here then returns None and the metric is left out.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

if __name__ == "__main__":  # the child: make the repository importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks import common  # noqa: E402
from benchmarks.trace import reduce  # noqa: E402

PREFIX = "llm.loop."
HOST_PLANE = "/host:CPU"
# device idle goes to the host work that filled it; idle under a phase in
# which the host itself launches or awaits the device (the dispatch and
# fetch phases) is launch latency or the tracer: unattributed
IDLE_CLASSES = {
    "admit_host": ("admit", "prefill_host", "prefill_emit", "hydrate",
                   "gauges"),
    "decode_host": ("decode_host", "decode_emit"),
    "no_work": ("idle",),
}
DECODE_STEP_HOST = ("decode_host", "decode_dispatch", "decode_emit")
ADMISSION_HOST = ("admit", "prefill_host", "prefill_emit")


# -- the child: from the file to JSON ----------------------------------------

def extract(path: str, host_as_device: bool = False) -> dict:
    """The loop's annotations, device 0's busy intervals and its program
    runs, in seconds from the trace's own zero."""
    from jax.profiler import ProfileData

    annotations, ops, modules = [], [], []
    device = None
    for plane in ProfileData.from_file(path).planes:
        m = reduce.DEVICE_PLANE.match(plane.name)
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(PREFIX):
                        it = dict(ev.stats).get("it")
                        annotations.append([
                            ev.name[len(PREFIX):],
                            None if it is None else int(it),
                            ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9])
                    elif host_as_device and "XLA" in line.name:
                        ops.append([ev.start_ns, ev.start_ns
                                    + ev.duration_ns])
        elif m and not host_as_device and (
                device is None or int(m.group(1)) < device):
            lines = {line.name: line for line in plane.lines}
            if reduce.OPS_LINE not in lines:
                continue
            device = int(m.group(1))
            ops = [[ev.start_ns, ev.start_ns + ev.duration_ns]
                   for ev in lines[reduce.OPS_LINE].events]
            modules = [[reduce.module_name(ev.name), ev.start_ns * 1e-9,
                        (ev.start_ns + ev.duration_ns) * 1e-9]
                       for ev in getattr(lines.get(reduce.MODULES_LINE),
                                         "events", ())]
    annotations.sort(key=lambda a: a[2])
    return {"annotations": annotations, "modules": modules,
            "busy": [[s * 1e-9, e * 1e-9] for s, e in reduce.union(ops)]}


def planes(ctx: dict):
    """``extract`` of the run's trace, made once a run by a child process;
    None where there is no trace or it cannot be read (with a note)."""
    if "_host_phase_planes" not in ctx:
        ctx["_host_phase_planes"] = None
        path = (ctx.get("device_trace") or {}).get("xplane")
        if path and os.path.exists(path):
            child = subprocess.run(
                [sys.executable, os.path.abspath(__file__), path,
                 str(int(common.rehearsing()))],
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
                capture_output=True, text=True, timeout=600)
            if child.returncode == 0:
                ctx["_host_phase_planes"] = json.loads(
                    child.stdout.strip().splitlines()[-1])
            else:
                ctx.setdefault("notes", []).append(
                    "host_phases: the trace could not be read: "
                    + child.stderr.strip()[-300:])
    return ctx["_host_phase_planes"]


# -- device idle, split by the phase under it (the profiler's clock) ---------

def intersect(a: list, b: list) -> list:
    """The part of union ``a`` that union ``b`` covers."""
    return reduce.subtract(a, reduce.subtract(a, b))


def idle_split(extracted: dict, lo: float, hi: float):
    """Shares (%) of the slice [lo, hi) in which device 0 was idle, by what
    the engine thread was doing; they sum to the idle share, which is
    taken over EVERY gap.  None without annotations."""
    if not extracted["annotations"] or hi <= lo:
        return None
    idle = reduce.subtract([[lo, hi]], extracted["busy"])
    out, rest = {}, idle
    for cls, names in IDLE_CLASSES.items():
        cover = reduce.union([[s, e] for n, _, s, e
                              in extracted["annotations"] if n in names])
        out[cls] = 100.0 * reduce.total(intersect(idle, cover)) / (hi - lo)
        rest = reduce.subtract(rest, cover)
    out["unattributed"] = 100.0 * reduce.total(rest) / (hi - lo)
    out["idle"] = 100.0 * reduce.total(idle) / (hi - lo)
    return out


def idle_share(ctx: dict, cls: str):
    """One class of ``idle_split`` over the slice ``reduce.py`` took."""
    if "_host_phase_idle" not in ctx:
        ex, tr = planes(ctx), ctx.get("device_trace")
        ctx["_host_phase_idle"] = idle_split(
            ex, tr["t_lo_s"], tr["t_hi_s"]) if ex and tr else None
        split = ctx["_host_phase_idle"]
        if split:
            ctx.setdefault("notes", []).append(
                "device idle by engine phase (% of the slice): "
                + ", ".join(f"{k} {v:.3f}" for k, v in split.items()))
    split = ctx["_host_phase_idle"]
    return None if split is None else split[cls]


# -- the banked phase spans (the wall clock) ---------------------------------

def phase_spans(ctx: dict, names=None, within=None) -> list:
    """The loop's banked phases (``llm.loop.<name>``) that ended inside
    ``within`` (by default the window), oldest first."""
    if within is None:
        t0 = ctx["window"]["t0_wall"]
        within = (t0, t0 + ctx["seconds"])
    out = [s for s in ctx.get("spans") or []
           if (s.get("name") or "").startswith(PREFIX)
           and (names is None or s["name"][len(PREFIX):] in names)
           and within[0] <= s["end_ts"] < within[1]]
    return sorted(out, key=lambda s: s["start_ts"])


def queue_wait_share(ctx: dict, prefix: str):
    """Share (%) of the requests' summed ``llm.queue`` time, over the
    queue spans that ended in the window, during which the engine thread
    was in a phase whose name starts with ``prefix`` (for ``prefill_``:
    serving another request).  None without queue or phase spans."""
    queues = common.spans_named(ctx, "llm.queue")
    waited = sum(q["end_ts"] - q["start_ts"] for q in queues)
    phases = [p for p in phase_spans(ctx, within=(0.0, float("inf")))
              if p["name"][len(PREFIX):].startswith(prefix)]
    if not phases or waited <= 0:
        return None
    under = 0.0
    for q in queues:
        rid = (q.get("args") or {}).get("request_id")
        for p in phases:
            if p["start_ts"] >= q["end_ts"]:
                break
            if (p["end_ts"] > q["start_ts"]
                    and (p.get("args") or {}).get("request_id") != rid):
                under += (min(p["end_ts"], q["end_ts"])
                          - max(p["start_ts"], q["start_ts"]))
    return 100.0 * under / waited


def decode_host_ms_per_step(ctx: dict):
    """Host time of the decode phases other than the wait for the device,
    over the decode steps the engine counted in the window."""
    steps = (ctx.get("counters") or {}).get("decode_steps")
    spans = phase_spans(ctx, DECODE_STEP_HOST)
    if not steps or not spans:
        return None
    return sum(s["end_ts"] - s["start_ts"] for s in spans) * 1e3 / steps


def admission_host_ms(ctx: dict) -> list:
    """Per admitted request, the host time of its admission: its admit,
    prefill_host and prefill_emit phases (ms)."""
    per_request = {}
    for s in phase_spans(ctx, ADMISSION_HOST):
        args = s.get("args") or {}
        if "request_id" in args and args.get("outcome", "admitted") \
                == "admitted":
            per_request[args["request_id"]] = per_request.get(
                args["request_id"], 0.0) + (s["end_ts"] - s["start_ts"]) * 1e3
    return list(per_request.values())


def longest_phase(ctx: dict):
    """The longest phase other than idle that ended in the window: the
    span itself (a stall shows here, and the phase it struck)."""
    spans = [s for s in phase_spans(ctx) if s["name"] != PREFIX + "idle"]
    if not spans:
        return None
    return max(spans, key=lambda s: s["end_ts"] - s["start_ts"])


# -- both clocks -------------------------------------------------------------

def clock_skew_ms(extracted: dict, spans: list, zero_wall: float):
    """Median, over the phases found both as an annotation and as a banked
    span (the k-th of a name in an iteration), of the span's wall-clock
    start minus the annotation's start put on the wall clock through
    ``zero_wall``: how wrong every wall-clock label of a gap is.  None
    where nothing matches."""
    its = [a[1] for a in extracted["annotations"] if a[1] is not None]
    seen, annotated = {}, {}
    for name, it, start, _ in extracted["annotations"]:
        # not the iterations the session's two ends cut: their k-th
        # annotation need not be the iteration's k-th phase
        if it is not None and name != "idle" and min(its) < it < max(its):
            k = seen[(name, it)] = seen.get((name, it), 0) + 1
            annotated[(name, it, k)] = start
    seen, diffs = {}, []
    for s in sorted(spans, key=lambda s: s["start_ts"]):
        name = (s.get("name") or "")[len(PREFIX):]
        it = (s.get("args") or {}).get("it")
        if not (s.get("name") or "").startswith(PREFIX) or it is None:
            continue
        k = seen[(name, it)] = seen.get((name, it), 0) + 1
        if (name, it, k) in annotated:
            diffs.append(s["start_ts"] - zero_wall
                         - annotated[(name, it, k)])
    return common.median(diffs) * 1e3 if diffs else None


if __name__ == "__main__":
    print(json.dumps(extract(sys.argv[1], sys.argv[2:] == ["1"])))
