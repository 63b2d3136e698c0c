"""From a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: per device the busy intervals, the time per program (XLA module) and
per operation, the collectives' exposed time and the longest idle gaps.

Read with ``jax.profiler.ProfileData`` (nothing but JAX), so it runs in the
process that took the trace or after that has exited, never in a driver
that must stay off the chip.  The interval arithmetic below is plain Python
and is what ``tests/test_reduce.py`` checks on synthetic intervals; the
whole reduction is checked there on a small recorded trace.

A TPU's plane is named ``/device:TPU:<n>``.  Its line ``XLA Modules`` has
one event per run of a jitted program (``jit_<function>(<fingerprint>)``),
``XLA Ops`` one per operation of it on the core's own timeline; other lines
(steps, async copies and collectives in flight) are kept by name.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute"
    r"|async-collective")
N_GAPS = 20


# -- interval arithmetic (half-open [start, end), any unit) ------------------

def union(intervals) -> list:
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def total(disjoint) -> float:
    return float(sum(e - s for s, e in disjoint))


def subtract(a, b) -> list:
    """The part of union ``a`` that union ``b`` does not cover."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def gaps(busy, lo, hi) -> list:
    """Idle intervals of [lo, hi) given the busy union, longest first."""
    idle = subtract([[lo, hi]], busy)
    return sorted(idle, key=lambda g: g[0] - g[1])


KERNEL = re.compile(r"flash_attention_\w+|ring_attention_\w+")
CONTAINER = re.compile(r"^(while|conditional|call)[.\d]*$")


def op_name(event_text: str) -> str:
    """An operation's event carries its whole HLO line: keep the name left
    of `` = `` (``%fusion.12`` -> ``fusion.12``).  A Pallas kernel's call is
    named after the kernel (``%flash_attention_fwd.3``): all its calls go
    under the kernel's own name.  Only the left side counts: the consumers
    of a kernel's result name it among their operands."""
    own = event_text.split(" = ", 1)[0].lstrip("%")
    k = KERNEL.match(own)
    if k is None and " custom-call(" in event_text:
        k = KERNEL.search(event_text)  # named in the call's own attributes
    return k.group(0) if k else own


def module_name(event_name: str) -> str:
    """``jit_decode_step_greedy(1234567)`` -> ``jit_decode_step_greedy``."""
    return event_name.split("(", 1)[0]


# -- the reduction -----------------------------------------------------------

def _aggregate(events) -> dict:
    agg = {}
    for name, s, e in events:
        a = agg.setdefault(name, [0, 0.0])
        a[0] += 1
        a[1] += (e - s) * 1e-9
    return {k: {"count": v[0], "seconds": v[1]} for k, v in agg.items()}


def reduce_planes(planes, host_as_device: bool = False) -> dict:
    """``planes``: [(plane name, [(line name, [(event name, start_ns,
    end_ns)])])].  Returns the reduced trace (times in seconds; interval
    ends in seconds from the trace's own zero).  ``host_as_device`` is for
    a rehearsal on the CPU, which has no device plane: the host's XLA
    threads then stand in for one, so that the rest of the path runs."""
    devices = {}
    if host_as_device:
        ops = [ev for pname, lines in planes if pname == "/host:CPU"
               for lname, evs in lines if "XLA" in lname for ev in evs]
        planes = [("/device:TPU:0", [(OPS_LINE, ops)])]
    for pname, lines in planes:
        m = DEVICE_PLANE.match(pname)
        if not m:
            continue
        lines = dict(lines)
        ops = lines.get(OPS_LINE, [])
        if not ops:
            continue
        devices[int(m.group(1))] = {
            "ops": ops, "modules": lines.get(MODULES_LINE, []),
            "other_lines": {k: len(v) for k, v in lines.items()
                            if k not in (OPS_LINE, MODULES_LINE)}}
    if not devices:
        raise ValueError("no device plane with operations in the trace: "
                         + ", ".join(p for p, _ in planes))
    lo = min(e[1] for d in devices.values() for e in d["ops"])
    hi = max(e[2] for d in devices.values() for e in d["ops"])
    per_device, busy_total, exposed_total, coll_total = {}, 0.0, 0.0, 0.0
    for n, d in sorted(devices.items()):
        busy = union([[s, e] for _, s, e in d["ops"]])
        coll = union([[s, e] for name, s, e in d["ops"]
                      if COLLECTIVE.search(name)])
        compute = union([[s, e] for name, s, e in d["ops"]
                         if not COLLECTIVE.search(name)])
        exposed = subtract(coll, compute)
        per_device[str(n)] = {
            "busy_s": total(busy) * 1e-9,
            "collective_s": total(coll) * 1e-9,
            "collective_exposed_s": total(exposed) * 1e-9,
            "other_lines": d["other_lines"]}
        busy_total += total(busy) * 1e-9
        coll_total += total(coll) * 1e-9
        exposed_total += total(exposed) * 1e-9
    first = devices[min(devices)]
    busy0 = union([[s, e] for _, s, e in first["ops"]])
    # whole runs of a program only: a run cut by the slice's edge would
    # spoil the time per run
    modules = _aggregate((module_name(n), s, e) for n, s, e
                         in first["modules"] if s >= lo and e <= hi)
    n_dev = len(devices)
    return {
        "devices": n_dev, "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_total / n_dev,
        "collective_s": coll_total / n_dev,
        "collective_exposed_s": exposed_total / n_dev,
        "per_device": per_device,
        "modules": modules,
        # a loop's own event spans its body's operations, which are
        # listed too: leave the containers out of the operations' table
        "ops": _aggregate((op_name(n), s, e) for n, s, e in first["ops"]
                          if not CONTAINER.match(op_name(n))),
        "gaps": [[g[0] * 1e-9, g[1] * 1e-9]
                 for g in gaps(busy0, lo, hi)[:N_GAPS]],
        "t_lo_s": lo * 1e-9, "t_hi_s": hi * 1e-9,
        "lines": {p: {ln: len(evs) for ln, evs in lines}
                  for p, lines in planes},
    }


def read_planes(path: str) -> list:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append((line.name, [
                (ev.name, float(ev.start_ns),
                 float(ev.start_ns) + float(ev.duration_ns))
                for ev in line.events]))
        planes.append((plane.name, lines))
    return planes


def reduce_file(path: str, host_as_device: bool = False) -> dict:
    return reduce_planes(read_planes(path), host_as_device)
