"""Device time by model part.

The programs name their parts (``ray_tpu/models/llama.py`` ``PARTS``:
``jax.named_scope`` inside each part's own function), so the compiled
operations carry paths like ``jit(decode_step_greedy)/while/body/closed_call/
attn/qkv/dot_general``.  On a TPU v5e the profiler keeps that path in the
``tf_op`` stat of an operation's EVENT METADATA (the event itself has only
its times, and its name is the HLO line without ``metadata=``).
``jax.profiler.ProfileData`` does not show event-metadata stats, so the
child below walks the ``.xplane.pb``'s few protobuf fields by hand
(``XSpace.planes > XPlane.{lines, event_metadata, stat_metadata}``;
tensorflow is not a dependency).

For device 0, inside the slice ``reduce.py`` took, every ``XLA Ops`` event
(the containers ``while`` / ``conditional`` / ``call`` left out, as there)
is put under the ``XLA Modules`` run whose interval contains it: the two
lines share the device's clock.  An operation outside every whole run is
dropped with the runs the slice's edge cut.  Its path is split on ``/``,
transform wrappers are peeled (``jit(..)`` with its content, ``jvp(..)``,
``transpose(..)`` and the like around theirs, which may be a scope:
``transpose(jvp(head))``) and the INNERMOST run of components that is an
entry of ``PARTS`` is its part: the layer scan is itself a part
(``layers``: the slices of the stacked weights, the transposes XLA makes
of them, the loop's counter), and whatever a layer's own part wrote lies
deeper, ``layers/while/body/closed_call/attn/qkv/dot_general``.
``rematted_computation`` anywhere in the path marks recomputation,
``transpose(`` the backward pass, neither the forward.

A FUSION IS COUNTED UNDER THE ONE PATH XLA GAVE IT, its root's: a fusion
that spans two parts goes to one of them whole.  What has no part (the
operations a program writes outside its parts, those XLA adds without any
path) stays ``unscoped``, listed by name.

``read(ctx)`` hands back, per program (module name), ``{part: {fwd,
recompute, bwd}}`` seconds, ``unscoped`` seconds with its largest names,
the summed operation seconds and the summed module seconds; None where
there is no trace, where the program exports no ``PARTS`` (the parent of
the PR that named them) and where no operation carries a part (a compile
cache warmed by a tree without scopes: metadata is not in the cache's
key).  The parse happens once a run, in a child process, because ``PARTS``
lives in a module that imports JAX and the driver never does.
"""

from __future__ import annotations

import bisect
import json
import os
import re
import subprocess
import sys
import time

if __name__ == "__main__":  # the child: make the repository importable
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmarks import common  # noqa: E402
from benchmarks.trace import reduce  # noqa: E402

PHASES = ("fwd", "recompute", "bwd")
N_UNSCOPED = 8  # names of unscoped operations kept, largest first
_JIT = re.compile(r"\bp?jit\([^()]*\)")
_WRAPPER = re.compile(r"[\w.\-]+\(")


# -- the path ----------------------------------------------------------------

def part_runs(op_name: str, parts) -> list:
    """Every run of an ``op_name``'s components that is an entry of
    ``parts`` (the longest at each place), outermost first."""
    flat = _WRAPPER.sub("/", _JIT.sub("", op_name)).replace(")", "/")
    comps = [c for c in flat.split("/") if c]
    runs, i = [], 0
    while i < len(comps):
        k = max((k for k in range(1, len(comps) - i + 1)
                 if "/".join(comps[i:i + k]) in parts), default=0)
        if k:
            runs.append("/".join(comps[i:i + k]))
        i += k or 1
    return runs


def split_path(op_name: str, parts) -> tuple:
    """(part or None, phase) of one operation's ``op_name``."""
    phase = ("recompute" if "rematted_computation" in op_name
             else "bwd" if "transpose(" in op_name else "fwd")
    runs = part_runs(op_name, parts)
    return (runs[-1] if runs else None), phase


# -- the file, by hand -------------------------------------------------------

def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf, lo, hi):
    """(field number, wire type, value, or (start, end) of a
    length-delimited one) over one message's bytes."""
    i = lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, wire, v


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _map_entry(buf, span):
    """A ``map<int64, message>`` entry: (key, the value's span)."""
    key, val = 0, None
    for f, _, v in _fields(buf, *span):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _plane(buf, span) -> dict:
    """One XPlane's name and the spans of its lines and metadata."""
    out = {"name": "", "lines": [], "event_md": [], "stat_md": []}
    for f, _, v in _fields(buf, *span):
        if f == 2:
            out["name"] = _text(buf, v)
        elif f == 3:
            out["lines"].append(v)
        elif f == 4:
            out["event_md"].append(v)
        elif f == 5:
            out["stat_md"].append(v)
    return out


def _events(buf, span) -> list:
    """[(metadata id, start ns, end ns)] of one XLine."""
    t0_ns, events = 0, []
    for f, _, v in _fields(buf, *span):
        if f == 3:
            t0_ns = v
        elif f == 4:
            events.append(v)
    out = []
    for ev in events:
        md = off = dur = 0
        for f, _, v in _fields(buf, *ev):
            if f == 1:
                md = v
            elif f == 2:
                off = v
            elif f == 3:
                dur = v
        out.append((md, t0_ns + off * 1e-3, t0_ns + (off + dur) * 1e-3))
    return out


def _line_name(buf, span) -> str:
    for f, _, v in _fields(buf, *span):
        if f == 2:
            return _text(buf, v)
    return ""


def _event_metadata(buf, plane) -> dict:
    """{metadata id: (name, op_name)} of a plane: ``name`` is the event's
    (an operation's whole HLO line), ``op_name`` its ``tf_op`` stat
    (``jit(f)/.../attn/qkv/dot_general:``), "" where it has none."""
    stat_names = {}
    for span in plane["stat_md"]:
        key, val = _map_entry(buf, span)
        for f, _, v in _fields(buf, *val):
            if f == 2:
                stat_names[key] = _text(buf, v)
    wanted = {k for k, n in stat_names.items() if n == "tf_op"}
    out = {}
    for span in plane["event_md"]:
        key, val = _map_entry(buf, span)
        name, op = "", ""
        for f, _, v in _fields(buf, *val):
            if f == 2:
                name = _text(buf, v)
            elif f == 5 and wanted:
                sid, text = 0, ""
                for g, _, w in _fields(buf, *v):
                    if g == 1:
                        sid = w
                    elif g == 5:
                        text = _text(buf, w)
                    elif g == 7:  # a reference to a stat metadata's name
                        text = stat_names.get(w, "")
                if sid in wanted:
                    op = text
        out[key] = (name, op)
    return out


def read_device(path: str, host_as_device: bool = False):
    """(modules, ops, metadata) of the first device with operations:
    ``modules`` and ``ops`` [(metadata id, start ns, end ns)], ``metadata``
    as ``_event_metadata`` gives it; None without such a plane.  In a
    rehearsal on the CPU the host's XLA threads stand in for a device, as
    in ``reduce.py``: no program runs, and their events carry no paths, so
    a rehearsal walks the whole path and finds no part."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    best = None
    for f, _, v in _fields(buf, 0, len(buf)):
        if f != 1:
            continue
        plane = _plane(buf, v)
        if host_as_device:
            if plane["name"] == "/host:CPU":
                ops = [ev for span in plane["lines"]
                       if "XLA" in _line_name(buf, span)
                       for ev in _events(buf, span)]
                return [], ops, _event_metadata(buf, plane)
            continue
        m = reduce.DEVICE_PLANE.match(plane["name"])
        if m and (best is None or int(m.group(1)) < best[0]):
            names = [_line_name(buf, span) for span in plane["lines"]]
            if reduce.OPS_LINE in names:
                best = (int(m.group(1)), plane, names)
    if best is None:
        return None
    _, plane, names = best
    lines = {n: _events(buf, span)
             for n, span in zip(names, plane["lines"])
             if n in (reduce.OPS_LINE, reduce.MODULES_LINE)}
    return (lines.get(reduce.MODULES_LINE, []), lines[reduce.OPS_LINE],
            _event_metadata(buf, plane))


# -- the table ---------------------------------------------------------------

def table(modules, ops, metadata, parts, lo_ns, hi_ns):
    """The per-program table over the slice [lo_ns, hi_ns]; see the
    module's docstring.  None where no operation carries a part."""
    resolved = {mid: split_path(op, parts) + (reduce.op_name(name),)
                for mid, (name, op) in metadata.items()}
    runs = sorted((s, e, reduce.module_name(metadata[mid][0]))
                  for mid, s, e in modules if s >= lo_ns and e <= hi_ns)
    starts = [r[0] for r in runs]
    progs, any_part = {}, False
    for s, e, name in runs:
        p = progs.setdefault(name, {
            "runs": 0, "module_s": 0.0, "ops_s": 0.0, "unscoped_s": 0.0,
            "parts": {}, "_unscoped": {}})
        p["runs"] += 1
        p["module_s"] += (e - s) * 1e-9
    for mid, s, e in ops:
        part, phase, short = resolved[mid]
        if reduce.CONTAINER.match(short):
            continue
        k = bisect.bisect_right(starts, s) - 1
        if k < 0 or e > runs[k][1] + 1.0:  # in no whole run of the slice
            continue
        p, dt = progs[runs[k][2]], (e - s) * 1e-9
        p["ops_s"] += dt
        if part is None:
            p["unscoped_s"] += dt
            p["_unscoped"][short] = p["_unscoped"].get(short, 0.0) + dt
            continue
        any_part = True
        cell = p["parts"].setdefault(part, dict.fromkeys(PHASES, 0.0))
        cell[phase] += dt
    if not any_part:
        return None
    for p in progs.values():
        top = sorted(p.pop("_unscoped").items(), key=lambda kv: -kv[1])
        p["unscoped"] = [[k, v] for k, v in top[:N_UNSCOPED]]
    return progs


def extract(path: str, lo_s: float, hi_s: float,
            host_as_device: bool = False):
    """The child's whole work: None where the program names no parts or
    the trace has no device; ``programs`` None where no operation of the
    slice carries one."""
    t0 = time.time()
    from ray_tpu.models import llama

    parts = getattr(llama, "PARTS", None)
    device = read_device(path, host_as_device) if parts else None
    if device is None:
        return None
    modules, ops, metadata = device
    progs = table(modules, ops, metadata, frozenset(parts), lo_s * 1e9,
                  hi_s * 1e9)
    return {"programs": progs, "events": len(ops),
            "parse_s": time.time() - t0}


# -- the driver's side -------------------------------------------------------

def read(ctx: dict):
    """The programs' tables (``extract`` of the run's trace over the slice
    ``reduce.py`` took), made once a run by a child process, or None; the
    whole of it goes to the run's note lines."""
    if "_device_parts" not in ctx:
        ctx["_device_parts"] = None
        tr = ctx.get("device_trace") or {}
        path = tr.get("xplane")
        if path and os.path.exists(path):
            child = subprocess.run(
                [sys.executable, os.path.abspath(__file__), path,
                 repr(tr["t_lo_s"]), repr(tr["t_hi_s"]),
                 str(int(common.rehearsing()))],
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
                capture_output=True, text=True, timeout=600)
            notes = ctx.setdefault("notes", [])
            if child.returncode != 0:
                notes.append("device_parts: the trace could not be read: "
                             + child.stderr.strip()[-300:])
            else:
                got = json.loads(child.stdout.strip().splitlines()[-1])
                if got:
                    notes.extend(note_lines(got))
                    ctx["_device_parts"] = got["programs"]
    return ctx["_device_parts"]


def note_lines(got: dict) -> list:
    """What the child read, and one line a program: every part's share of
    the program's operation time (fwd / recompute / bwd where it has more
    than a forward pass), the unscoped time with its largest names,
    operation over module time."""
    lines = [f"device_parts: {got['events']} operation events read in "
             f"{got['parse_s']:.1f} s"]
    if not got["programs"]:
        lines[0] += (": none carries a part (an executable from a compile "
                     "cache that a tree without scopes wrote? the cache's "
                     "key leaves metadata out)")
        return lines
    for name, p in sorted(got["programs"].items(),
                          key=lambda kv: -kv[1]["ops_s"]):
        if not p["parts"]:
            continue
        pct = lambda s: 100.0 * s / p["ops_s"]  # noqa: E731
        cells = []
        for part, c in sorted(p["parts"].items(),
                              key=lambda kv: -sum(kv[1].values())):
            text = f"{part} {pct(sum(c.values())):.2f}"
            if c["recompute"] or c["bwd"]:
                text += (f" ({pct(c['fwd']):.2f}/{pct(c['recompute']):.2f}/"
                         f"{pct(c['bwd']):.2f})")
            cells.append(text)
        unscoped = ", ".join(f"{k} {pct(v):.2f}" for k, v in p["unscoped"])
        lines.append(
            f"device time by part, {name} ({p['runs']} runs; % of "
            f"{p['ops_s']:.4f} s of operations, which is "
            f"{100 * p['ops_s'] / p['module_s']:.1f} % of module time; "
            f"fwd/recompute/bwd in brackets): " + ", ".join(cells)
            + f"; unscoped {pct(p['unscoped_s']):.2f}"
            + (f" [{unscoped}]" if unscoped else ""))
    return lines


def share(ctx: dict, prefix: str, wanted, phases=PHASES):
    """Share (%) of the summed operation time of the programs named
    ``prefix*`` (their whole runs in the slice) that lies under the parts
    ``wanted(part)`` accepts, in ``phases``.  None where the trace has no
    parts or no such program."""
    progs = [p for name, p in (read(ctx) or {}).items()
             if name.startswith(prefix)]
    total = sum(p["ops_s"] for p in progs)
    if total <= 0:
        return None
    hit = sum(c[ph] for p in progs for part, c in p["parts"].items()
              if wanted(part) for ph in phases)
    return 100.0 * hit / total


if __name__ == "__main__":
    print(json.dumps(extract(sys.argv[1], float(sys.argv[2]),
                             float(sys.argv[3]), sys.argv[4:] == ["1"])))
