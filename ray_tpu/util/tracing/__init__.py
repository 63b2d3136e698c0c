"""Distributed tracing: trace propagation, span collection, chrome export.

Counterpart of /root/reference/python/ray/util/tracing/tracing_helper.py
(OpenTelemetry monkey-patching of submission/execution) — redesigned on
the runtime's own planes.  A trace context (``trace_id``, parent
``span_id``) is minted at ``.remote()`` submission, rides the ``TaskSpec``
into the worker, and is re-established around task execution so nested
submissions and actor calls parent correctly: one driver call yields one
connected cross-process tree.  Completed spans flush to the node scheduler
over the control socket (same pattern as ``metrics_push``);
``ray_tpu.util.state.get_trace`` fans out over the cluster and calls
:func:`assemble_trace` here to build the tree plus a critical-path summary
(queue-wait vs. arg-fetch vs. run time).  :func:`trace_to_chrome_events`
emits chrome-trace flow events (``ph:"s"/"f"``) so Perfetto draws the
cross-process arrows.
"""

from __future__ import annotations

import contextlib
import json
import os
import random
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

# Spans carrying a trace id queue here until pushed to the node scheduler
# ("spans_push").  Bounded: tracing is observability, not ground truth.
_remote_buf: List[Dict[str, Any]] = []
_REMOTE_BUF_CAP = 50_000

# The process-local copy behind collected_spans()/export_chrome_trace():
# the newest spans only, since a serving replica records them for as long
# as it lives (its engine loop alone banks several a second).
_spans: "deque[Dict[str, Any]]" = deque(maxlen=_REMOTE_BUF_CAP)
_lock = threading.Lock()
_enabled = False

_tls = threading.local()

# What the serving engine's loop was doing while a request stood, by class
# of phase: attributes (seconds, summing to the span's length) of a
# request's ``llm.queue``, ``llm.admission`` and ``llm.decode`` spans
# (llm/engine.py keeps the sums; ``rtpu trace <id>`` prints them).
WAIT_ATTRS = ("step_s", "own_prefill_s", "other_prefill_s", "host_s",
              "idle_s")

_flusher_started = False
_flush_stop = threading.Event()
_flush_gen = 0


def enable_tracing() -> None:
    """Turn on app-span collection in this process.  Workers don't need
    this: a spec arriving with a trace context is traced regardless."""
    global _enabled
    _enabled = True


def disable_tracing() -> None:
    """Stop minting new root traces here (in-flight contexts still
    propagate; already-buffered spans still flush)."""
    global _enabled
    _enabled = False


def is_tracing_enabled() -> bool:
    return _enabled


def new_trace_id() -> str:
    return os.urandom(16).hex()


def new_span_id() -> str:
    return os.urandom(8).hex()


def current_context() -> Optional[Tuple[str, Optional[str]]]:
    """The calling thread's (trace_id, span_id), or None outside a trace."""
    return getattr(_tls, "ctx", None)


def attach_trace(spec) -> None:
    """Stamp a submission-side trace context onto a TaskSpec.

    Inside an active span (driver ``trace_span`` block or a traced task's
    execution) the spec inherits that context; otherwise, when tracing is
    enabled in this process, each ``.remote()`` mints a fresh root trace.
    The stamped fields pickle through every submission lane — scheduler
    conn, native raylet frames, nested 0x10 submits, direct actor calls.
    """
    ctx = getattr(_tls, "ctx", None)
    if ctx is None:
        if not _enabled:
            return
        ctx = (new_trace_id(), None)
    spec.trace_id, spec.parent_span_id = ctx
    spec.trace_submit_ts = time.time()


class Span:
    """Handle yielded by :func:`trace_span`: exposes the ids so callers can
    look the trace up later (``state.get_trace(span.trace_id)``).  Mutating
    ``attrs`` inside the block adds attributes resolved mid-span (e.g. the
    router's chosen replica) to the recorded span."""

    __slots__ = ("trace_id", "span_id", "parent_id", "name", "attrs")

    def __init__(self, trace_id: str, span_id: str,
                 parent_id: Optional[str], name: str):
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.name = name
        self.attrs: Dict[str, Any] = {}

    def __repr__(self):
        return f"Span({self.name!r}, trace_id={self.trace_id})"


def _record(rec: Dict[str, Any]) -> None:
    with _lock:
        _spans.append({
            "name": rec["name"], "ph": "X", "pid": rec["pid"],
            "tid": threading.get_ident() % 1_000_000,
            "ts": rec["start_ts"] * 1e6,
            "dur": (rec["end_ts"] - rec["start_ts"]) * 1e6,
            "args": dict(rec.get("args") or {},
                         **({"trace_id": rec["trace_id"],
                             "span_id": rec["span_id"]}
                            if rec.get("trace_id") else {})),
        })
        if rec.get("trace_id"):
            if len(_remote_buf) < _REMOTE_BUF_CAP:
                _remote_buf.append(rec)
    if rec.get("trace_id"):
        _ensure_flusher()


@contextlib.contextmanager
def trace_span(name: str, **attributes):
    """Record one span.  Yields a :class:`Span` when a trace is active
    (tracing enabled here, or running inside a traced task) so nested
    ``.remote()`` calls parent under it; yields None when tracing is off
    (the historical no-op behavior)."""
    ctx = getattr(_tls, "ctx", None)
    if not _enabled and ctx is None:
        yield None
        return
    trace_id = ctx[0] if ctx else new_trace_id()
    parent_id = ctx[1] if ctx else None
    span = Span(trace_id, new_span_id(), parent_id, name)
    _tls.ctx = (trace_id, span.span_id)
    t0 = time.time()
    try:
        yield span
    finally:
        _tls.ctx = ctx
        _record({
            "trace_id": trace_id, "span_id": span.span_id,
            "parent_id": parent_id, "name": name, "kind": "user",
            "pid": os.getpid(), "start_ts": t0, "end_ts": time.time(),
            "queue_wait_s": 0.0, "arg_fetch_s": 0.0,
            "run_s": time.time() - t0, "ok": True,
            "args": dict(attributes, **span.attrs),
        })


def sample_request() -> bool:
    """Head-sampling decision for a new serving root trace
    (``RTPU_TRACE_SAMPLE``, default 1.0).  Children of an existing trace
    always inherit — sampling happens only where roots are minted, so a
    sampled request is traced end to end and a dropped one costs nothing."""
    from ray_tpu._private import flags

    p = float(flags.get("RTPU_TRACE_SAMPLE"))
    if p >= 1.0:
        return True
    if p <= 0.0:
        return False
    return random.random() < p


@contextlib.contextmanager
def serving_span(name: str, **attributes):
    """Root entry point for a serving request (OpenAI server, P/D router).

    Unlike :func:`trace_span`, this mints a root even when tracing was
    never enabled in this process — serving anatomy should be on by
    default — but each new root passes the ``RTPU_TRACE_SAMPLE`` head
    sampler first.  Inside an existing trace it nests exactly like
    ``trace_span``; sampled-out requests yield None and record nothing.
    """
    ctx = getattr(_tls, "ctx", None)
    if ctx is None and not sample_request():
        yield None
        return
    with trace_span(name, **attributes) as span:
        if span is not None:
            yield span
            return
        # no ambient context and tracing disabled: mint the root ourselves
        trace_id, parent_id = new_trace_id(), None
        span = Span(trace_id, new_span_id(), parent_id, name)
        _tls.ctx = (trace_id, span.span_id)
        t0 = time.time()
        try:
            yield span
        finally:
            _tls.ctx = ctx
            _record({
                "trace_id": trace_id, "span_id": span.span_id,
                "parent_id": parent_id, "name": name, "kind": "user",
                "pid": os.getpid(), "start_ts": t0, "end_ts": time.time(),
                "queue_wait_s": 0.0, "arg_fetch_s": 0.0,
                "run_s": time.time() - t0, "ok": True,
                "args": dict(attributes, **span.attrs),
            })


@contextlib.contextmanager
def use_context(ctx: Optional[Tuple[str, Optional[str]]]):
    """Re-establish a captured ``(trace_id, span_id)`` context on this
    thread — for work handed across threads or processes (SSE generators,
    the P/D prefill→decode handoff) that should parent under the capture
    point rather than wherever it happens to run."""
    prev = getattr(_tls, "ctx", None)
    _tls.ctx = ctx
    try:
        yield
    finally:
        _tls.ctx = prev


def record_span(trace_id: str, name: str, start_ts: float, end_ts: float, *,
                parent_id: Optional[str] = None,
                span_id: Optional[str] = None, kind: str = "engine",
                ok: bool = True,
                attrs: Optional[Dict[str, Any]] = None) -> str:
    """Record a span with an explicit context instead of thread-local
    state.  The engine's scheduler thread interleaves many requests, so it
    carries each request's ``(trace_id, span_id)`` and stamps phase spans
    (queue, kv-pull, prefill, decode) here as they complete."""
    sid = span_id or new_span_id()
    _record({
        "trace_id": trace_id, "span_id": sid, "parent_id": parent_id,
        "name": name, "kind": kind, "pid": os.getpid(),
        "start_ts": start_ts, "end_ts": end_ts,
        "queue_wait_s": 0.0, "arg_fetch_s": 0.0,
        "run_s": max(0.0, end_ts - start_ts), "ok": ok,
        "args": dict(attrs or {}),
    })
    return sid


class LoopTrace:
    """Trace ids for a loop that runs as long as its process (the serving
    engine's scheduler loop): its spans cannot live in one trace, which the
    node scheduler caps at 10,000 spans, nor in one trace an iteration,
    which would push the requests' traces out of ``RTPU_TRACE_CAP``.  The
    id is rotated once ``budget`` spans have been charged to it, so a loop
    that banks ~100 spans a second costs under one trace a minute."""

    def __init__(self, budget: int = 9_000):
        self._budget = budget
        self._id: Optional[str] = None
        self._n = 0

    def take(self, n_spans: int) -> str:
        """The trace id that the caller's next ``n_spans`` spans go to."""
        if self._id is None or self._n + n_spans > self._budget:
            self._id, self._n = new_trace_id(), 0
        self._n += n_spans
        return self._id


# ---------------------------------------------------------------------------
# built-in task-execution spans (worker_main drives these)

def begin_task_span(spec, start_ts: Optional[float] = None) -> Optional[dict]:
    """Open the built-in execution span for a traced TaskSpec: establishes
    the thread's trace context (so nested submissions parent here) and
    returns a token for :func:`end_task_span`.  None for untraced specs."""
    trace_id = getattr(spec, "trace_id", None)
    if not trace_id:
        return None
    token = {
        "trace_id": trace_id, "span_id": new_span_id(),
        "parent_id": getattr(spec, "parent_span_id", None),
        "name": spec.name or (spec.method_name or spec.kind),
        "kind": spec.kind, "pid": os.getpid(),
        "submit_ts": getattr(spec, "trace_submit_ts", 0.0) or None,
        "start_ts": start_ts if start_ts is not None else time.time(),
        "arg_fetch_s": 0.0,
        "prev_ctx": getattr(_tls, "ctx", None),
        "prev_token": getattr(_tls, "task_token", None),
    }
    _tls.ctx = (trace_id, token["span_id"])
    _tls.task_token = token
    return token


def note_arg_fetch(seconds: float) -> None:
    """Charge dependency-resolution time to the current task span."""
    token = getattr(_tls, "task_token", None)
    if token is not None:
        token["arg_fetch_s"] += seconds


def end_task_span(token: Optional[dict], ok: bool = True,
                  flush: bool = True) -> None:
    """Close a task-execution span, restore the previous context, and (by
    default) flush pending spans to the node scheduler right away so the
    trace is queryable as soon as the task finishes."""
    if token is None:
        return
    _tls.ctx = token.pop("prev_ctx")
    _tls.task_token = token.pop("prev_token")
    end_ts = time.time()
    start_ts = token.pop("start_ts")
    submit_ts = token.pop("submit_ts")
    arg_fetch = token.pop("arg_fetch_s")
    queue_wait = max(0.0, start_ts - submit_ts) if submit_ts else 0.0
    _record(dict(token, submit_ts=submit_ts, start_ts=start_ts,
                 end_ts=end_ts, ok=ok,
                 queue_wait_s=queue_wait, arg_fetch_s=arg_fetch,
                 run_s=max(0.0, (end_ts - start_ts) - arg_fetch),
                 args={}))
    if flush:
        flush_spans()


# ---------------------------------------------------------------------------
# flush plane: spans -> node scheduler ("spans_push", like metrics_push)

def flush_spans() -> int:
    """Push queued spans to the node scheduler; returns how many landed.
    Best-effort: on failure the batch re-queues for the next attempt."""
    with _lock:
        if not _remote_buf:
            return 0
        batch = list(_remote_buf)
        del _remote_buf[:]
    from ray_tpu._private import worker as worker_mod

    ctx = worker_mod.global_worker_or_none()
    if ctx is None:
        with _lock:
            _remote_buf[:0] = batch
        return 0
    try:
        ctx.rpc("spans_push", {"spans": batch})
        return len(batch)
    except Exception:
        with _lock:
            _remote_buf[:0] = batch[:_REMOTE_BUF_CAP - len(_remote_buf)]
        return 0


def _flush_interval() -> float:
    from ray_tpu._private import flags

    return max(0.25, float(flags.get("RTPU_METRICS_FLUSH_S")))


def _ensure_flusher() -> None:
    global _flusher_started, _flush_gen
    with _lock:
        if _flusher_started:
            return
        _flusher_started = True
        _flush_gen += 1
        gen = _flush_gen
        _flush_stop.clear()
    threading.Thread(target=_flush_loop, args=(gen,), name="trace-flush",
                     daemon=True).start()


def _flush_loop(gen: int) -> None:
    global _flusher_started
    while True:
        stopped = _flush_stop.wait(_flush_interval())
        with _lock:
            if gen != _flush_gen:
                return  # superseded by a newer flusher
            if stopped:
                _flusher_started = False
                return
        try:
            flush_spans()
        except Exception:
            pass


def shutdown_flusher(flush: bool = False) -> None:
    """Stop the background span flusher (clean worker/driver shutdown);
    optionally pushing one final batch first."""
    if flush:
        try:
            flush_spans()
        except Exception:
            pass
    _flush_stop.set()


# ---------------------------------------------------------------------------
# trace assembly + critical path (pure functions: state.py, the dashboard,
# and the CLI all share them; the latter two have no driver context)

def assemble_trace(trace_id: str, spans: List[dict]) -> dict:
    """Merge per-node span lists into one tree with a critical-path
    summary.  Tolerates duplicates (flush retries) and orphans (parent
    span not yet flushed: the child becomes a root)."""
    by_id: Dict[str, dict] = {}
    for s in spans:
        sid = s.get("span_id")
        if sid and sid not in by_id:
            by_id[sid] = s
    flat = sorted(by_id.values(), key=lambda s: s.get("start_ts") or 0.0)
    children: Dict[str, List[dict]] = {}
    roots: List[dict] = []
    for s in flat:
        pid = s.get("parent_id")
        if pid and pid in by_id:
            children.setdefault(pid, []).append(s)
        else:
            roots.append(s)

    def _node(s: dict) -> dict:
        return dict(s, children=[_node(c)
                                 for c in children.get(s["span_id"], ())])

    tree = [_node(r) for r in roots]

    critical: List[dict] = []
    if flat:
        cur = max(roots, key=lambda s: s.get("end_ts") or 0.0)
        while cur is not None:
            critical.append(cur)
            kids = children.get(cur["span_id"])
            cur = max(kids, key=lambda s: s.get("end_ts") or 0.0) \
                if kids else None

    def _tot(key: str) -> float:
        return sum(s.get(key) or 0.0 for s in critical)

    summary = {
        "trace_id": trace_id,
        "num_spans": len(flat),
        "num_processes": len({(s.get("node"), s.get("pid")) for s in flat}),
        "wall_s": (max(s.get("end_ts") or 0.0 for s in flat)
                   - min(s.get("start_ts") or 0.0 for s in flat))
        if flat else 0.0,
        "queue_wait_s": _tot("queue_wait_s"),
        "arg_fetch_s": _tot("arg_fetch_s"),
        "run_s": _tot("run_s"),
        "critical_path": [{
            "name": s.get("name"), "span_id": s.get("span_id"),
            "kind": s.get("kind"), "node": s.get("node"),
            "pid": s.get("pid"),
            "dur_s": (s.get("end_ts") or 0.0) - (s.get("start_ts") or 0.0),
            "queue_wait_s": s.get("queue_wait_s") or 0.0,
            "arg_fetch_s": s.get("arg_fetch_s") or 0.0,
            "run_s": s.get("run_s") or 0.0,
        } for s in critical],
    }
    return {"trace_id": trace_id, "spans": flat, "tree": tree,
            "summary": summary}


def trace_to_chrome_events(spans: List[dict]) -> List[dict]:
    """Chrome-trace events for one trace: an "X" slice per span grouped by
    (node, pid), plus flow events (``ph:"s"/"f"``) wherever a child span
    runs in a different process than its parent — Perfetto renders those
    as cross-process arrows."""
    by_id = {s["span_id"]: s for s in spans if s.get("span_id")}
    events: List[dict] = []

    def _proc(s: dict) -> str:
        node = s.get("node") or "?"
        return f"{str(node)[:8]}/pid{s.get('pid')}"

    for s in by_id.values():
        start = s.get("start_ts") or 0.0
        end = s.get("end_ts") or start
        events.append({
            "name": s.get("name"), "cat": s.get("kind") or "span",
            "ph": "X", "pid": _proc(s), "tid": s.get("pid") or 0,
            "ts": start * 1e6, "dur": max(end - start, 1e-6) * 1e6,
            "args": {
                "span_id": s.get("span_id"),
                "parent_id": s.get("parent_id"),
                "queue_wait_s": s.get("queue_wait_s"),
                "arg_fetch_s": s.get("arg_fetch_s"),
                "run_s": s.get("run_s"), "ok": s.get("ok"),
            },
        })
        parent = by_id.get(s.get("parent_id") or "")
        if parent is None:
            continue
        if (parent.get("node"), parent.get("pid")) == \
                (s.get("node"), s.get("pid")):
            continue
        flow_id = int(s["span_id"][:8], 16)
        p_start = parent.get("start_ts") or 0.0
        p_end = parent.get("end_ts") or p_start
        s_ts = min(max(s.get("submit_ts") or start, p_start), p_end)
        events.append({"name": "submit", "cat": "flow", "ph": "s",
                       "id": flow_id, "pid": _proc(parent),
                       "tid": parent.get("pid") or 0, "ts": s_ts * 1e6})
        events.append({"name": "submit", "cat": "flow", "ph": "f",
                       "bp": "e", "id": flow_id, "pid": _proc(s),
                       "tid": s.get("pid") or 0, "ts": start * 1e6})
    events.sort(key=lambda e: e["ts"])
    return events


def export_trace_chrome_trace(trace: dict, path: str) -> int:
    """Write an assembled trace (from ``state.get_trace``) as a chrome
    trace with cross-process flow arrows; returns the event count."""
    events = trace_to_chrome_events(trace.get("spans") or [])
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return len(events)


# ---------------------------------------------------------------------------
# process-local exports (historical API)

def collected_spans() -> List[Dict[str, Any]]:
    with _lock:
        return list(_spans)


def export_chrome_trace(path: str, include_task_events: bool = True) -> int:
    """Write collected spans (+ the cluster task timeline) as a chrome
    trace; returns the event count. Open in chrome://tracing or Perfetto."""
    events = collected_spans()
    if include_task_events:
        try:
            from ray_tpu._private.worker import global_worker

            for e in global_worker().rpc("list_task_events", {}):
                # FORWARDED is a hand-off record on the forwarding node;
                # the executing node logs the same task again — skip, as
                # state.events_to_chrome_trace does, or every spilled task
                # shows up twice.
                if e.get("state") == "FORWARDED":
                    continue
                if e.get("start_ts") and e.get("end_ts"):
                    events.append({
                        "name": e["name"], "ph": "X", "pid": 1,
                        "tid": int.from_bytes(
                            e["task_id"][:4], "little") % 1_000_000,
                        "ts": e["start_ts"] * 1e6,
                        "dur": (e["end_ts"] - e["start_ts"]) * 1e6,
                        "args": {"state": e["state"]},
                    })
        except Exception:
            pass
    with open(path, "w") as f:
        json.dump({"traceEvents": events}, f)
    return len(events)
