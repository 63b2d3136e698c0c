"""Goodput & step-anatomy telemetry: where training wall time actually goes.

Training throughput has been flat for rounds (ROADMAP item 4) while the
runtime instrumented only its control planes — traces, profiles, scheduler
metrics — and stayed blind inside the train step.  This module is the
missing layer: a per-step anatomy timer that splits every step into
data-wait / host-to-device / compute (block-until-ready bracket) /
checkpoint, tracks compile time and restarts separately, and attributes the
run's whole wall clock to goodput vs badput buckets that sum to elapsed
time by construction (idle is the remainder):

    goodput    — compute seconds inside steps (the block-until-ready span)
    compile    — jit/AOT compilation brackets
    data_stall — data-wait + host-to-device inside steps
    checkpoint — checkpoint save brackets inside steps
    recovery   — restart/recovery brackets (elastic re-gang, restore)
    idle       — everything unaccounted (framework overhead, between-step
                 host work, controller polling)

The tf.data service paper (PAPERS.md 2210.14826) is the motivation for the
data_stall split: input-wait routinely dominates step time and must be
measured per-step to be attacked.

Usage (see train/llama3.py for the production hook):

    gp = GoodputTracker(run="llama3-8b", tokens_per_step=B * S)
    with gp.compile_bracket():
        compiled = step.lower(state, batch).compile()
    gp.set_flops_per_step(*step_flops(compiled, n_params=n, tokens=B * S))
    for i in range(steps):
        with gp.step() as st:
            with st.phase("data"):
                batch_np = next(it)
            with st.phase("h2d"):
                batch = jax.device_put(batch_np)
            with st.phase("compute"):
                state, metrics = compiled(state, batch)
                jax.block_until_ready(metrics)
            if want_ckpt:
                with st.phase("checkpoint"):
                    save(state)
    report = gp.report()   # buckets sum to elapsed_s; MFU, steady tok/s
    gp.close()             # final goodput_push to the node scheduler

Records ride the existing push plane (``goodput_push`` — the same lane as
``spans_push``/``profiles_push``), are banked per node scheduler (bounded
by ``RTPU_GOODPUT_CAP``), and surface through ``state.get_goodput``, the
dashboard's ``/api/goodput``, and ``rtpu goodput``.

MFU accounting: counted FLOPs per step come from the compiled program's
``cost_analysis()`` when available, else the analytic dense-LM
``6 * n_params * tokens`` (attention inner products and non-matmul work are
NOT counted as useful flops), divided by ``RTPU_GOODPUT_PEAK_TFLOPS``
(default 197, the v5e's bf16 peak in TFLOP/s).
"""

from __future__ import annotations

import os
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Dict, List, Optional, Tuple

PHASES = ("data", "h2d", "compute", "checkpoint")
BUCKETS = ("goodput", "compile", "data_stall", "checkpoint", "recovery",
           "idle")

# ---------------------------------------------------------------------------
# process-global metric instruments (created once; every tracker shares them,
# distinguished by the "run" tag)

_metrics_lock = threading.Lock()
_METRICS: Optional[dict] = None

_STEP_BOUNDARIES = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0,
                    2.5, 5.0, 15.0, 60.0)


def _instruments() -> dict:
    global _METRICS
    with _metrics_lock:
        if _METRICS is None:
            from ray_tpu.util.metrics import Counter, Gauge, Histogram

            _METRICS = {
                "step": Histogram(
                    "train_step_s", "Wall time per training step",
                    boundaries=_STEP_BOUNDARIES, tag_keys=("run",)),
                "phase": Histogram(
                    "train_step_phase_s",
                    "Per-step anatomy: data / h2d / compute / checkpoint",
                    boundaries=_STEP_BOUNDARIES, tag_keys=("run", "phase")),
                "goodput_frac": Gauge(
                    "train_goodput_fraction",
                    "Fraction of run wall time spent in step compute",
                    tag_keys=("run",)),
                "badput": Gauge(
                    "train_badput_s",
                    "Cumulative badput seconds per bucket "
                    "(compile/data_stall/checkpoint/recovery/idle)",
                    tag_keys=("run", "bucket")),
                "mfu": Gauge(
                    "train_mfu",
                    "Model flops utilization vs RTPU_GOODPUT_PEAK_TFLOPS "
                    "(counted flops: 6*N*tokens or compiled "
                    "cost_analysis)", tag_keys=("run",)),
                "tflops": Gauge(
                    "train_model_tflops_per_s",
                    "Counted model TFLOP/s over steady-state steps",
                    tag_keys=("run",)),
                "tok_s": Gauge(
                    "train_tokens_per_sec",
                    "Steady-state (post-warmup) training throughput",
                    tag_keys=("run",)),
                "compile_s": Gauge(
                    "train_compile_s", "Cumulative compile seconds",
                    tag_keys=("run",)),
                "restarts": Counter(
                    "train_restarts_total",
                    "Training restarts/recoveries", tag_keys=("run",)),
            }
        return _METRICS


# ---------------------------------------------------------------------------
# FLOPs accounting

def analytic_step_flops(n_params: int, tokens: int) -> float:
    """Dense-LM counted flops for one step: 6*N*tokens (fwd 2N + bwd 4N per
    token; attention inner products and non-matmul work excluded)."""
    return 6.0 * float(n_params) * float(tokens)


def compiled_flops(compiled) -> Optional[float]:
    """Counted flops from a compiled executable's cost analysis, or None.

    Accepts anything with ``cost_analysis()`` (jax ``Compiled`` objects).
    """
    try:
        flops = float(compiled.cost_analysis().get("flops", 0.0) or 0.0)
        return flops if flops > 0 else None
    except Exception:
        return None


def step_flops(compiled, n_params: int = 0,
               tokens: int = 0) -> Tuple[float, str]:
    """(flops_per_step, source): compiled ``cost_analysis()`` when it
    reports a usable number, else the analytic 6*N*tokens fallback."""
    flops = compiled_flops(compiled) if compiled is not None else None
    if flops is not None:
        return flops, "cost_analysis"
    return analytic_step_flops(n_params, tokens), "analytic"


def _peak_tflops() -> float:
    from ray_tpu._private import flags

    return float(flags.get("RTPU_GOODPUT_PEAK_TFLOPS"))


# ---------------------------------------------------------------------------
# the tracker

class _StepTimer:
    """Phase brackets for ONE step; handed out by GoodputTracker.step()."""

    def __init__(self):
        self.phases: Dict[str, float] = {}
        self.t0 = time.perf_counter()
        self.wall = 0.0

    @contextmanager
    def phase(self, name: str):
        if name not in PHASES:
            raise ValueError(f"unknown phase {name!r}; one of {PHASES}")
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.phases[name] = (self.phases.get(name, 0.0)
                                 + time.perf_counter() - t0)


class GoodputTracker:
    """Accumulates step anatomy + run-level goodput/badput for one run.

    Thread-compat: one tracker is driven by one training thread; report()
    and flush() may be called from that thread (the background metrics
    flusher reads only the shared Metric instruments, which lock
    themselves).
    """

    def __init__(self, run: str, tokens_per_step: int = 0,
                 flops_per_step: Optional[float] = None,
                 peak_tflops: Optional[float] = None,
                 warmup_steps: Optional[int] = None,
                 export_metrics: bool = True):
        from ray_tpu._private import flags

        self.run = str(run)
        self.tokens_per_step = int(tokens_per_step)
        self.flops_per_step = flops_per_step
        self.flops_source = "analytic" if flops_per_step is not None else None
        self.peak_tflops = (peak_tflops if peak_tflops is not None
                            else _peak_tflops())
        self.warmup_steps = (int(flags.get("RTPU_GOODPUT_WARMUP"))
                             if warmup_steps is None else int(warmup_steps))
        self._export = export_metrics
        self._flush_every = max(0.5, float(flags.get("RTPU_GOODPUT_FLUSH_S")))
        self._t_start = time.perf_counter()
        self._wall_start = time.time()
        self._phase_sum: Dict[str, float] = {p: 0.0 for p in PHASES}
        self._compile_s = 0.0
        self._recovery_s = 0.0
        self._restarts = 0
        self.steps = 0
        # post-warmup accounting for steady-state throughput
        self._steady_steps = 0
        self._steady_wall = 0.0
        # recent per-step anatomy ring for percentile reporting
        self._recent: "deque[dict]" = deque(maxlen=512)
        self._last_flush = 0.0
        self._closed = False
        _set_current(self)

    # -- brackets -----------------------------------------------------------

    @contextmanager
    def compile_bracket(self):
        """Bracket jit/AOT compilation; badput bucket 'compile'."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            dt = time.perf_counter() - t0
            self._compile_s += dt
            if self._export:
                _instruments()["compile_s"].set(
                    self._compile_s, tags={"run": self.run})

    @contextmanager
    def recovery(self):
        """Bracket a restart/restore; badput bucket 'recovery'."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.note_restart(time.perf_counter() - t0)

    def note_restart(self, seconds: float = 0.0):
        self._restarts += 1
        self._recovery_s += max(0.0, float(seconds))
        if self._export:
            _instruments()["restarts"].inc(tags={"run": self.run})

    @contextmanager
    def step(self):
        """Bracket one training step; yields the phase timer."""
        st = _StepTimer()
        try:
            yield st
        finally:
            st.wall = time.perf_counter() - st.t0
            self._absorb_step(st)

    # -- accounting ---------------------------------------------------------

    def _absorb_step(self, st: _StepTimer):
        self.steps += 1
        for p, dt in st.phases.items():
            self._phase_sum[p] += dt
        if self.steps > self.warmup_steps:
            self._steady_steps += 1
            self._steady_wall += st.wall
        rec = {p: st.phases.get(p, 0.0) for p in PHASES}
        rec["total"] = st.wall
        self._recent.append(rec)
        if self._export:
            m = _instruments()
            m["step"].observe(st.wall, tags={"run": self.run})
            for p, dt in st.phases.items():
                m["phase"].observe(dt, tags={"run": self.run, "phase": p})
            self._export_gauges()
        now = time.monotonic()
        if now - self._last_flush >= self._flush_every:
            self.flush()

    def set_flops_per_step(self, flops: float, source: str = "analytic"):
        self.flops_per_step = float(flops)
        self.flops_source = source

    def set_tokens_per_step(self, tokens: int):
        self.tokens_per_step = int(tokens)

    # -- derived numbers ----------------------------------------------------

    def _buckets(self, elapsed: float) -> Dict[str, float]:
        tracked = {
            "goodput": self._phase_sum["compute"],
            "compile": self._compile_s,
            "data_stall": self._phase_sum["data"] + self._phase_sum["h2d"],
            "checkpoint": self._phase_sum["checkpoint"],
            "recovery": self._recovery_s,
        }
        tracked["idle"] = max(0.0, elapsed - sum(tracked.values()))
        return tracked

    def tokens_per_sec_steady(self) -> Optional[float]:
        if not self.tokens_per_step or self._steady_wall <= 0:
            return None
        return self.tokens_per_step * self._steady_steps / self._steady_wall

    def model_tflops_per_s(self) -> Optional[float]:
        if not self.flops_per_step or self._steady_wall <= 0 \
                or not self._steady_steps:
            return None
        return (self.flops_per_step * self._steady_steps
                / self._steady_wall / 1e12)

    def mfu(self) -> Optional[float]:
        tf = self.model_tflops_per_s()
        if tf is None or not self.peak_tflops:
            return None
        return tf / self.peak_tflops

    def _export_gauges(self):
        m = _instruments()
        elapsed = time.perf_counter() - self._t_start
        buckets = self._buckets(elapsed)
        tags = {"run": self.run}
        if elapsed > 0:
            m["goodput_frac"].set(buckets["goodput"] / elapsed, tags=tags)
        for name in ("compile", "data_stall", "checkpoint", "recovery",
                     "idle"):
            m["badput"].set(buckets[name],
                            tags={"run": self.run, "bucket": name})
        tok_s = self.tokens_per_sec_steady()
        if tok_s is not None:
            m["tok_s"].set(tok_s, tags=tags)
        tf = self.model_tflops_per_s()
        if tf is not None:
            m["tflops"].set(tf, tags=tags)
        mfu = self.mfu()
        if mfu is not None:
            m["mfu"].set(mfu, tags=tags)

    @staticmethod
    def _pctiles(xs: List[float]) -> dict:
        if not xs:
            return {"mean_ms": 0.0, "p50_ms": 0.0, "p90_ms": 0.0}
        xs = sorted(xs)
        return {
            "mean_ms": round(sum(xs) / len(xs) * 1e3, 3),
            "p50_ms": round(xs[(len(xs) - 1) // 2] * 1e3, 3),
            "p90_ms": round(xs[int((len(xs) - 1) * 0.9)] * 1e3, 3),
        }

    def report(self) -> dict:
        """The goodput record: buckets sum to elapsed_s exactly."""
        elapsed = time.perf_counter() - self._t_start
        buckets = self._buckets(elapsed)
        anatomy = {p: self._pctiles([r[p] for r in self._recent])
                   for p in PHASES}
        anatomy["total"] = self._pctiles([r["total"] for r in self._recent])
        tok_s = self.tokens_per_sec_steady()
        tf = self.model_tflops_per_s()
        mfu = self.mfu()
        return {
            "run": self.run,
            "t0": self._wall_start,
            "ts": time.time(),
            "steps": self.steps,
            "warmup_steps": self.warmup_steps,
            "restarts": self._restarts,
            "elapsed_s": elapsed,
            "buckets": buckets,
            "fractions": {k: (v / elapsed if elapsed > 0 else 0.0)
                          for k, v in buckets.items()},
            "anatomy": anatomy,
            "phase_sum_s": dict(self._phase_sum),
            "compile_s": self._compile_s,
            "tokens_per_step": self.tokens_per_step,
            "tokens_per_sec_steady": tok_s,
            "flops_per_step": self.flops_per_step,
            "flops_source": self.flops_source,
            "model_tflops_per_s": tf,
            "peak_tflops": self.peak_tflops,
            "mfu": mfu,
        }

    # -- push plane ---------------------------------------------------------

    def flush(self) -> bool:
        """Push the current record to the node scheduler ("goodput_push",
        the spans_push/profiles_push lane).  Best-effort; returns whether
        the record landed."""
        self._last_flush = time.monotonic()
        from ray_tpu._private import worker as worker_mod

        ctx = worker_mod.global_worker_or_none()
        if ctx is None:
            return False
        rec = self.report()
        rec["source"] = (ctx.worker_id.hex()
                         if getattr(ctx, "worker_id", None) else "driver")
        rec["rank"] = _env_rank()
        try:
            ctx.rpc("goodput_push", {"records": [rec]})
            return True
        except Exception:
            return False

    def close(self):
        """Final gauge export + push; idempotent."""
        if self._closed:
            return
        self._closed = True
        if self._export:
            try:
                self._export_gauges()
            except Exception:
                pass
        self.flush()
        _clear_current(self)


def _env_rank() -> Optional[int]:
    # train workers run under a TrainContext; fall back to None elsewhere
    try:
        from ray_tpu.train.context import get_context

        return get_context().get_world_rank()
    except Exception:
        return None


# ---------------------------------------------------------------------------
# current-tracker registry (train/trainer.py's hook flushes on fn exit so a
# record lands even when the user loop never called close())

_current_lock = threading.Lock()
_current: Optional[GoodputTracker] = None


def _set_current(gp: GoodputTracker):
    global _current
    with _current_lock:
        _current = gp


def _clear_current(gp: GoodputTracker):
    global _current
    with _current_lock:
        if _current is gp:
            _current = None


def current_tracker() -> Optional[GoodputTracker]:
    return _current


def flush_current(final: bool = False) -> bool:
    """Flush (and with ``final=True`` close) the process's active tracker."""
    gp = current_tracker()
    if gp is None:
        return False
    if final:
        gp.close()
        return True
    return gp.flush()


# ---------------------------------------------------------------------------
# merge helpers shared by state.py, the dashboard, and the CLI (none of
# which may assume a driver context — same pattern as profiling.py)

def merge_goodput_rows(rows: List[dict]) -> List[dict]:
    """Dedupe per-(run, source) summary rows across nodes, newest first."""
    best: Dict[tuple, dict] = {}
    for r in rows:
        key = (r.get("run"), r.get("source"))
        cur = best.get(key)
        if cur is None or (r.get("ts") or 0) > (cur.get("ts") or 0):
            best[key] = r
    return sorted(best.values(), key=lambda r: r.get("ts") or 0,
                  reverse=True)


def merge_records(records: List[dict]) -> Optional[dict]:
    """Combine one run's per-process records into a run view.

    For the common single-process run the summary IS the record.  For
    SPMD multi-worker runs the workers proceed in lockstep, so: steps /
    elapsed / compile are max over ranks, buckets are averaged (each
    rank attributes its own wall clock), throughput sums (each rank
    feeds distinct tokens), and mfu averages (it is already per-chip).
    """
    records = [r for r in records if r]
    if not records:
        return None
    records = merge_goodput_rows(records)
    n = len(records)
    buckets = {k: sum((r.get("buckets") or {}).get(k, 0.0)
                      for r in records) / n for k in BUCKETS}
    elapsed = max(r.get("elapsed_s") or 0.0 for r in records)
    tok = [r.get("tokens_per_sec_steady") for r in records
           if r.get("tokens_per_sec_steady")]
    mfu = [r.get("mfu") for r in records if r.get("mfu")]
    primary = min(records, key=lambda r: (r.get("rank") is None,
                                          r.get("rank") or 0))
    return {
        "run": primary.get("run"),
        "num_sources": n,
        "records": records,
        "summary": {
            "steps": max(r.get("steps") or 0 for r in records),
            "restarts": sum(r.get("restarts") or 0 for r in records),
            "elapsed_s": elapsed,
            "buckets": buckets,
            "fractions": {k: (v / elapsed if elapsed > 0 else 0.0)
                          for k, v in buckets.items()},
            "compile_s": max(r.get("compile_s") or 0.0 for r in records),
            "tokens_per_sec_steady": sum(tok) if tok else None,
            "mfu": (sum(mfu) / len(mfu)) if mfu else None,
            "anatomy": primary.get("anatomy"),
        },
    }
