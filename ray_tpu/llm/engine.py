"""Continuous-batching LLM engine for TPU.

Counterpart of the vLLM engine the reference wraps
(/root/reference/python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_engine.py:181, engine start :312): an admission queue + slot table in
front of two compiled programs — a per-bucket prefill and ONE batched decode
step (llm/model.py).  The scheduler thread admits waiting requests into free
slots whenever pages are available (prefill), then advances every active
slot one token per iteration (decode), streaming tokens into per-request
queues.  Static shapes throughout: no recompiles after warmup.

A model configuration with a ``block_length`` (models/sdar_moe.py)
generates by diffusion over blocks: a slot then holds an open BLOCK of
positions, a decode step is one denoising pass over every slot's block
(``lm.block_step``) that fills 0..block_length of its masks, a block's K/V
is final only after a pass whose input held no mask, and a prefill emits
nothing.  The loop, its phases, admission, the page pool, the prefix cache
and preemption are the same code; what differs is marked "block" below.

A prompt longer than the largest prefill bucket C is computed in CHUNKS of
C: the first by ``lm.prefill``, the rest by ``lm.prefill_with_prefix`` over
what the earlier chunks wrote (the program a prefix hit runs), ONE chunk an
iteration of the loop, so a decode burst runs between two chunks whenever a
slot is live.  The slot is taken at the first chunk and decodes after the
last (``_Slot.prefill_at``); a ``prefill_only`` request (P/D) holds no slot
and runs all its chunks inline before its pages ship.  A model with window layers (``cfg.window``,
paged_cache.py) has a second allocator and a second page list a slot
(``wpages``); what differs is marked "window" below.  A model whose
recurrent layers take a prompt in chunks (it refuses neither
``chunked_prompt`` nor ``suffix_prefill``: models/minicpm_sala.py) has the
later chunks go on from the slot's state rows, which only a prompt's FIRST
chunk begins anew; what its block-sparse layers read of a slot's pages a
step its programs count themselves, on the device, from the lists the
kernel is handed (``counted``; ops/block_sparse.py ``walked``).
"""

from __future__ import annotations

import functools
import queue as queue_mod
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu._private import flags
from ray_tpu.llm import model as lm
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.kv_tier import KVPullError
from ray_tpu.llm.paged_cache import (CacheConfig, PageAllocator, PrefixCache,
                                     init_cache, init_state)
from ray_tpu.ops import paged_attention
from ray_tpu.util import tracing

# Serving observability (ISSUE 8): the engine-local stats() dict stays the
# cheap in-process view, but the same events also feed util.metrics so
# TTFT/TPOT/e2e land on /metrics as real histograms and ride the existing
# metrics push plane.  Created lazily once per process; every engine in
# the process shares the instruments.
_METRICS = None
_metrics_lock = threading.Lock()


def _engine_metrics():
    global _METRICS
    with _metrics_lock:
        if _METRICS is None:
            from ray_tpu.util.metrics import Counter, Gauge, Histogram

            _METRICS = {
                "ttft": Histogram(
                    "llm_ttft_s", "Time to first token (submit -> first "
                    "emitted token)"),
                "tpot": Histogram(
                    "llm_tpot_s", "Time per output token after the first "
                    "(decode steady state)"),
                "e2e": Histogram(
                    "llm_e2e_s", "End-to-end request latency (submit -> "
                    "stream end)"),
                "queue_wait": Histogram(
                    "llm_queue_wait_s", "Submit -> admission wait (slot + "
                    "pages available)"),
                "prefill_t": Histogram(
                    "llm_prefill_s", "Prefill compute time per request"),
                "prefills": Counter(
                    "llm_prefills_total", "Prefill executions"),
                "decode_steps": Counter(
                    "llm_decode_steps_total", "Batched decode steps (for "
                    "a block-diffusion model, denoising passes)"),
                "decode_programs": Counter(
                    "llm_decode_programs_total", "Device programs the "
                    "token-at-a-time decode loop launched (a greedy burst: "
                    "one a step and no other)"),
                "decode_fetches": Counter(
                    "llm_decode_fetches_total", "Arrays that loop fetched "
                    "from the device (a greedy burst: ONE, its tokens and "
                    "what its steps counted together)"),
                "block_slot_passes": Counter(
                    "llm_block_slot_passes_total", "Denoising passes a "
                    "live slot took part in (tokens / this = tokens a "
                    "slot's pass yields)"),
                "masks_filled": Counter(
                    "llm_masks_filled_total", "Masked positions that "
                    "denoising passes filled"),
                "experts_read": Counter(
                    "llm_experts_read_total", "Experts the routed layers "
                    "of denoising passes, decode steps and prefills read "
                    "(x 3 matrices = the grouped product's weight "
                    "traffic)"),
                "moe_local_rows": Counter(
                    "llm_moe_local_rows_total", "A chip's share of an "
                    "expert-parallel layer: (token, expert) rows the "
                    "experts HELD here computed, summed over layers "
                    "(counted by the programs, on the device)"),
                "moe_zero_picks": Counter(
                    "llm_moe_zero_picks_total", "Of the router's picks, "
                    "those on identity experts (no weights: the pick's "
                    "weight times the layer's input), summed over layers"),
                "moe_absent_picks": Counter(
                    "llm_moe_absent_picks_total", "Of the router's picks, "
                    "those on experts another chip holds, which add "
                    "nothing here, summed over layers"),
                "blocks_final": Counter(
                    "llm_blocks_final_total", "Blocks whose K/V a pass "
                    "over their mask-free tokens made final"),
                "state_slot_steps": Counter(
                    "llm_state_slot_steps_total", "Recurrent state: live "
                    "slots whose rows a decode step updated, summed over "
                    "the steps (x the bytes of a slot's rows = what the "
                    "update has to move)"),
                "state_resets": Counter(
                    "llm_state_resets_total", "Recurrent state: "
                    "admissions whose prefill began a slot's rows anew "
                    "from a zero state"),
                "scan_chunks": Counter(
                    "llm_scan_chunks_total", "Recurrent state: chunks the "
                    "prefills' chunked recurrence went through (a "
                    "bucket's, its padding included: the program scans "
                    "them all), summed over the recurrent layers"),
                "decode_pages_read": Counter(
                    "llm_decode_pages_read_total", "KV pages the decode "
                    "kernel walked: per step and active slot, the pages "
                    "its position reaches"),
                "decode_pages_in_runs": Counter(
                    "llm_decode_pages_in_runs_total", "Of those, pages "
                    "that moved several to a copy: their table entries "
                    "were page ids in a row (counted on the host from the "
                    "tables a burst is handed; a pool whose pages move one "
                    "by one counts none, nor do walks through lists the "
                    "device chooses)"),
                "latent_pages_read": Counter(
                    "llm_latent_pages_read_total", "Of those, pages of "
                    "LATENT rows (one row a token a layer, key and value "
                    "both): x page_size x the row's bytes x layers = what "
                    "the latent decode kernel has to read"),
                "sparse_blocks_selected": Counter(
                    "llm_sparse_blocks_selected_total", "Sparse layers: "
                    "blocks a decode step's lists held, summed over live "
                    "slots, KV heads and sparse layers (counted by the "
                    "step, on the device, from the lists' lengths)"),
                "sparse_pages_read": Counter(
                    "llm_sparse_pages_read_total", "Sparse layers: pages "
                    "the decode kernel's lists held, as far as the query's "
                    "own position, summed over live slots, KV heads and "
                    "sparse layers (counted by the step, on the device)"),
                "sparse_pages_resident": Counter(
                    "llm_sparse_pages_resident_total", "Sparse layers: "
                    "pages the slots held at that step, what a walk of "
                    "the whole table would have read, in the same sum"),
                "dense_rule_slot_steps": Counter(
                    "llm_dense_rule_slot_steps_total", "Sparse layers: "
                    "of the steps a live slot a sparse layer, those whose "
                    "lists held the whole context (at or under dense_len)"),
                "index_rows_written": Counter(
                    "llm_index_rows_written_total", "Sparse layers: rows "
                    "of pooled keys completed, by prefills (a row a page "
                    "but the last of the prompt) and by decode steps (the "
                    "step that fills a page), summed over sparse layers "
                    "(counted by the programs, on the device)"),
                "sparse_prefill_tiles_causal": Counter(
                    "llm_sparse_prefill_tiles_causal_total", "Sparse "
                    "layers: tiles of keys that a tile of a prefill's "
                    "queries reaches (at or under its last position, "
                    "under the prompt's end), summed over query tiles, KV "
                    "heads and sparse layers (counted by the program, on "
                    "the device, from the table its kernel is handed)"),
                "sparse_prefill_tiles_visited": Counter(
                    "llm_sparse_prefill_tiles_visited_total", "Of those, "
                    "tiles in which a query selected a block it sees: "
                    "the ones the prefills' kernel copies and multiplies"),
                "prefill_chunks": Counter(
                    "llm_prefill_chunks_total", "Prefill executions that "
                    "computed one chunk of a prompt longer than the "
                    "largest prefill bucket"),
                "window_pages_freed": Counter(
                    "llm_window_pages_freed_total", "Window layers: pages "
                    "given back to their pool while the sequence lived, "
                    "because they lay wholly behind its window"),
                "window_pages_read": Counter(
                    "llm_window_pages_read_total", "Window layers: pages a "
                    "decode step's kernel walked a layer, from the page "
                    "that holds length - window to the slot's length, "
                    "summed over steps and active slots"),
                "window_pages_in_runs": Counter(
                    "llm_window_pages_in_runs_total", "Window layers: of "
                    "the pages read, those that moved several to a copy "
                    "(the window pool's table, as decode_pages_in_runs "
                    "counts the other's)"),
                "window_pages_skipped": Counter(
                    "llm_window_pages_skipped_total", "Window layers: "
                    "pages up to a slot's length that the bound left "
                    "unread a layer (read + skipped = what a full layer "
                    "walks)"),
                "full_pages_read": Counter(
                    "llm_full_pages_read_total", "Of a model with window "
                    "layers, pages a decode step's kernel walked in a "
                    "FULL layer"),
                "tokens": Counter(
                    "llm_tokens_total", "Tokens emitted to callers"),
                "deliveries": Counter(
                    "llm_deliveries_total", "Times the engine loop handed "
                    "the streams what its replays had left for them "
                    "(tokens / this = what one delivery carries)"),
                "deliveries_behind_dispatch": Counter(
                    "llm_deliveries_behind_dispatch_total", "Deliveries "
                    "made right after a device program was dispatched, "
                    "not at the end of an iteration that dispatched none"),
                "admitted": Counter(
                    "llm_admitted_total", "Requests admitted to slots"),
                "preempted": Counter(
                    "llm_preempted_total", "Requests preempted/evicted "
                    "from their slot"),
                "prefix_hit": Counter(
                    "llm_prefix_hit_tokens_total", "Prompt tokens served "
                    "from resident prefix-cache pages"),
                "prefix_lookup": Counter(
                    "llm_prefix_lookup_tokens_total", "Prompt tokens "
                    "looked up against the prefix cache"),
                "page_evictions": Counter(
                    "llm_page_evictions_total", "Prefix-cache pages "
                    "reclaimed to satisfy allocations"),
                "eviction_scans": Counter(
                    "llm_eviction_scans_total", "Calls of the prefix "
                    "cache's eviction, one for all the pages a loop phase "
                    "is short of (page evictions / scans = pages a call "
                    "reclaimed)"),
                "eviction_blocks_examined": Counter(
                    "llm_eviction_blocks_examined_total", "Entries of the "
                    "prefix cache's eviction order those calls popped, "
                    "stale and pinned ones included, and blocks a forced "
                    "cut walked (examined / page evictions = what a "
                    "reclaimed page cost; a walk of the index reads in "
                    "the resident blocks a call)"),
                "prefill_saved": Counter(
                    "llm_prefill_tokens_saved_total", "Prompt tokens whose "
                    "prefill compute was skipped via resident prefix pages "
                    "or a COW boundary page"),
                "cache_evictions": Counter(
                    "llm_cache_evictions_total", "Prefix-cache block "
                    "evictions by class: cold_family (leaf of the least "
                    "recently hit family) vs hot_root_forced (chain cut "
                    "while its leaves were pinned)",
                    tag_keys=("class",)),
                "cow_copies": Counter(
                    "llm_cow_page_copies_total", "Copy-on-write boundary "
                    "page duplications (partial-block prefix reuse)"),
                "kv_seals": Counter(
                    "llm_kv_seals_total", "Hot family spines sealed into "
                    "the store-backed KV tier"),
                "kv_pulls": Counter(
                    "llm_kv_pulls_total", "Family spines pulled from the "
                    "KV tier and hydrated into the page pool"),
                "kv_pull_pages": Counter(
                    "llm_kv_pull_pages_total", "KV pages hydrated from "
                    "tier pulls (cold prefill compute avoided)"),
                "kv_pull_fallbacks": Counter(
                    "llm_kv_pull_fallbacks_total", "KV tier pulls that "
                    "fell back to cold prefill, by typed failure reason "
                    "(miss/evicted/store_died/truncated/corrupt/no_pages)",
                    tag_keys=("reason",)),
                "prefix_resident": Gauge(
                    "llm_prefix_resident_pages", "Cached-resident KV "
                    "pages with no live owner"),
                "active_slots": Gauge(
                    "llm_active_slots", "Decode slots currently occupied"),
                "free_pages": Gauge(
                    "llm_free_pages", "Allocatable KV-cache pages free"),
                "page_occupancy": Gauge(
                    "llm_page_occupancy", "Fraction of allocatable KV "
                    "pages in use"),
                "waiting": Gauge(
                    "llm_waiting", "Requests queued awaiting admission"),
                "pages_in_use": Gauge(
                    "llm_pages_in_use", "Pages live sequences hold, by "
                    "kind of pool (full | window; a model with one kind "
                    "of page: full)", tag_keys=("kind",)),
            }
        return _METRICS


def _inject_kv_pages_impl(cache_k, cache_v, idx, kv_k, kv_v):
    """Scatter shipped KV pages into the paged cache (P/D decode side).

    Donation makes this an in-place page write — without it every
    disaggregated admission would copy the whole multi-GiB cache.
    """
    return (cache_k.at[:, idx].set(kv_k), cache_v.at[:, idx].set(kv_v))


_inject_kv_pages = jax.jit(_inject_kv_pages_impl, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# Engine-loop anatomy (ISSUE 24): what the scheduler THREAD was doing, next
# to the per-request spans that say who was waiting.  Each iteration of
# _loop is cut into phases that do not overlap and together cover it.  The
# names are read by PERF.md and benchmarks/trace/host_phases.py.  A name
# may occur more than once an iteration: decode_emit is the replay of a
# burst and, with ``delivered``, a delivery (which may cut a dispatch phase
# in two).

P_HYDRATE = "llm.loop.hydrate"
P_ADMIT = "llm.loop.admit"
P_PREFILL_HOST = "llm.loop.prefill_host"
P_PREFILL_DISPATCH = "llm.loop.prefill_dispatch"
P_PREFILL_FETCH = "llm.loop.prefill_fetch"
P_PREFILL_EMIT = "llm.loop.prefill_emit"
P_DECODE_HOST = "llm.loop.decode_host"
P_DECODE_DISPATCH = "llm.loop.decode_dispatch"
P_DECODE_FETCH = "llm.loop.decode_fetch"
P_DECODE_EMIT = "llm.loop.decode_emit"
P_GAUGES = "llm.loop.gauges"
P_IDLE = "llm.loop.idle"
S_LOOP = "llm.loop"  # an iteration's umbrella span
S_COMPILE = "xla.compile"

# what the values a call site leaves in ``vals`` are called in the record
# (a ``vals`` that is a dict names its own: a delivery's decode_emit)
_PHASE_ATTRS = {
    P_HYDRATE: ("pages",), P_ADMIT: ("outcome",),
    P_PREFILL_HOST: ("bucket", "prefix_len"),
    P_PREFILL_DISPATCH: ("bucket",), P_PREFILL_FETCH: (),
    P_PREFILL_EMIT: (), P_DECODE_HOST: ("active_slots", "burst"),
    P_DECODE_DISPATCH: ("burst",), P_DECODE_FETCH: ("burst",),
    # a block-diffusion burst adds what its passes did
    # ... and a token-at-a-time burst names its own (a dict): ``tokens``,
    # ``slots_released``, its ``steps``, the device ``programs`` it
    # launched and the arrays it fetched (``fetches``), and what the
    # family counts: a model with recurrent layers ``state_slots``, the
    # live slots whose state rows the burst's steps updated, summed over
    # them; one with routed experts or latent pages the ``experts_read``
    # by the burst's steps and the ``latent_pages_read``; a model with
    # window layers ``window_pages_read``, ``window_pages_skipped`` and
    # ``full_pages_read`` (a layer of a kind)
    P_DECODE_EMIT: ("tokens", "slots_released", "slot_passes",
                    "masks_filled", "blocks_final", "experts_read",
                    "passes"),
    P_GAUGES: (),
    S_COMPILE: ("seconds", "phase", "program"),
}

# A phase (other than idle) that lasts longer is reported as an
# ``llm.loop_stall`` event: a burst's decode_fetch is 0.9 s at most.
STALL_S = 2.0

# What a phase's time is TO A REQUEST that stands behind it (ISSUE 51): a
# sampled loop keeps one running sum a class, a request takes a reading at
# each change of its state, and the differences go on its ``llm.queue``,
# ``llm.admission`` and ``llm.decode`` spans (``tracing.WAIT_ATTRS``).
# ``step``: a burst on the device and its launch; ``prefill``: some
# prompt's admission (the request's own apart, ``_Request.own_s``);
# ``host``: the loop's own work between programs, the banking of its spans
# included; ``idle``: nothing to do.
C_STEP, C_PREFILL, C_HOST, C_IDLE = range(4)
_PHASE_CLASS = {
    P_DECODE_DISPATCH: C_STEP, P_DECODE_FETCH: C_STEP,
    P_PREFILL_HOST: C_PREFILL, P_PREFILL_DISPATCH: C_PREFILL,
    P_PREFILL_FETCH: C_PREFILL, P_PREFILL_EMIT: C_PREFILL,
    # (an admit that HAS admitted is its prompt's: ``_ADMITTED``)
    P_ADMIT: C_HOST, P_HYDRATE: C_HOST, P_GAUGES: C_HOST,
    P_DECODE_HOST: C_HOST, P_DECODE_EMIT: C_HOST, P_IDLE: C_IDLE,
}
_ADMITTED = ("admitted",)  # an admit's ``vals`` once it has a request in


def _waited(a: Optional[tuple], b: Optional[tuple]) -> dict:
    """What the loop did between two readings of ``_LoopPhases.reading``,
    under ``tracing.WAIT_ATTRS``, seconds to 1 us: the five sum to the time
    between the readings.  {} where the loop is not sampled."""
    if a is None or b is None:
        return {}
    step, prefill, host, idle, own = (y - x for x, y in zip(a[1:], b[1:]))
    return {k: round(max(0.0, v), 6) for k, v in zip(
        tracing.WAIT_ATTRS, (step, own, prefill - own, host, idle))}

_mono = time.monotonic  # the loop's one clock (a test stretches it)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_sampled_loops: Dict[int, "_LoopPhases"] = {}  # engine thread -> its phases
_compile_listener_lock = threading.Lock()
_compile_listener_on = False


def _on_compile(name: str, secs: float, **kw) -> None:
    """``jax.monitoring`` listener: a backend compilation that ran on a
    sampled engine thread goes on that loop's timeline."""
    if name == _COMPILE_EVENT:
        ph = _sampled_loops.get(threading.get_ident())
        if ph is not None:
            ph.note_compile(secs, kw.get("fun_name"))


class _LoopPhases:
    """The phase open on one engine thread, and (when the loop is sampled)
    the records of the iteration so far.  Only that thread touches it.

    ``begin`` closes the open phase and opens the next at the same reading
    of the clock, so phases are disjoint and cover the iteration by
    construction.  Every phase is a ``jax.profiler.TraceAnnotation``: in a
    profiler session it lands on the host plane, on the profiler's clock,
    beside the device's operations; with no session that is a flag test.
    When the loop is NOT sampled nothing else happens: no record, no dict,
    no lock, no other clock.  When it is, a working iteration's phases are
    banked through ``tracing.record_span`` under one ``llm.loop`` span, in
    traces of the loop's own (``tracing.LoopTrace``), and every stretch of
    the clock, a phase or what lies between two, is added to the sum of
    its class (``_PHASE_CLASS``; between two phases ``host``, after an
    iteration that found nothing ``idle``): ``_mark`` is (when the open
    stretch began, its class, the four sums up to then), replaced whole
    at every turn, so ``reading`` needs no lock."""

    __slots__ = ("sampled", "it", "name", "t0", "req", "vals", "_ann",
                 "_done", "_trace", "_idle_t0", "_idle_it", "_idle_n",
                 "_slots", "_mark")

    def __init__(self, slots: list):
        self.sampled = False
        self.it = 0  # iteration number, on every annotation and record
        self.name: Optional[str] = None
        self.t0 = 0.0
        self.req: Optional[_Request] = None  # whom the open phase serves
        self.vals = ()  # its attributes, by _PHASE_ATTRS[name] or a dict
        self._ann = None
        self._done: list = []  # (name, t0, t1, req, vals), sampled only
        self._trace = tracing.LoopTrace()
        self._idle_t0: Optional[float] = None
        self._idle_it = self._idle_n = 0
        self._slots = slots  # the engine's slot table, for the stall event
        self._mark = (_mono(), C_IDLE, (0.0, 0.0, 0.0, 0.0))

    def begin(self, name: str, req: Optional[_Request] = None,
              vals: tuple = ()) -> None:
        """Open ``name``; attributes known only at its end go to ``vals``
        before the next ``begin``."""
        t = _mono()
        if self.name is not None:
            self._close(t)
        if self.sampled:
            self._turn(t, _PHASE_CLASS[name])
        self.name, self.t0, self.req, self.vals = name, t, req, vals
        self._ann = jax.profiler.TraceAnnotation(name, it=self.it)
        self._ann.__enter__()

    def end(self) -> None:
        if self.name is not None:
            t = _mono()
            self._close(t)
            if self.sampled:
                self._turn(t, C_HOST)
            self.name = self.req = None
            self.vals = ()

    def _upto(self, t: float) -> tuple:
        """(the four sums with the open stretch counted up to ``t``, its
        class, its length so far)."""
        t0, cls, sums = self._mark  # ONE load: a reader on another thread
        if cls == C_HOST and self.vals == _ADMITTED:
            cls = C_PREFILL
        dt = t - t0
        return sums[:cls] + (sums[cls] + dt,) + sums[cls + 1:], cls, dt

    def _turn(self, t: float, nxt: int) -> None:
        """Close the open stretch at ``t`` into its class's sum, and into
        its request's own where it was that request's prefill; a stretch
        of class ``nxt`` opens."""
        sums, cls, dt = self._upto(t)
        if cls == C_PREFILL and self.req is not None:
            self.req.own_s += dt
        self._mark = (t, nxt, sums)

    def reading(self, req: _Request) -> Optional[tuple]:
        """(wall clock now, step, prefill, host, idle, ``req``'s own
        prefill): the sums as they stand, the open stretch counted in its
        class, so that two readings differ by exactly the time between
        them.  None where the loop is not sampled.  The submitting thread
        calls this without a lock: its reading is whole (one load of
        ``_mark``), and stale by at most the stretch that is open, which
        it may count in the class of the one before (an admit that goes
        on to admit, or a turn the loop made during the call)."""
        if not self.sampled:
            return None
        sums, cls, dt = self._upto(_mono())
        own = req.own_s + (dt if cls == C_PREFILL and self.req is req else 0)
        return (time.time(),) + sums + (own,)

    def _close(self, t: float) -> None:
        self._ann.__exit__(None, None, None)
        if self.sampled:
            self._done.append((self.name, self.t0, t, self.req, self.vals))
            if t - self.t0 > STALL_S:
                self._stall(t - self.t0)

    def finish_iteration(self, worked: bool) -> None:
        """Close the iteration: bank its phases if it did anything, else
        fold it into the one idle span that lasts until work is found."""
        self.end()
        if not self.sampled:
            return
        if not worked:  # what follows, the sleep too, is idle time
            self._mark = (self._mark[0], C_IDLE, self._mark[2])
        now = _mono()
        start = min((r[1] for r in self._done), default=now)
        if not worked:
            if self._idle_t0 is None:
                self._idle_t0, self._idle_it, self._idle_n = start, self.it, 0
            self._idle_n += 1
        else:
            wall = time.time() - now  # wall time = the loop's clock + this
            self.flush_idle(start, wall)
            tid = self._trace.take(len(self._done) + 1)
            loop_id = tracing.record_span(
                tid, S_LOOP, start + wall, self._done[-1][2] + wall,
                kind="engine", attrs={"it": self.it})
            for name, t0, t1, req, vals in self._done:
                attrs = dict(vals if type(vals) is dict
                             else zip(_PHASE_ATTRS[name], vals), it=self.it)
                if req is not None:
                    attrs["request_id"] = req.request_id
                    if req.trace_ctx is not None:
                        attrs["request_trace_id"] = req.trace_ctx[0]
                tracing.record_span(tid, name, t0 + wall, t1 + wall,
                                    parent_id=loop_id, kind="engine",
                                    attrs=attrs)
        self._done.clear()

    def flush_idle(self, until: float, wall: float) -> None:
        if self._idle_t0 is not None:
            tracing.record_span(
                self._trace.take(1), P_IDLE, self._idle_t0 + wall,
                until + wall, kind="engine",
                attrs={"it": self._idle_it, "iterations": self._idle_n})
            self._idle_t0 = None

    def note_compile(self, secs: float, program) -> None:
        t = _mono()
        self._done.append((S_COMPILE, t - secs, t, self.req,
                           (round(secs, 6), self.name, program)))

    def _stall(self, seconds: float) -> None:
        try:
            from ray_tpu.util import events

            events.emit(
                "llm.loop_stall", severity="warning",
                message=f"engine loop spent {seconds:.2f} s in {self.name} "
                        f"(iteration {self.it})",
                data={"phase": self.name, "seconds": round(seconds, 3),
                      "it": self.it, "active_slots": sum(
                          s is not None for s in self._slots)})
        except Exception:
            pass


@dataclass
class _Request:
    request_id: str
    prompt_tokens: List[int]
    params: SamplingParams
    out_queue: queue_mod.Queue = field(default_factory=queue_mod.Queue)
    submitted_at: float = field(default_factory=time.monotonic)
    # P/D disaggregation (reference: serve prefill_decode_disagg.py):
    # "normal" | "prefill_only" (run prefill, ship KV pages + first token)
    # | "decode_kv" (inject shipped KV, skip prefill compute entirely)
    kind: str = "normal"
    first_token: Optional[int] = None  # decode_kv: token prefill sampled
    kv: Optional[tuple] = None  # decode_kv: (kv_k, kv_v) page arrays
    first_token_at: Optional[float] = None  # monotonic ts of first emit
    emitted: int = 0  # tokens delivered to the caller
    # Tokens produced toward max_tokens, surviving preemption/resume: a
    # preempted request folds its generated tokens into the prompt, so
    # len(slot.generated) restarts from zero while `produced` does not.
    produced: int = 0
    # Per-request trace anatomy (ISSUE 20): the submitting thread's
    # (trace_id, parent span_id) captured at submit; the scheduler thread
    # has no thread-local context, so every phase span it records carries
    # this explicitly.  span_id is the umbrella "llm.request" span phase
    # spans parent under; submitted_wall anchors it on the wall clock
    # (spans are wall-time; submitted_at stays monotonic for latency math).
    trace_ctx: Optional[tuple] = None
    span_id: Optional[str] = None
    submitted_wall: float = field(default_factory=time.time)
    preempts: int = 0
    # What the loop was doing while this request stood (ISSUE 51; a traced
    # request of a sampled loop only): the loop's time under the prefill
    # phases that carried THIS request, and its readings of the loop's
    # sums (``_LoopPhases.reading``) when it joined the queue (submit, or
    # a preemption), at the first program of its admission (None once
    # that admission's span is banked) and at its first token; ``chunks``
    # counts the programs of the admission that is open.
    own_s: float = 0.0
    at_queued: Optional[tuple] = None
    at_program: Optional[tuple] = None
    at_token: Optional[tuple] = None
    chunks: int = 0


@dataclass
class _Slot:
    request: _Request
    pages: List[int]
    num_tokens: int  # tokens with KV in cache (prompt + generated)
    last_token: int
    generated: List[int] = field(default_factory=list)
    rng: Optional[np.random.Generator] = None
    # block diffusion: the open block at positions [num_tokens, num_tokens
    # + B), as the last pass left it.  ``blk_masked`` is the state (an id
    # drawn from the vocabulary may be the mask token's); the first
    # ``blk_given`` positions are the prompt's tail and are never emitted
    blk_tokens: List[int] = field(default_factory=list)
    blk_masked: List[bool] = field(default_factory=list)
    blk_given: int = 0
    blk_step: int = 0  # passes this block has had
    # a prompt in chunks: the position its next chunk begins at (the slot
    # does not decode yet); None once the whole prompt is computed
    prefill_at: Optional[int] = None
    # window: the window layers' pages BY ABSOLUTE PAGE INDEX, the null
    # page 0 where one was given back (paged_cache.py)
    wpages: List[int] = field(default_factory=list)


def _by_name(counted: dict) -> dict:
    """A program's fetched ``counted`` by counter name.  A key is a name
    (its value a scalar) or a TUPLE of names (a vector of as many).  Every
    array fetched is a round trip of ~0.3 ms on the chip's host: five
    scalars a step were 40 a burst and 7 % of a token's time for
    models/minicpm_sala.py, a vector a step 8 and 1.8 % (PERF.md section 6,
    PR 49), hence one vector a step; a greedy burst's ride in the array
    its tokens come back in (``_burst_counts``)."""
    out = {}
    for key, n in counted.items():
        if isinstance(key, tuple):
            out.update(zip(key, (int(x) for x in n)))
        else:
            out[key] = int(n)
    return out


def _burst_counts(layout: tuple, flat) -> dict:
    """A greedy burst's counts by counter name: ``flat`` is the sum over
    the burst's rows of ``acc``'s columns behind the tokens, which hold a
    step's ``counted`` key after key as ``layout`` (``lm.counted_layout``)
    says."""
    counted, at = {}, 0
    for key, entries in layout:
        counted[key] = (flat[at:at + entries] if isinstance(key, tuple)
                        else flat[at])
        at += entries
    return _by_name(counted)


class LLMEngine:
    """Single-process engine; wrap in an actor for serving (server.py).

    What the family of ``model_cfg`` is, the engine reads off the
    configuration's own declaration (llm/model.py says who owns which
    decision) and nowhere else: ``cache_layout()`` is what it allocates
    (page pools for the layers that attend, a latent pool, state rows a
    slot beside them), ``serving_layout`` how it holds the tree,
    ``block_length`` whether a decode step is ``decode_step`` /
    ``decode_step_greedy_chained`` or ``block_step``, and ``refuses`` what
    it cannot be served with: a feature listed there is refused by the
    family's own sentence (``_refuse``) or not built (``prefix_cache``,
    ``kv_tier``).  What the programs counted comes back by name and goes
    to ``stats()``, the metrics and the spans under that name.

    A slot's state rows are begun anew by the prefill that admits a
    sequence to it (``lm.prefill``: a prompt's first chunk or all of it,
    from a zero state, whatever the last tenant left; a later chunk goes on
    from them) and mean nothing once it is released; without a
    ``PrefixCache`` a
    preempted sequence's resume prefill recomputes from position 0.  The
    same holds for the rows a family's PAGES hold in slot order
    (paged_cache.py ``CacheConfig``: written from row 0 by that prefill,
    read as far as the slot's context has completed them): a sequence
    takes a slot only through a prefill that names it, and never moves.

    The engine holds the parameters in the SERVING layout, made once here
    from whichever tree it is handed, and keeps no reference to the
    unstacked weights: they are freed when the caller lets go.
    """

    def __init__(self, params, model_cfg, cfg: Optional[EngineConfig] = None,
                 kv_tier=None):
        self.cfg = cfg or EngineConfig()
        self.model_cfg = model_cfg
        self.params = model_cfg.serving_layout(params)
        # block: positions a block (0: a token at a time)
        self._block = int(model_cfg.block_length)
        if self._block and (self.cfg.page_size % self._block
                            or self.cfg.max_seq_len % self._block):
            raise ValueError(
                f"a block of {self._block} positions must divide page_size "
                f"({self.cfg.page_size}) and max_seq_len "
                f"({self.cfg.max_seq_len}): a block lies in one page")
        layout = model_cfg.cache_layout()
        # window: positions a window layer sees (0: the model has none)
        self._window = int(model_cfg.window)
        ps = self.cfg.page_size
        # a prompt past the largest bucket is computed in chunks of it
        self._chunk = int(self.cfg.prefill_buckets[-1])
        # window: what one sequence holds of a window layer's pool at most
        # (CacheConfig.window_pages_per_seq), and the pool's default size
        most = -(-(self._window + self._chunk) // ps) + 1
        self.max_pages_per_seq = -(-self.cfg.max_seq_len // ps)
        ccfg = CacheConfig(
            **layout, num_pages=self.cfg.num_pages,
            page_size=ps, dtype=model_cfg.dtype,
            max_slots=self.cfg.max_slots,
            max_pages_per_seq=self.max_pages_per_seq,
            window_pages=(self.cfg.window_pages or max(
                self.cfg.max_slots * (self._window // ps + 4), most + 1))
            if self._window else 0)
        self.window_allocator: Optional[PageAllocator] = None
        if self._window:
            if most > ccfg.window_pages - 1:
                raise ValueError(
                    f"one sequence holds up to {most} pages of a window "
                    f"layer (window {self._window} + a chunk of "
                    f"{self._chunk}): window_pages {ccfg.window_pages} "
                    f"cannot admit one")
            self.window_allocator = PageAllocator(ccfg.window_pages)
        # (for latent pages cache_k is the one pool and cache_v None; for
        # pools by layer type each is a dict of a pool a kind)
        self.cache_k, self.cache_v = init_cache(ccfg)
        self._latent = "latent_dim" in layout
        # pages a block and a copy of the decode kernel's walk (the second
        # 1: a pool whose pages move one by one, and a family whose walks
        # go through lists the device chooses: the host sees none of them)
        pool = jax.tree_util.tree_leaves(self.cache_k)[0]
        self._walk_block, self._run_pages = paged_attention.walk_blocks(
            pool.shape, pool.dtype.itemsize, self.max_pages_per_seq)
        if layout.get("page_rows"):
            self._run_pages = 1
        # recurrent layers' rows, a slot each (None: the model has none)
        self.state = init_state(ccfg)
        self._state_layers = ccfg.state_layers
        self._scan_chunk = ccfg.scan_chunk  # of the family's prefill scan
        self.allocator = PageAllocator(self.cfg.num_pages)
        # Prefix caching (ISSUE 10): finished sequences leave their full
        # prompt pages resident; later prompts sharing a page-aligned
        # prefix skip that prefill compute.  A pure index over pages — all
        # page ownership still flows through self.allocator, so swapping
        # the allocator (tests do) starts from an empty, consistent state.
        # A family that refuses it has every prompt computed whole.
        self.prefix_cache: Optional[PrefixCache] = (
            PrefixCache(self.cfg.page_size)
            if flags.get("RTPU_PREFIX_CACHE")
            and "prefix_cache" not in model_cfg.refuses else None)
        # Store-backed KV tier (ISSUE 16): hot family spines seal into
        # the shm store and failure/spill paths pull them back instead
        # of cold-prefilling.  All tier I/O (seal extraction, pull
        # hydration) runs on the scheduler thread — the single-writer
        # contract below covers it; kv_prehydrate() crosses threads only
        # through the thread-safe _hydrate_q.
        # (a server hands every engine its worker's tier unasked: a family
        # that refuses it lets it go, and refuses what asks for it)
        self.kv_tier = None if "kv_tier" in model_cfg.refuses else kv_tier
        self._hydrate_q: queue_mod.Queue = queue_mod.Queue()
        self._waiting: queue_mod.Queue = queue_mod.Queue()
        # a greedy burst's first row index: a constant of the device (the
        # chained step donates the rest of its carry, never this)
        self._row0 = jnp.zeros((), jnp.int32)
        # Single-writer design: _slots, the allocator, and _stats are
        # mutated ONLY by the scheduler thread (_loop); other threads
        # submit through the thread-safe _waiting queue and read counters
        # via stats(), whose individual reads are GIL-atomic.  Do not add
        # cross-thread mutation without introducing a real lock.
        self._slots: List[Optional[_Slot]] = [None] * self.cfg.max_slots
        # (out_queue, items) a replay left for the streams, in order: put
        # by _deliver once the next device program is dispatched
        self._undelivered: List[tuple] = []
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        # decode-state host mirrors (device arrays rebuilt when they change)
        self._stats = {"prefills": 0, "decode_steps": 0,
                       "decode_programs": 0, "decode_fetches": 0,
                       "decode_pages_read": 0, "decode_pages_in_runs": 0,
                       "latent_pages_read": 0, "window_pages_in_runs": 0,
                       "block_slot_passes": 0,
                       "masks_filled": 0, "blocks_final": 0,
                       "experts_read": 0, "moe_local_rows": 0,
                       "moe_zero_picks": 0, "moe_absent_picks": 0,
                       "state_slot_steps": 0,
                       "state_resets": 0, "scan_chunks": 0,
                       "tokens_generated": 0, "deliveries": 0,
                       "deliveries_behind_dispatch": 0, "preempted": 0,
                       "admitted": 0, "page_evictions": 0,
                       "eviction_scans": 0, "eviction_blocks_examined": 0,
                       "prefill_tokens_saved": 0, "cow_copies": 0,
                       "kv_seals": 0, "kv_pulls": 0, "kv_pull_pages": 0,
                       "kv_pull_fallbacks": 0, "prefill_chunks": 0,
                       "window_pages_freed": 0, "window_pages_read": 0,
                       "window_pages_skipped": 0, "full_pages_read": 0,
                       "sparse_blocks_selected": 0, "sparse_pages_read": 0,
                       "sparse_pages_resident": 0,
                       "dense_rule_slot_steps": 0, "index_rows_written": 0,
                       "sparse_prefill_tiles_causal": 0,
                       "sparse_prefill_tiles_visited": 0}
        # Hit-aware admission (ISSUE 14): under pool pressure prefer the
        # waiting request whose prefix is resident, but never once the
        # head of the queue has waited longer than this cap (seconds) —
        # bounded unfairness, misses can't starve.
        self._admit_age_cap_s = flags.get("RTPU_ADMIT_AGE_CAP_S")
        self._m = _engine_metrics()
        # what the scheduler thread is doing, phase by phase (ISSUE 24)
        self._ph = _LoopPhases(self._slots)
        self._gauges_at = 0.0  # last gauge refresh (throttled in _loop)

    # ------------------------- public API ---------------------------------

    def start(self):
        if self._thread is None:
            # the loop is sampled iff requests are: read once, here
            self._ph.sampled = float(flags.get("RTPU_TRACE_SAMPLE")) > 0
            self._thread = threading.Thread(target=self._loop, daemon=True)
            self._thread.start()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=10)

    def submit(self, prompt_tokens: List[int],
               params: Optional[SamplingParams] = None) -> _Request:
        params = params or SamplingParams()
        if params.temperature > 0:
            self._refuse("sampling", f"temperature {params.temperature}")
        total = len(prompt_tokens) + params.max_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(
                f"prompt+max_tokens = {total} exceeds max_seq_len "
                f"{self.cfg.max_seq_len}")
        if len(prompt_tokens) > self._chunk:
            self._refuse("chunked_prompt",
                         f"a prompt of {len(prompt_tokens)} tokens, over "
                         f"the largest prefill bucket {self._chunk},")
        # Page 0 is the reserved null page, so only num_pages-1 are ever
        # allocatable: an infeasible request would otherwise sit at the
        # queue head forever, wedging the engine for everyone behind it.
        n_pages = -(-total // self.cfg.page_size)
        if n_pages > self.cfg.num_pages - 1:
            raise ValueError(
                f"request needs {n_pages} KV pages but the cache has only "
                f"{self.cfg.num_pages - 1} allocatable pages")
        req = _Request(request_id=uuid.uuid4().hex[:12],
                       prompt_tokens=list(prompt_tokens), params=params)
        self._trace_init(req)
        self._waiting.put(req)
        return req

    def prefill_extract(self, prompt_tokens: List[int],
                        params: Optional[SamplingParams] = None,
                        timeout_s: float = 300.0):
        """P/D disaggregation, prefill side: run ONLY the prefill, sample
        the first token, and return (first_token, kv_k, kv_v, n_tokens) —
        the KV page arrays a decode engine injects via submit_with_kv.
        Pages are freed here immediately; this engine keeps no state."""
        self._refuse("pd", "prefill_extract")
        if len(prompt_tokens) > self._chunk:
            self._refuse("chunked_prompt",
                         f"a prompt of {len(prompt_tokens)} tokens, over "
                         f"the largest prefill bucket {self._chunk},")
        self.start()
        params = params or SamplingParams()
        req = _Request(request_id=uuid.uuid4().hex[:12],
                       prompt_tokens=list(prompt_tokens), params=params,
                       kind="prefill_only")
        n_pages = -(-len(prompt_tokens) // self.cfg.page_size)
        if n_pages > self.cfg.num_pages - 1:
            raise ValueError(f"prompt needs {n_pages} KV pages > capacity")
        self._trace_init(req)
        self._waiting.put(req)
        item = req.out_queue.get(timeout=timeout_s)
        if isinstance(item, Exception):
            raise item
        tag, first, kv_k, kv_v = item
        assert tag == "prefill_done"
        req.out_queue.get(timeout=timeout_s)  # drain the None terminator
        return first, kv_k, kv_v, len(prompt_tokens)

    def submit_with_kv(self, prompt_tokens: List[int], first_token: int,
                       kv_k, kv_v,
                       params: Optional[SamplingParams] = None) -> _Request:
        """P/D disaggregation, decode side: admit a sequence whose prompt
        KV was computed elsewhere. No prefill compute happens here."""
        self._refuse("pd", "submit_with_kv")
        self.start()
        params = params or SamplingParams()
        total = len(prompt_tokens) + params.max_tokens
        if total > self.cfg.max_seq_len:
            raise ValueError(f"prompt+max_tokens {total} > max_seq_len")
        n_pages = -(-total // self.cfg.page_size)
        if n_pages > self.cfg.num_pages - 1:
            # same guard as submit(): an infeasible request would sit at
            # the queue head forever, wedging the engine
            raise ValueError(
                f"request needs {n_pages} KV pages but the cache has only "
                f"{self.cfg.num_pages - 1} allocatable pages")
        req = _Request(request_id=uuid.uuid4().hex[:12],
                       prompt_tokens=list(prompt_tokens), params=params,
                       kind="decode_kv", first_token=int(first_token),
                       kv=(kv_k, kv_v))
        self._trace_init(req)
        self._waiting.put(req)
        return req

    def _refuse(self, feature: str, where: str) -> None:
        """Raise, in the family's own words, if ``model_cfg`` declares that
        it cannot be served with ``feature`` (``where``: the call)."""
        lm.refuse(self.model_cfg, feature, where)

    def generate(self, prompt_tokens: List[int],
                 params: Optional[SamplingParams] = None,
                 timeout_s: float = 300.0) -> List[int]:
        """Blocking convenience: submit + drain to completion."""
        self.start()
        req = self.submit(prompt_tokens, params)
        out: List[int] = []
        deadline = time.monotonic() + timeout_s
        while True:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise TimeoutError(f"generation {req.request_id} timed out")
            item = req.out_queue.get(timeout=remaining)
            if item is None:
                return out
            if isinstance(item, Exception):
                raise item
            out.append(item)

    def stats(self) -> dict:
        active = sum(s is not None for s in self._slots)
        pc = self.prefix_cache
        # per-family heat rows (root digest hex + hits + resident blocks):
        # the controller's KV replication policy ranks families across
        # replicas from these.  family_stats iterates a dict the scheduler
        # thread mutates, which invalidates the iterator: retry.
        kv_families: List[dict] = []
        if pc is not None:
            for _ in range(4):
                try:
                    kv_families = pc.family_stats()[:8]
                    break
                except RuntimeError:
                    continue
        return {**self._stats, "active_slots": active,
                "kv_families": kv_families,
                "kv_tier": (self.kv_tier.stats()
                            if self.kv_tier is not None else None),
                "free_pages": self.allocator.num_free(),
                **self._pages_in_use(),
                "waiting": self._waiting.qsize(),
                # prefix-cache plane (ISSUE 10): hit/miss + resident pages
                # + recent block digests — the router's KV-locality signal
                "prefix_cache": pc.stats() if pc is not None else None,
                "resident_pages": self.allocator.num_resident(),
                "prefix_digests": pc.digests() if pc is not None else []}

    # ------------------------- scheduler loop ------------------------------

    def _loop(self):
        ph = self._ph
        if ph.sampled:
            self._watch_compiles()
        try:
            self._run_loop(ph)
        finally:
            self._deliver(False)  # stop(): what the last replay left
            ph.end()
            if ph.sampled:
                _sampled_loops.pop(threading.get_ident(), None)
                now = _mono()
                ph.flush_idle(now, time.time() - now)

    def _watch_compiles(self) -> None:
        """Put this thread's backend compilations on the loop's timeline
        (one listener a process, registered by its first sampled loop:
        jax.monitoring has no way to take one back)."""
        global _compile_listener_on
        _sampled_loops[threading.get_ident()] = self._ph
        with _compile_listener_lock:
            if not _compile_listener_on:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_compile)
                _compile_listener_on = True

    def _run_loop(self, ph: _LoopPhases):
        while not self._stop.is_set():
            ph.it += 1
            try:
                hydrated = self._drain_hydrations()
                admitted = self._admit()
                stepped = self._decode_all()
            except Exception as e:  # noqa: BLE001 — a dead scheduler
                # thread would hang every generate() forever; fail the
                # in-flight requests loudly instead and keep serving.
                import traceback

                traceback.print_exc()
                self._deliver(False)  # replayed tokens, then the error
                ph.finish_iteration(True)
                for i, s in enumerate(self._slots):
                    if s is not None:
                        self._fail(s.request, e)
                        self._free_pages(s)
                        self._slots[i] = None
                while True:
                    try:
                        req = self._waiting.get_nowait()
                    except queue_mod.Empty:
                        break
                    self._fail(req, e)
                continue
            # an iteration that gave the device no program delivers here:
            # nothing is stranded behind an idle loop
            delivered = not (admitted or stepped) and self._deliver(False)
            worked = admitted or stepped or hydrated or delivered
            now = time.monotonic()
            if now - self._gauges_at >= 0.25:
                self._gauges_at = now
                if worked:  # an idle iteration's refresh is idle time
                    ph.begin(P_GAUGES)
                self._refresh_gauges()
            ph.finish_iteration(worked)
            if not worked:
                with jax.profiler.TraceAnnotation(P_IDLE, it=ph.it):
                    time.sleep(0.002)

    def _refresh_gauges(self):
        m = self._m
        free = self.allocator.num_free()
        allocatable = self.cfg.num_pages - 1  # page 0 is the null page
        m["active_slots"].set(sum(s is not None for s in self._slots))
        m["free_pages"].set(free)
        if allocatable > 0:
            m["page_occupancy"].set(1.0 - free / allocatable)
        m["waiting"].set(self._waiting.qsize())
        m["prefix_resident"].set(self.allocator.num_resident())
        for name, n in self._pages_in_use().items():
            if name.endswith("_pages_in_use"):
                m["pages_in_use"].set(n, {"kind": name.split("_")[0]})

    def _pages_in_use(self) -> dict:
        """Pages live sequences hold, by kind of pool, and (window) the
        tokens those sequences hold: page bytes over them is what a
        resident token costs."""
        live = [s for s in self._slots if s is not None]
        out = {"full_pages_in_use": sum(len(s.pages) for s in live)}
        if self._window:
            out["window_pages_in_use"] = sum(
                sum(p != 0 for p in s.wpages) for s in live)
            out["live_tokens"] = sum(s.num_tokens for s in live)
        return out

    # -------------------- per-request trace anatomy (ISSUE 20) -------------

    def _trace_init(self, req: _Request) -> None:
        """Capture the submitting thread's trace context onto the request
        so the scheduler thread can stamp phase spans for it."""
        ctx = tracing.current_context()
        if ctx is not None:
            req.trace_ctx = ctx
            req.span_id = tracing.new_span_id()
            req.at_queued = self._ph.reading(req)

    def _span(self, req: _Request, name: str, t0: float, t1: float,
              ok: bool = True, **attrs) -> None:
        """One phase span under the request's umbrella span."""
        if req.trace_ctx is None:
            return
        tracing.record_span(
            req.trace_ctx[0], name, t0, t1, parent_id=req.span_id,
            kind="engine", ok=ok,
            attrs=dict(attrs, request_id=req.request_id))

    def _queue_ends(self, req: _Request, t: float) -> float:
        """A request's wait for a slot and pages ends at ``t`` (the loop's
        clock), where the first program of its admission begins (or its
        shipped pages go in): the wait since its submission, and for a
        traced request its ``llm.queue`` span, with what the loop did
        meanwhile.  A resumed request's second wait began at its
        preemption and has a span of its own."""
        qw = max(0.0, t - req.submitted_at)
        if req.trace_ctx is not None:
            now = self._ph.reading(req)
            w0, wait = req.submitted_wall, qw
            if req.preempts and req.at_queued is not None and now is not None:
                w0 = req.at_queued[0]
                wait = max(0.0, now[0] - w0)
            self._span(req, "llm.queue", w0, w0 + wait,
                       wait_s=round(wait, 6), resumed=bool(req.preempts),
                       **_waited(req.at_queued, now))
            req.at_program, req.chunks = now, 0
        return qw

    def _admission_ends(self, req: _Request, ok: bool = True) -> tuple:
        """The admission of a traced request ends: at its first token
        counted (or at a preemption, ``ok`` False).  ONE ``llm.admission``
        span however many programs computed the prompt: what the loop did
        between them is on it (``step_s``: the bursts run between this
        prompt's own chunks).  Returns the reading it ended at."""
        now = self._ph.reading(req)
        self._span(req, "llm.admission", req.at_program[0], now[0], ok=ok,
                   chunks=req.chunks, tokens=len(req.prompt_tokens),
                   resumed=bool(req.preempts),
                   **_waited(req.at_program, now))
        req.at_program = None
        return now

    def _close_request_span(self, req: _Request, ok: bool = True,
                            **attrs) -> None:
        """Close the umbrella "llm.request" span (submit -> stream end),
        parented under whatever the submitter was doing (replica task
        span, SSE generator, P/D decode span)."""
        if req.trace_ctx is None or req.span_id is None:
            return
        tracing.record_span(
            req.trace_ctx[0], "llm.request", req.submitted_wall,
            time.time(), parent_id=req.trace_ctx[1], span_id=req.span_id,
            kind="engine", ok=ok,
            attrs=dict(attrs, request_id=req.request_id,
                       req_kind=req.kind, preempts=req.preempts))
        req.span_id = None  # closed exactly once

    def _finish_request(self, req: _Request):
        """Latency histograms at stream end (successful finishes only;
        prefill_only requests are half a request and are skipped)."""
        if req.kind == "prefill_only":
            return
        now = time.monotonic()
        tid = req.trace_ctx[0] if req.trace_ctx else None
        self._m["e2e"].observe(now - req.submitted_at, exemplar=tid)
        if req.first_token_at is not None and req.emitted > 1:
            self._m["tpot"].observe(
                (now - req.first_token_at) / (req.emitted - 1),
                exemplar=tid)
        if req.trace_ctx is not None:
            w_now = time.time()
            if req.first_token_at is not None:
                # decode aggregate: first token -> stream end (per-step
                # spans would be noise).  What the loop did meanwhile is
                # on it: ``other_prefill_s / (tokens - 1)`` is what other
                # requests' admissions cost this one a token
                self._span(req, "llm.decode",
                           w_now - max(0.0, now - req.first_token_at),
                           w_now, tokens=req.emitted,
                           preempts=req.preempts,
                           **_waited(req.at_token, self._ph.reading(req)))
            self._close_request_span(req, ok=True, tokens=req.emitted)

    def _pick_waiting(self) -> Optional[_Request]:
        """Next request to admit: FIFO normally; under pool pressure (the
        head's pages aren't free) prefer the waiting request with the most
        prefix tokens resident — admitting a hit costs fewer fresh pages
        and zero evictions, so it unblocks the queue faster than forcing
        the head in.  Bounded: once the head has waited RTPU_ADMIT_AGE_CAP_S
        it goes next regardless, so misses can't starve.  Scans only the
        first 8 waiters via peek (no LRU refresh — ranking must not
        reorder eviction)."""
        q = self._waiting.queue  # type: ignore[attr-defined]
        if not q:
            return None
        head = q[0]
        pc = self.prefix_cache
        pressure = False
        if pc is not None and head.kind == "normal":
            need = len(head.prompt_tokens) // self.cfg.page_size + 1
            pressure = self.allocator.num_free() < need
        if (not pressure or time.monotonic() - head.submitted_at
                >= self._admit_age_cap_s):
            try:
                return self._waiting.get_nowait()
            except queue_mod.Empty:
                return None
        best_i, best_m = 0, -1
        for i in range(min(8, len(q))):
            r = q[i]
            if r.kind != "normal":
                continue
            m = pc.peek_match_tokens(r.prompt_tokens)
            if m > best_m:
                best_i, best_m = i, m
        try:
            req = q[best_i]
            del q[best_i]
        except IndexError:  # drained between len() and del (benign)
            return None
        return req

    def _admit(self) -> bool:
        """Move waiting requests into free slots while pages last
        (vLLM analogue: Scheduler admitting to the running batch)."""
        admitted = False
        ph = self._ph
        # a prompt in chunks goes on before anything new is admitted, one
        # chunk an iteration: the decode burst of the live slots follows
        for i, s in enumerate(self._slots):
            if s is not None and s.prefill_at is not None:
                self._next_chunk(i, s)
                return True
        while True:
            # everything up to the prefill is the admit phase; the phase a
            # prefill leaves open (prefill_emit) lasts through the slot
            # set-up below and ends here
            ph.begin(P_ADMIT)
            req = self._pick_waiting()
            if req is None:
                ph.vals = ("none_waiting",)
                return admitted
            ph.req = req
            # prefill_only completes inline and occupies no decode slot, so
            # it is admitted even with all slots busy (only pages gate it)
            if req.kind != "prefill_only":
                free_slot = next((i for i, s in enumerate(self._slots)
                                  if s is None), None)
                if free_slot is None:
                    self._waiting.queue.appendleft(req)  # type: ignore[attr-defined]
                    ph.vals = ("no_slot",)
                    return admitted
            if req.kind == "prefill_only":
                # KV only lives for the prefill compute+extract; afterwards
                # the full prompt pages stay CACHED-RESIDENT (not freed),
                # so repeat prefills of shared prompts and the P/D decode
                # hand-back both find warm pages.
                n_pages = -(-len(req.prompt_tokens) // self.cfg.page_size)
                if not self._reserve(n_pages):
                    self._waiting.queue.appendleft(req)  # type: ignore[attr-defined]
                    ph.vals = ("no_pages",)
                    return admitted
                ph.vals = ("admitted",)
                pages = self.allocator.allocate(n_pages)
                rng = (np.random.default_rng(req.params.seed)
                       if req.params.temperature > 0 else None)
                try:
                    # inline, so a prompt over the largest bucket runs all
                    # its chunks here: the pages ship only when every row
                    # behind them is written
                    last, done = self._prefill(req, pages, rng)
                    while done < self._prompt_end(req):
                        last, done = self._prefill(req, pages, rng, done,
                                                   later_chunk=True)
                    idx = np.asarray(pages)
                    kv_k = np.asarray(self.cache_k[:, idx])
                    kv_v = np.asarray(self.cache_v[:, idx])
                    self._undelivered.append(
                        (req.out_queue,
                         (("prefill_done", last, kv_k, kv_v), None)))
                    self._register_blocks(req.prompt_tokens, pages)
                    # P/D tier handoff: seal regardless of family heat —
                    # the sealed spine IS the page transfer the decode
                    # engine pulls (pd_disagg ships only the digest)
                    self._maybe_seal(req.prompt_tokens, force=True)
                    self._close_request_span(req)
                except Exception as e:  # noqa: BLE001
                    self._fail(req, e)
                    self._close_request_span(req, ok=False)
                finally:
                    self.allocator.free(pages)
                admitted = True
                continue
            # Lazy allocation (ISSUE 10): admit with just the pages the
            # prompt + the first decode write need; _ensure_capacity grows
            # the slot as decode advances, evicting cache LRU or preempting
            # when the pool runs dry.  Admitting lazily is what lets the
            # pool oversubscribe — the load wall the serving bench climbs.
            n = len(req.prompt_tokens)
            matched: List[int] = []
            cow_src: Optional[int] = None
            cow_len = 0
            if self.prefix_cache is not None and req.kind == "normal":
                # KV tier pull (ISSUE 16): if this prompt's family has a
                # deeper spine sealed in the store than is locally
                # resident (imbalance shed, P/D tier handoff, failover
                # from a killed replica), hydrate it FIRST so match_cow
                # below finds warm pages instead of cold-prefilling.
                if self.kv_tier is not None:
                    t_pull = time.time()
                    outcome, pulled = self._maybe_tier_pull(
                        req.prompt_tokens, req=req)
                    if outcome is not None:
                        self._span(req, "llm.kv_pull", t_pull, time.time(),
                                   ok=outcome in ("resident", "hydrated"),
                                   outcome=outcome, pages=pulled)
                matched, cow_src, cow_len = \
                    self.prefix_cache.match_cow(req.prompt_tokens)
                if self._block:
                    # block: a token's K/V depends on its whole block, so a
                    # hit that ends inside one stops at the block's start
                    # (whole pages are whole blocks)
                    cow_len -= cow_len % self._block
                    if cow_len == 0:
                        cow_src = None
            need_total = n // self.cfg.page_size + 1
            # pin matched pages — and the COW source, which eviction in
            # _reserve would otherwise happily reclaim before the copy —
            # BEFORE eviction can consider them
            pin = matched + ([cow_src] if cow_src is not None else [])
            self.allocator.retain(pin)
            # window: the first chunk's pages of the window layers (the
            # full layers' are taken for the whole prompt here)
            wneed = self._window_short([], 0, n)
            if not (self._reserve(need_total - len(matched)) and (
                    not wneed or self.window_allocator.can_allocate(wneed))):
                self.allocator.free(pin)  # unpin; stays resident
                self._waiting.queue.appendleft(req)  # type: ignore[attr-defined]
                ph.vals = ("no_pages",)
                return admitted
            ph.vals = ("admitted",)
            pages = matched + self.allocator.allocate(
                need_total - len(matched))
            wpages = self.window_allocator.allocate(wneed) if wneed else []
            prefix_len = len(matched) * self.cfg.page_size
            rng = (np.random.default_rng(req.params.seed)
                   if req.params.temperature > 0 else None)
            try:
                if req.kind == "decode_kv":
                    # Inject the shipped KV pages; skip prefill compute.
                    # Donated jitted scatter: in-place page update, not a
                    # whole-cache copy per admission. Shapes are padded to
                    # max_pages_per_seq so ONE compilation serves every
                    # request (page 0 is the scratch/null page; writing it
                    # matches prefill's existing padded-position behavior).
                    kv_k, kv_v = req.kv
                    req.kv = None  # free the host copy promptly
                    src = kv_k.shape[1]
                    P = self.max_pages_per_seq
                    idx = np.zeros(P, np.int32)
                    idx[:src] = pages[:src]
                    pad = ((0, 0), (0, P - src), (0, 0), (0, 0), (0, 0))
                    kv_k = np.pad(kv_k, pad) if src < P else kv_k
                    kv_v = np.pad(kv_v, pad) if src < P else kv_v
                    self.cache_k, self.cache_v = _inject_kv_pages(
                        self.cache_k, self.cache_v, jnp.asarray(idx),
                        jnp.asarray(kv_k, self.cache_k.dtype),
                        jnp.asarray(kv_v, self.cache_v.dtype))
                    last = int(req.first_token)
                    # no prefill here, so stamp the admission wait itself
                    self._queue_ends(req, time.monotonic())
                else:
                    if cow_src is not None:
                        # COW boundary page: duplicate the diverging
                        # block's page into this sequence's first fresh
                        # page, then prefill only past the shared slots.
                        # Slots >= cow_len hold the OTHER sequence's KV,
                        # but the suffix prefill overwrites every one of
                        # them before attention reads it (null-page
                        # invariant).
                        dst = pages[len(matched)]
                        self.cache_k, self.cache_v = lm.copy_page(
                            self.cache_k, self.cache_v,
                            jnp.int32(cow_src), jnp.int32(dst))
                        prefix_len += cow_len
                        self._stats["cow_copies"] += 1
                        self._m["cow_copies"].inc()
                    last, done = self._prefill(req, pages, rng, prefix_len,
                                               free_slot, wpages)
            except Exception as e:  # noqa: BLE001 — surface to caller
                self.allocator.free(pages)
                if wpages:
                    self.window_allocator.free(wpages)
                self._fail(req, e)
                self._close_request_span(req, ok=False, error=repr(e))
                continue
            finally:
                if cow_src is not None:
                    self.allocator.free([cow_src])  # drop the copy pin
            if self.prefix_cache is not None and req.kind == "normal":
                # commit hit/lookup accounting only on successful admission
                # (a request bouncing off a full pool retries its match)
                self.prefix_cache.note_lookup(n, prefix_len)
                self._m["prefix_lookup"].inc(n)
                self._stats["prefill_tokens_saved"] += prefix_len
                if prefix_len:
                    self._m["prefix_hit"].inc(prefix_len)
                    self._m["prefill_saved"].inc(prefix_len)
            slot = _Slot(request=req, pages=pages, num_tokens=n,
                         last_token=-1, rng=rng, wpages=wpages)
            if req.kind != "decode_kv" and done < self._prompt_end(req):
                # a prompt in chunks: the slot is taken and decodes after
                # its last chunk; this iteration goes on to the live
                # slots' burst
                slot.num_tokens = slot.prefill_at = done
                self._trim_window(slot)
                self._slots[free_slot] = slot
                return True
            self._seat(free_slot, slot, last)
            admitted = True

    def _prompt_end(self, req: _Request) -> int:
        """The position a request's prefill ends at: its prompt's end
        (block: the end of the prompt's whole blocks)."""
        n = len(req.prompt_tokens)
        return n - n % self._block if self._block else n

    def _seat(self, i: int, slot: _Slot, last: Optional[int]) -> None:
        """What follows the prefill of a WHOLE prompt (its one program, or
        its last chunk): the prompt's full pages are index-able, and the
        sequence takes slot ``i`` on the token ``last`` that follows the
        prompt unless that token ends it (block: on its open block)."""
        req, pages = slot.request, slot.pages
        # every full prompt page — freshly computed or injected — is
        # now index-able for later prompts sharing the prefix
        self._register_blocks(req.prompt_tokens, pages)
        if self._block:
            # block: no token follows from a prefill; the slot opens on
            # the prompt's tail and masks
            self._slots[i] = self._open_block(req, pages)
            return
        slot.last_token = last
        self._slots[i] = None
        if last in req.params.stop_token_ids:
            self._end_stream(req)
            self._free_pages(slot)
            return
        slot.generated.append(last)
        if req.kind == "decode_kv":
            # the prefill engine already delivered this token to
            # the caller; count it, don't re-emit
            self._stats["tokens_generated"] += 1
            req.produced += 1
        else:
            self._emit(slot, [last])
        if req.produced >= req.params.max_tokens:
            self._end_stream(req)
            self._free_pages(slot)
        else:
            self._slots[i] = slot

    def _next_chunk(self, i: int, s: _Slot) -> None:
        """The next chunk of the prompt that slot ``i`` is being admitted
        on; after the last, the slot decodes."""
        req = s.request
        try:
            # window: this chunk's pages of the window layers
            short = self._window_short(s.wpages, s.prefill_at,
                                       len(req.prompt_tokens))
            if short:
                if not self.window_allocator.can_allocate(short):
                    self._preempt(i, s)  # resumes when the pool has room
                    return
                s.wpages.extend(self.window_allocator.allocate(short))
            last, done = self._prefill(req, s.pages, s.rng, s.prefill_at, i,
                                       s.wpages, later_chunk=True)
        except Exception as e:  # noqa: BLE001 — surface to caller
            self._free_pages(s)
            self._slots[i] = None
            self._fail(req, e)
            self._close_request_span(req, ok=False, error=repr(e))
            return
        s.num_tokens = done
        if done < self._prompt_end(req):
            s.prefill_at = done
            self._trim_window(s)
            return
        s.prefill_at = None
        s.num_tokens = len(req.prompt_tokens)
        self._seat(i, s, last)

    def _window_short(self, wpages: List[int], start: int, n: int) -> int:
        """window: pages of the window layers that the chunk from ``start``
        of a prompt of ``n`` tokens still lacks (through the position after
        it, which the first decode step writes); 0 without such layers."""
        if not self._window:
            return 0
        return max(0, min(n, start + self._chunk) // self.cfg.page_size + 1
                   - len(wpages))

    def _free_pages(self, s: _Slot) -> None:
        """Give back every page a sequence holds, of either kind."""
        self.allocator.free(s.pages)
        if s.wpages:
            self.window_allocator.free(s.wpages)  # (skips the null entries)

    def _trim_window(self, s: _Slot) -> int:
        """window: give back the pages of the window layers that lie
        wholly behind what the query at the slot's next position sees
        (``num_tokens - window + 1`` on), leaving the null page in their
        place; how many."""
        if not self._window:
            return 0
        behind = min(max(0, s.num_tokens - self._window + 1)
                     // self.cfg.page_size, len(s.wpages))
        gone = [p for p in s.wpages[:behind] if p]
        if gone:
            self.window_allocator.free(gone)
            s.wpages[:behind] = [0] * behind
            self._count({"window_pages_freed": len(gone)})
        return len(gone)

    def _prefill(self, req: _Request, pages: List[int],
                 rng: Optional[np.random.Generator],
                 prefix_len: int = 0, slot: Optional[int] = None,
                 wpages: List[int] = (), later_chunk: bool = False) -> tuple:
        """Compute the prompt's K/V past ``prefix_len``, ONE program's
        worth: all of it, or where more than the largest bucket is left
        the next chunk of that size.  Returns (the token that follows the
        prompt, sampled, or None where this call did not reach the
        prompt's end; the position it reached).  Block: the prompt's whole
        blocks only (the tail opens the slot's first block), nothing
        sampled, and no program at all when the hit covers them.
        Recurrent layers: the same program also begins ``slot``'s state
        rows anew.  window: ``wpages`` are the window layers' pages.
        ``later_chunk``: what lies before ``prefix_len`` is this prompt's
        own earlier chunks, not a prefix hit."""
        n = len(req.prompt_tokens)
        ps = self.cfg.page_size
        ph = self._ph
        ph.begin(P_PREFILL_HOST, req)
        t0 = time.monotonic()
        first = not later_chunk
        if first:  # (before anything can raise: the wait ends here)
            qw = self._queue_ends(req, t0)
        # pages[:prefix_len // ps] already hold a cached prefix's KV (none
        # without a hit) or the earlier chunks': compute what follows it
        end = self._prompt_end(req)
        chunked = later_chunk or end - prefix_len > self._chunk
        if end - prefix_len > self._chunk:
            self._refuse("chunked_prompt",
                         f"{end - prefix_len} tokens of a prompt left to "
                         f"compute, over the largest prefill bucket "
                         f"{self._chunk},")
            if self._block and self._chunk % self._block:
                raise ValueError(
                    f"a chunk of {self._chunk} positions is no whole "
                    f"number of blocks of {self._block}")
            end = prefix_len + self._chunk
        suffix = req.prompt_tokens[prefix_len:end]
        bucket = self.cfg.bucket_for(max(1, len(suffix)))
        tokens = np.zeros(bucket, np.int32)
        tokens[:len(suffix)] = suffix
        positions = prefix_len + np.arange(bucket, dtype=np.int32)

        def rows_of(pages):
            # map each padded position to (page, slot); positions beyond
            # the allocated pages land in the null page (masked out of
            # attention)
            page_rows = np.zeros(bucket, np.int32)
            for i in range(bucket):
                pi = (prefix_len + i) // ps
                page_rows[i] = pages[pi] if pi < len(pages) else 0
            return jnp.asarray(page_rows)

        def table_of(pages):
            table = np.zeros(self.max_pages_per_seq, np.int32)
            table[:len(pages)] = pages
            return jnp.asarray(table)

        def by_kind(make):  # window: one of each a kind of pool
            return ({"full": make(pages), "window": make(wpages)}
                    if self._window else make(pages))

        program = lm.prefill
        args = (by_kind(rows_of), jnp.int32(len(suffix)),
                jnp.asarray(positions % ps))
        if prefix_len > 0:
            # attend through the full page table (suffix writes never
            # touch shared pages: every write position is >= prefix_len)
            program = lm.prefill_with_prefix
            args += (by_kind(table_of), jnp.asarray(positions))
        tokens = jnp.asarray(tokens)
        ph.vals = (bucket, prefix_len)
        out, did = None, {}  # what the prefill did, by name, for its span
        if chunked:  # which chunk of how many, of the largest bucket each
            did = {"chunk": prefix_len // self._chunk,
                   "chunks": -(-self._prompt_end(req) // self._chunk)}
            self._count({"prefill_chunks": 1})
        if suffix or not self._block:
            ph.begin(P_PREFILL_DISPATCH, req, (bucket,))
            logits, counted = self._run(program, tokens, *args, slot=slot)
            if self.state is not None:
                # (a later chunk goes on from the slot's rows: no reset)
                did["scan_chunks"] = (-(-bucket // self._scan_chunk)
                                      * self._state_layers)
                self._count({"scan_chunks": did["scan_chunks"],
                             "state_resets": int(prefix_len == 0)})
            self._deliver(True)
            ph.begin(P_PREFILL_FETCH, req)  # the host waits for the device
            logits, counted = jax.device_get((logits, counted))
            ph.begin(P_PREFILL_EMIT, req)
            counted = _by_name(counted)
            did.update(counted)
            self._count({**counted, "prefills": 1})
            if logits is not None and end == self._prompt_end(req):
                # (else no token follows: block, or more chunks to come)
                out = self._sample_one(logits, req.params, rng)
        dt = time.monotonic() - t0
        tid = req.trace_ctx[0] if req.trace_ctx else None
        self._m["prefill_t"].observe(dt, exemplar=tid)
        if first:
            self._stats["admitted"] += 1
            self._m["admitted"].inc()
            self._m["queue_wait"].observe(qw, exemplar=tid)
        if req.trace_ctx is not None:
            w_end = time.time()
            req.chunks += 1
            # ONE span a program: ``tokens`` the prompt's through this
            # chunk, ``prefix_len`` those before it
            self._span(req, "llm.prefill", w_end - dt, w_end,
                       tokens=end if chunked else n,
                       prefix_len=prefix_len, resumed=bool(req.preempts),
                       **did)
        if req.preempts and first:
            try:
                from ray_tpu.util import events

                events.emit(
                    "llm.resume",
                    message=f"request {req.request_id} resumed after "
                            f"preemption (prefix_len={prefix_len})",
                    data={"request_id": req.request_id,
                          "preempts": req.preempts,
                          "prefix_len": prefix_len},
                    trace_id=tid)
            except Exception:
                pass
        return out, end

    def _reserve(self, n: int) -> bool:
        """Make n pages allocatable, reclaiming prefix-cache pages as
        needed: ONE `PrefixCache.evict` for all the pages that are short,
        off the order the cache keeps (`eviction_blocks_examined`: what
        the call looked at to find them).  Returns False (leaving partial
        reclaims in place — they were the coldest blocks anyway) if the
        pool can't cover it."""
        short = n - self.allocator.num_free()
        if short <= 0:
            return True
        pc = self.prefix_cache
        if pc is None:
            return False
        examined = pc.eviction_blocks_examined
        hits = pc.evict(self.allocator.refcount, short)
        self._count({"eviction_scans": 1, "eviction_blocks_examined":
                     pc.eviction_blocks_examined - examined})
        for page, klass in hits:
            self.allocator.reclaim(page)
            self._stats["page_evictions"] += 1
            self._m["page_evictions"].inc()
            self._m["cache_evictions"].inc(1, {"class": klass})
        return len(hits) == short

    def _register_blocks(self, tokens: List[int], pages: List[int]) -> None:
        if self.prefix_cache is None:
            return
        cached = self.prefix_cache.insert(tokens, pages)
        self.allocator.mark_cached(cached)
        self._maybe_seal(tokens)

    # ------------------------- KV tier (ISSUE 16) --------------------------

    def kv_prehydrate(self, roots: List[str]) -> None:
        """Ask the engine to pull these family spines from the KV tier
        (controller replication fan-out / warm restart).  Thread-safe:
        roots queue through _hydrate_q and the scheduler thread performs
        the actual pool mutation in _drain_hydrations."""
        self._refuse("kv_tier", "kv_prehydrate")
        self.start()
        for r in roots or ():
            self._hydrate_q.put(str(r))

    def _tier_expect(self) -> dict:
        layers, _, _, kv_heads, head_dim = self.cache_k.shape
        return {"page_size": self.cfg.page_size, "layers": layers,
                "kv_heads": kv_heads, "head_dim": head_dim,
                "dtype": str(np.dtype(self.cache_k.dtype))}

    def _kv_fallback(self, reason: str,
                     req: Optional[_Request] = None) -> None:
        self._stats["kv_pull_fallbacks"] += 1
        self._m["kv_pull_fallbacks"].inc(tags={"reason": reason})
        try:
            from ray_tpu.util import events

            data: Dict[str, Any] = {"reason": reason}
            if req is not None:
                data["request_id"] = req.request_id
            events.emit("kv.pull_fallback", severity="warning",
                        message=f"KV tier pull fell back to cold prefill "
                                f"({reason})", data=data,
                        trace_id=(req.trace_ctx[0]
                                  if req is not None and req.trace_ctx
                                  else None),
                        # identity-bearing events must not merge
                        coalesce_s=0.0 if req is not None else 1.0)
        except Exception:
            pass

    def _note_kv_pull(self, pages: int,
                      req: Optional[_Request] = None) -> None:
        self._stats["kv_pulls"] += 1
        self._stats["kv_pull_pages"] += pages
        self._m["kv_pulls"].inc()
        self._m["kv_pull_pages"].inc(pages)
        try:
            from ray_tpu.util import events

            data: Dict[str, Any] = {"pages": pages}
            if req is not None:
                data["request_id"] = req.request_id
            events.emit("kv.pull",
                        message=f"hydrated {pages} KV pages from the "
                                f"store tier", data=data,
                        trace_id=(req.trace_ctx[0]
                                  if req is not None and req.trace_ctx
                                  else None),
                        coalesce_s=0.0 if req is not None else 1.0)
        except Exception:
            pass

    def _extract_pages(self, pages: List[int]):
        """Host copies of the given pages' KV (seal extraction).  Runs on
        the scheduler thread; registered full pages are append-only (COW
        duplicates into fresh pages, suffix prefill writes positions past
        the registered prefix), so the read is not torn."""
        idx = np.asarray(pages)
        return (np.asarray(self.cache_k[:, idx]),
                np.asarray(self.cache_v[:, idx]))

    def _maybe_seal(self, tokens: List[int], force: bool = False) -> None:
        tier, pc = self.kv_tier, self.prefix_cache
        if tier is None or pc is None:
            return
        if tier.maybe_seal(pc, self._extract_pages, tokens, force=force):
            self._stats["kv_seals"] += 1
            self._m["kv_seals"].inc()

    def _maybe_tier_pull(self, tokens: List[int],
                         req: Optional[_Request] = None):
        """Admission-path pull: hydrate this prompt's family spine from
        the tier when the store holds more of it than the local pool.
        Every failure is a typed fallback to cold prefill, never an
        admission error.  Returns ``(outcome, pages_hydrated)`` where
        outcome is None (prompt too short to ever pull), "miss" (family
        never sealed), "resident" (pool already covers the blob),
        "hydrated", or the typed KVPullError reason — the admission path
        stamps it on the request's kv-pull span."""
        tier, pc = self.kv_tier, self.prefix_cache
        ps = self.cfg.page_size
        cap = (len(tokens) - 1) // ps  # ≥1 suffix token stays to prefill
        if cap <= 0:
            return None, 0
        root_hex = pc.root_digest_for(tokens, ps)
        rec = tier.lookup_for_pull(root_hex)
        if rec is None:
            # never sealed: plain cold traffic, not a fallback
            return "miss", 0
        local = pc.peek_match_tokens(tokens) // ps
        if min(int(rec.get("blocks", 0)), cap) <= local:
            return "resident", 0  # the pool already covers the blob
        try:
            spine, kv_k, kv_v = tier.pull(root_hex, rec=rec,
                                          expect=self._tier_expect())
        except KVPullError as e:
            self._kv_fallback(e.reason, req=req)
            return e.reason, 0
        n = self._hydrate_spine(spine, kv_k, kv_v, limit_tokens=tokens,
                                req=req)
        if n is None:
            return "no_pages", 0  # _hydrate_spine already logged fallback
        if n > 0:
            self._note_kv_pull(n, req=req)
            return "hydrated", n
        return "resident", 0

    def _drain_hydrations(self) -> bool:
        """Scheduler-thread half of kv_prehydrate: pull queued family
        roots and hydrate their full spines."""
        tier, pc = self.kv_tier, self.prefix_cache
        did = False
        while tier is not None and pc is not None:
            try:
                root_hex = self._hydrate_q.get_nowait()
            except queue_mod.Empty:
                break
            if self._ph.name != P_HYDRATE:
                self._ph.begin(P_HYDRATE, vals=(0,))
            rec = tier.lookup(root_hex)
            if rec is None:
                continue  # nothing sealed under that root (yet)
            try:
                spine, kv_k, kv_v = tier.pull(root_hex, rec=rec,
                                              expect=self._tier_expect())
            except KVPullError as e:
                self._kv_fallback(e.reason)
                continue
            n = self._hydrate_spine(spine, kv_k, kv_v)
            if n:
                did = True
                self._note_kv_pull(n)
                self._ph.vals = (self._ph.vals[0] + n,)
        return did

    def _hydrate_spine(self, spine: List[int], kv_k, kv_v,
                       limit_tokens: Optional[List[int]] = None,
                       req: Optional[_Request] = None) -> Optional[int]:
        """Scatter a pulled spine's missing blocks into fresh pages and
        register them cached-resident; returns pages hydrated (0 = all
        resident / nothing usable, None = the pool couldn't cover the
        scatter — a "no_pages" fallback).  With ``limit_tokens``
        (admission path) only the blocks that are a true prefix of that
        prompt are hydrated, capped so ≥1 suffix token remains to
        prefill."""
        pc = self.prefix_cache
        ps = self.cfg.page_size
        nblk = int(kv_k.shape[1])
        m = min(nblk, self.max_pages_per_seq)
        if limit_tokens is not None:
            cap = min(m, (len(limit_tokens) - 1) // ps)
            m = 0
            while (m < cap and list(spine[m * ps:(m + 1) * ps])
                   == [int(t) for t in limit_tokens[m * ps:(m + 1) * ps]]):
                m += 1
        if m <= 0:
            return 0
        probe = list(spine[:m * ps]) + [0]  # sentinel suffix token: _walk
        # caps at (n-1)//ps, so this matches exactly the m spine blocks
        resident = pc.match(probe)
        k_res = len(resident)
        if k_res >= m:
            return 0
        need = m - k_res
        # pin the resident prefix BEFORE reserving — eviction inside
        # _reserve must not reclaim the chain we're extending
        self.allocator.retain(resident)
        if not self._reserve(need):
            self.allocator.free(resident)
            self._kv_fallback("no_pages", req=req)
            return None
        fresh = self.allocator.allocate(need)
        P = self.max_pages_per_seq
        idx = np.zeros(P, np.int32)
        idx[:need] = fresh
        sel_k = np.ascontiguousarray(kv_k[:, k_res:m])
        sel_v = np.ascontiguousarray(kv_v[:, k_res:m])
        if need < P:
            pad = ((0, 0), (0, P - need), (0, 0), (0, 0), (0, 0))
            sel_k = np.pad(sel_k, pad)
            sel_v = np.pad(sel_v, pad)
        # same donated jitted scatter (and compiled shape) as decode_kv
        # admission: padded rows land in the null page 0
        self.cache_k, self.cache_v = _inject_kv_pages(
            self.cache_k, self.cache_v, jnp.asarray(idx),
            jnp.asarray(sel_k, self.cache_k.dtype),
            jnp.asarray(sel_v, self.cache_v.dtype))
        cached = pc.insert(list(spine[:m * ps]), resident + fresh)
        self.allocator.mark_cached(cached)
        # release both the fresh allocation and the resident pins: every
        # spine page ends cached-resident, exactly like a finished
        # sequence's pages — the next match_cow retains them as a hit
        self.allocator.free(fresh)
        self.allocator.free(resident)
        return need

    def _preempt(self, i: int, s: _Slot) -> None:
        """Evict a running sequence (vLLM's recompute preemption): accepted
        tokens fold into the prompt and the request requeues at the FRONT.
        Its full pages are registered in the prefix cache first, so the
        resume prefill usually restarts from a long prefix hit rather than
        from scratch."""
        req = s.request
        seq = req.prompt_tokens + s.generated
        # KV is resident exactly for positions < num_tokens
        self._register_blocks(seq[:s.num_tokens], s.pages)
        req.prompt_tokens = seq
        req.kind = "normal"
        req.kv = None
        req.first_token = None
        self._free_pages(s)
        self._slots[i] = None
        self._stats["preempted"] += 1
        self._m["preempted"].inc()
        req.preempts += 1
        if req.trace_ctx is not None:
            now_w = time.time()
            self._span(req, "llm.preempt", now_w, now_w, ok=False,
                       tokens=s.num_tokens, produced=req.produced)
            # its second wait begins (and a prompt still in chunks is let go)
            req.at_queued = (self._admission_ends(req, ok=False)
                             if req.at_program is not None
                             else self._ph.reading(req))
        try:
            from ray_tpu.util import events

            # identity, not an anonymous count: `rtpu events --trace`
            # shows this preemption inside the request's own tree
            events.emit("llm.preempt",
                        message=f"request {req.request_id} evicted from "
                                f"its slot (recompute preemption, "
                                f"{s.num_tokens} tokens resident)",
                        data={"tokens": s.num_tokens,
                              "request_id": req.request_id,
                              "produced": req.produced},
                        trace_id=(req.trace_ctx[0]
                                  if req.trace_ctx else None))
        except Exception:
            pass
        self._waiting.queue.appendleft(req)  # type: ignore[attr-defined]

    def _shared_pages(self, s: _Slot) -> int:
        """Pages of slot `s` also held by another sequence or by the
        prefix cache — KV that survives this slot's preemption for free."""
        alloc = self.allocator
        return sum(1 for p in s.pages
                   if alloc.refcount(p) > 1 or alloc.is_cached(p))

    def _ensure_capacity(self, steps: int) -> None:
        """Grow each slot's page list to cover the next `steps` decode
        writes (lazy allocation's other half).  Earliest-submitted slots
        grow first; when the pool is dry even after cache eviction, the
        victim is the slot holding the FEWEST shared (refcount>1 or
        cached-resident) pages — its resume prefill recomputes the most
        from scratch either way, so preempting it throws away the least
        reusable KV.  Ties fall to the latest-submitted slot (FCFS)."""
        ps = self.cfg.page_size
        order = sorted(
            ((i, s) for i, s in enumerate(self._slots) if s is not None),
            key=lambda t: t[1].request.submitted_at)

        def need_pages(s: _Slot) -> int:
            sp = s.request.params
            remaining = max(1, sp.max_tokens - s.request.produced)
            if self._block:
                # block: a block takes a filling pass and a final one at
                # least, so `steps` passes reach 1 + steps // 2 blocks, and
                # never one past the block of the request's last token
                B = self._block
                last = (len(s.request.prompt_tokens) + len(s.generated)
                        + remaining)
                end = min(s.num_tokens + B * (1 + steps // 2),
                          -(-last // B) * B)
                need = min(-(-end // ps), self.max_pages_per_seq)
                return need - len(s.pages)
            k = min(steps, remaining)
            need = min((s.num_tokens + k - 1) // ps + 1,
                       self.max_pages_per_seq)
            return need - len(s.pages)

        def need_window(s: _Slot) -> int:
            # window: the same positions in the window layers' table (a
            # prompt in chunks takes its pages chunk by chunk)
            if not self._window or s.prefill_at is not None:
                return 0
            return need_pages(s) + len(s.pages) - len(s.wpages)

        # one eviction call for the whole burst: when it
        # covers the sum every slot below finds its pages free; when it
        # cannot, everything reclaimable is already back and the loop
        # fails at the slot, and preempts the victim, it always did
        self._reserve(sum(max(0, need_pages(s)) for _, s in order))
        for i, s in order:
            if self._slots[i] is s and s.prefill_at is None:
                self._trim_window(s)  # window: before the burst's pages
            while self._slots[i] is s:
                delta, wdelta = need_pages(s), need_window(s)
                if delta <= 0 and wdelta <= 0:
                    break
                if self._reserve(delta) and (
                        wdelta <= 0
                        or self.window_allocator.can_allocate(wdelta)):
                    s.pages.extend(self.allocator.allocate(max(0, delta)))
                    if wdelta > 0:
                        s.wpages.extend(
                            self.window_allocator.allocate(wdelta))
                    break
                victim = min(
                    ((j, t) for j, t in enumerate(self._slots)
                     if t is not None),
                    key=lambda t: (self._shared_pages(t[1]),
                                   -t[1].request.submitted_at))
                self._preempt(*victim)
                # if we preempted ourselves the while condition exits

    def _decode_all(self) -> bool:
        # (a slot whose prompt is still being computed in chunks waits)
        active_slots = [(i, s) for i, s in enumerate(self._slots)
                        if s is not None and s.prefill_at is None]
        if not active_slots:
            return False
        ph = self._ph
        ph.begin(P_DECODE_HOST)
        all_greedy = all(s.request.params.temperature <= 0
                         for _, s in active_slots)
        # Burst decode: chain several device-fed greedy steps and fetch
        # once.  The host round trip (device to host) costs many times the
        # decode compute itself; each step's argmax token feeds the
        # next step ON DEVICE.  Overshoot is safe: a slot that finishes
        # mid-burst keeps writing into its own (or the null) pages and
        # the extra tokens are simply not emitted.
        # Stay responsive to admissions only when one could actually
        # happen: work waiting, a slot to put it in, AND enough pool
        # headroom (free + reclaimable cache pages) for the head-of-queue
        # request's lazy admission (mirrors _admit's own checks) —
        # otherwise burst; admission is impossible until a sequence
        # finishes anyway.
        can_admit = False
        # (nor while a prompt is being computed in chunks: nothing new is
        # admitted before its last chunk, and a burst of one step between
        # two chunks would hold every live slot to a token a chunk)
        if any(s is None for s in self._slots) and not any(
                s is not None and s.prefill_at is not None
                for s in self._slots):
            try:
                head = self._waiting.queue[0]  # type: ignore[attr-defined]
                n = len(head.prompt_tokens)
                if head.kind == "prefill_only":
                    n_pages = -(-n // self.cfg.page_size)
                else:
                    n_pages = n // self.cfg.page_size + 1
                can_admit = (self.allocator.num_free()
                             + self.allocator.num_resident()) >= n_pages
            except IndexError:
                pass
        burst = lm.BURST_ROWS if (all_greedy and not can_admit) else 1
        # lazy allocation's second half: cover the burst's decode writes,
        # preempting under pool pressure — slots may vanish here
        self._ensure_capacity(burst)
        active_slots = [(i, s) for i, s in enumerate(self._slots)
                        if s is not None and s.prefill_at is None]
        if not active_slots:
            ph.vals = (0, burst)
            return True  # everything preempted; _admit resumes them
        if self._block:
            self._block_burst(active_slots, burst)
            return True
        B = self.cfg.max_slots
        P = self.max_pages_per_seq
        tokens = np.zeros(B, np.int32)
        positions = np.zeros(B, np.int32)
        tables = np.zeros((B, P), np.int32)
        active = np.zeros(B, bool)
        for i, s in active_slots:
            tokens[i] = s.last_token
            positions[i] = s.num_tokens  # position of the new token
            tables[i, :len(s.pages)] = s.pages
            active[i] = True
        if all_greedy:
            # a greedy burst's ``acc`` goes up FIRST, ahead of the tables:
            # its first step cannot start before every argument has arrived
            acc = jnp.asarray(np.zeros(
                lm.acc_shape(B, self._counted_layout), np.int32))
        toks_dev = jnp.asarray(tokens)
        pos_dev = jnp.asarray(positions)
        tables_dev = jnp.asarray(tables)
        active_dev = jnp.asarray(active)
        # what the paged kernel walks: step j of the burst attends to
        # positions 0..position+j of each active slot, a page at a time
        reached = np.minimum((positions[active][:, None] + np.arange(burst))
                             // self.cfg.page_size + 1, P)
        pages_read = int(reached.sum())
        # what the family adds to a burst's counts, by name: by the kind of
        # its cache here, and below what its steps' programs counted
        named = {}
        if self._run_pages > 1:  # small pages: what moves merged
            named["decode_pages_in_runs"] = self._pages_in_runs(
                tables[active], positions[active], burst)
        if self._latent:
            named["latent_pages_read"] = pages_read
        if self._window:
            # window: a second table, and what a layer of each kind walks:
            # a window layer from the page that holds length - window on
            # (arithmetic on positions and the window, what a bounded walk
            # reads: the kernel counts nothing)
            wtables = np.zeros((B, P), np.int32)
            for i, s in active_slots:
                wtables[i, :len(s.wpages)] = s.wpages
            tables_dev = {"full": tables_dev, "window": jnp.asarray(wtables)}
            skipped = int((np.maximum(
                positions[active][:, None] + np.arange(burst) + 1
                - self._window, 0) // self.cfg.page_size).sum())
            named.update(full_pages_read=pages_read,
                         window_pages_read=pages_read - skipped,
                         window_pages_skipped=skipped)
            if self._run_pages > 1:
                named["window_pages_in_runs"] = self._pages_in_runs(
                    wtables[active], positions[active], burst, self._window)
        if self.state is not None:
            named["state_slot_steps"] = burst * len(active_slots)
        emitted = self._stats["tokens_generated"]
        ph.vals = (len(active_slots), burst)
        ph.begin(P_DECODE_DISPATCH, vals=(burst,))
        if all_greedy:
            # the burst's carry lives on the device: each launch takes the
            # last one's tokens, positions, row and ``acc`` (a row a step:
            # its tokens, then what it counted) and NOTHING runs between
            # two steps; one array comes back, whatever the burst's length
            row = self._row0
            for j in range(burst):
                (toks_dev, pos_dev, row, acc), _ = self._run(
                    lm.decode_step_greedy_chained, toks_dev, tables_dev,
                    pos_dev, active_dev, row, acc)
                if j == 0 and self._deliver(True) and burst > 1:
                    ph.begin(P_DECODE_DISPATCH, vals=(burst,))
            # the host waits for the device: ONE round trip a burst
            ph.begin(P_DECODE_FETCH, vals=(burst,))
            acc = np.asarray(acc)[:burst]
            ph.begin(P_DECODE_EMIT)
            rows = acc[:, :B]
            named.update(_burst_counts(self._counted_layout,
                                       acc[:, B:].sum(axis=0)))
            programs, fetches = burst, 1
        else:
            logits, counted = self._run(lm.decode_step, toks_dev,
                                        tables_dev, pos_dev, active_dev)
            self._deliver(True)
            ph.begin(P_DECODE_FETCH, vals=(burst,))
            logits_np = np.asarray(logits)
            ph.begin(P_DECODE_EMIT)
            rows = np.zeros((1, B), np.int32)
            for i, s in active_slots:
                rows[0, i] = self._sample_one(
                    logits_np[i], s.request.params, s.rng)
            # what the step counted, a second fetch: every key a transfer
            named.update(_by_name(jax.device_get(counted)))
            programs, fetches = 1, 1 + len(counted)
        self._count({"decode_steps": burst, "decode_programs": programs,
                     "decode_fetches": fetches,
                     "decode_pages_read": pages_read, **named})
        self._accept_burst(active_slots, rows)
        if ph.sampled:
            # the span names the steps too, what the burst launched and
            # fetched (``programs``, ``fetches``) and the family's counts
            if "state_slot_steps" in named:  # (under the span's name)
                named["state_slots"] = named.pop("state_slot_steps")
            ph.vals = dict(
                tokens=self._stats["tokens_generated"] - emitted,
                slots_released=sum(self._slots[i] is not s
                                   for i, s in active_slots),
                steps=burst, programs=programs, fetches=fetches, **named)
        return True

    def _merged_upto(self, tables):
        """[S, blocks + 1]: the pages of each table's (a slot a row) first
        0, 1, .. whole blocks that lie in a block the kernel moves several
        pages a copy (``paged_attention.blocks_in_runs``)."""
        runs = np.cumsum(paged_attention.blocks_in_runs(
            tables, self._walk_block, self._run_pages), axis=1)
        return self._walk_block * np.concatenate(
            [np.zeros_like(runs[:, :1]), runs], axis=1)

    def _pages_in_runs(self, tables, positions, burst: int,
                       window: int = 0) -> int:
        """Of the pages a burst's walks read through ``tables`` (a live
        slot a row), those in a block the kernel moves several pages a copy
        (``paged_attention.block_kinds`` == 2 over each step's lengths,
        here as differences of one running sum a slot)."""
        ps, ppb = self.cfg.page_size, self._walk_block
        lengths = np.minimum(positions[:, None] + 1 + np.arange(burst),
                             tables.shape[1] * ps)
        # whole blocks under a step's length, and wholly behind its start
        upto = -(-lengths // ps) // ppb
        behind = np.minimum(
            -(-(np.maximum(lengths - window, 0) // ps) // ppb)
            if window else 0, upto)
        runs = self._merged_upto(tables)
        return int((np.take_along_axis(runs, upto, axis=1)
                    - np.take_along_axis(runs, behind, axis=1)).sum())

    def _count(self, did: dict) -> None:
        """Add what was done, by name, to ``stats()`` and the metrics."""
        for name, n in did.items():
            self._stats[name] += n
            self._m[name].inc(n)

    @functools.cached_property
    def _counted_layout(self) -> tuple:
        """What a decode step of this model counts on the device, key by
        key and how many entries each (``lm.counted_layout``: one abstract
        trace an engine, before its first greedy burst)."""
        slots = jax.ShapeDtypeStruct((self.cfg.max_slots,), jnp.int32)
        tables = jax.ShapeDtypeStruct(
            (self.cfg.max_slots, self.max_pages_per_seq), jnp.int32)
        return lm.counted_layout(
            self.params, slots, self.cache_k, self.cache_v,
            {"full": tables, "window": tables} if self._window else tables,
            slots, jax.ShapeDtypeStruct(slots.shape, bool), self.model_cfg,
            state=self.state)

    def _run(self, program, tokens, *args, slot=None):
        """One of llm/model.py's token-at-a-time programs over the pools
        and, for a model with recurrent layers, the state rows (a prefill
        names the ``slot`` it admits to): what it yields and what it
        counted by name; pools and rows come back in place."""
        rows = {} if self.state is None else {"state": self.state}
        if rows and slot is not None:
            rows["slot"] = jnp.int32(slot)
        out, counted, self.cache_k, self.cache_v, self.state = program(
            self.params, tokens, self.cache_k, self.cache_v, *args,
            self.model_cfg, **rows)
        return out, counted

    # ------------------------- block diffusion ----------------------------

    def _open_block(self, req: _Request, pages: List[int]) -> _Slot:
        """A slot whose K/V is final for the prompt's whole blocks and
        whose first block is the prompt's tail followed by masks."""
        B = self._block
        n = len(req.prompt_tokens)
        r = n % B
        return _Slot(
            request=req, pages=pages, num_tokens=n - r, last_token=-1,
            blk_tokens=(req.prompt_tokens[n - r:]
                        + [self.model_cfg.mask_token_id] * (B - r)),
            blk_masked=[False] * r + [True] * (B - r), blk_given=r)

    def _block_burst(self, active_slots, burst: int) -> None:
        """``burst`` denoising passes over every slot's open block, chained
        on the device (a pass's state feeds the next), one fetch for all of
        their records.  Overshoot is safe as in greedy decoding: a slot that
        finishes mid-burst keeps passing over its own (or the null) pages
        and what those passes fill is not emitted."""
        ph = self._ph
        S, B, P = self.cfg.max_slots, self._block, self.max_pages_per_seq
        tokens = np.full((S, B), self.model_cfg.mask_token_id, np.int32)
        masked = np.ones((S, B), bool)
        starts = np.zeros(S, np.int32)
        step = np.zeros(S, np.int32)
        tables = np.zeros((S, P), np.int32)
        active = np.zeros(S, bool)
        for i, s in active_slots:
            tokens[i], masked[i] = s.blk_tokens, s.blk_masked
            starts[i], step[i] = s.num_tokens, s.blk_step
            tables[i, :len(s.pages)] = s.pages
            active[i] = True
        tables_dev, active_dev = jnp.asarray(tables), jnp.asarray(active)
        state = (jnp.asarray(tokens), jnp.asarray(masked),
                 jnp.asarray(starts), jnp.asarray(step))
        counted = ("tokens_generated", "block_slot_passes", "masks_filled",
                   "blocks_final", "experts_read", "decode_pages_read",
                   "decode_pages_in_runs")
        # small pages: the pages in merged blocks up to each block of a
        # slot's table
        runs = self._merged_upto(tables) if self._run_pages > 1 else None
        before = [self._stats[k] for k in counted]
        ph.vals = (len(active_slots), burst)
        ph.begin(P_DECODE_DISPATCH, vals=(burst,))
        records = []
        for j in range(burst):
            record, *state, self.cache_k, self.cache_v = lm.block_step(
                self.params, self.cache_k, self.cache_v, tables_dev,
                active_dev, *state, self.model_cfg)
            records.append(record)
            if j == 0 and self._deliver(True) and burst > 1:
                ph.begin(P_DECODE_DISPATCH, vals=(burst,))
        ph.begin(P_DECODE_FETCH, vals=(burst,))  # the host waits
        # plain lists: the replay below reads every number of them
        rows = (np.asarray(jnp.stack(records)).tolist() if burst > 1
                else [np.asarray(records[0]).tolist()])
        ph.begin(P_DECODE_EMIT)
        self._stats["decode_steps"] += burst
        self._m["decode_steps"].inc(burst)
        for row in rows:
            self._stats["experts_read"] += row[0][2 * B + 1]  # in every row
            for i, s in active_slots:
                if self._slots[i] is s:  # else finished earlier in the burst
                    self._accept_pass(i, s, row[i], runs)
        did = {k: self._stats[k] - b for k, b in zip(counted, before)}
        for k in counted[1:]:  # _emit counts the tokens itself
            self._m[k].inc(did[k])
        if ph.sampled:
            ph.vals = (did["tokens_generated"],
                       sum(self._slots[i] is not s for i, s in active_slots),
                       did["block_slot_passes"], did["masks_filled"],
                       did["blocks_final"], did["experts_read"], burst)

    def _accept_pass(self, i: int, s: _Slot, record, runs=None) -> None:
        """Replay one pass's record (a list) for slot i: the block after
        the pass [B], its masks after it [B], whether the pass made it
        final.  ``runs``: a slot a row, the pages in merged blocks up to
        each block of its table (None: its pool's pages move singly)."""
        B = self._block
        stats = self._stats
        stats["block_slot_passes"] += 1
        # the kernel walked the pages up to the block's end
        pages = min((s.num_tokens + B - 1) // self.cfg.page_size + 1,
                    self.max_pages_per_seq)
        stats["decode_pages_read"] += pages
        if runs is not None:
            stats["decode_pages_in_runs"] += int(
                runs[i, pages // self._walk_block])
        if record[2 * B]:
            # the input held no mask: the K/V this pass wrote is final and
            # the next block opens, all masks
            stats["blocks_final"] += 1
            s.num_tokens += B
            s.blk_tokens = [self.model_cfg.mask_token_id] * B
            s.blk_masked = [True] * B
            s.blk_given = s.blk_step = 0
            return
        still = [bool(m) for m in record[B:2 * B]]
        stats["masks_filled"] += sum(s.blk_masked) - sum(still)
        s.blk_tokens = record[:B]
        s.blk_masked = still
        s.blk_step += 1
        if any(still):
            return
        # the pass that filled the block's last mask emits its new tokens,
        # in position order; max_tokens and stop tokens cut in that order
        new, _, ends = self._cut(s.request, s.blk_tokens[s.blk_given:])
        if new:
            s.generated.extend(new)
            self._emit(s, new)
        if ends:
            self._release_slot(i, s)

    @staticmethod
    def _cut(req: _Request, toks: List[int]):
        """What a request makes of ``toks``, in order: the tokens it takes
        (those before the first stop token, up to the one that reaches
        ``max_tokens``), how many of ``toks`` that used up (a stop token
        is used and not taken), and whether the request ends there."""
        sp = req.params
        n = len(toks)
        room = max(1, sp.max_tokens - req.produced)
        stop = n
        if sp.stop_token_ids:
            stop = next((j for j, t in enumerate(toks)
                         if t in sp.stop_token_ids), n)
        stopped = stop < min(n, room)
        taken = min(stop, room)
        return toks[:taken], taken + stopped, stopped or room <= n

    def _accept_burst(self, active_slots, rows: np.ndarray) -> None:
        """Replay a burst's sampled tokens ``rows`` [steps, slots], a slot
        at a time: a slot takes its column as far as its request goes (what
        later steps wrote and sampled for it is overshoot), and the slots
        that ended are released in the order the steps ended them."""
        ended = []
        cols = rows.T.tolist()
        for i, s in active_slots:
            new, used, ends = self._cut(s.request, cols[i])
            s.num_tokens += used  # a step put its input's K/V in the cache
            if new:
                s.generated.extend(new)
                self._emit(s, new)
            if ends:
                ended.append((used, i, s))
            else:
                s.last_token = new[-1]
        for _, i, s in sorted(ended, key=lambda e: e[:2]):
            self._release_slot(i, s)

    def _release_slot(self, i: int, s: _Slot) -> None:
        """Finish a sequence: register its full pages (prompt AND generated
        KV — a follow-up turn extending this conversation hits them) and
        release; cached pages stay resident until the pool reclaims them."""
        self._end_stream(s.request)
        seq = s.request.prompt_tokens + s.generated
        self._register_blocks(seq[:s.num_tokens], s.pages)
        self._free_pages(s)
        self._slots[i] = None

    # ------------------------- delivery ------------------------------------
    # A replay changes STATE (slots, requests, counters, pages), which the
    # next device program is built from; what the streams are to receive it
    # leaves in _undelivered.  _deliver puts that once the next program is
    # on the device, so the pull threads it wakes (32 of them, each with
    # JSON to write under the interpreter lock) run while the chip is busy
    # and not while it waits for this thread.

    def _emit(self, slot: _Slot, tokens: List[int]) -> None:
        """Count ``tokens`` (ints, in order) as generated by the slot's
        request and leave them for the next delivery."""
        n = len(tokens)
        self._stats["tokens_generated"] += n
        req = slot.request
        req.emitted += n
        req.produced += n  # survives preemption (len(generated) does not)
        if req.first_token_at is None:
            req.first_token_at = time.monotonic()
            self._m["ttft"].observe(
                req.first_token_at - req.submitted_at,
                exemplar=req.trace_ctx[0] if req.trace_ctx else None)
        if req.at_program is not None:  # (a traced request's admission)
            now = self._admission_ends(req)
            if req.at_token is None:
                req.at_token = now
        self._m["tokens"].inc(n)
        self._undelivered.append((req.out_queue, tokens))

    def _end_stream(self, req: _Request) -> None:
        """A request finished: its latencies, and the terminator behind
        whatever of it is still to be delivered."""
        self._finish_request(req)
        self._undelivered.append((req.out_queue, (None,)))

    def _deliver(self, behind_dispatch: bool) -> bool:
        """Put what the replays left, in their order; every put of the
        decode and prefill side is made here.  Banked as a decode_emit
        phase of its own (``delivered``: the items put); the caller opens
        the phase that follows.  False where nothing was left."""
        left = self._undelivered
        if not left:
            return False
        ph = self._ph
        ph.begin(P_DECODE_EMIT)
        for k in (("deliveries", "deliveries_behind_dispatch")
                  if behind_dispatch else ("deliveries",)):
            self._stats[k] += 1
            self._m[k].inc()
        n = 0
        for out_queue, items in left:
            for item in items:
                out_queue.put(item)
            n += len(items)
        left.clear()
        ph.vals = {"delivered": n}
        return True

    def _fail(self, req: _Request, e: Exception) -> None:
        """The error, then the terminator, behind everything the request
        was still to be delivered."""
        self._deliver(False)
        req.out_queue.put(e)
        req.out_queue.put(None)

    def _sample_one(self, logits: np.ndarray, params: SamplingParams,
                    rng: Optional[np.random.Generator]) -> int:
        if params.temperature <= 0 or rng is None:
            return int(np.argmax(logits))
        probs = logits / params.temperature
        probs = np.exp(probs - probs.max())
        probs /= probs.sum()
        if params.top_p < 1.0:
            order = np.argsort(-probs)
            csum = np.cumsum(probs[order])
            cut = np.searchsorted(csum, params.top_p) + 1
            keep = order[:cut]
            mask = np.zeros_like(probs)
            mask[keep] = probs[keep]
            probs = mask / mask.sum()
        return int(rng.choice(len(probs), p=probs))
