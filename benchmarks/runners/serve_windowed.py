"""Runs a serving cell whose model has window and full attention layers
over a page pool a kind, prompts in chunks and routed experts: everything is
``runners/serve.py``'s (cluster, application, load, counters, context) but
the loader the replica runs and the comparison that decides ``correct``,
which is three (``in_worker_windowed.py`` says what each sees): (a) logits
under the reference's routing inside the first window, past it and past a
chunk boundary, (b) the K and V rows the engine's own programs left in BOTH
pools, with the window layers' pages behind the window given back, (c) the
engine's greedy tokens on its own history, a preempted and resumed sequence
among them.

``runners/serve.py`` builds its loader and reads its ``CHECK`` by name
inside ``start`` and is not this file's to edit, so the names are swapped
for the length of the call, as ``serve_latent.py`` swaps them; a
``benchmark`` PR that makes it one serving runner with hooks can drop the
swaps (PERF.md section 7).
"""

from __future__ import annotations

import contextlib
import importlib.util
import os
import random
import time

from benchmarks import common, in_worker, in_worker_windowed
from benchmarks.runners import serve

CHECK = {
    **serve.CHECK,
    # what ``serve.Stack.start`` draws and hands the loader, which this
    # family's loader does not read (it draws (a)'s sequences itself)
    "n_prompts": 2, "min_len": 20, "max_len": 60,
    # the check's own prompts: 8 of 1,500-5,000 tokens spread evenly (four
    # of them past 3,072, so three chunks and more), 32 greedy steps each;
    # the last is preempted after 8 of them and recomputed in chunks
    "prompts": 8, "shortest": 1500, "longest": 5000, "steps": 32,
    "pad_to": 5120,
    # LIMITS.  Each lies between two readings taken on the chip at the
    # published widths (PERF.md section 6, PR 46; my chip runs): the served
    # path as it is over the 26 runs made so far (20 seeds), and a fault
    # planted in it (seed 2147483659; ``window_short`` and ``experts_3bit``
    # also as CONTROLS through ``check_correct``, which read the same).
    # (a) Logits under the reference's routing, rms over 32 positions a
    # group and the whole vocabulary (the logits are 1.0 rms), the worst
    # group.  bf16 as served 0.00840-0.00871 (14 seeds); planted (seed
    # 2147483659, clean 0.00860): the experts' weights cut to 3 bits of
    # mantissa 0.0262; a full layer's q and k rotated 0.0478; the window a
    # page short 0.0746 past the window (0.0086 inside the first: no key is
    # cut off there); the gate left out 0.452.  The limit lies a factor of
    # 1.7 from the served path and from the nearest fault.
    "pinned_rms_max": 0.015,
    # (b) The rows the engine's programs left in its pools, relative rms
    # against the reference's, the worse of a prefill's rows and the decode
    # steps'.  Layer 0 (one bf16 product deep on both sides): clean
    # 0.00262-0.00263 (every one of 26 runs); the same rows cut to 3 bits
    # of mantissa 0.0268.  Layer 1 (behind layer 0's windowed attention,
    # the chunks' gather and the kernel's bounded walk, and before any
    # router): clean 0.00687-0.00692 / 0.00643-0.00652 (prefill / decode);
    # an engine whose window is ONE PAGE SHORT (2,032 against the
    # reference's 2,048: 16 keys of 2,048 missing from a window layer's
    # softmax) 0.0599 / 0.0708.  Every layer (behind routers a bf16 stream
    # takes another 8th expert than float32 in a share of tokens: no
    # fault): clean 0.0476-0.0498 / 0.0316-0.0518; the window a page short
    # 0.112 / 0.131.
    "first_layer_rows_rel_rms_max": 0.009,
    "second_layer_rows_rel_rms_max": 0.02,
    "all_layers_rows_rel_rms_max": 0.08,
    # (c) The engine's greedy tokens (256: 8 sequences x 32, one of them
    # preempted and recomputed in chunks), each held against the reference
    # ON THE ENGINE'S OWN HISTORY.  The share within serve.py's margin of
    # the best: clean 0.918-0.965 (26 runs); the window a page short 0.672
    # (0.742 as a control).
    # How far the furthest token lies under the best (clean 0.38-1.24) is
    # printed and holds no limit: the short window does not move it (0.88)
    # and no planted fault gave it an upper reading.
    "within_min": 0.8,
}

# The CONTROLS (``control`` below; PERF.md section 6 has their readings on
# the chip): a fault planted in what ``correct`` compares, which must come
# out as not correct through ``check_correct`` itself.  ``window_short``
# reaches all three comparisons (the engine is built a page short too), the
# others (a) alone; ``experts_3bit`` is the nearest precision below the
# configuration's.
FAULTS = ("window_short", "experts_3bit", "full_rotated", "no_gate")

# which rows (``verify_and_rows``' keys) each limit of (b) holds
ROW_LIMITS = {"first": "first_layer_rows_rel_rms_max",
              "second": "second_layer_rows_rel_rms_max",
              "all": "all_layers_rows_rel_rms_max"}


# the engine's page gauges at both ends of the window, for
# ``layer_metrics/kv_resident_bytes_per_token`` (``serve.run`` makes the
# Stack and keeps it to itself)
GAUGES = ("full_pages_in_use", "window_pages_in_use", "live_tokens")
_page_samples: list = []


class Stack(serve.Stack):

    _in_window = False
    fault = None  # a control's planted fault, never a run's

    def engine_stats(self) -> dict:
        stats = super().engine_stats()
        if self._in_window:
            _page_samples.append({k: stats[k] for k in GAUGES if k in stats})
        return stats

    def run_load(self, schedule: dict, seconds: float, tag: str = "w"):
        self._in_window = True
        try:
            return super().run_load(schedule, seconds, tag)
        finally:
            self._in_window = False

    def start(self):
        made = in_worker.make_loader
        in_worker.make_loader = lambda spec: in_worker_windowed.make_loader(
            {**spec, "fault": self.fault})
        try:
            super().start()
        finally:
            in_worker.make_loader = made
        rng = random.Random(self.seed)
        vocab, n = self.cfg["vocab_size"], CHECK["prompts"]
        span = (CHECK["longest"] - CHECK["shortest"]) / (n - 1)
        firsts = rng.sample(range(vocab // 32, vocab // 16), n)
        self.check_prompts = [
            [first] + [rng.randrange(3, vocab) for _ in range(
                int(CHECK["shortest"] + i * span) + rng.randrange(64) - 1)]
            for i, first in enumerate(firsts)]

    def _ask(self, name: str, obj: dict, answer: str) -> dict:
        common.write_json(os.path.join(self.run_dir, name), obj)
        out = common.read_json_when_there(
            os.path.join(self.run_dir, f"{answer}-{self.note['pid']}.json"),
            time.monotonic() + 1500)
        if "error" in out:
            raise RuntimeError(f"{answer} failed: {out['error']}")
        return out

    def check_correct(self) -> dict:
        from ray_tpu.serve.handle import DeploymentHandle

        server = DeploymentHandle("llm", f"LLMServer:{serve.MODEL_ID}")
        steps, prompts = CHECK["steps"], self.check_prompts
        self._ask("cmd-arm.json", {"preempt": prompts[-1]}, "armed")
        got = [c.result(timeout_s=900) for c in [
            server.generate_tokens.remote(p, max_tokens=steps)
            for p in prompts]]
        stats = self.engine_stats()
        verdict = self._ask("cmd-verify.json", {
            "prompts": prompts, "outputs": got, "steps": steps,
            "pad_to": CHECK["pad_to"]}, "verify")
        gaps = [g for row in verdict["gaps"] for g in row]
        there = [g for g in gaps if g is not None]
        within = sum(g < CHECK["margin"] for g in there) / len(gaps)
        pinned, rows = self.note["pinned"], verdict["rows"]
        back = verdict["given_back"]
        # the worse of a prefill's rows and the decode steps', by limit
        worst = {limit: max((rows[f"{k}_prefill"], rows[f"{k}_decode"]),
                            key=lambda v: float("inf") if v is None else v)
                 for k, limit in ROW_LIMITS.items()}
        met = {  # each comparison by name: a control says which one fell
            "tokens_present": len(there) == len(gaps),
            "within_margin": within >= CHECK["within_min"],
            "pinned_logits": max(pinned["logit_rms_error"].values())
            < CHECK["pinned_rms_max"],
            **{limit: v is not None and v < CHECK[limit]
               for limit, v in worst.items()},
            "given_back": bool(back["every_sequence"] and back["some_page"] > 0
                               and back["all_free_after"]),
            "preempted_once": back["preempted"] == 1,
            "chunked": stats.get("prefill_chunks", 0) >= 2 * len(prompts),
            # the window times the engine alone
            "disarmed": bool(verdict["disarmed"])}
        return {"ok": all(met.values()),
                "not_met": [name for name, good in met.items() if not good],
                "positions_compared": len(gaps),
                "tokens_missing": len(gaps) - len(there),
                "within_margin_share": within,
                "the_references_best_share":
                    sum(g == 0.0 for g in there) / len(gaps),
                "furthest_under_best": max(there, default=None),
                "pinned": pinned, "rows": rows, "given_back": back,
                "prefill_chunks": stats.get("prefill_chunks"),
                "window_pages_freed": stats.get("window_pages_freed"),
                "limits": {k: CHECK[k] for k in (
                    "margin", "within_min", "pinned_rms_max",
                    *ROW_LIMITS.values())},
                "verify_s": verdict["verify_s"]}


@contextlib.contextmanager
def _names_swapped():
    """``runners/serve.py`` under this runner's ``Stack``, ``CHECK`` and
    counters (the module docstring says why by name)."""
    base = serve.Stack, serve.CHECK, serve.COUNTERS
    serve.Stack, serve.CHECK = Stack, CHECK
    serve.COUNTERS = base[2] + (
        "experts_read", "decode_pages_read", "prefill_chunks",
        "window_pages_freed", "window_pages_read", "window_pages_skipped",
        "full_pages_read", *GAUGES)
    try:
        yield
    finally:
        serve.Stack, serve.CHECK, serve.COUNTERS = base


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    # a program without this family says so here, at once, and not from
    # inside a replica that the driver would wait on
    if importlib.util.find_spec("ray_tpu.models.afmoe") is None:
        raise RuntimeError(
            f"this program has no ray_tpu.models.afmoe: it cannot run "
            f"configuration {cell['config']!r}")
    del _page_samples[:]
    with _names_swapped():
        ctx = serve.run(cell, seed, seconds, trace, t_start)
    ctx["page_samples"] = list(_page_samples)
    for gauge in GAUGES:  # a gauge's difference over the window is no count
        ctx["counters"].pop(gauge, None)
    return ctx


def control(cell_name: str, seed: int, fault: str) -> dict:
    """``check_correct`` of the cell's own stack with ``fault`` planted, no
    load: the verdict, which a limit must have made not correct."""
    cell = common.load_cell(cell_name)
    stack = Stack(cell, seed, False, os.path.join(
        common.OUT, "runs", f"control.{cell_name}.{fault}.s{seed}"))
    stack.fault = fault
    with _names_swapped():
        try:
            stack.start()
            return stack.check_correct()
        finally:
            stack.stop()


if __name__ == "__main__":
    # python3 -m benchmarks.runners.serve_windowed <cell> <seed> <fault>
    import json
    import sys

    name, seed, fault = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    if fault not in FAULTS:
        raise SystemExit(f"fault {fault!r} is none of {FAULTS}")
    verdict = control(name, seed, fault)
    print(f"# control {fault}: " + json.dumps(verdict), flush=True)
    print(json.dumps({"fault": fault, "correct": verdict["ok"]}))
    sys.exit(1 if verdict["ok"] else 0)  # a control that passes has failed
