"""Runs a serving cell whose model routes tokens to experts: everything is
``runners/serve.py``'s (cluster, application, load, counters, context) but
the loader the replica runs and the comparison that decides ``correct``
(``in_worker_routed.py`` says why a routed model needs its own).

``runners/serve.py`` builds its loader and its ``Stack`` by name inside
``start`` and ``run``, and is not this file's to edit, so both names are
swapped for the length of the call; a ``benchmark`` PR that gives it the
two hooks can drop the swaps (PERF.md section 7).
"""

from __future__ import annotations

import os
import time

from benchmarks import common, in_worker, in_worker_routed
from benchmarks.runners import serve

CHECK = {
    # (1) Before the engine exists: the program's layers under the
    # reference's expert sets against the reference's logits, rms over the
    # compared positions and the whole vocabulary (the logits themselves
    # are 1.0 rms).  Readings (PERF.md section 6, PR 34, my chip runs):
    # bf16 as served 0.00716-0.00747 over 14 seeds; the experts' weights
    # rounded to 3 bits of mantissa (fp8 e4m3 at an ideal scale) 0.0236;
    # every expert index off by one 0.575; and 0.047 with the program's OWN
    # routing, which is why it is pinned.  The limit lies between the first
    # two, a factor of 1.8 from each.
    "pinned_rms_max": 0.013,
    # (2) The engine's greedy tokens for the check's prompts (and the first
    # prompt again: a prefix hit), each held against the reference ON THE
    # ENGINE'S OWN HISTORY.  A token counts as the reference's if its logit
    # lies less than serve.py's margin under the best; a router's near-ties
    # put a few tokens further off, so two limits, each between readings:
    # the share of the 208 tokens within the margin (bf16 engine
    # 0.966-0.995 over 14 seeds; the same tokens against a reference whose
    # router columns are rolled by one, which is an engine reading expert
    # e-1's weights for e, 0.078; random tokens 0.0), and how far the
    # furthest token lies under the best (bf16 engine 0.109-0.238; router
    # rolled 2.27; random tokens 7.5, a single one 3 and up).
    "margin": serve.CHECK["margin"], "within_min": 0.75, "gap_max": 1.0,
}


class Stack(serve.Stack):

    def start(self):
        made = in_worker.make_loader
        in_worker.make_loader = in_worker_routed.make_loader
        try:
            super().start()
        finally:
            in_worker.make_loader = made

    def check_correct(self) -> dict:
        from ray_tpu.serve.handle import DeploymentHandle

        server = DeploymentHandle("llm", f"LLMServer:{serve.MODEL_ID}")
        steps = serve.CHECK["steps"]
        # the first prompt again, last: by then a prefix hit
        prompts = self.check_prompts + self.check_prompts[:1]
        got = [c.result(timeout_s=600) for c in [
            server.generate_tokens.remote(p, max_tokens=steps)
            for p in self.check_prompts]]
        got.append(server.generate_tokens.remote(
            prompts[-1], max_tokens=steps).result(timeout_s=600))
        common.write_json(os.path.join(self.run_dir, "cmd-verify.json"),
                          {"prompts": prompts, "outputs": got})
        verdict = common.read_json_when_there(
            os.path.join(self.run_dir, f"verify-{self.note['pid']}.json"),
            time.monotonic() + 900)
        if "error" in verdict:
            raise RuntimeError(f"the reference failed: {verdict['error']}")
        gaps = [g for row in verdict["gaps"] for g in row]
        there = [g for g in gaps if g is not None]
        within = sum(g < CHECK["margin"] for g in there) / len(gaps)
        pinned = self.note["pinned"]
        return {"ok": (len(there) == len(gaps)
                       and within >= CHECK["within_min"]
                       and max(there) < CHECK["gap_max"]
                       and pinned["logit_rms_error"]
                       < CHECK["pinned_rms_max"]),
                "positions_compared": len(gaps),
                "tokens_missing": len(gaps) - len(there),
                "within_margin_share": within,
                "the_references_best_share":
                    sum(g == 0.0 for g in there) / len(gaps),
                "furthest_under_best": max(there, default=None),
                "pinned": pinned, "limits": CHECK,
                "verify_s": verdict["verify_s"],
                "repeat_equals_first": got[-1] == got[0]}


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    base, serve.Stack = serve.Stack, Stack
    try:
        return serve.run(cell, seed, seconds, trace, t_start)
    finally:
        serve.Stack = base
