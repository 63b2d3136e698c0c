"""Runs a serving cell whose model caches latent rows and routes tokens to
experts: everything is ``runners/serve.py``'s (cluster, application, load,
counters, context) but the loader the replica runs and the comparison that
decides ``correct``, which is three (``in_worker_latent.py`` says what each
sees): (a) logits under the reference's routing through both attention
forms, (b) the rows the engine's own programs left in its latent pool, (c)
the engine's greedy tokens on its own history, a prefix hit among them.

``runners/serve.py`` builds its loader and reads its ``CHECK`` by name
inside ``start`` and is not this file's to edit, so the names are swapped
for the length of the call, as ``serve_routed.py`` and
``serve_recurrent.py`` swap theirs; a ``benchmark`` PR that makes it one
serving runner with hooks can drop the swaps (PERF.md section 7).
"""

from __future__ import annotations

import importlib.util
import os
import time

from benchmarks import common, in_worker, in_worker_latent
from benchmarks.runners import serve

CHECK = {
    **serve.CHECK,
    # 8 prompts of 200-1100 tokens, 48 greedy steps each through latent
    # pages; the first again last (a prefix hit: ``jit_prefill_with_prefix``
    # gathers its rows through the page table and up-projects them).  The
    # longest cross one and two of the decode kernel's 512-token blocks.
    "n_prompts": 8, "min_len": 200, "max_len": 1100, "steps": 48,
    "pad_to": 1152,
    # LIMITS.  Each lies between two readings taken on the chip at the
    # published widths (PERF.md section 6, PR 41; my chip runs): the served
    # path as it is over the seeds run so far, and a fault planted in it.
    # (a) Logits under the reference's routing, rms over 128 positions and
    # the whole vocabulary (the logits are 1.0 rms), the worse of the two
    # attention forms.  bf16 as served 0.0209-0.0220 (9 seeds); the experts'
    # weights cut to 3 bits of mantissa 0.0573; the latent rows cut to 3
    # bits before they are attended to 0.0889.
    "pinned_rms_max": 0.03,
    # (b) The rows the engine's programs left in its pool, relative rms
    # against the reference's (``in_worker_latent.verify_and_rows`` says
    # what each sees), the worse of a prefill's rows and the decode steps'.
    # Layer 0: clean 0.0029 (every seed); the page's rows cut to 3 bits
    # 0.0268.  Layer 1 (behind layer 0's attention, before any
    # router): clean 0.0104-0.0105 (2 seeds); the decode kernel walking only
    # a slot's first block 0.609; the page cut 0.0286.
    # Every layer: clean 0.129-0.156 (a bf16 stream's other experts, no
    # fault); every sequence reading the page after its own 1.30-1.42.
    "first_layer_rows_rel_rms_max": 0.009,
    "second_layer_rows_rel_rms_max": 0.02,
    "all_layers_rows_rel_rms_max": 0.5,
    # (c) The engine's greedy tokens (432: 9 sequences x 48, a prefix hit
    # among them), each held against the reference ON THE ENGINE'S OWN
    # HISTORY.  With 4 experts of 64 at weights near 0.45 one swapped
    # expert moves a token's stream by a quarter of its routed part and the
    # layers behind it then route otherwise too, so a share of tokens
    # leaves the reference far: the share within serve.py's margin of the
    # best reads 0.771-0.812 clean, 0.248 with the kernel walking only a
    # slot's first block, 0.463 with rows written in 3 bits, 0.0 for random
    # tokens; the furthest token lies 2.38-3.54 under the best clean, 5.65
    # with the first block only, 7.83 (median 4.52) for random tokens.
    "within_min": 0.6, "gap_max": 4.5,
}


# which rows (``verify_and_rows``' keys) each limit of (b) holds
ROW_LIMITS = {"first": "first_layer_rows_rel_rms_max",
              "second": "second_layer_rows_rel_rms_max",
              "all": "all_layers_rows_rel_rms_max"}


class Stack(serve.Stack):

    def start(self):
        made = in_worker.make_loader
        in_worker.make_loader = in_worker_latent.make_loader
        try:
            super().start()
        finally:
            in_worker.make_loader = made

    def check_correct(self) -> dict:
        from ray_tpu.serve.handle import DeploymentHandle

        server = DeploymentHandle("llm", f"LLMServer:{serve.MODEL_ID}")
        steps = CHECK["steps"]
        # the first prompt again, last: by then a prefix hit
        prompts = self.check_prompts + self.check_prompts[:1]
        got = [c.result(timeout_s=600) for c in [
            server.generate_tokens.remote(p, max_tokens=steps)
            for p in self.check_prompts]]
        got.append(server.generate_tokens.remote(
            prompts[-1], max_tokens=steps).result(timeout_s=600))
        hit = self.engine_stats().get("prefill_tokens_saved", 0)
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
        pinned, rows = self.note["pinned"], verdict["rows"]
        # the worse of a prefill's rows and the decode steps', by limit
        worst = {limit: max((rows[f"{k}_prefill"], rows[f"{k}_decode"]),
                            key=lambda v: float("inf") if v is None else v)
                 for k, limit in ROW_LIMITS.items()}
        ok = (len(there) == len(gaps)
              and within >= CHECK["within_min"]
              and max(there) < CHECK["gap_max"]
              and max(pinned["logit_rms_error"].values())
              < CHECK["pinned_rms_max"]
              and all(v is not None and v < CHECK[limit]
                      for limit, v in worst.items())
              and hit > 0)
        return {"ok": bool(ok), "positions_compared": len(gaps),
                "tokens_missing": len(gaps) - len(there),
                "within_margin_share": within,
                "the_references_best_share":
                    sum(g == 0.0 for g in there) / len(gaps),
                "furthest_under_best": max(there, default=None),
                "pinned": pinned, "rows": rows,
                "prefix_hit_tokens": hit,
                "limits": {k: CHECK[k] for k in (
                    "margin", "within_min", "gap_max", "pinned_rms_max",
                    *ROW_LIMITS.values())},
                "verify_s": verdict["verify_s"],
                "repeat_equals_first": got[-1] == got[0]}


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    # a program without this family says so here, at once, and not from
    # inside a replica that the driver would wait on
    if importlib.util.find_spec("ray_tpu.models.glm_moe_lite") is None:
        raise RuntimeError(
            f"this program has no ray_tpu.models.glm_moe_lite: it cannot "
            f"run configuration {cell['config']!r}")
    base = serve.Stack, serve.CHECK, serve.COUNTERS
    serve.Stack, serve.CHECK = Stack, CHECK
    serve.COUNTERS = base[2] + ("experts_read", "latent_pages_read",
                                "decode_pages_read")
    try:
        return serve.run(cell, seed, seconds, trace, t_start)
    finally:
        serve.Stack, serve.CHECK, serve.COUNTERS = base
