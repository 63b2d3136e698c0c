"""Runs a serving cell whose model has recurrent layers: everything is
``runners/serve.py``'s (cluster, application, load, counters, context) but
the comparison that decides ``correct``, which is three:

(1) ``serve.py``'s own, greedy tokens through the engine against the float32
reference's candidates, over prompts LONGER THAN TWO CHUNKS of the prefill's
chunked recurrence (64 tokens each), so that a state carried from chunk to
chunk and then through the decode steps' updates reaches a token that is
compared; at a margin this family's bf16 noise allows.  It sees a wrong
operation, layer or slot; it cannot see a state kept in fewer bits.

(2) The rows the ENGINE's programs leave in ``engine.state`` (the timed
``jit_prefill*`` and ``jit_decode_step*``, at the cell's layers and slots,
24 of the 32 live at once) against the reference's state of the same tokens,
after 64 decode steps, in the replica once the engine has answered
(``in_worker_recurrent.py`` ``rows_check``): the served path's own state,
where one kept in bf16 shows.

(3) The program's recurrence itself, both forms, against the reference's
token-by-token scan on the same float32 inputs, in the replica before the
engine exists (``state_check``): nothing but the recurrence's own arithmetic
between the two sides, so a limit a hundred times under (2)'s.

``runners/serve.py`` builds its loader and reads its ``CHECK`` by name
inside ``start`` and ``check_correct`` and is not this file's to edit, so
both names are swapped for the length of the call, as ``serve_routed.py``
swaps its two; a ``benchmark`` PR that makes it one serving runner with
hooks can drop the swaps (PERF.md section 7).
"""

from __future__ import annotations

import importlib.util
import os
import time

from benchmarks import common, in_worker, in_worker_recurrent
from benchmarks.runners import serve

CHECK = {
    **serve.CHECK,
    # 12 prompts of 150-400 tokens (3 to 7 chunks), 16 greedy steps each
    # through pages and state; the first again last (for this family no
    # prefix hit: it is recomputed, and has to come out the same).
    "n_prompts": 12, "min_len": 150, "max_len": 400, "pad_to": 416,
    # (1) the margin.  Readings on the chip (PERF.md section 6, PR 38; my
    # chip runs): over 16 layers at these widths the bf16 served path's
    # logits lie 0.055-0.067 rms (largest 0.38) from the float32
    # reference's, logits 1.0 rms: two to three times Mistral's, whose
    # flips all lay under 0.03.  The engine's token left the reference's
    # best by up to 0.14 in logit (two of eight prompts over serve.py's
    # 0.1); a deviation of 0.3 is a 3.5-sigma event of the difference of
    # two such errors and would strike one run in ten of the driver's, one
    # of 0.5 none; the largest flip read in 33 runs is 0.273.  With the delta rule's ``a S k``
    # correction dropped (planted in the served path, the harness's own
    # run) every prompt's FIRST token is none of the reference's 64
    # candidates, the last of which lies 0.86-1.75 under its best (logits
    # 1.1-1.2 rms off): the margin lies between 0.273 and 0.86.
    # A prompt's comparison ends at its first near-tie, one position in six
    # here: 40-118 positions a run over 13 sequences (33 runs, mean 75).
    # ``min_compared`` only keeps the comparison from passing on first
    # tokens alone: two positions a sequence, which a run in some thousands
    # would miss by chance (48 one in twenty); the decode steps' updates are
    # (2)'s to hold, which reads every sequence after 63 or more of them.
    "margin": 0.5, "min_compared": 26,
    # (2) the engine's own rows: every prompt again, ``row_copies`` times at
    # once (24 live slots of the cell's 32), ``row_steps`` tokens each (64:
    # the clean path's distance stays where the prompt left it while a
    # rounding at every update adds up, so the more steps the wider apart
    # the two readings); then the first linear layer's row of every slot
    # against the reference's state after the prompt and the tokens the
    # engine continued it with (relative rms;
    # ``in_worker_recurrent.rows_check`` says why the first layer and which
    # lengths).  Readings on the chip (PERF.md section 6, PR 38; my chip
    # runs, through this runner): clean 2.82e-3 to 2.98e-3 over the 24
    # slots of nine seeds (what is left is the served path's bf16 products
    # for q, k and v, one layer deep); the state rounded to bf16 after
    # every update and between a prefill's chunks 1.03e-2 to 1.05e-2; the
    # correction dropped 1.0-1.4.  The limit lies between the first two
    # with room on both sides (1.8 x, 1.9 x).
    "row_steps": 64, "row_copies": 2, "row_rel_rms_max": 5.5e-3,
    # (3) the recurrence on pinned float32 inputs, relative rms of its
    # outputs against the reference's scan, the worse of the prefill's
    # tokens and the decode steps'.  Readings on the chip (same section):
    # the program's two forms 0.7e-5 to 1.2e-5 over five seeds (what is left
    # is the REFERENCE's: against float64 the chunked form lies 2.8e-6 off,
    # the token-by-token scan 1.1e-5, the TPU's exp compounding); the state
    # rounded to bf16 between chunks and after every update 8.0e-3 to 8.3e-3
    # (1.2e-3 over the prefill's tokens alone); the correction dropped
    # 0.98-1.02.  The limit lies between the first two with room on both
    # sides (8 x, 80 x).
    "state_rel_rms_max": 1e-4,
}


class Stack(serve.Stack):

    def start(self):
        made = in_worker.make_loader
        in_worker.make_loader = in_worker_recurrent.make_loader
        try:
            super().start()
        finally:
            in_worker.make_loader = made

    def check_correct(self) -> dict:
        from ray_tpu.serve.handle import DeploymentHandle

        verdict = super().check_correct()
        pid = self.note["pid"]
        pinned = common.read_json_when_there(
            os.path.join(self.run_dir, f"state-{pid}.json"),
            time.monotonic() + 60)
        # (2): what the engine continues each prompt with, further than any
        # slot can have run; then every prompt ``row_copies`` times at once,
        # and the rows those sequences leave
        server = DeploymentHandle("llm", f"LLMServer:{serve.MODEL_ID}")
        family = common.module("families", self.cfg["family"])
        steps, copies = CHECK["row_steps"], CHECK["row_copies"]

        def answers(prompts, n):
            return [c.result(timeout_s=600) for c in [
                server.generate_tokens.remote(p, max_tokens=n)
                for p in prompts]]

        ahead = answers(self.check_prompts, steps + family.DECODE_OVERSHOOT)
        again = answers(self.check_prompts * copies, steps)
        common.write_json(os.path.join(self.run_dir, "cmd-rows.json"), {
            "sequences": [p + a for p, a in zip(self.check_prompts, ahead)],
            "prompt_lens": [len(p) for p in self.check_prompts],
            "steps": steps, "copies": copies})
        rows = common.read_json_when_there(
            os.path.join(self.run_dir, f"rows-{pid}.json"),
            time.monotonic() + 600)
        if "error" in rows:
            raise RuntimeError(f"the rows' check failed: {rows['error']}")
        rows["replays_equal"] = all(
            a == ahead[i % len(ahead)][:steps] for i, a in enumerate(again))
        verdict.update(
            rows=rows, row_rel_rms_max=CHECK["row_rel_rms_max"],
            recurrence=pinned, state_rel_rms_max=CHECK["state_rel_rms_max"])
        verdict["ok"] = bool(
            verdict["ok"]
            and rows["worst_rel_rms"] < CHECK["row_rel_rms_max"]
            and max(pinned["prefill_rel_rms"], pinned["decode_rel_rms"])
            < CHECK["state_rel_rms_max"])
        return verdict


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    # a program without this family says so here, at once, and not from
    # inside a replica that the driver would wait on
    family = cell["config_file"]["family"]
    if importlib.util.find_spec(f"ray_tpu.models.{family}") is None:
        raise RuntimeError(
            f"this program has no ray_tpu.models.{family}: it cannot run "
            f"configuration {cell['config']!r}")
    base = serve.Stack, serve.CHECK
    serve.Stack, serve.CHECK = Stack, CHECK
    try:
        return serve.run(cell, seed, seconds, trace, t_start)
    finally:
        serve.Stack, serve.CHECK = base
