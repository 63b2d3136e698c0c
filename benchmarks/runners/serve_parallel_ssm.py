"""Runs a serving cell whose model runs a state-space mixer BESIDE attention
in every block, state rows and pages in one layer: everything is
``runners/serve.py``'s (cluster, application, load, counters, context) but
the loader the replica runs and the comparison that decides ``correct``,
which is four (``in_worker_parallel_ssm.py`` says what each sees).  Three
after the engine has answered the check's prompts: (a) logits of the
engine's own prefill and decode programs through its pages and state rows
against the reference's full forward, (b) the rows those programs left
(float32 state, the convolution's tail, K and V) against the reference's,
(c) the engine's greedy tokens on its own history.  One after the load:
(d) the tokens of a sample of the sequences the engine finished INSIDE THE
WINDOW, on its own history (``run_load`` adds it to the verdict that
``check_correct`` returned).

``runners/serve.py`` builds its loader and reads its ``CHECK`` by name
inside ``start`` and is not this file's to edit, so the names are swapped
for the length of the call, as ``serve_sparse_linear.py`` swaps them
(PERF.md section 7).

A CONTROL, a fault planted in what ``correct`` compares, runs through the
same ``check_correct``, with no load:

    python3 -m benchmarks.runners.serve_parallel_ssm <cell> <seed> <fault> \
        [<seconds>]

(``families/falcon_h1.FAULTS``, or ``none`` for the clean reading; exit 0
when a fault came out NOT correct; ``not_met`` names the comparisons that
fell.  With ``<seconds>`` the control also offers the cell's traffic for a
window that long, for (d)'s reading.)
"""

from __future__ import annotations

import contextlib
import importlib.util
import os
import time

from benchmarks import common, in_worker, in_worker_parallel_ssm
from benchmarks.runners import serve

CHECK = {
    **serve.CHECK,
    # 6 prompts of 150-700 tokens (the traffic's are 32-768: 3 to 11 chunks
    # of the prefill's scan), 256 greedy tokens each through pages and state
    # rows (the traffic's answers are 256-768; a state kept in less than
    # float32 shows in the rows only once its rounding has had a slow head's
    # memory to pile up: after 48 steps it read 1.3 x the clean rows, section
    # 6), side by side in six slots
    "n_prompts": 6, "min_len": 150, "max_len": 700, "steps": 256,
    "pad_to": 1024,
    # LIMITS.  Each lies between the served path as it is and a planted
    # fault, BOTH read on the chip at the published widths and the cell's
    # sizes through ``check_correct`` itself (PERF.md section 6, PR 57; my
    # chip runs).  Clean at 256 steps: 21 runs and a control on 21 seeds
    # (another 15 at 48 steps).  Faults: ``control`` under every fault of
    # ``FAULTS`` at 256 steps (seed 2147488001) and, the same within a few
    # per cent, at 48 (seed 2147487001); ``state_in_bf16`` on EIGHT seeds
    # at 256 steps (2147488001, 2147488409, 2147489203, 2147490021,
    # 2147493001, 2147493107 twice, 2147493209, 2147494207: the last two
    # controls and three clean runs with these limits as they stand, from
    # the committed files alone).
    # (a) Logits of the engine's own programs through pages and state rows,
    # rms over 1,536 positions and the whole vocabulary (the logits are 1.0
    # rms).  Clean 0.0086-0.0091.  ``gate_after_norm`` 0.420-0.428,
    # ``dt_bias_left_out`` 0.660, ``mup_vector_left_out`` 0.667,
    # ``tail_one_late`` 0.680, ``key_multiplier_left_out`` 1.302: the limit
    # stands 2.2 x over the clean maximum and 21 x under the nearest.
    # ``state_in_bf16`` reads 0.0088-0.0114 and is NOT (a)'s to catch: with
    # seeded, uncorrelated B and C the state's part of y is small beside
    # D x, so a rounding of the state shows in the rows ((b)) long before
    # it shows in a logit.
    "logit_rms_max": 0.02,
    # (b) The rows the engine's programs left, relative rms.  Every layer's
    # state, POOLED: clean 0.0087-0.0110; ``gate_after_norm`` 0.487-0.515
    # the nearest structural fault; the state kept in bf16
    # (``state_in_bf16``: rounded at every update and between a prefill's
    # pieces) 0.0121, 0.0164, 0.0175, 0.0183, 0.0183, 0.0364, 0.0415 and
    # 0.0471 on eight seeds after 256 steps (0.0122 after 48): the limit
    # stands 1.23 x over the clean maximum and catches seven of the eight.
    # It cannot catch the mildest: a pooled reading weighs a head by the
    # size of its state, the largest states are the heads with a large dt,
    # which forget in a few tokens and round once, and how many of them a
    # seed draws moves the reading by a factor of four.
    # Layer 0's state (its mixer reads the embedding's rows through one bf16
    # product, the same on both sides) A HEAD AT A TIME, each head's error
    # against its own size, rms over 32 heads and six sequences: the heads
    # that remember longest, where a rounding piles up, count as much as
    # the rest.  Clean 0.00476-0.00523 on nine seeds (standard deviation
    # 0.00016; the seed whose POOLED reading stood out reads 0.00501) |
    # ``state_in_bf16`` 0.00783 (twice: the seed whose pooled reading
    # passes), 0.0135, 0.0154 and 0.0377: the limit stands 1.22 x over the
    # clean maximum (8 of its standard deviations over its mean) and 1.22 x
    # under the lowest bf16 reading.  (The first hand-in held layer 0's
    # POOLED state to 0.007: clean read 0.0048-0.0066 on 13 seeds and bf16
    # 0.0075-0.0776, no room on either side; it is gone.)  The
    # convolution's tail: clean 0.0071-0.0076 | ``gate_after_norm``
    # 0.351-0.361 the nearest, ``tail_one_late`` 1.25.  K and V, every
    # layer: clean 0.0076-0.0079 | ``gate_after_norm`` 0.346 the nearest,
    # ``key_multiplier_left_out`` 63.6; layer 0's (one bf16 product deep,
    # as every family's: the same rows at 3 bits of mantissa read 0.0268 in
    # PRs 41 and 46): clean 0.00320-0.00324 | ``key_multiplier_left_out``
    # 63.3.
    "state_rel_rms_max": 0.0135,
    "first_state_by_head_rel_rms_max": 0.0064,
    "tail_rel_rms_max": 0.02,
    "kv_rel_rms_max": 0.02,
    "first_kv_rel_rms_max": 0.009,
    # (c) The engine's greedy tokens (1,536) on ITS OWN history: the share
    # within ``margin`` of the reference's best 1.0 clean (every reading) |
    # 0.365-0.406 (``gate_after_norm``) at most under a structural fault;
    # the furthest under the best 0.003-0.037 clean | 1.79 at least; the share
    # the replay of (a) also puts first 1.0 in every reading, faults too
    # (the replay and the scheduler's path run the same programs).
    "margin": 0.1, "within_min": 0.9, "gap_max": 1.0, "replay_first_min": 0.9,
    # (d) After the load: 6 of the sequences finished inside the window (of
    # some 220), 64 positions of each answer, the last among them (a slot's
    # rows have then taken the whole answer), under (c)'s limits: the same
    # programs made them.  Clean, 13 runs: 1.0 and 0.004-0.029 |
    # ``gate_after_norm`` with 20 s of the cell's traffic 0.302 and 1.61.
    # Prompt and answer are up to 768 + 768.
    "window_requests": 6, "window_positions": 64, "window_pad_to": 1536,
}

ROW_LIMITS = {"state": "state_rel_rms_max",
              "first_state_by_head": "first_state_by_head_rel_rms_max",
              "tail": "tail_rel_rms_max", "kv": "kv_rel_rms_max",
              "first_kv": "first_kv_rel_rms_max"}
COUNTED = ("decode_pages_read", "prefill_chunks", "state_slot_steps",
           "state_resets", "scan_chunks")


class Stack(serve.Stack):

    fault = None  # a control's planted fault, never a run's
    verdict = None  # ``check_correct``'s, once it has run

    def start(self):
        made = in_worker.make_loader
        in_worker.make_loader = lambda spec: \
            in_worker_parallel_ssm.make_loader({**spec, "fault": self.fault})
        try:
            super().start()
        finally:
            in_worker.make_loader = made

    def check_correct(self) -> dict:
        from ray_tpu.serve.handle import DeploymentHandle

        server = DeploymentHandle("llm", f"LLMServer:{serve.MODEL_ID}")
        steps, prompts = CHECK["steps"], self.check_prompts
        got = [c.result(timeout_s=900) for c in [
            server.generate_tokens.remote(p, max_tokens=steps)
            for p in prompts]]
        stats = self.engine_stats()
        common.write_json(os.path.join(self.run_dir, "cmd-verify.json"), {
            "prompts": prompts, "outputs": got, "steps": steps,
            "pad_to": CHECK["pad_to"], "margin": CHECK["margin"]})
        out = common.read_json_when_there(
            os.path.join(self.run_dir, f"verify-{self.note['pid']}.json"),
            time.monotonic() + 1500)
        if "error" in out:
            raise RuntimeError(f"verify failed: {out['error']}")
        # the reference's passes, told apart from the rest of ``setup_s``
        self.note["reference_s"] = out["reference_s"]
        rows = out["rows"]
        met = {  # each comparison by name: a control says which one fell
            "logits": out["logit_rms_error"] < CHECK["logit_rms_max"],
            **{f"rows_{name}": rows[name] is not None
               and rows[name] < CHECK[limit]
               for name, limit in ROW_LIMITS.items()},
            "within_margin": out["within_margin_share"]
            >= CHECK["within_min"],
            "furthest": out["furthest_under_best"] < CHECK["gap_max"],
            "replay_puts_first": out["replay_puts_first_share"]
            >= CHECK["replay_first_min"],
            # the stated precision of the state is what the engine holds
            "state_float32": out["state_dtype"] == "float32",
            "all_free_after": bool(out["all_free_after"]),
            "state_counted": stats.get("state_resets", 0) >= len(prompts)
            and stats.get("state_slot_steps", 0)
            >= len(prompts) * (steps - 1)}
        out.pop("gaps")
        self.verdict = {
            **out, "ok": all(met.values()),
            "not_met": [name for name, good in met.items() if not good],
            "limits": {k: v for k, v in CHECK.items()
                       if k.endswith(("_max", "_min")) or k == "margin"}}
        return self.verdict

    def run_load(self, schedule: dict, seconds: float, tag: str = "w") -> dict:
        """The window, then (d) into the verdict ``check_correct`` returned
        (the same dict: ``runners/serve.py`` holds it from before the load
        and reports it after)."""
        load = super().run_load(schedule, seconds, tag)
        if self.verdict is None:  # a knee sweep: windows, and no verdict
            return load
        common.write_json(
            os.path.join(self.run_dir, "cmd-verify-window.json"), {
                "t0_wall": load["window"]["t0_wall"], "seconds": seconds,
                "requests": CHECK["window_requests"],
                "positions": CHECK["window_positions"],
                "pad_to": CHECK["window_pad_to"], "margin": CHECK["margin"]})
        out = common.read_json_when_there(os.path.join(
            self.run_dir, f"verify-window-{self.note['pid']}.json"),
            time.monotonic() + 900)
        if "error" in out:
            raise RuntimeError(f"the window's check failed: {out['error']}")
        met = {"window_within_margin": out["window_within_margin_share"]
               >= CHECK["within_min"],
               "window_furthest": out["window_furthest_under_best"]
               < CHECK["gap_max"]}
        self.verdict.update(out)
        self.verdict["not_met"] += [k for k, good in met.items() if not good]
        self.verdict["ok"] = not self.verdict["not_met"]
        return load


@contextlib.contextmanager
def _names_swapped():
    """``runners/serve.py`` under this runner's ``Stack``, ``CHECK`` and
    counters (the module docstring says why by name)."""
    base = serve.Stack, serve.CHECK, serve.COUNTERS
    serve.Stack, serve.CHECK = Stack, CHECK
    serve.COUNTERS = base[2] + COUNTED
    try:
        yield
    finally:
        serve.Stack, serve.CHECK, serve.COUNTERS = base


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    # a program without this family says so here, at once, and not from
    # inside a replica that the driver would wait on
    family = cell["config_file"]["family"]
    if importlib.util.find_spec(f"ray_tpu.models.{family}") is None:
        raise RuntimeError(
            f"this program has no ray_tpu.models.{family}: it cannot run "
            f"configuration {cell['config']!r}")
    with _names_swapped():
        return serve.run(cell, seed, seconds, trace, t_start)


def control(cell_name: str, seed: int, fault: str,
            seconds: float = 0.0) -> dict:
    """``check_correct`` of the cell's own stack with ``fault`` planted
    (None: the clean reading): the verdict, which a limit must have made
    not correct.  No load, or the cell's traffic for ``seconds`` and (d)."""
    cell = common.load_cell(cell_name)
    stack = Stack(cell, seed, False, os.path.join(
        common.OUT, "runs", f"control.{cell_name}.{fault}.s{seed}"))
    stack.fault = fault
    with _names_swapped():
        try:
            stack.start()
            verdict = stack.check_correct()
            if seconds:
                mix = cell["mix"]
                stack.run_load(common.module("generators", mix["kind"])
                               .generate(mix, seed, seconds,
                                         stack.cfg["engine"],
                                         stack.cfg["vocab_size"]), seconds)
            return verdict
        finally:
            stack.stop()


if __name__ == "__main__":
    import json
    import sys

    name, seed, fault = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    seconds = float(sys.argv[4]) if len(sys.argv) > 4 else 0.0
    faults = common.module(
        "families", common.load_cell(name)["config_file"]["family"]).FAULTS
    if fault not in faults + ("none",):
        raise SystemExit(f"fault {fault!r} is none of {faults}")
    verdict = control(name, seed, None if fault == "none" else fault,
                      seconds)
    print(f"# control {fault}: " + json.dumps(verdict), flush=True)
    print(json.dumps({"fault": fault, "correct": verdict["ok"],
                      "not_met": verdict["not_met"]}))
    # a control that passes has failed ("none" is the clean reading)
    sys.exit(int(verdict["ok"]) if fault != "none" else 0)
