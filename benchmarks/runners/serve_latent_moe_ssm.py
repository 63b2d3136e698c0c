"""Runs a serving cell whose layers are ONE thing each, a state-space mixer
OR attention without positions OR a routed feed-forward (experts in a
latent, a chip's share of them held, beside a shared expert): everything is
``runners/serve_parallel_ssm.py``'s (and through it ``runners/serve.py``'s:
cluster, application, load, counters, context; the four comparisons (a) to
(d) of the engine's own programs through its pages and state rows) but the
loader the replica runs, the limits, and three more comparisons made before
the engine exists, with the routed layer held apart
(``in_worker_latent_moe_ssm.py`` says what each sees): (p) logits of the
program's layers under the reference's routing, (h) the held experts' part
on the reference's rows, (r) the program's router on the reference's rows.

``runners/serve.py`` builds its loader and reads its ``CHECK`` by name
inside ``start`` and is not this file's to edit, so the names are swapped
for the length of the call, as ``serve_parallel_ssm.py`` swaps them (PERF.md
section 7).

A CONTROL, a fault planted in what ``correct`` compares, runs through the
same ``check_correct``, with no load:

    python3 -m benchmarks.runners.serve_latent_moe_ssm <cell> <seed> <fault> \
        [<seconds>]

(``families/nemotron_h.FAULTS``, or ``none`` for the clean reading; exit 0
when a fault came out NOT correct; ``not_met`` names the comparisons that
fell.  With ``<seconds>`` the control also offers the cell's traffic for a
window that long, for (d)'s reading.)
"""

from __future__ import annotations

import contextlib
import importlib.util
import os

from benchmarks import common, in_worker, in_worker_latent_moe_ssm
from benchmarks.runners import serve, serve_parallel_ssm

CHECK = {
    **serve_parallel_ssm.CHECK,
    # 6 prompts of 300-1400 tokens (the traffic's are 256-2,048), 128 greedy
    # tokens each through pages and packed state rows, side by side in six
    # slots.
    "n_prompts": 6, "min_len": 300, "max_len": 1400, "steps": 128,
    "pad_to": 1536,
    # LIMITS.  Each lies between the served path as it is and a planted
    # fault, BOTH read on the chip at the published widths and the cell's
    # sizes through ``check_correct`` itself (PERF.md section 6, PR 61; my
    # chip runs).  Clean: the clean control and four runs on five seeds
    # (2147483777, 2147484001, 2147484103, 2147484211, 2147484307) before
    # the limits were set, every later run with them.  Faults: ``control``
    # under every fault of ``FAULTS`` (seed 2147483777).
    # BEFORE THE ENGINE EXISTS, the routed layer held apart.
    # (p) Logits of the program's layers under the reference's routing, rms
    # over 384 positions and the vocabulary slice (the logits are 1.0 rms).
    # Clean 0.00965-0.00976.  ``rope_applied`` 0.0208 (the nearest: ONE
    # layer of eleven attends, over a stream of 3 rms),
    # ``experts_gated_silu`` 0.132, ``relu_not_squared`` 0.611,
    # ``shared_on_latent`` 1.113: the limit stands 1.5 x over the clean
    # maximum and 1.4 x under the nearest.
    "pinned_rms_max": 0.015,
    # (h) The held experts' part ``r W_lout`` on the reference's rows under
    # its routing, relative rms over 5 layers x 384 rows.  Clean
    # 0.00428-0.00429; ``experts_gated_silu`` 0.199, ``relu_not_squared``
    # 0.520 (the same comparison read 0.047 with a share's experts cut to 3
    # bits of mantissa, PR 54): weights kept in fewer bits than stated fall
    # here.
    "held_rel_rms_max": 0.015,
    # (r) The program's router on the reference's rows (cast to the served
    # type): the share of rows whose chosen SET of 22 is the reference's,
    # clean 0.950-0.962 (a bf16 row moves the 22nd of 512 scores past the
    # 23rd in one row of 25) | ``bias_left_out`` 0.0073; and where the set
    # agrees, the weights' relative rms error, clean 0.00019 |
    # ``scale_left_out`` 0.800.
    "router_same_set_min": 0.8, "router_weight_rel_rms_max": 0.01,
    # THROUGH THE CACHE, the engine's own programs, routing NOT pinned (no
    # program takes a routing handed in): the same bf16 stream swaps the
    # last of 22 picks in about half the rows of every routed layer, each
    # swap a fifth of the row's held part, so the readings below are noise
    # of that size and their limits catch what is larger.
    # (a) Logits through pages and state rows, rms over 768 positions.
    # Clean 0.109-0.124.  ``experts_gated_silu`` 0.244 (the nearest that
    # moves it), ``bias_left_out`` 0.387, ``scale_left_out`` 0.536,
    # ``relu_not_squared`` 0.676, ``tail_one_late`` 1.095,
    # ``shared_on_latent`` 1.154; ``rope_applied`` 0.113 and
    # ``state_in_bf16`` 0.122 are NOT (a)'s to catch ((p) and (b) are).
    "logit_rms_max": 0.2,
    # (b) The rows the engine's programs left, relative rms.  Layer 0's
    # state A HEAD AT A TIME (the first mixer reads the embedding's rows
    # through one bf16 product, the same on both sides, and lies before
    # any router): clean 0.00417-0.00440 on six seeds | ``state_in_bf16``
    # 0.00860, ``tail_one_late`` 2.48: the limit stands 1.36 x over the
    # clean maximum and 1.43 x under the state kept in bf16, the nearest
    # precision below the stated float32.  Every layer's state, POOLED
    # (behind up to four routed layers' swaps): clean 0.073-0.119 |
    # ``experts_gated_silu`` 0.201, ``bias_left_out`` 0.363,
    # ``tail_one_late`` 1.90.  The convolution's tail: clean 0.044-0.064 |
    # ``scale_left_out`` 0.370, ``tail_one_late`` 1.35 (``state_in_bf16``
    # 0.100, ``experts_gated_silu`` 0.134: under the limit).  K and V of
    # the one attention layer (behind four routed layers): clean
    # 0.103-0.104 | ``experts_gated_silu`` 0.216, ``rope_applied`` 0.752.
    "state_rel_rms_max": 0.25,
    "first_state_by_head_rel_rms_max": 0.006,
    "tail_rel_rms_max": 0.2,
    "kv_rel_rms_max": 0.15,
    "first_kv_rel_rms_max": 0.15,
    # (c) The engine's greedy tokens (768) on ITS OWN history: the share
    # within ``margin`` of the reference's best 0.898-0.928 clean |
    # ``experts_gated_silu`` 0.667, ``bias_left_out`` 0.434,
    # ``scale_left_out`` 0.234; the furthest under the best 0.63-1.14
    # clean | ``scale_left_out`` 2.35, ``relu_not_squared`` 3.24; the share
    # the replay of (a) also puts first 1.0 in every reading.
    "margin": 0.1, "within_min": 0.75, "gap_max": 3.0, "replay_first_min": 0.9,
    # (d) After the load: 6 of the sequences finished inside the window (of
    # some 120), 64 positions of each answer, under (c)'s limits: clean
    # 0.898-0.925 and 0.58-1.14.  Prompt and answer are up to 2,048 + 1,024.
    "window_requests": 6, "window_positions": 64, "window_pad_to": 3072,
}


class Stack(serve_parallel_ssm.Stack):

    def start(self):
        made = in_worker.make_loader
        in_worker.make_loader = lambda spec: \
            in_worker_latent_moe_ssm.make_loader({**spec,
                                                  "fault": self.fault})
        try:  # (``serve.Stack``'s own: the name is swapped meanwhile)
            super(serve_parallel_ssm.Stack, self).start()
        finally:
            in_worker.make_loader = made

    def check_correct(self) -> dict:
        # serve_parallel_ssm's comparisons, as they are, under this
        # runner's limits (``_names_swapped``: every caller is inside it)
        out = super().check_correct()
        pinned = self.note["pinned"]
        met = {  # each comparison by name: a control says which one fell
            "pinned_logits": pinned["logit_rms_error"]
            < CHECK["pinned_rms_max"],
            "held_experts": pinned["held_rel_rms_error"]
            < CHECK["held_rel_rms_max"],
            "router_same_set": pinned["router_same_set_share"]
            >= CHECK["router_same_set_min"],
            "router_weights": pinned["router_weight_rel_rms_error"]
            is not None and pinned["router_weight_rel_rms_error"]
            < CHECK["router_weight_rel_rms_max"]}
        out["pinned"] = pinned
        out["not_met"] += [k for k, good in met.items() if not good]
        out["ok"] = not out["not_met"]
        return out


@contextlib.contextmanager
def _names_swapped():
    """``runners/serve.py`` under this runner's ``Stack``, ``CHECK`` and
    counters, and ``serve_parallel_ssm``'s ``check_correct`` and
    ``run_load`` under this runner's limits (the module docstring says why
    by name)."""
    base = (serve.Stack, serve.CHECK, serve.COUNTERS,
            serve_parallel_ssm.CHECK)
    serve.Stack, serve.CHECK = Stack, CHECK
    serve.COUNTERS = base[2] + serve_parallel_ssm.COUNTED
    serve_parallel_ssm.CHECK = CHECK
    try:
        yield
    finally:
        (serve.Stack, serve.CHECK, serve.COUNTERS,
         serve_parallel_ssm.CHECK) = base


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
