"""Runs a serving cell whose model is built of shortcut-connected double
layers and holds one chip's share of its routed experts: everything is
``runners/serve_latent.py``'s (and through it ``runners/serve.py``'s:
cluster, application, load, counters, context) but the loader the replica
runs, the limits, and one more comparison, (a'): the held experts' product
on the reference's rows (``in_worker_shortcut_moe.py`` says what each
sees).

``runners/serve.py`` builds its loader and reads its ``CHECK`` by name
inside ``start`` and is not this file's to edit, so the names are swapped
for the length of the call, as ``serve_latent.py`` swaps them (PERF.md
section 7).

A CONTROL, a fault planted in what ``correct`` compares, runs through the
same ``check_correct``, with no load:

    python3 -m benchmarks.runners.serve_shortcut_moe <cell> <seed> <fault>

(``in_worker_shortcut_moe.FAULTS``; exit 0 when it came out NOT correct;
``not_met`` names the comparisons that fell.)  ``pinned`` in place of the
fault reads (a) and (a') under every fault in one process, without the
engine (``control_pinned``).
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os

from benchmarks import common, in_worker, in_worker_shortcut_moe
from benchmarks.runners import serve, serve_latent

CHECK = {
    **serve_latent.CHECK,
    # 8 prompts of 200-1100 tokens, 48 greedy steps each through latent
    # pages, the first again last (a prefix hit), as serve_latent's.
    # LIMITS.  Each lies between the served path as it is and a planted
    # fault, BOTH read on the chip at the published widths and the cell's
    # sizes (PERF.md section 6, PR 54; my chip runs).  Clean: the eight
    # runs that reached their end on the committed weights, a seed each.
    # Faults: (a) and (a') under every fault of ``FAULTS`` from
    # ``control_pinned`` (seed 2147490451), (b) and (c) under
    # ``kv_scale_left_out`` from ``control`` (seed 1000000013).
    # (a) Logits under the reference's routing, rms over 512 positions and
    # the vocabulary slice (the logits are 1.0 rms), the worse of the two
    # attention forms.  Clean 0.0117-0.0122.  The identity picks left out
    # 1.024, ``kv_scale_left_out`` 1.023, the branch taken from
    # norm_f1(h3) 0.868, ``q_scale_left_out`` 0.579, the latent rows cut to
    # 3 bits of mantissa 0.0472 (the nearest: the limit stands 2.0 x over
    # the clean maximum and 1.9 x under it); the held experts cut to 3 bits
    # 0.0129, which (a) does not see and (a') is for.  With W_qb and W_kvb
    # drawn at variance 1 / fan_in (no fault: the softmax an argmax) it
    # read 0.313-0.327, which is why they are not drawn so.
    "pinned_rms_max": 0.025,
    # (a') The held experts' part on the reference's rows, relative rms
    # over the rows some held expert was picked for (389-531 of 512 x 4).
    # Clean 0.0033-0.0034; the experts' weights cut to 3 bits of mantissa
    # 0.0473; the identity picks left out 9.38 (the program's part then
    # lacks what ``held_part`` takes off again).  No other limit sees the
    # held experts: they are ~0.03 of the stream's rms.
    "held_rel_rms_max": 0.015,
    # (b) The rows the engine's programs left in its pool, relative rms,
    # the worse of a prefill's rows and the decode steps'.  Sublayer 0 of
    # layer 0: clean 0.0032 (every seed); ``kv_scale_left_out`` 0.708; rows
    # cut to 3 bits 0.0264 (float32 arithmetic at the tiny size, which a
    # cut of mantissa bits does not depend on: GLM's same row format read
    # 0.0268 on the chip; this fault's full control was not run there).
    # Sublayer 1 of layer 0 (behind one attention and one dense FFN, before
    # any router's output rejoins): clean 0.0098-0.0110;
    # ``kv_scale_left_out`` 0.881-0.908; GLM's page cut read 0.0286.  All
    # eight: clean 0.0129-0.0196 (a swapped column here is a twelfth of
    # 3.5, so a bf16 stream stays near); ``kv_scale_left_out`` 0.867-0.885.
    "first_layer_rows_rel_rms_max": 0.009,
    "second_layer_rows_rel_rms_max": 0.02,
    "all_layers_rows_rel_rms_max": 0.1,
    # (c) The engine's greedy tokens (432) on ITS OWN history: the share
    # within serve.py's margin of the reference's best 0.998-1.0 clean,
    # 0.007 with ``kv_scale_left_out``; the furthest token 0.018-0.115
    # under the best clean, 5.59 with the fault.
    "within_min": 0.6, "gap_max": 1.0,
}


class Stack(serve.Stack):

    fault = None  # a control's planted fault, never a run's

    def start(self):
        made = in_worker.make_loader
        in_worker.make_loader = (
            lambda spec: in_worker_shortcut_moe.make_loader(
                {**spec, "fault": self.fault}))
        try:
            super().start()
        finally:
            in_worker.make_loader = made

    def check_correct(self) -> dict:
        with _check_swapped():  # serve_latent's three comparisons, as is
            out = serve_latent.Stack.check_correct(self)
        held = self.note["pinned"]["held_rel_rms_error"]
        held_ok = held is not None and held < CHECK["held_rel_rms_max"]
        pinned, rows = out["pinned"], out["rows"]
        worst = {limit: max((rows[f"{k}_prefill"], rows[f"{k}_decode"]),
                            key=lambda v: float("inf") if v is None else v)
                 for k, limit in serve_latent.ROW_LIMITS.items()}
        met = {  # each comparison by name: a control says which one fell
            "tokens_present": out["tokens_missing"] == 0,
            "within_margin": out["within_margin_share"]
            >= CHECK["within_min"],
            "furthest": out["furthest_under_best"] is not None
            and out["furthest_under_best"] < CHECK["gap_max"],
            "pinned_logits": max(pinned["logit_rms_error"].values())
            < CHECK["pinned_rms_max"],
            "held_experts": held_ok,
            **{limit: v is not None and v < CHECK[limit]
               for limit, v in worst.items()},
            "prefix_hit": out["prefix_hit_tokens"] > 0}
        out["limits"]["held_rel_rms_max"] = CHECK["held_rel_rms_max"]
        return {**out, "ok": all(met.values()),
                "not_met": [k for k, good in met.items() if not good]}


@contextlib.contextmanager
def _check_swapped():
    """``serve_latent``'s ``check_correct`` under THIS runner's limits."""
    theirs = serve_latent.CHECK
    serve_latent.CHECK = CHECK
    try:
        yield
    finally:
        serve_latent.CHECK = theirs


@contextlib.contextmanager
def _names_swapped():
    """``runners/serve.py`` under this runner's ``Stack``, ``CHECK`` and
    counters (the module docstring says why by name)."""
    base = serve.Stack, serve.CHECK, serve.COUNTERS
    serve.Stack, serve.CHECK = Stack, CHECK
    serve.COUNTERS = base[2] + (
        "experts_read", "latent_pages_read", "decode_pages_read",
        "moe_local_rows", "moe_zero_picks", "moe_absent_picks")
    try:
        yield
    finally:
        serve.Stack, serve.CHECK, serve.COUNTERS = base


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    # a program without this family says so here, at once, and not from
    # inside a replica that the driver would wait on
    if importlib.util.find_spec("ray_tpu.models.longcat_flash") is None:
        raise RuntimeError(
            f"this program has no ray_tpu.models.longcat_flash: it cannot "
            f"run configuration {cell['config']!r}")
    with _names_swapped():
        return serve.run(cell, seed, seconds, trace, t_start)


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


def control_pinned(cell_name: str, seed: int) -> dict:
    """(a) and (a') with NO fault and then with every fault of ``FAULTS``,
    one after another in THIS process, which takes the chip itself: no
    cluster, no engine; the check prompts ``serve.Stack.start`` draws from
    ``seed``, the cell's sizes.  A fault's ``fell`` names which of the two
    limits it failed."""
    import random

    cell = common.load_cell(cell_name)
    c = cell["config_file"]
    family = common.module("families", c["family"])
    reference = common.module("reference", c["family"])
    rng, vocab = random.Random(seed), c["vocab_size"]
    firsts = rng.sample(range(vocab // 32, vocab // 16), CHECK["n_prompts"])
    prompts = [[first] + [rng.randrange(3, vocab) for _ in range(
        rng.randint(CHECK["min_len"], CHECK["max_len"]) - 1)]
        for first in firsts]
    params = family.make_params(c, seed, c["dtype"])
    out = {}
    for fault in (None,) + in_worker_shortcut_moe.FAULTS:
        undo = in_worker_shortcut_moe.plant(fault) if fault else None
        try:
            got = in_worker_shortcut_moe.pinned_check(
                c, params, family, reference, prompts, CHECK["pad_to"], fault)
        finally:
            if undo:
                undo()
        read = {"pinned_rms_max": max(got["logit_rms_error"].values()),
                "held_rel_rms_max": got["held_rel_rms_error"]}
        out[fault or "none"] = {
            **read, "fell": [k for k, v in read.items() if v >= CHECK[k]]}
        print(f"# pinned {fault or 'none'}: {json.dumps(got)}", flush=True)
    return out


if __name__ == "__main__":
    # python3 -m benchmarks.runners.serve_shortcut_moe <cell> <seed> <fault>
    # python3 -m benchmarks.runners.serve_shortcut_moe <cell> <seed> pinned
    import sys

    name, seed, fault = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    if fault == "pinned":
        print(json.dumps(control_pinned(name, seed)))
        sys.exit(0)
    if fault not in in_worker_shortcut_moe.FAULTS + ("none",):
        raise SystemExit(f"fault {fault!r} is none of "
                         f"{in_worker_shortcut_moe.FAULTS}")
    verdict = control(name, seed, None if fault == "none" else fault)
    print(f"# control {fault}: " + json.dumps(verdict), flush=True)
    print(json.dumps({"fault": fault, "correct": verdict["ok"],
                      "not_met": verdict["not_met"]}))
    # a control that passes has failed ("none" is the clean reading)
    sys.exit(int(verdict["ok"]) if fault != "none" else 0)
