"""Runs a serving cell whose model has block-sparse attention layers and
fixed-decay linear-attention layers, prompts in chunks that carry the
state: everything is ``runners/serve.py``'s (cluster, application, load,
counters, context) but the loader the replica runs and the comparison that
decides ``correct``, which is six (``in_worker_sparse_linear.py`` says
what each sees): (a) logits under the reference's choice of blocks through
the prefills' own attention by key block, (b) the program's own choice
against the reference's, (e) the recurrence on pinned inputs, (c) the rows
the engine's own programs left (K/V pages, pooled keys, state rows, after
chunks and after as many steps as an answer of the traffic has), (f) the
decode step's sparse attention (its lists and the paged kernel that walks
them) over the engine's own pools, (d) the engine's greedy tokens on its
own history, a preempted and resumed sequence among them.

``runners/serve.py`` builds its loader and reads its ``CHECK`` by name
inside ``start`` and is not this file's to edit, so the names are swapped
for the length of the call, as ``serve_windowed.py`` swaps them (PERF.md
section 7).
"""

from __future__ import annotations

import contextlib
import importlib.util
import os
import random
import time

from benchmarks import common, in_worker, in_worker_sparse_linear
from benchmarks.runners import serve

CHECK = {
    **serve.CHECK,
    # what ``serve.Stack.start`` draws and hands the loader, which this
    # family's loader does not read (it draws (a)'s sequences itself)
    "n_prompts": 2, "min_len": 20, "max_len": 60,
    # the check's own prompts: 8 of 6,000-11,000 tokens spread evenly (3-6
    # chunks of 2,048; four of them past dense_len 8,192 at once, three
    # cross it nowhere: the steps of the fourth do), 257 greedy tokens each
    # (the prompt's and 32 bursts of eight steps: the traffic's answers are
    # 256-768, and a state kept in less than float32 shows in the rows only
    # once its rounding has had a slow head's memory, ~256 steps, to pile
    # up), the last but one ONE token (it ends on its prompt: its state
    # rows are the chunks' alone), the last preempted after 8 tokens and
    # recomputed in chunks
    "prompts": 8, "shortest": 6000, "longest": 11000, "steps": 257,
    "pad_to": 11520,
    # the head divides the normed stream by hidden_size / dim_model_base =
    # 16, so this model's logits are 0.06 rms where the other families' are
    # ~1: serve.py's margin of 0.1, a sixteenth
    "margin": 0.00625,
    # LIMITS.  Each lies between two readings taken on the chip at the
    # published widths (PERF.md section 6, PR 49; my chip runs): the served
    # path as it is, and a planted fault's (``FAULTS`` below, each a
    # CONTROL through ``check_correct``).
    # (a) Logits under the reference's choice, rms over 16 positions a
    # group and the whole vocabulary (the logits are 0.0625 rms), the worst
    # group: clean 0.000489-0.000497 (11 runs) | the residual's root over
    # the depth that is run (``depth_root_8``) 0.0269-0.0271.
    "pinned_rms_max": 0.002,
    # (b) The share of the reference's selection, past dense_len, that the
    # program's own choice (bf16 stream, bf16 pooled keys) also holds: of
    # its BLOCKS in the pinned pass (720,896 blocks), and of its PAGES in
    # the lists the step's own ``page_lists`` builds over the rows of
    # pooled keys the ENGINE's programs left, at each check sequence's last
    # position: clean 0.9970-0.9972 (pinned pass) and 0.9945-1.0 (the
    # engine's lists) | a choice of 48 blocks (``topk_48``) 0.75 and 0.749,
    # all it can share.
    "selection_in_common_min": 0.9,
    # (e) The recurrence on pinned float32 inputs, relative rms, the worse
    # of the outputs and the final state: clean 5.1e-5 (state; outputs
    # 5.0e-6) | the state kept in bf16 1.0e-2 (outputs 1.2e-3).
    "recurrence_rel_rms_max": 5e-4,
    # (c) The rows the engine's programs left, relative rms.  Layer 0's K
    # and V (one bf16 product deep, as every family's: 0.0026 clean, the
    # same rows at 3 bits of mantissa 0.0268 in PRs 41 and 46): clean
    # 0.002621 in every run.  Both layers' K and V: clean 0.00579-0.00595 |
    # ``depth_root_8`` 0.298-0.299; the pooled keys: clean 0.00608-0.00614
    # | 0.299; the state rows after chunks alone: clean 0.0078-0.0084 |
    # 0.415.  The state rows after 257 steps, as many as an answer of the
    # traffic has: clean 0.00780-0.00782 (0.0078-0.0084 after 33 steps, 11
    # runs: bf16 inputs bound these rows and nothing piles up) | a state
    # kept in bf16 (``state_bf16``, planted in the engine's own recurrence)
    # 0.01577 (0.0113 after 33 steps, when this limit could not see it).
    "first_kv_rel_rms_max": 0.009,
    "kv_rel_rms_max": 0.012,
    "pooled_rel_rms_max": 0.012,
    "state_rel_rms_max": 0.016,
    "state_steps_rel_rms_max": 0.011,
    # (f) The decode step's sparse attention (its ``lists_from`` and the
    # paged kernel with ``heads_apart``) over the ENGINE's pools, with the
    # reference's query and choice at each check sequence's last cached
    # position, against the reference's attention output there, relative
    # rms, under dense_len (3 sequences) and past it (5): clean
    # 0.0066-0.0069 and 0.0073-0.0074 | every list a page late
    # (``list_page_shifted``: the first page's 16 keys for 16 later ones,
    # of ~4,000) 0.0736 and 0.0847; the other KV head's rows
    # (``other_heads_columns``) 1.417 and 1.411; 48 blocks of the
    # reference's 64 listed (``topk_48``) 0.480 past dense_len.
    "attend_rel_rms_max": 0.02,
    # (d) The engine's greedy tokens (1,800: 7 sequences x 257 and one of
    # 1), each held against the reference ON THE ENGINE'S OWN HISTORY: the
    # share within the margin of the best: clean 1.0 in every run (the
    # furthest under the best 0.0003-0.0017) | ``depth_root_8`` 0.349 (the
    # furthest 0.115; read over 232 tokens, 33 a sequence).
    "within_min": 0.8,
}

# The CONTROLS (``control`` below; PERF.md section 6 has their readings on
# the chip): a fault planted in what ``correct`` compares, which must come
# out as not correct through ``check_correct`` itself.  ``state_bf16``: the
# recurrent state kept in bf16, the nearest precision below the
# configuration's float32 (the replica's process is planted before anything
# compiles, so (e) and the engine both run it); ``topk_48``: a selection
# short of the published one, in the pinned pass and in the engine;
# ``list_page_shifted`` and ``other_heads_columns``: the decode step's
# lists a page late, and a KV head's queries over the other head's rows,
# in (f) and in the engine's step alike (neither moves a row or a token past
# its limit: only (f) sees the sparse layers' decode attention);
# ``depth_root_8``: the residual's root over the 8 layers that are run, not
# the published 32 (every sublayer's output twice too large).
FAULTS = ("state_bf16", "topk_48", "depth_root_8", "list_page_shifted",
          "other_heads_columns")

ROW_LIMITS = {"first_kv": "first_kv_rel_rms_max",
              "kv_prefill": "kv_rel_rms_max", "kv_decode": "kv_rel_rms_max",
              "pooled": "pooled_rel_rms_max",
              "state_chunks": "state_rel_rms_max",
              "state_steps": "state_steps_rel_rms_max"}
COUNTED = ("decode_pages_read", "prefill_chunks", "state_slot_steps",
           "state_resets", "scan_chunks", "sparse_blocks_selected",
           "sparse_pages_read", "sparse_pages_resident",
           "dense_rule_slot_steps", "index_rows_written")


class Stack(serve.Stack):

    fault = None  # a control's planted fault, never a run's

    def start(self):
        made = in_worker.make_loader
        in_worker.make_loader = lambda spec: \
            in_worker_sparse_linear.make_loader({**spec, "fault": self.fault})
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
        asked = [1 if i == len(prompts) - 2 else steps
                 for i in range(len(prompts))]
        got = [c.result(timeout_s=900) for c in [
            server.generate_tokens.remote(p, max_tokens=m)
            for p, m in zip(prompts, asked)]]
        stats = self.engine_stats()
        verdict = self._ask("cmd-verify.json", {
            "prompts": prompts, "outputs": got, "steps": steps,
            "pad_to": CHECK["pad_to"]}, "verify")
        gaps = [g for row, m in zip(verdict["gaps"], asked) for g in row[:m]]
        there = [g for g in gaps if g is not None]
        within = sum(g < CHECK["margin"] for g in there) / len(gaps)
        pinned, rec = self.note["pinned"], self.note["recurrence"]
        rows = verdict["rows"]
        met = {  # each comparison by name: a control says which one fell
            "tokens_present": len(there) == len(gaps),
            "within_margin": within >= CHECK["within_min"],
            "pinned_logits": max(pinned["logit_rms_error"].values())
            < CHECK["pinned_rms_max"],
            "selection_in_common": pinned["selection_in_common"]
            >= CHECK["selection_in_common_min"],
            "engine_selection_in_common":
                (verdict["engine_selection_in_common"] or 0.0)
                >= CHECK["selection_in_common_min"],
            "recurrence": max(rec["outputs_rel_rms"], rec["state_rel_rms"])
            < CHECK["recurrence_rel_rms_max"],
            **{f"rows_{name}": rows[name] is not None
               and rows[name] < CHECK[limit]
               for name, limit in ROW_LIMITS.items()},
            "served_attention": all(
                e is not None and e < CHECK["attend_rel_rms_max"]
                for e in verdict["attend_rel_rms"].values()),
            # the stated precision of the state is what the engine holds
            "state_float32": verdict["state_dtype"] == "float32",
            "all_free_after": bool(verdict["all_free_after"]),
            "preempted_once": verdict["preempted"] == 1,
            "chunked": stats.get("prefill_chunks", 0) >= 3 * len(prompts),
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
                "pinned": pinned, "recurrence": rec, "rows": rows,
                "engine_selection_in_common":
                    verdict["engine_selection_in_common"],
                "attend_rel_rms": verdict["attend_rel_rms"],
                "state_dtype": verdict["state_dtype"],
                "prefill_chunks": stats.get("prefill_chunks"),
                "limits": {k: v for k, v in CHECK.items()
                           if k.endswith(("_max", "_min")) or k == "margin"},
                "verify_s": verdict["verify_s"]}


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
    if importlib.util.find_spec("ray_tpu.models.minicpm_sala") is None:
        raise RuntimeError(
            f"this program has no ray_tpu.models.minicpm_sala: it cannot "
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


if __name__ == "__main__":
    # python3 -m benchmarks.runners.serve_sparse_linear <cell> <seed> <fault>
    import json
    import sys

    name, seed, fault = sys.argv[1], int(sys.argv[2]), sys.argv[3]
    if fault not in FAULTS:
        raise SystemExit(f"fault {fault!r} is none of {FAULTS}")
    verdict = control(name, seed, fault)
    print(f"# control {fault}: " + json.dumps(verdict), flush=True)
    print(json.dumps({"fault": fault, "correct": verdict["ok"]}))
    sys.exit(1 if verdict["ok"] else 0)  # a control that passes has failed
