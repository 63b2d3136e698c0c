#!/usr/bin/env python3
"""A prompt OVER THE LARGEST PREFILL BUCKET through the engine, on the chip,
while another request decodes between its chunks: what no cell's traffic
sends to a model whose slots hold a recurrent state and a convolution's
tail beside their pages (``serve_parallel_ssm_chat``'s prompts end at 768 of
a bucket of 1,024), and where PR 57's review found a fault: the decode
bursts between two chunks overwrote the chunked slot's convolution rows.

    python3 benchmarks/chunked_prompt_check.py <cell> <seed>

One engine in THIS process (it takes the chip itself: no cluster, no load)
at the cell's configuration and engine sizes, seeded weights.  A short
request is admitted and decodes throughout; then the long one (``LONG``
tokens: two chunks) is admitted, computed a chunk a loop iteration with the
other's bursts between, and answers ``ANSWER`` greedy tokens.  Judged
against the float32 reference over the long sequence: its tokens on the
engine's own history (the runner's (c) limits) and the rows its slot holds
once it has left and the other has decoded on, state and convolution tail
(the runner's (b) limits).  One JSON line; exit 0 when every limit is met.
"""

from __future__ import annotations

import json
import os
import random
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import common  # noqa: E402

SHORT, SHORT_ANSWER = 200, 600  # decodes from before the long prompt to after
# The answer is 1 + 8 k tokens, so that it ENDS WITH A BURST (the engine
# chains 8 greedy steps a fetch while nothing waits): a slot that finishes
# mid-burst goes on taking the burst's tokens into its rows, which is safe
# (they are begun anew at the next admission) and makes them no sequence's.
LONG, ANSWER, PAD_TO = 1400, 65, 1536


def _drain(req) -> list:
    out = []
    while True:
        item = req.out_queue.get(timeout=600)
        if item is None:
            return out
        if isinstance(item, Exception):
            raise item
        out.append(item)


def check(c: dict, seed: int, limits: dict, short=(SHORT, SHORT_ANSWER),
          long=(LONG, ANSWER), pad_to: int = PAD_TO) -> dict:
    import numpy as np

    from ray_tpu.llm.config import EngineConfig, SamplingParams
    from ray_tpu.llm.engine import LLMEngine

    family = common.module("families", c["family"])
    reference = common.module("reference", c["family"])
    eng = dict(c["engine"])
    eng["prefill_buckets"] = tuple(eng["prefill_buckets"])
    if long[0] <= max(eng["prefill_buckets"]):
        raise ValueError(f"{long[0]} tokens fit the largest bucket")
    rng = random.Random(seed)
    prompts = [[rng.randrange(3, c["vocab_size"]) for _ in range(n)]
               for n in (short[0], long[0])]
    engine = LLMEngine(family.make_params(c, seed, c["dtype"]),
                       family.model_config(c), EngineConfig(**eng))
    engine.start()
    try:
        a = engine.submit(prompts[0], SamplingParams(max_tokens=short[1]))
        head = a.out_queue.get(timeout=600)  # a decodes by now
        b = engine.submit(prompts[1], SamplingParams(max_tokens=long[1]))
        first = b.out_queue.get(timeout=600)
        (slot,) = [i for i, s in enumerate(engine._slots)
                   if s is not None and s.request is b]
        out_b = [first] + _drain(b)
        still = a.produced < short[1]  # a was decoding all the while
        out_a = [head] + _drain(a)  # and decodes on past b's end
        stats = engine.stats()
        rows = {k: np.asarray(v, np.float32)
                for k, v in family.engine_state(engine, slot).items()}
        seq = prompts[1] + out_b[:-1]  # what the slot's rows have taken
        ref = reference.forward(
            c, engine.params, seq, len(prompts[1]) - 1 + np.arange(len(out_b)),
            len(seq), pad_to)
    finally:
        engine.stop()
    want = np.asarray(ref["logits"])
    gaps = want.max(axis=-1) - want[np.arange(len(out_b)), np.asarray(out_b)]

    def rel(have, want):
        want = np.asarray(want, np.float32)
        return float((np.sum((have - want) ** 2) / np.sum(want ** 2)) ** 0.5)

    got = {"within_margin_share": float(np.mean(gaps < limits["margin"])),
           "furthest_under_best": float(gaps.max()),
           "state": rel(rows["S"], ref["S"]),
           "tail": rel(rows["conv"], ref["conv"])}
    met = {"other_decoded_throughout": still and len(out_a) == short[1],
           "chunks": stats["prefill_chunks"] == -(-long[0] // max(
               eng["prefill_buckets"])),
           "within_margin": got["within_margin_share"] >= limits["within_min"],
           "furthest": got["furthest_under_best"] < limits["gap_max"],
           "rows_state": got["state"] < limits["state_rel_rms_max"],
           "rows_tail": got["tail"] < limits["tail_rel_rms_max"]}
    return {**got, "prompt": long[0], "answer": len(out_b), "slot": slot,
            "prefill_chunks": stats["prefill_chunks"],
            "state_resets": stats["state_resets"], "ok": all(met.values()),
            "not_met": [k for k, good in met.items() if not good]}


if __name__ == "__main__":
    from benchmarks.runners import serve_parallel_ssm

    cell = common.load_cell(sys.argv[1])
    verdict = check(cell["config_file"], int(sys.argv[2]),
                    serve_parallel_ssm.CHECK)
    print(json.dumps(verdict), flush=True)
    sys.exit(0 if verdict["ok"] else 1)
