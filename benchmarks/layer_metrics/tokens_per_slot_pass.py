"""Tokens a live slot's denoising pass yields: tokens emitted over slot
passes, from the ``llm.loop.decode_emit`` spans that ended in the window
(a burst's span says what its passes did).  4 / 3 by construction with
sequential remasking at 2 steps (two filling passes and a final one a
block of 4); a prompt's tail in the first block, and the passes a burst
runs past a request's end, move it a little."""

from benchmarks import common


def read(ctx):
    args = [s.get("args") or {} for s in common.spans_named(
        ctx, "llm.loop.decode_emit")]
    slot_passes = sum(a.get("slot_passes", 0) for a in args)
    if not slot_passes:
        return None
    return sum(a.get("tokens", 0) for a in args
               if "slot_passes" in a) / slot_passes
