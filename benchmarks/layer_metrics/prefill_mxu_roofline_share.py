"""Prefill against the MXU roofline: the FLOPs the prompts prefilled in
the slice require (the family's count, from the published sizes) at the
chip's peak bf16 rate, over the device time their programs took.  Bound:
compute (a prefill of hundreds of tokens reads each weight once for
hundreds of multiply-adds)."""

from benchmarks import common
from benchmarks.layer_metrics import _prefill


def read(ctx):
    got = _prefill.in_slice(ctx)
    if got is None or not ctx.get("peaks"):
        return None
    seconds, work = got
    family = common.module("families", ctx["config"]["family"])
    flops = sum(family.prefill_flops(ctx["config"], n, cached)
                for n, cached in work)
    return 100.0 * flops / ctx["peaks"]["bf16_flops_per_s"] / seconds
