"""Everything of the routed feed-forward but the experts' kernel
(``moe/route``, ``moe/dispatch``, ``moe/combine``: the router, the sort and
the gathers of rows) as a share of a denoising pass's operation time
(``jit_block_step`` runs)."""

from benchmarks.trace import device_parts

PARTS = ("moe/route", "moe/dispatch", "moe/combine")


def read(ctx):
    return device_parts.share(ctx, "jit_block_step", PARTS.__contains__)
