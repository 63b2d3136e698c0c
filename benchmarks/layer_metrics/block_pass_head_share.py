"""The output head over the whole vocabulary and the token selection
(``head``, ``sample``) as a share of a denoising pass's operation time
(``jit_block_step`` runs)."""

from benchmarks.trace import device_parts

PARTS = ("head", "sample")


def read(ctx):
    return device_parts.share(ctx, "jit_block_step", PARTS.__contains__)
