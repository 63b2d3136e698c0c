"""The loss head (``loss``: the chunked cross-entropy's products, its
softmax and what it moves between chips) as a share of the train step's
operation time (``jit_step_fn`` runs)."""

from benchmarks.trace import device_parts


def read(ctx):
    return device_parts.share(ctx, "jit_step_fn", "loss".__eq__)
