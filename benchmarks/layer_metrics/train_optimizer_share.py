"""The optimizer (``optim``: Adam's update, applying it, the gradient
norm) as a share of the train step's operation time (``jit_step_fn``
runs)."""

from benchmarks.trace import device_parts


def read(ctx):
    return device_parts.share(ctx, "jit_step_fn", "optim".__eq__)
