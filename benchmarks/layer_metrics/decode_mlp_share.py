"""The dense MLP's three weight products (``mlp/*``: norm, gate and up,
down) as a share of the decode step's operation time (``jit_decode_step*``
runs): the weights' floor of the step."""

from benchmarks.trace import device_parts


def read(ctx):
    return device_parts.share(ctx, "jit_decode_step",
                              lambda part: part.startswith("mlp/"))
