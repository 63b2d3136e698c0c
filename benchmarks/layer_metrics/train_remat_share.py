"""Recomputation (operations under ``rematted_computation``: the layers'
second forward pass in the backward pass, and the chunked loss's) as a
share of the train step's operation time (``jit_step_fn`` runs)."""

from benchmarks.trace import device_parts


def read(ctx):
    return device_parts.share(ctx, "jit_step_fn", lambda part: True,
                              phases=("recompute",))
