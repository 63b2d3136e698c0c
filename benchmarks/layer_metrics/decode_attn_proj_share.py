"""The attention projections as a share of the decode step's operation
time (``jit_decode_step*`` runs): the four products (``attn/qkv``,
``attn/out``) and the layer scan's own operations (``layers``), which in
this program are the slices of the stacked projection weights and the
transposes XLA makes of them (the MLP's products read their weights where
they lie).  Read once at the bandwidth, the four weights are 13 % of the
step: what lies above that is waste."""

from benchmarks.trace import device_parts

PARTS = ("attn/qkv", "attn/out", "layers")


def read(ctx):
    return device_parts.share(ctx, "jit_decode_step", PARTS.__contains__)
