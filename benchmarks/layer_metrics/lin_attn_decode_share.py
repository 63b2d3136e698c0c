"""The linear-attention layers' own work (``lin_attn/*``: the stacked input
product, the short convolution, norms and gates, the state update, the
gated norm and output product) as a share of the decode step's operation
time (``jit_decode_step*`` runs).  The rest is the MLPs, the full layers'
attention and the head."""

from benchmarks.layer_metrics import _lin_attn


def read(ctx):
    return _lin_attn.share(ctx, "jit_decode_step")
