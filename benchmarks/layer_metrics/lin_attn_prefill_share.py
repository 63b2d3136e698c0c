"""The linear-attention layers' own work (``lin_attn/*``, the chunked scan
of the delta rule among it) as a share of the prefill programs' operation
time (``jit_prefill*`` runs)."""

from benchmarks.layer_metrics import _lin_attn


def read(ctx):
    return _lin_attn.share(ctx, "jit_prefill")
