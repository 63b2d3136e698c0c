"""The linear layers' own work (``lightning/*``: the stacked input product
with its norms, the state update, the output norm, gate and product) as a
share of the decode step's operation time (``jit_decode_step*`` runs).  The
rest is the MLPs, the sparse layers and the head."""

from benchmarks.layer_metrics import _sparse_linear


def read(ctx):
    fam = _sparse_linear.family(ctx)
    return fam and _sparse_linear.share(
        ctx, fam.DECODE_MODULE,
        lambda part: part.startswith(fam.LINEAR_PREFIX))
