"""The sparse layers' choice of blocks (``sparse_attn/index``: the row of
pooled keys a step completes, every slot's rows gathered through its table,
the pooled scores, the max-pool onto blocks, the top-k and the page lists)
as a share of the decode step's operation time (``jit_decode_step*``
runs): what selecting costs beside what it saves."""

from benchmarks.layer_metrics import _sparse_linear


def read(ctx):
    fam = _sparse_linear.family(ctx)
    return fam and _sparse_linear.share(
        ctx, fam.DECODE_MODULE, lambda part: part == fam.INDEX_PART)
