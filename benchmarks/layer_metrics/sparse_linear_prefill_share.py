"""Both mixers' own work (``sparse_attn/*``: the products, the pooled keys
and the choice of blocks, attention by key block under the mask;
``lightning/*``: the products, the chunked recurrence from the state the
chunk before left, the output) as a share of the prefill programs' operation
time (``jit_prefill*`` runs).  The rest is the MLPs and the head."""

from benchmarks.layer_metrics import _sparse_linear


def read(ctx):
    fam = _sparse_linear.family(ctx)
    return fam and _sparse_linear.share(
        ctx, fam.PREFILL_MODULE, lambda part: _sparse_linear.ours(fam, part))
