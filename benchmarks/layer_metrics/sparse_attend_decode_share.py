"""The sparse layers' paged kernel over the chosen pages
(``sparse_attn/attend``) as a share of the decode step's operation time
(``jit_decode_step*`` runs)."""

from benchmarks.layer_metrics import _sparse_linear


def read(ctx):
    fam = _sparse_linear.family(ctx)
    return fam and _sparse_linear.share(
        ctx, fam.DECODE_MODULE, lambda part: part == fam.ATTEND_PART)
