"""The sparse layers' attention by key block under the chosen blocks' mask
(``sparse_attn/attend``: every visible key block computed and masked) as a
share of the prefill programs' operation time (``jit_prefill*`` runs).  A
prefill that skipped the key blocks no query of the chunk selected would
move this and nothing else."""

from benchmarks.layer_metrics import _sparse_linear


def read(ctx):
    fam = _sparse_linear.family(ctx)
    return fam and _sparse_linear.share(
        ctx, fam.PREFILL_MODULE, lambda part: part == fam.ATTEND_PART)
