"""The sparse layers' feed-forward (``moe/*``: the router, the dispatch's
sort and gathers, the grouped product over the experts reached, the combine
and the shared expert) as a share of the decode step's operation time
(``jit_decode_step*`` runs)."""

from benchmarks.layer_metrics import _latent


def read(ctx):
    fam = _latent.family(ctx)
    return fam and _latent.share(
        ctx, lambda part: part.startswith(fam.MOE_PARTS_PREFIX))
