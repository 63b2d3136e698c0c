"""The sparse layers' feed-forward (``moe/*``: the router, the dispatch's
sort and gathers, the grouped product over the experts reached, the combine
and the shared expert) as a share of the decode step's operation time
(``jit_decode_step*`` runs) of a model served over pools by layer type.
(The grouped product's bytes against the roofline:
``windowed_moe_expert_hbm_roofline_share``.)"""

from benchmarks.layer_metrics import _windowed


def read(ctx):
    fam = _windowed.family(ctx)
    return fam and _windowed.share(
        ctx, lambda part: part.startswith(fam.MOE_PARTS_PREFIX))
