"""What the LATENT costs a decode step outside its experts: the two
projections every routed layer makes, into the latent before the dispatch
and back out of it behind the combine (``moe/latent_in`` +
``moe/latent_out``), as a share of the decode step's operation time
(``jit_decode_step*`` runs).  None where the family declares no such parts
or the trace holds none."""

from benchmarks.layer_metrics import _latent
from benchmarks.layer_metrics import latent_moe_expert_hbm_roofline_share as _m


def read(ctx):
    fam = _m.family(ctx)
    return fam and _latent.share(
        ctx, lambda part: part in fam.LATENT_PROJ_PARTS)
