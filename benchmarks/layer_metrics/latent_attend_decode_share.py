"""Latent attention's own work in a decode step (``mla/absorb``: the
query through ``W_uk``; ``mla/attend``: the paged kernel over the latent
rows; ``mla/unabsorb``: its output through ``W_uv``) as a share of the
step's operation time (``jit_decode_step*`` runs).  The projections around
it (``mla/kv_down``, ``mla/q_proj``, ``attn/out``) are weight streaming
like the rest of the step and are not in it."""

from benchmarks.layer_metrics import _latent


def read(ctx):
    fam = _latent.family(ctx)
    return fam and _latent.share(ctx, lambda part: part in fam.LATENT_PARTS)
