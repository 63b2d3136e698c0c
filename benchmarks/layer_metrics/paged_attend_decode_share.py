"""The paged decode kernel (``attn/attend``: in the window layers with its
lower bound, in the full layers without) as a share of the decode step's
operation time (``jit_decode_step*`` runs): what attention costs a step once
a window layer walks its window and not the sequence."""

from benchmarks.layer_metrics import _windowed


def read(ctx):
    fam = _windowed.family(ctx)
    return fam and _windowed.share(
        ctx, lambda part: part.startswith(fam.ATTEND_PART))
