"""Device time of one decode step of a model that attends over latent
pages: the ``jit_decode_step*`` modules in the trace, over their runs; None
where no such program holds an ``mla/*`` part."""

from benchmarks import common
from benchmarks.layer_metrics import _latent


def read(ctx):
    fam = _latent.family(ctx)
    mod = fam and common.module_time(ctx, fam.DECODE_MODULE)
    if not mod or not mod[0] or not _latent.programs(ctx):
        return None
    return mod[1] / mod[0] * 1e3
