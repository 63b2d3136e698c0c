"""Device time of one decode step of a model served over pools by layer
type: the ``jit_decode_step*`` modules in the trace, over their runs; None
where the slice's bursts name no window pages."""

from benchmarks import common
from benchmarks.layer_metrics import _windowed


def read(ctx):
    fam = _windowed.family(ctx)
    mod = fam and common.module_time(ctx, fam.DECODE_MODULE)
    if not mod or not mod[0] or not _windowed.bursts(
            ctx, common.slice_wall(ctx)):
        return None
    return mod[1] / mod[0] * 1e3
