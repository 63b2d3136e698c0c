"""Device time of one decode step of a model with linear-attention layers:
the ``jit_decode_step*`` modules in the trace, over their runs; None where
no such program holds a ``lin_attn/*`` part."""

from benchmarks import common
from benchmarks.layer_metrics import _lin_attn


def read(ctx):
    mod = common.module_time(ctx, "jit_decode_step")
    if not mod or not mod[0] or not _lin_attn.programs(ctx,
                                                       "jit_decode_step"):
        return None
    return mod[1] / mod[0] * 1e3
