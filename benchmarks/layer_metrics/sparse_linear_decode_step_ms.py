"""Device time of one decode step of a model with block-sparse and
linear-attention layers: the ``jit_decode_step*`` modules in the trace, over
their runs; None where no such program holds a part of the family's."""

from benchmarks import common
from benchmarks.layer_metrics import _sparse_linear


def read(ctx):
    mod = common.module_time(ctx, "jit_decode_step")
    if not mod or not mod[0] or not _sparse_linear.programs(
            ctx, "jit_decode_step"):
        return None
    return mod[1] / mod[0] * 1e3
