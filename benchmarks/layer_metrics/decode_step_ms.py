"""Device time of one decode step: the ``jit_decode_step*`` modules in the
trace, over their runs."""

from benchmarks import common


def read(ctx):
    mod = common.module_time(ctx, "jit_decode_step")
    if not mod or not mod[0]:
        return None
    return mod[1] / mod[0] * 1e3
