"""Device time of one denoising pass over every slot's open block: the
``jit_block_step`` modules in the trace, over their runs."""

from benchmarks.layer_metrics import _block_pass


def read(ctx):
    p = _block_pass.passes(ctx)
    return p[1] / p[0] * 1e3 if p else None
