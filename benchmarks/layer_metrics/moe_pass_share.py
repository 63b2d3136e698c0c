"""The routed experts' grouped product as a share of a denoising pass:
device time of the ``moe_grouped_mlp`` operations that the passes ran, over
the device time of the ``jit_block_step`` modules."""

from benchmarks.layer_metrics import _block_pass


def read(ctx):
    p, k = _block_pass.passes(ctx), _block_pass.kernel_in_passes(ctx)
    return 100.0 * k[1] / p[1] if p and k else None
