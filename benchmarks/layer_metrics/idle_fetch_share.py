"""Share of the traced slice in which the device was idle while the engine
thread was in a fetch phase (``decode_fetch``, ``prefill_fetch``): the host
waiting for the device, so launch gaps between a burst's steps and the
transfer's tail.  Part of ``idle_unattributed_share``."""

from benchmarks.layer_metrics import _idle_launch


def read(ctx):
    return _idle_launch.share(ctx, "fetch")
