"""Share of the traced slice in which the device was idle while the engine
thread was in a dispatch phase (``decode_dispatch``, ``prefill_dispatch``):
the device waiting for the host to launch.  Part of
``idle_unattributed_share``."""

from benchmarks.layer_metrics import _idle_launch


def read(ctx):
    return _idle_launch.share(ctx, "dispatch")
