"""The grouped product over the routed experts in the decode steps of a
model served over pools by layer type against the HBM roofline
(``_windowed.expert_roofline`` says from what; the note beside it has the
experts a sparse layer read a step)."""

from benchmarks.layer_metrics import _windowed


def read(ctx):
    return _windowed.expert_roofline(ctx)
