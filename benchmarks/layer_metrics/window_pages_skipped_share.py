"""Of the pages up to each live slot's length, the share a window layer's
kernel did NOT walk because they lie wholly behind ``length - window``: the
``llm.loop.decode_emit`` spans that ended in the window, skipped / (read +
skipped).  0 while every sequence is shorter than the window."""

from benchmarks.layer_metrics import _windowed


def read(ctx):
    did = _windowed.bursts(ctx)
    skipped = sum(a["window_pages_skipped"] for a in did)
    reached = skipped + sum(a["window_pages_read"] for a in did)
    return 100.0 * skipped / reached if reached else None
