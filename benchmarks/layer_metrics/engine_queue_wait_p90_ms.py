"""Engine admission wait (submit -> a slot and pages are free): the
``llm.queue`` spans that ended in the window, 90th percentile."""

from benchmarks import common


def read(ctx):
    xs = [s["end_ts"] - s["start_ts"]
          for s in common.spans_named(ctx, "llm.queue")]
    return common.percentile(xs, 0.9) * 1e3 if xs else None
