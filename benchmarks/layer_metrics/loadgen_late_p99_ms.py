"""How late the load generator sent: actual send time minus due time, 99th
percentile.  A validity check on the latency tails: a starved generator
must not be read as a fast server."""

from benchmarks import common


def read(ctx):
    xs = [r["sent"] - r["due"] for r in common.window_records(ctx)
          if r.get("due") is not None]
    return common.percentile(xs, 0.99) * 1e3 if xs else None
