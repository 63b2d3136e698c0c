"""Client-side time from when a request was DUE to its first streamed
token, 90th percentile over the requests due in the window.  A request
that failed or was still open after the drain lies beyond every
percentile; where that reaches the 90th, the value is the longest a
request could have been observed (window plus drain).

What a user feels first, and yet kept among the unbounded metrics for now:
a request waits out whatever burst of decode steps is running, about
uniform on 0..0.9 s, and the ~120 requests a window holds at this engine's
knee put 3 % of sampling error on their 90th percentile, more than half of
the widest bound allowed (PERF.md, PR 22).  To be promoted to a bounded
end-to-end metric when the knee gives a window some 400 requests."""

from benchmarks import common


def read(ctx):
    recs = common.window_records(ctx)
    if not recs or ctx.get("schedule_mode") != "open":
        return None
    cap = ctx["seconds"] + ctx.get("drain_s", 0.0)
    xs = [(r["first"] - r["due"]) if r["ok"] else cap for r in recs]
    return min(cap, common.percentile(xs, 0.9)) * 1e3
