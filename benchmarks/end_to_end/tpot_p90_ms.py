"""Per request, (last token time - first token time) / (output tokens - 1);
90th percentile over the requests due in the window.  Not the raw gap
between tokens: the engine emits greedy tokens in bursts of 8 per host
fetch, so raw gaps are bimodal by construction."""

from benchmarks import common


def read(ctx):
    recs = common.window_records(ctx)
    if not recs or ctx.get("schedule_mode") != "open":
        return None
    cap = ctx["seconds"] + ctx.get("drain_s", 0.0)
    xs = [(r["last"] - r["first"]) / (r["n_out"] - 1) if r["ok"] else cap
          for r in recs if r["max_tokens"] > 1]
    ctx["notes"].append(f"tpot: {len(xs)} requests, median "
                        f"{common.median(xs) * 1e3:.1f} ms")
    return min(cap, common.percentile(xs, 0.9)) * 1e3
