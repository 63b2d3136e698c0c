"""Entry and routing (OpenAI router, proxy, handle, request router, the
SSE pull path): median client time from send to first token, minus the
median of what the engine itself accounts for per request (``llm.queue`` +
``llm.prefill`` spans), over the window.  Medians over the same window,
not per request: the response does not carry the trace id."""

from benchmarks import common


def read(ctx):
    client = [r["first"] - r["sent"] for r in common.window_records(ctx)
              if r["ok"]]
    per_trace = {}
    for name in ("llm.queue", "llm.prefill"):
        for s in common.spans_named(ctx, name):
            per_trace[s["trace_id"]] = per_trace.get(s["trace_id"], 0.0) \
                + s["end_ts"] - s["start_ts"]
    if not client or not per_trace:
        return None
    return (common.median(client)
            - common.median(list(per_trace.values()))) * 1e3
