"""Entry and routing (OpenAI router, proxy, handle, request router, the SSE
pull path): median client time from send to first token, minus the median of
what the engine accounts for per request up to its first token
(``llm.queue`` + ``llm.admission`` spans).  ``entry_overhead_p50_ms``'s
arithmetic with the admission in place of the per-chunk prefills, so what
lies between a prompt's chunks is no longer counted here.  Medians over the
same window, not per request: the response does not carry the trace id."""

from benchmarks import common
from benchmarks.layer_metrics import _request_time


def read(ctx):
    client = [r["first"] - r["sent"] for r in common.window_records(ctx)
              if r["ok"]]
    # (a request whose admission has not ended in the window is left out,
    # its wait too)
    per_trace = {s["trace_id"]: 0.0
                 for s in _request_time.spans(ctx, "llm.admission")}
    for name in ("llm.queue", "llm.admission"):
        for s in _request_time.spans(ctx, name):
            if s["trace_id"] in per_trace:
                per_trace[s["trace_id"]] += _request_time.length(s)
    if not client or not per_trace:
        return None
    return (common.median(client)
            - common.median(list(per_trace.values()))) * 1e3
