"""Of a tail token (the slowest fifth of the requests by engine-side time a
token), the ms the loop spent in decode bursts: ``llm.decode``'s ``step_s``
/ (tokens - 1), mean over those requests.  Above the device's step by the
launch and the burst's tail."""

from benchmarks.layer_metrics import _request_time


def read(ctx):
    return _request_time.tail_ms(ctx, "step")
