"""Of a tail token (the slowest fifth of the requests by engine-side time a
token), the ms that were neither a burst nor another request's admission:
``llm.decode``'s ``host_s`` + ``idle_s`` (+ the request's own prefill, see
``_request_time.tail``) / (tokens - 1), mean over those requests."""

from benchmarks.layer_metrics import _request_time


def read(ctx):
    return _request_time.tail_ms(ctx, "host")
