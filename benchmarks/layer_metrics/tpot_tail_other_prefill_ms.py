"""Of a tail token (the slowest fifth of the requests by engine-side time a
token), the ms the loop spent admitting OTHER requests: ``llm.decode``'s
``other_prefill_s`` / (tokens - 1), mean over those requests.  What an
admission that does not stop the bursts would give back."""

from benchmarks.layer_metrics import _request_time


def read(ctx):
    return _request_time.tail_ms(ctx, "other_prefill")
