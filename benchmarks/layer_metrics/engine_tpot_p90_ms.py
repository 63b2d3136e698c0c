"""The engine's side of ``tpot_p90_ms``: over the ``llm.decode`` spans that
ended in the window with two tokens or more, p90 of (first token counted ->
stream end) / (tokens - 1).  The client's minus this is the delivery and
the stream path."""

from benchmarks import common
from benchmarks.layer_metrics import _request_time


def read(ctx):
    rates = [r for r, _ in _request_time.per_token(ctx)]
    return common.percentile(rates, 0.9) * 1e3 if rates else None
