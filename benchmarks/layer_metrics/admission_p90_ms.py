"""A prompt's admission, first program's start -> first token counted,
however many chunks: the ``llm.admission`` spans that ended in the window,
90th percentile."""

from benchmarks import common
from benchmarks.layer_metrics import _request_time


def read(ctx):
    xs = [_request_time.length(s)
          for s in _request_time.spans(ctx, "llm.admission")]
    return common.percentile(xs, 0.9) * 1e3 if xs else None
