"""Device time of the prefill programs (``jit_prefill*`` modules in the
trace) per thousand prompt tokens actually computed in the slice."""

from benchmarks.layer_metrics import _prefill


def read(ctx):
    got = _prefill.in_slice(ctx)
    if got is None:
        return None
    seconds, work = got
    return seconds * 1e3 / (sum(n for n, _ in work) / 1e3)
