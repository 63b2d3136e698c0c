"""What an admission costs the host: per admitted request the sum of its
``llm.loop.admit``, ``prefill_host`` and ``prefill_emit`` spans, median
over the requests admitted in the window."""

from benchmarks import common
from benchmarks.trace import host_phases


def read(ctx):
    return common.median(host_phases.admission_host_ms(ctx))
