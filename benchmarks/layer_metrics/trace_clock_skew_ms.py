"""How far the wall clock's guess at the trace's zero (the midpoint of two
readings around ``start_trace``) lies from the profiler's own: median over
the engine-loop phases of the banked span's start minus the matching
annotation's.  Every gap label made on the wall clock (``breakdown``) is
off by this much."""

from benchmarks import common
from benchmarks.trace import host_phases


def read(ctx):
    extracted, tr = host_phases.planes(ctx), ctx.get("device_trace")
    if not extracted or not tr:
        return None
    return host_phases.clock_skew_ms(extracted, ctx.get("spans") or [],
                                     common.trace_zero(tr))
