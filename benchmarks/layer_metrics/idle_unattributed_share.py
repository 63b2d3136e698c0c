"""Share of the traced slice in which the device was idle and the engine
thread was in none of the host phases: inside a dispatch or fetch phase
(launch latency, the tracer) or under no annotation at all.  What the
brackets miss."""

from benchmarks.trace import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, "unattributed")
