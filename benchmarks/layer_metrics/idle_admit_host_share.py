"""Share of the traced slice in which the device was idle while the engine
thread was admitting: under an ``llm.loop.admit``, ``prefill_host``,
``prefill_emit``, ``hydrate`` or ``gauges`` annotation, on the profiler's
clock.  With the three other ``idle_*_share`` it sums to the idle share."""

from benchmarks.trace import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, "admit_host")
