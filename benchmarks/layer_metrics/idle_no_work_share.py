"""Share of the traced slice in which the device was idle because nothing
was asked of the engine: under an ``llm.loop.idle`` annotation."""

from benchmarks.trace import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, "no_work")
