"""Share of the traced slice in which the device was idle while the engine
thread was between bursts: under an ``llm.loop.decode_host`` (capacity,
page table, transfers) or ``decode_emit`` (tokens out, slots released)
annotation, on the profiler's clock."""

from benchmarks.trace import host_phases


def read(ctx):
    return host_phases.idle_share(ctx, "decode_host")
