"""What a decode step costs the host: the ``llm.loop.decode_host``,
``decode_dispatch`` and ``decode_emit`` spans that ended in the window,
over the decode steps the engine counted in it (the wait for the device,
``decode_fetch``, is left out)."""

from benchmarks.trace import host_phases


def read(ctx):
    return host_phases.decode_host_ms_per_step(ctx)
