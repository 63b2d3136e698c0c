"""Share of the requests' summed admission wait (``llm.queue`` spans ended
in the window) spent while the engine thread was in a decode phase
(``llm.loop.decode_*`` spans): waiting out a burst of decode steps."""

from benchmarks.trace import host_phases


def read(ctx):
    return host_phases.queue_wait_share(ctx, "decode_")
