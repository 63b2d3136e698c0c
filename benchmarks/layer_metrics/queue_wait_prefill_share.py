"""Share of the requests' summed admission wait (``llm.queue`` spans ended
in the window) spent while the engine thread was prefilling ANOTHER request
(``llm.loop.prefill_*`` spans).  What is left after this and
``queue_wait_decode_share`` is admission work and no slot or pages."""

from benchmarks.trace import host_phases


def read(ctx):
    return host_phases.queue_wait_share(ctx, "prefill_")
