"""Share of the slice's device operation time that lies under a part the
programs name (``models/llama.py`` ``PARTS``; ``trace/device_parts.py``),
over every program's whole runs: how much of the chip's time the per-part
metrics speak of.  Near 0 where the executables came from a compile cache
that a tree without scopes wrote (metadata is not in the cache's key)."""

from benchmarks.trace import device_parts


def read(ctx):
    return device_parts.share(ctx, "", lambda part: True)
