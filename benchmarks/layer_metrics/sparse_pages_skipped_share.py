"""Of the pages each live slot held at each decode step of the window, the
share a sparse layer's kernel did NOT read because their blocks were not
selected: the engine's counters over the window (what the steps counted
on the device from the lists the kernel was handed), 1 -
``sparse_pages_read`` / ``sparse_pages_resident``.  0 while every sequence is at or under
``dense_len``."""


def read(ctx):
    counted = ctx.get("counters") or {}
    held = counted.get("sparse_pages_resident")
    if not held:
        return None
    return 100.0 * (1.0 - counted.get("sparse_pages_read", 0) / held)
