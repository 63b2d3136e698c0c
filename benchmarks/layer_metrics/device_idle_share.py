"""Share of the traced slice in which no operation ran on the device: 1 -
the union of the device-op intervals over the slice, averaged over the
chips.  Fewer layers than the published model make it larger than in a
deployment."""


def read(ctx):
    tr = ctx.get("device_trace")
    if not tr:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
