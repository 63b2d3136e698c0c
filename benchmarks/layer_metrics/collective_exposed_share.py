"""Share of the traced slice in which a collective (all-gather,
all-reduce, reduce-scatter, all-to-all, collective-permute) ran on a
device and no compute ran beside it, averaged over the chips."""


def read(ctx):
    tr = ctx.get("device_trace")
    if not tr or tr["devices"] < 2:
        return None
    return 100.0 * tr["collective_exposed_s"] / tr["window_s"]
