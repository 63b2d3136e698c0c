"""Peak bytes in use on the fullest device after the window
(``memory_stats()["peak_bytes_in_use"]``): guards that the cell fills the
memory as a deployment fills it."""


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes")
    return peak / 1e9 if peak else None
