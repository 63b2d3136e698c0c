"""Backend compilations JAX reported (``jax.monitoring``) in the process
that holds the chip after the window opened.  Must read 0: a run with
another value is not ``correct``."""


def read(ctx):
    return ctx["compiles_in_window"]
