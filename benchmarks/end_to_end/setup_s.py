"""Process start to the first instant of the window: loading, warming up
and, in a run that compiles, compilation."""


def read(ctx):
    return ctx["setup_s"]
