"""Output tokens the clients received inside the window, over the window,
at saturation (closed loop)."""


def read(ctx):
    if ctx.get("schedule_mode") != "closed":
        return None
    return sum(r["n_in_window"] for r in ctx["records"]) / ctx["seconds"]
