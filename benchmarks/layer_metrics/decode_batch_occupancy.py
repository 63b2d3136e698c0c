"""Share of the decode batch's slots that produced a token: tokens the
decode steps generated (all generated minus one per prefill, which emits
the first) over decode steps x max_slots, from the engine's counters at
the window's two ends.  A burst's overshoot steps count as empty."""


def read(ctx):
    c = ctx.get("counters")
    if not c or not c.get("decode_steps"):
        return None
    made = c["tokens_generated"] - c["prefills"]
    return 100.0 * made / (c["decode_steps"] * ctx["max_slots"])
