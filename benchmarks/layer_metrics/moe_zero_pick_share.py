"""Share (%) of the router's picks that landed on IDENTITY experts (no
weights: the pick's weight times the layer's own input), from the engine's
counters over the window: ``moe_zero_picks`` over all picks
(``moe_local_rows`` + ``moe_zero_picks`` + ``moe_absent_picks``; prefills'
and decode steps', every routed row).  A uniform router gives the family's
``expected_identity_share`` (256 of 768 columns: a third)."""


def read(ctx):
    c = ctx.get("counters") or {}
    if "moe_zero_picks" not in c:
        return None
    picks = (c["moe_zero_picks"] + c.get("moe_local_rows", 0)
             + c.get("moe_absent_picks", 0))
    return 100.0 * c["moe_zero_picks"] / picks if picks else None
