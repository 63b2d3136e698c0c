"""(token, expert) rows the HELD experts of one layer computed in one
decode step: ``moe_local_rows`` of the ``llm.loop.decode_emit`` spans that
ended in the window, over steps x layers.  Every slot's row is routed, an
inactive slot's too (they all hold token 0 and take the same columns);
``max_slots x top_k x held / columns`` (16 at 64 slots) is what a uniform
router gives, the family's ``expected_local_rows``."""

from benchmarks.layer_metrics import _shortcut_moe


def read(ctx):
    fam = _shortcut_moe.family(ctx)
    did = _shortcut_moe.bursts(ctx)
    steps = sum(a["steps"] for a in did)
    if not steps:
        return None
    return (sum(a["moe_local_rows"] for a in did) / steps
            / fam.n_layers(ctx["config"])[1])
