"""Routed experts a sparse layer reads in one decode step: the experts the
``llm.loop.decode_emit`` spans that ended in the window say their steps
read, over steps x sparse layers.  64 is all of them; what 4 x live slots
assignments reach with a near-uniform router (seeded weights) is the
family's ``expected_experts_hit``."""

from benchmarks import common
from benchmarks.layer_metrics import _latent


def read(ctx):
    fam = _latent.family(ctx)
    did = [a for a in _latent.bursts(ctx) if "experts_read" in a] if fam \
        else []
    steps = sum(a["steps"] for a in did)
    if not steps:
        return None
    return (sum(a["experts_read"] for a in did) / steps
            / fam.n_layers(ctx["config"])[1])
