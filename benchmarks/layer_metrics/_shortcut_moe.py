"""What the metrics of a shortcut-connected, shared-expert cell share
(``serve_shortcut_moe_long_answer``): ``_latent``'s decode programs, part
times and bursts, for a family that also counts what its share of a routed
layer did (``moe_local_rows`` in the ``llm.loop.decode_emit`` spans).  A
trace without those parts or spans (any other family's cell, a parent
without the model) gives None everywhere."""

from benchmarks import common
from benchmarks.layer_metrics import _latent


def family(ctx):
    fam = _latent.family(ctx)
    return fam if fam is not None and hasattr(fam, "expected_local_rows") \
        else None


def programs(ctx) -> list:
    return _latent.programs(ctx) if family(ctx) else []


def bursts(ctx, within=None) -> list:
    """The decode bursts whose spans say what the share did."""
    if family(ctx) is None:
        return []
    return [a for a in _latent.bursts(ctx, within) if "moe_local_rows" in a]


def roofline(ctx, part: str, need_bytes, what: str):
    """Share (%) of the HBM peak that the bytes ``need_bytes(fam, c, did,
    steps)`` of ONE decode step, times the decode runs the trace holds,
    make over the device time under ``part`` of those runs."""
    fam, progs = family(ctx), programs(ctx)
    within = common.slice_wall(ctx)
    if not progs or within is None or not ctx.get("peaks"):
        return None
    secs = _latent.seconds(progs, lambda p: p == part)
    did = bursts(ctx, within)
    steps = sum(a["steps"] for a in did)
    if secs <= 0 or not steps:
        return None
    runs = sum(p["runs"] for p in progs)
    need = runs * need_bytes(fam, ctx["config"], did, steps)
    ctx["notes"].append(
        f"{what}: {runs} decode steps have to move {need / 1e9:.2f} GB in "
        f"{secs * 1e3:.1f} ms under {part}")
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
