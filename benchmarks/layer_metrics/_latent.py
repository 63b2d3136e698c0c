"""What the latent-attention and token-step expert metrics share: the decode
step programs of the traced slice that hold a latent part (``mla/*``), the
device time under some of their parts, and what the engine's
``llm.loop.decode_emit`` spans say the slice's bursts did (steps, the latent
pages they walked, the experts they read).  A trace without those parts or
spans (any other family's cell, a parent without the model) gives None
everywhere."""

from benchmarks import common
from benchmarks.trace import device_parts


def family(ctx):
    fam = common.module("families", ctx["config"]["family"])
    return fam if hasattr(fam, "LATENT_KERNEL_PART") else None


def programs(ctx) -> list:
    """The per-part tables of the decode step programs in which some
    operation lies under a latent part; [] without any."""
    fam = family(ctx)
    if fam is None:
        return []
    return [p for name, p in (device_parts.read(ctx) or {}).items()
            if name.startswith(fam.DECODE_MODULE)
            and any(part in fam.LATENT_PARTS for part in p["parts"])]


def seconds(progs: list, wanted) -> float:
    """Device seconds of ``progs`` under the parts ``wanted(part)`` takes."""
    return sum(sum(c.values()) for p in progs
               for part, c in p["parts"].items() if wanted(part))


def share(ctx, wanted):
    """Share (%) of the decode steps' operation time under those parts."""
    progs = programs(ctx)
    total = sum(p["ops_s"] for p in progs)
    return 100.0 * seconds(progs, wanted) / total if total > 0 else None


def bursts(ctx, within=None) -> list:
    """The ``args`` of the decode bursts that ended in ``within`` (default:
    the window) and say what a latent, routed model's steps did."""
    return [s["args"] for s in common.spans_named(
        ctx, "llm.loop.decode_emit", within)
        if "latent_pages_read" in (s.get("args") or {})]
