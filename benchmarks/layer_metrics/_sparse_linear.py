"""What the metrics of a model with block-sparse and linear-attention layers
share: the family's part names (``sparse_attn/*``, ``lightning/*``), the
programs of the traced slice that hold such a part, the device time under
some of their parts, and what the engine's ``llm.loop.decode_emit`` spans
say the slice's bursts did (steps, the pages a KV head's list held, the
live slots whose state rows the steps updated).  A configuration whose
family names no such parts, or a trace without them (any other family's
cell, a parent without the model), gives None everywhere."""

from benchmarks import common
from benchmarks.trace import device_parts


def family(ctx):
    fam = common.module("families", ctx["config"]["family"])
    return fam if hasattr(fam, "SPARSE_PREFIX") else None


def ours(fam, part: str) -> bool:
    return part.startswith((fam.SPARSE_PREFIX, fam.LINEAR_PREFIX))


def programs(ctx, prefix: str) -> list:
    """The per-part tables of the programs named ``prefix*`` in which some
    operation lies under one of the family's parts; [] without any."""
    fam = family(ctx)
    if fam is None:
        return []
    return [p for name, p in (device_parts.read(ctx) or {}).items()
            if name.startswith(prefix)
            and any(ours(fam, part) for part in p["parts"])]


def seconds(progs: list, wanted) -> float:
    """Device seconds of ``progs`` under the parts ``wanted(part)`` takes."""
    return sum(sum(c.values()) for p in progs
               for part, c in p["parts"].items() if wanted(part))


def share(ctx, prefix: str, wanted):
    """Share (%) of the operation time of the programs ``prefix*`` under
    the parts ``wanted(part)`` takes; None where they hold none of the
    family's."""
    progs = programs(ctx, prefix)
    total = sum(p["ops_s"] for p in progs)
    return 100.0 * seconds(progs, wanted) / total if total > 0 else None


def bursts(ctx, within) -> list:
    """The ``args`` of the decode bursts that ended in ``within`` and say
    what a sparse layer's steps read."""
    if family(ctx) is None or within is None:
        return []
    return [s["args"] for s in common.spans_named(
        ctx, "llm.loop.decode_emit", within)
        if "sparse_pages_read" in (s.get("args") or {})]


def roofline(ctx, part: str, bytes_a_step, what: str):
    """The decode steps' time under ``part`` against the HBM roofline, %:
    ``bytes_a_step(fam, c, bursts, steps)`` of the slice's bursts, times
    the WHOLE decode runs the trace holds, at the chip's peak bandwidth.
    Says what it read on a note; None without the part, the spans or the
    peaks."""
    fam = family(ctx)
    progs = programs(ctx, fam.DECODE_MODULE) if fam else []
    did = bursts(ctx, common.slice_wall(ctx))
    steps = sum(a["steps"] for a in did)
    secs = seconds(progs, lambda p: p == part)
    if not steps or secs <= 0 or not ctx.get("peaks"):
        return None
    runs = sum(p["runs"] for p in progs)
    need = runs * bytes_a_step(fam, ctx["config"], did, steps)
    got = 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
    ctx["notes"].append(
        f"{what}: {runs} decode steps have to move {need / 1e9:.2f} GB in "
        f"{secs * 1e3:.1f} ms under {part} = {got:.1f} % of the HBM roofline")
    return got
