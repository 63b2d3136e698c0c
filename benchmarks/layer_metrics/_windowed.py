"""What the metrics of a model served over pools by layer type share: the
decode step programs of the traced slice, the device time under some of
their parts, and what the engine's ``llm.loop.decode_emit`` spans say the
slice's bursts did (steps, the pages a layer of each kind walked, the pages
the window's bound skipped, the experts read).  A configuration whose family
counts no pages by layer type, or a trace without such spans (any other
family's cell, a parent without the model), gives None everywhere."""

from benchmarks import common
from benchmarks.trace import device_parts


def family(ctx):
    fam = common.module("families", ctx["config"]["family"])
    return fam if hasattr(fam, "paged_attend_bytes") else None


def programs(ctx) -> list:
    """The per-part tables of the decode step programs; [] without any."""
    fam = family(ctx)
    if fam is None:
        return []
    return [p for name, p in (device_parts.read(ctx) or {}).items()
            if name.startswith(fam.DECODE_MODULE)]


def seconds(progs: list, wanted) -> float:
    """Device seconds of ``progs`` under the parts ``wanted(part)`` takes."""
    return sum(sum(c.values()) for p in progs
               for part, c in p["parts"].items() if wanted(part))


def share(ctx, wanted):
    """Share (%) of the decode steps' operation time under those parts."""
    progs = programs(ctx) if bursts(ctx, common.slice_wall(ctx)) else []
    total = sum(p["ops_s"] for p in progs)
    return 100.0 * seconds(progs, wanted) / total if total > 0 else None


def bursts(ctx, within=None) -> list:
    """The ``args`` of the decode bursts that ended in ``within`` (default:
    the window) and say what a windowed model's steps walked."""
    if family(ctx) is None:
        return []
    return [s["args"] for s in common.spans_named(
        ctx, "llm.loop.decode_emit", within)
        if "window_pages_read" in (s.get("args") or {})]


def expert_roofline(ctx):
    """The grouped product over the experts (the family's
    ``EXPERT_KERNEL_PART``) in the decode steps of the slice against the
    HBM roofline, %: the weights of the experts the spans' ``experts_read``
    say a sparse layer read a step, plus its rows in and out (the family's
    ``expert_bytes_per_call``), times the WHOLE decode runs the trace
    holds, at the chip's peak bandwidth, over the device time under that
    part.  Bound: memory.  Says what it read on a note; None without the
    part, the spans or the peaks."""
    fam, progs = family(ctx), programs(ctx)
    within = common.slice_wall(ctx)
    if not progs or within is None or not ctx.get("peaks"):
        return None
    did = [a for a in bursts(ctx, within) if "experts_read" in a]
    steps = sum(a["steps"] for a in did)
    secs = seconds(progs, lambda p: p == fam.EXPERT_KERNEL_PART)
    if not steps or secs <= 0:
        return None
    c = ctx["config"]
    sparse = fam.n_layers(c)[1]
    hit = sum(a["experts_read"] for a in did) / steps / sparse
    rows = sum(a.get("tokens", 0) for a in did) / steps
    need = sum(p["runs"] for p in progs) * sparse * \
        fam.expert_bytes_per_call(c, rows, hit, c["dtype"])
    got = 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
    ctx["notes"].append(
        f"windowed moe: {rows:.1f} rows a step reach {hit:.1f} of "
        f"{c['num_experts']} experts a layer (uniform routing: "
        f"{fam.expected_experts_hit(c, rows * c['num_experts_per_tok']):.1f}"
        f"); {fam.EXPERT_KERNEL_PART} moves {need / 1e9:.2f} GB in "
        f"{secs * 1e3:.1f} ms = {got:.1f} % of the HBM roofline")
    return got
