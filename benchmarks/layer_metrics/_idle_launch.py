"""The idle label nobody owned, split (ISSUE 51).  ``idle_unattributed_share``
is device idle under a dispatch phase, under a fetch phase, or under no
annotation at all.  Under ``*_dispatch`` the host is launching programs
(the device waits for the host: what a burst in flight ahead of the host
removes); under ``*_fetch`` the host waits for the device (launch gaps
between a burst's steps, the transfer's tail: what it does not).  The
loop's annotations on the profiler's clock, through ``host_phases``."""

from benchmarks.trace import host_phases, reduce

LAUNCH = {"dispatch": ("decode_dispatch", "prefill_dispatch"),
          "fetch": ("decode_fetch", "prefill_fetch")}


def split(extracted: dict, lo: float, hi: float):
    """Shares (%) of the slice [lo, hi) in which device 0 was idle under a
    dispatch phase and under a fetch phase.  None without annotations."""
    if not extracted["annotations"] or hi <= lo:
        return None
    idle = reduce.subtract([[lo, hi]], extracted["busy"])
    out = {}
    for cls, names in LAUNCH.items():
        cover = reduce.union([[s, e] for n, _, s, e
                              in extracted["annotations"] if n in names])
        out[cls] = 100.0 * reduce.total(
            host_phases.intersect(idle, cover)) / (hi - lo)
    return out


def share(ctx: dict, cls: str):
    """One class of ``split`` over the slice ``reduce.py`` took; the note
    line gives what is left of ``idle_unattributed_share``."""
    if "_idle_launch" not in ctx:
        ex, tr = host_phases.planes(ctx), ctx.get("device_trace")
        ctx["_idle_launch"] = split(
            ex, tr["t_lo_s"], tr["t_hi_s"]) if ex and tr else None
        out = ctx["_idle_launch"]
        unattributed = host_phases.idle_share(ctx, "unattributed")
        if out and unattributed is not None:
            ctx.setdefault("notes", []).append(
                "idle_unattributed_share {:.3f} = under dispatch {:.3f} + "
                "under fetch {:.3f} + under no annotation {:.3f}".format(
                    unattributed, out["dispatch"], out["fetch"],
                    unattributed - out["dispatch"] - out["fetch"]))
    out = ctx["_idle_launch"]
    return None if out is None else out[cls]
