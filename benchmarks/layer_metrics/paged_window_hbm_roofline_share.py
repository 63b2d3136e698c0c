"""The paged decode kernel WITH its window bound against the HBM roofline:
the bytes its calls have to move (the K and V rows INSIDE the bounds: in a
window layer the pages from the one that holds ``length - window`` to the
slot's length, in a full layer every page up to the length; plus a query in
and an output out a head; the family's ``paged_attend_bytes``, from the
published sizes) at the chip's peak bandwidth, over the device time under
``attn/attend`` in the ``jit_decode_step*`` runs of the slice.  Bound:
memory.  A bound that skipped nothing would count the skipped pages as
neither moved nor needed: the share cannot rise by skipping less.

How many pages a step walks comes from the program's spans, which is traffic
and not bytes: ``llm.loop.decode_emit`` says for each burst how many steps it
made and how many pages a layer of each kind walked between them (whole
pages: the kernel copies a page whole, so a slot's last page and the first
page inside its window count whole).  The spans' means over the slice are
taken times the WHOLE runs of the decode program that the trace holds, as
``latent_attend_hbm_roofline_share`` does."""

from benchmarks import common
from benchmarks.layer_metrics import _windowed


def read(ctx):
    fam, progs = _windowed.family(ctx), _windowed.programs(ctx)
    within = common.slice_wall(ctx)
    if not progs or within is None or not ctx.get("peaks"):
        return None
    secs = _windowed.seconds(progs,
                             lambda p: p.startswith(fam.ATTEND_PART))
    did = _windowed.bursts(ctx, within)
    steps = sum(a["steps"] for a in did)
    if secs <= 0 or not steps:
        return None
    c = ctx["config"]
    ps = c["engine"]["page_size"]
    runs = sum(p["runs"] for p in progs)
    window = sum(a["window_pages_read"] for a in did) / steps
    full = sum(a["full_pages_read"] for a in did) / steps
    slots = sum(a.get("tokens", 0) for a in did) / steps
    need = runs * fam.paged_attend_bytes(c, window * ps, full * ps, slots,
                                         c["dtype"])
    ctx["notes"].append(
        f"paged window roofline: {runs} decode steps of {slots:.1f} live "
        f"slots walk {window:.0f} pages a window layer and {full:.0f} a "
        f"full layer and have to move {need / 1e9:.2f} GB in "
        f"{secs * 1e3:.1f} ms under {fam.ATTEND_PART}")
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
