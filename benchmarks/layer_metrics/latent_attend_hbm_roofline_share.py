"""The paged latent decode kernel against the HBM roofline: the bytes its
calls have to move (the latent rows of every page a live slot's position
reaches, each row ONCE although it is key and value both, plus a query in
and an output out a head; the family's ``latent_attend_bytes``, from the
published sizes) at the chip's peak bandwidth, over the device time under
``mla/attend`` in the ``jit_decode_step*`` runs of the slice.  Bound:
memory (20 heads x 2 products of a 1,280-byte row: 70 operations a byte).

How many pages a step walks comes from the program's spans, which is
traffic and not bytes: ``llm.loop.decode_emit`` says for each burst how
many steps it made and how many latent pages those steps reached between
them (whole pages: the kernel copies a page whole, so the last page of a
slot counts whole, half a page a slot too many on average, 0.6 % at 1,400
tokens).  Live slots a step are taken as the tokens a step emitted.  The
spans' means over the slice are taken times the WHOLE runs of the decode
program that the trace holds, as ``lin_state_update_hbm_roofline_share``
does.  The part's time also holds the query's padding to 32 heads and the
output's slice, so the share reads low rather than high."""

from benchmarks import common
from benchmarks.layer_metrics import _latent


def read(ctx):
    fam, progs = _latent.family(ctx), _latent.programs(ctx)
    within = common.slice_wall(ctx)
    if not progs or within is None or not ctx.get("peaks"):
        return None
    secs = _latent.seconds(progs, lambda p: p == fam.LATENT_KERNEL_PART)
    did = _latent.bursts(ctx, within)
    steps = sum(a["steps"] for a in did)
    if secs <= 0 or not steps:
        return None
    c = ctx["config"]
    runs = sum(p["runs"] for p in progs)
    pages = sum(a["latent_pages_read"] for a in did) / steps
    slots = sum(a.get("tokens", 0) for a in did) / steps
    need = runs * c["num_hidden_layers"] * fam.latent_attend_bytes(
        c, slots, pages, c["engine"]["page_size"], c["dtype"])
    ctx["notes"].append(
        f"latent roofline: {runs} decode steps of {slots:.1f} live slots "
        f"walk {pages:.0f} pages a layer and have to move "
        f"{need / 1e9:.2f} GB in {secs * 1e3:.1f} ms under "
        f"{fam.LATENT_KERNEL_PART}")
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
