"""The paged latent decode kernel at 64 heads against the HBM roofline: the
bytes its calls have to move (the latent rows of every page a live slot's
position reaches, each row ONCE although it is key and value both, plus a
query in and an output out a head; the family's ``latent_attend_bytes``) at
the chip's peak bandwidth, over the device time under ``mla/attend`` in the
``jit_decode_step*`` runs of the slice.  A call an attention SUBLAYER: two a
layer.  At 64 heads a row's 1,280 bytes meet 2 x 64 x (640 + 512)
operations, 115 a byte, near the chip's ridge (240): the kernel may be
bound by the MXU before the memory, and then this share reads low.

Pages walked come from the program's spans (``latent_pages_read`` a burst,
whole pages), live slots are the tokens a step emitted; their means over
the slice times the WHOLE runs of the decode program the trace holds."""

from benchmarks.layer_metrics import _shortcut_moe


def _need(fam, c, did, steps):
    pages = sum(a["latent_pages_read"] for a in did) / steps
    slots = sum(a.get("tokens", 0) for a in did) / steps
    return fam.latent_layers(c) * fam.latent_attend_bytes(
        c, slots, pages, c["engine"]["page_size"], c["dtype"])


def read(ctx):
    fam = _shortcut_moe.family(ctx)
    return fam and _shortcut_moe.roofline(
        ctx, fam.LATENT_KERNEL_PART, _need, "latent roofline at 64 heads")
