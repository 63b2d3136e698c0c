"""The sparse layers' decode attention against the HBM roofline: the bytes
it has to move (the K and V rows, at a KV head's width, of the pages the
engine's ``sparse_pages_read`` says the kernel's lists held, a list a KV
head a sparse layer a live slot, counted by the step on the device; the
family's ``sparse_attend_bytes``, never the program's shapes) at the
chip's peak bandwidth, over the device time under
``sparse_attn/attend`` in the ``jit_decode_step*`` runs of the slice.
Bound: memory.  The kernel copies a page WHOLE (both KV heads' rows) for
each head's list, so where the two heads chose different pages it moves up
to twice these bytes: the share reads low by that, and cannot pass 100 %
unless a selected page is not read."""

from benchmarks.layer_metrics import _sparse_linear


def read(ctx):
    fam = _sparse_linear.family(ctx)
    if fam is None:
        return None
    ps = ctx["config"]["engine"]["page_size"]
    return _sparse_linear.roofline(
        ctx, fam.ATTEND_PART,
        lambda fam, c, did, steps: fam.sparse_attend_bytes(
            c, sum(a["sparse_pages_read"] for a in did) / steps, ps,
            c["dtype"]),
        "sparse attend roofline")
