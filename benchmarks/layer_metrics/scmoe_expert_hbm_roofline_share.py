"""The held experts' grouped product INSIDE DECODE STEPS against the HBM
roofline, for a chip's share of a routed layer: the bytes its calls have to
move (the three matrices of every HELD expert some row of the step reaches,
once a layer, plus each computed row in and out; the family's
``expert_bytes_per_call``) at the chip's peak bandwidth, over the device
time under ``moe/experts`` in the ``jit_decode_step*`` runs of the slice.
Bound: memory (about one row an expert).

Experts reached and rows computed are the program's own counts
(``experts_read``, ``moe_local_rows`` of the ``llm.loop.decode_emit``
spans; every slot's row is routed, an inactive slot's too, and the kernel
did read what they reached), their means over the slice times the WHOLE
runs of the decode program that the trace holds."""

from benchmarks.layer_metrics import _shortcut_moe


def _need(fam, c, did, steps):
    layers = fam.n_layers(c)[1]
    hit = sum(a["experts_read"] for a in did) / steps / layers
    rows = sum(a["moe_local_rows"] for a in did) / steps / layers
    return layers * fam.expert_bytes_per_call(c, rows, hit, c["dtype"])


def read(ctx):
    fam = _shortcut_moe.family(ctx)
    return fam and _shortcut_moe.roofline(
        ctx, fam.EXPERT_KERNEL_PART, _need, "held experts' roofline")
