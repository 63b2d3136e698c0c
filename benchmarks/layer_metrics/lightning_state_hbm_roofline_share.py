"""The decode step's fixed-decay state update against the HBM roofline: the
bytes it has to move (each live slot's float32 state of every linear layer
read once and written once; the family's ``state_update_bytes``, from the
published sizes) at the chip's peak bandwidth, over the device time under
``lightning/state`` in the ``jit_decode_step*`` runs of the slice.  Bound:
memory (three operations a state element).  How many slots were live comes
from the ``llm.loop.decode_emit`` spans (``state_slots`` over ``steps``), as
``lin_state_update_hbm_roofline_share`` reads them."""

from benchmarks.layer_metrics import _sparse_linear


def read(ctx):
    fam = _sparse_linear.family(ctx)
    if fam is None:
        return None
    return _sparse_linear.roofline(
        ctx, fam.STATE_PART,
        lambda fam, c, did, steps: fam.state_update_bytes(
            c, sum(a.get("state_slots", 0) for a in did) / steps),
        "lightning state roofline")
