"""The decode step's recurrent-state update against the HBM roofline: the
bytes it has to move (each live slot's float32 state of every linear layer
read once and written once; the family's ``state_update_bytes``, from the
published sizes) at the chip's peak bandwidth, over the device time under
``lin_attn/state`` in the ``jit_decode_step*`` runs of the slice.  Bound:
memory (7 operations a state element, each element read and written).

How many slots were live comes from the program's spans, which is traffic
and not bytes: ``llm.loop.decode_emit`` says for each burst how many steps
it made and how many live slots those steps updated between them.  The
spans' mean over the slice is taken times the WHOLE runs of the decode
program that the trace holds, as ``moe_expert_hbm_roofline_share`` does.
The part's time also holds what else the program does there (the
convolution's rows shifted, the slots ordered), so the share reads low
rather than high; it cannot pass 100 % unless a live slot's state is not
read or not written."""

from benchmarks import common
from benchmarks.layer_metrics import _lin_attn


def read(ctx):
    fam = _lin_attn.family(ctx)
    progs = _lin_attn.programs(ctx, "jit_decode_step")
    within = common.slice_wall(ctx)
    if not progs or within is None or not ctx.get("peaks"):
        return None
    seconds = sum(sum(p["parts"].get(fam.STATE_PART, {}).values())
                  for p in progs)
    runs = sum(p["runs"] for p in progs)
    bursts = [s["args"] for s in common.spans_named(
        ctx, "llm.loop.decode_emit", within)
        if "state_slots" in (s.get("args") or {})]
    steps = sum(a["steps"] for a in bursts)
    if seconds <= 0 or not steps:
        return None
    live = sum(a["state_slots"] for a in bursts) / steps
    need = runs * fam.state_update_bytes(ctx["config"], live)
    ctx["notes"].append(
        f"state roofline: {runs} decode steps of {live:.1f} live slots have "
        f"to move {need / 1e9:.2f} GB of state in {seconds * 1e3:.1f} ms "
        f"under {fam.STATE_PART}")
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / seconds
