"""The held LATENT experts' grouped product INSIDE DECODE STEPS against the
HBM roofline, for a chip's share of a routed layer whose experts are two
matrices in a latent narrower than the stream: the bytes its calls have to
move (both matrices of every HELD expert some row of the step reaches, once
a layer, plus each computed latent row in and out; the family's
``expert_bytes_per_call``) at the chip's peak bandwidth, over the device
time under ``moe/experts`` in the ``jit_decode_step*`` runs of the slice.
Bound: memory (under two rows an expert).

Experts reached and rows computed are the program's own counts
(``experts_read``, ``moe_local_rows`` of the ``llm.loop.decode_emit``
spans; every slot's row is routed, an inactive slot's too, and the kernel
did read what they reached), their means over the slice times the WHOLE
runs of the decode program that the trace holds.  The decode programs and
their part times are ``_latent``'s (the family tells its programs by
``LATENT_PARTS``, here the latent's two projections); None where the family
declares no latent projections, or the trace holds no such part or span (any
other family's cell, a parent without the model)."""

from benchmarks import common
from benchmarks.layer_metrics import _latent


def family(ctx):
    fam = _latent.family(ctx)
    return fam if fam is not None and hasattr(fam, "LATENT_PROJ_PARTS") \
        else None


def read(ctx):
    fam = family(ctx)
    progs = _latent.programs(ctx) if fam else []
    within = common.slice_wall(ctx)
    if not progs or within is None or not ctx.get("peaks"):
        return None
    did = [s["args"] for s in common.spans_named(
        ctx, "llm.loop.decode_emit", within)
        if "moe_local_rows" in (s.get("args") or {})]
    steps = sum(a["steps"] for a in did)
    secs = _latent.seconds(progs, lambda p: p == fam.EXPERT_KERNEL_PART)
    if not steps or secs <= 0:
        return None
    c = ctx["config"]
    layers = fam.n_layers(c)[1]
    hit = sum(a["experts_read"] for a in did) / steps / layers
    rows = sum(a["moe_local_rows"] for a in did) / steps / layers
    absent = sum(a["moe_absent_picks"] for a in did) / steps / layers
    need = sum(p["runs"] for p in progs) * layers * \
        fam.expert_bytes_per_call(c, rows, hit, c["dtype"])
    ctx["notes"].append(
        f"latent experts: a step's {rows + absent:.0f} picks a layer leave "
        f"{rows:.1f} rows on {hit:.1f} of {c['n_routed_experts']} held "
        f"experts ({absent:.0f} picks on absent ones; uniform routing: "
        f"{fam.expected_experts_hit(c, ctx['max_slots']):.1f} experts); "
        f"{fam.EXPERT_KERNEL_PART} moves {need / 1e9:.2f} GB in "
        f"{secs * 1e3:.1f} ms")
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
