"""The routed experts' grouped product INSIDE DECODE STEPS against the HBM
roofline: the kernel ``serve_block_diffusion`` guards at 128-row passes,
here at about 4 rows an expert.  The bytes its calls have to move (the
three matrices of every expert some token of the step reaches, once a
layer, plus its rows in and out; sizes from the published configuration) at
the chip's peak bandwidth, over the device time under ``moe/experts`` in
the ``jit_decode_step*`` runs of the slice (``trace/device_parts.py`` puts
an operation under the program whose run contains it, so a prefill's calls
are not in it).  Bound: memory.

How many experts a step reaches is the program's own count, from its
spans: ``llm.loop.decode_emit`` says for each burst how many steps it made
and how many experts its routed layers read between them (every slot's row
is routed, an inactive slot's too: they all hold token 0 and reach the same
few experts, which the count holds and which the kernel did read).  The
spans' means over the slice are taken times the WHOLE runs of the decode
program that the trace holds."""

from benchmarks import common
from benchmarks.layer_metrics import _latent


def read(ctx):
    fam, progs = _latent.family(ctx), _latent.programs(ctx)
    within = common.slice_wall(ctx)
    if not progs or within is None or not ctx.get("peaks"):
        return None
    secs = _latent.seconds(progs, lambda p: p == fam.EXPERT_KERNEL_PART)
    did = [a for a in _latent.bursts(ctx, within) if "experts_read" in a]
    steps = sum(a["steps"] for a in did)
    if secs <= 0 or not steps:
        return None
    c = ctx["config"]
    runs = sum(p["runs"] for p in progs)
    sparse = fam.n_layers(c)[1]
    hit = sum(a["experts_read"] for a in did) / steps / sparse
    rows = sum(a.get("tokens", 0) for a in did) / steps
    need = runs * sparse * fam.expert_bytes_per_call(c, rows, hit,
                                                     c["dtype"])
    ctx["notes"].append(
        f"decode expert roofline: {runs} decode steps of {rows:.1f} rows "
        f"reach {hit:.1f} experts a layer and have to move "
        f"{need / 1e9:.2f} GB in {secs * 1e3:.1f} ms under "
        f"{fam.EXPERT_KERNEL_PART}")
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / secs
