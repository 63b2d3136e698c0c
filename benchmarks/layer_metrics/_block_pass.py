"""What the block-diffusion metrics share: the denoising passes
(``jit_block_step`` runs) and the routed experts' grouped product
(``moe_grouped_mlp`` calls) in the traced slice.  A program without them
(the parent of the PR that added them) gives None everywhere."""

from benchmarks import common


def family(ctx):
    fam = common.module("families", ctx["config"]["family"])
    return fam if hasattr(fam, "PASS_MODULE") else None


def passes(ctx):
    """(runs, device seconds) of the pass program; None without any."""
    fam = family(ctx)
    mod = fam and common.module_time(ctx, fam.PASS_MODULE)
    return mod if mod and mod[0] else None


def kernel(ctx):
    """(calls, device seconds) of the grouped product over every program
    of the slice (a call is one layer of a pass or of a prefill)."""
    fam, tr = family(ctx), ctx.get("device_trace")
    if not fam or not tr:
        return None
    hit = [v for k, v in tr["ops"].items()
           if k.startswith(fam.EXPERT_KERNEL)]
    calls = sum(v["count"] for v in hit)
    return (calls, sum(v["seconds"] for v in hit)) if calls else None


def kernel_in_passes(ctx):
    """(calls, device seconds) of the grouped product inside the passes:
    a pass calls it once a layer; the trace's operations do not say which
    program ran them, so the prefills' calls (the rest) are taken to last
    as long as a pass's (both stream the experts they reach)."""
    p, k = passes(ctx), kernel(ctx)
    if not p or not k:
        return None
    calls = min(k[0], p[0] * ctx["config"]["num_hidden_layers"])
    return calls, k[1] * calls / k[0]
