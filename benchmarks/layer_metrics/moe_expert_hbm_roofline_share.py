"""The routed experts' grouped product against the HBM roofline: the bytes
its calls have to move (the three matrices of every expert that some token
of the call reaches, once a call, plus its rows in and out; sizes from the
published configuration) at the chip's peak bandwidth, over the device time
of the ``moe_grouped_mlp`` operations in the slice.  Bound: memory (a
decoding call multiplies each expert's 9.4 MB with about 8 rows).

How many experts a call has to reach is the benchmark's own count, not the
program's: the family's ``experts_reached_by_*`` hold what the REFERENCE's
router reaches a block and a prompt (routing is far from uniform: tokens of
one sequence choose alike, and a count from shapes alone read 103 %).  From
the program come only the shape of the work, which is traffic and not
bytes: the ``llm.loop.decode_emit`` spans say how many slots a pass held
and how many of them it made final, the ``llm.prefill`` spans how many
tokens a prefill computed.  Only the rows of slots that hold a request are
required work.  The spans' means over the slice are taken times the WHOLE
runs of ``jit_block_step`` and ``jit_prefill*`` the trace holds: a run that
the slice's edge cuts counts in the time and not in the bytes.  The
program's own ``experts_read`` goes into the note beside the count, as a
cross-check: it counts what the inactive slots' rows reach as well."""

from benchmarks import common
from benchmarks.layer_metrics import _block_pass


def read(ctx):
    p, k = _block_pass.passes(ctx), _block_pass.kernel(ctx)
    within = common.slice_wall(ctx)
    if not p or not k or within is None or not ctx.get("peaks"):
        return None
    fam, c = _block_pass.family(ctx), ctx["config"]
    layers, dtype = c["num_hidden_layers"], c["dtype"]
    bursts = [s["args"] for s in common.spans_named(
        ctx, "llm.loop.decode_emit", within)
        if "slot_passes" in (s.get("args") or {})]
    n_passes = sum(a["passes"] for a in bursts)
    if not n_passes:
        return None
    slots = sum(a["slot_passes"] for a in bursts) / n_passes
    final = sum(a["blocks_final"] for a in bursts) / n_passes
    reached = fam.experts_reached_by_blocks(c, slots - final, final)
    need = p[0] * layers * fam.expert_bytes_per_call(
        c, slots * c["sampler"]["block_length"], dtype, reached)
    prefills = [s["args"] for s in common.spans_named(
        ctx, "llm.prefill", within) if "experts_read" in s["args"]]
    runs = (common.module_time(ctx, "jit_prefill") or (0, 0.0))[0]
    if prefills and runs:
        new = [a["tokens"] - a["prefix_len"] for a in prefills]
        need += runs * layers * sum(fam.expert_bytes_per_call(
            c, m, dtype, fam.experts_reached_by_prompt(m))
            for m in new) / len(new)
    said = sum(a.get("experts_read", 0) for a in bursts) / n_passes / layers
    ctx["notes"].append(
        f"expert roofline: {p[0]} passes of {slots:.1f} slots ({final:.1f} "
        f"made final) have to reach {reached:.1f} experts a layer (the "
        f"program read {said:.1f}, inactive slots' rows too), {runs} "
        f"prefills, {k[0]} calls, {need / 1e9:.2f} GB required in "
        f"{k[1] * 1e3:.1f} ms")
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / k[1]
