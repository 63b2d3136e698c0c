"""The decode step against the HBM roofline: the bytes a step has to read
(every weight a token is multiplied with, plus the K and V of the tokens
actually present in the running sequences; the family's count) at the
chip's peak bandwidth, over the step's device time.  Bound: memory
(a step does 2 FLOPs per weight byte per sequence, far under the ridge).

Tokens present are taken at the middle of the traced slice from the
client's records: a running request holds its prompt plus the share of its
output it had received by then."""

from benchmarks import common


def read(ctx):
    mod = common.module_time(ctx, "jit_decode_step")
    within = common.slice_wall(ctx)
    if not mod or not mod[0] or within is None or not ctx.get("peaks"):
        return None
    mid = (within[0] + within[1]) / 2.0 - ctx["window"]["t0_wall"]
    present = 0.0
    for r in ctx["records"]:
        if r["first"] is None or not r["first"] <= mid <= r["last"]:
            continue
        span = max(r["last"] - r["first"], 1e-9)
        present += r["prompt_len"] + r["n_out"] * (mid - r["first"]) / span
    family = common.module("families", ctx["config"]["family"])
    need = family.decode_step_bytes(ctx["config"], present,
                                    ctx["config"]["dtype"])
    ctx["notes"].append(f"decode roofline: {present:.0f} tokens present, "
                        f"{need / 1e9:.2f} GB a step required")
    return 100.0 * need / ctx["peaks"]["hbm_bytes_per_s"] / (mod[1] / mod[0])
