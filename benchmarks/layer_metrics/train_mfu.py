"""Model FLOP/s utilisation: the forward and backward FLOPs a token
requires (the family's count; recomputation does not count) x tokens per
second, over chips x the peak bf16 rate."""

from benchmarks import common


def read(ctx):
    if not ctx.get("steps") or not ctx.get("peaks"):
        return None
    family = common.module("families", ctx["config"]["family"])
    per_token = family.train_flops_per_token(ctx["config"],
                                             ctx["mix"]["seq_len"])
    rate = common.train_tokens_per_s(ctx)
    return 100.0 * per_token * rate / (
        ctx["device"]["count"] * ctx["peaks"]["bf16_flops_per_s"])
