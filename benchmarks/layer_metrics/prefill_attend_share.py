"""The attention core (``attn/attend``: scores, softmax, values, with the
repeat of K and V to the query heads under it) as a share of the prefill
programs' operation time (``jit_prefill*`` runs): what a kernel at KV-head
width could take off a prefill."""

from benchmarks.trace import device_parts


def read(ctx):
    return device_parts.share(ctx, "jit_prefill",
                              lambda part: part.startswith("attn/attend"))
