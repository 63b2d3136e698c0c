"""What the two prefill metrics share: the prefills inside the traced
slice, from the engine's ``llm.prefill`` spans (tokens computed = prompt
tokens minus the resident prefix), and the device time of the prefill
programs in the trace."""

from benchmarks import common


def in_slice(ctx):
    """(device seconds, [(new tokens, cached tokens)]) or None."""
    within = common.slice_wall(ctx)
    mod = common.module_time(ctx, "jit_prefill")
    if within is None or not mod or not mod[0]:
        return None
    spans = common.spans_named(ctx, "llm.prefill", within)
    work = [(s["args"]["tokens"] - s["args"]["prefix_len"],
             s["args"]["prefix_len"]) for s in spans]
    if not work:
        return None
    return mod[1], work
