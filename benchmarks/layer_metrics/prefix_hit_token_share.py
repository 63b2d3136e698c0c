"""Share of the prompt tokens admitted in the window whose prefill was
skipped because their pages were resident (prefix cache, copy-on-write
boundary page): counter ``prefill_tokens_saved`` over the prompt tokens of
the window's requests."""

from benchmarks import common


def read(ctx):
    c = ctx.get("counters")
    sent = sum(r["prompt_len"] for r in common.window_records(ctx))
    if not c or not sent:
        return None
    return 100.0 * c["prefill_tokens_saved"] / sent
