"""Tokens consumed by the train steps that finished (to
``block_until_ready``) inside the window, over the time they took x chips
(``common.train_tokens_per_s`` says why not over the whole window)."""


from benchmarks import common


def read(ctx):
    if not ctx.get("steps"):
        return None
    ctx["notes"].append(f"train: {len(common.steps_done(ctx))} steps "
                        f"finished in the window")
    return common.train_tokens_per_s(ctx) / ctx["device"]["count"]
