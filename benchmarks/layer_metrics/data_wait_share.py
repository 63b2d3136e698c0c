"""Share of the window the train loop spent waiting for its next batch
(``ray_tpu.data`` -> ``get_dataset_shard`` -> ``iter_batches``)."""


from benchmarks import common


def read(ctx):
    if not ctx.get("steps"):
        return None
    return 100.0 * sum(s["wait_s"] for s in common.steps_done(ctx)) \
        / ctx["seconds"]
