"""Runs a training cell: cluster -> ``JaxTrainer.fit()`` with one worker
that is granted all the cell's chips and a ``ray_tpu.data`` stream whose
map tasks make and pack the documents.  This process never imports JAX.
"""

from __future__ import annotations

import sys

from benchmarks import common
from benchmarks.runners import _cluster

# The mesh computes in bf16 with the flash kernel, the reference in float32
# at the highest matmul precision.  On a loss near ln(vocab) = 10.4 the two
# differed by under 0.01 when measured (PERF.md); bf16's 8 bits of mantissa
# over 8 layers do not explain more than a few hundredths, and a step run in
# a lower precision, or without its softmax scale, lands far outside.
LOSS_TOLERANCE = 0.03


def _pack(batch, params, seed, vocab):
    """``map_batches`` function: shard ids -> packed token rows."""
    import numpy as np

    from benchmarks.generators import train_packed

    rows = [train_packed.pack_shard(params, seed, int(i), vocab)
            for i in batch["id"]]
    return {"tokens": np.concatenate(rows)}


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    import functools

    import ray_tpu
    from ray_tpu import data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    cfg, mix = cell["config_file"], cell["mix"]
    gen = common.module("generators", mix["kind"])
    job = gen.generate(mix, seed, seconds, None, cfg["vocab_size"])
    run_dir = _cluster.run_dir(cell, seed, trace)
    chips, rehearse = int(cell["chips"]), common.rehearsing()
    node = _cluster.start(cell, run_dir, 1 << 30)
    try:
        ds = data.range(job["shards"],
                        override_num_blocks=job["shards"]).map_batches(
            functools.partial(_pack, params=job["params"], seed=job["seed"],
                              vocab=job["vocab"]), batch_size=None)
        from benchmarks import in_worker

        trainer = JaxTrainer(
            in_worker.train_loop,
            train_loop_config={
                "model": cfg, "mix": mix, "seed": seed, "seconds": seconds,
                "trace": trace, "trace_slice_s": 4.0, "run_dir": run_dir,
                "t_start": t_start},
            scaling_config=ScalingConfig(
                num_workers=1,
                resources_per_worker={"CPU": 1, "TPU": chips}),
            run_config=RunConfig(name="bench-train", storage_path=run_dir),
            datasets={"train": ds})
        result = trainer.fit()
        if result.error is not None:
            raise result.error
        m = result.metrics
    except BaseException:
        sys.stderr.write(_cluster.stderr_tails(node) + "\n")
        raise
    finally:
        ray_tpu.shutdown()
    if "jax" in sys.modules:
        raise RuntimeError("the driver imported jax")
    if m["devices"]["platform"] != "tpu" and not rehearse:
        raise RuntimeError(f"the train worker ran on {m['devices']}")
    steps = m["steps"]
    finite = all(s["loss"] == s["loss"] and abs(s["loss"]) != float("inf")
                 for s in steps)
    diff = abs(m["mesh_first_loss"] - m["reference_loss"])
    compiles = [e for e in m["compile"]["events"] if e[0] >= m["t0_wall"]]
    mesh = cfg["train"]["mesh"]
    spans = []
    for s in steps:  # the loop's phases, on the wall clock, for gap labels
        for name, a, b in (("train.data_wait", s["start"], s["got_batch"]),
                           ("train.step", s["got_batch"], s["end"])):
            spans.append({"name": name, "start_ts": m["t0_wall"] + a,
                          "end_ts": m["t0_wall"] + b})
    return {
        "kind": "train", "cell": cell, "config": cfg, "mix": mix,
        "seconds": seconds, "trace": trace, "steps": steps,
        "window": {"t0_wall": m["t0_wall"]}, "setup_s": m["setup_s"],
        "setup_parts": {"reference_s": m["reference_s"],
                        "cache_hits": m["compile"]["cache_hits"],
                        "cache_misses": m["compile"]["cache_misses"],
                        "compile_s": sum(e[1] for e in
                                         m["compile"]["events"]),
                        "planned_bytes": m["planned_bytes"],
                        "bytes_limit": m["bytes_limit"],
                        "n_params": m["n_params"],
                        "collectives": m["collectives"]},
        "correct": {"ok": bool(finite and diff <= LOSS_TOLERANCE
                               and (m["has_kernel"] or rehearse)),
                    "mesh_first_loss": m["mesh_first_loss"],
                    "reference_loss": m["reference_loss"],
                    "tolerance": LOSS_TOLERANCE, "all_finite": finite,
                    "has_kernel": m["has_kernel"]},
        "compiles_in_window": len(compiles),
        # steps begun; a step is failed if its loss is not finite (the one
        # the window's end cuts is neither counted as done nor as failed)
        "attempted": len(steps),
        "failed": sum(1 for s in steps if s["loss"] != s["loss"]
                      or abs(s["loss"]) == float("inf")),
        "device": {"platform": m["devices"]["platform"],
                   "kind": m["devices"]["kind"],
                   "count": m["devices"]["count"],
                   "memory_peak_bytes": m["memory_peak_bytes"]},
        "spans": spans, "device_trace": m.get("trace"),
        "trace_error": m.get("trace_error"),
        # what one device's flash kernel call holds
        "kernel_layout": {
            "batch": (mix["global_batch_tokens"] // mix["seq_len"])
            // mesh.get("fsdp", 1) // mesh.get("dp", 1),
            "heads": cfg["num_attention_heads"] // mesh.get("tp", 1),
            "seq_len": mix["seq_len"], "dtype_bytes": 4},
    }
