"""Code that runs inside the process that holds the chip: the serving
replica (through ``LLMConfig.model_loader``) and the train worker (the
train loop).  The driver (``run.py``) never imports JAX; it talks to this
side through files in a notes directory and through the program's own RPCs.
"""

from __future__ import annotations

import glob
import os
import threading
import time

from benchmarks import common


def devices_note() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "pid": os.getpid(),
            "granted_chips": os.environ.get("TPU_VISIBLE_CHIPS", "all")}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend does
    not report it, as on the CPU)."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileClock:
    """What JAX itself reports of this process's compilations, each with
    the wall time it ended at, so that the driver can count those that fell
    inside the window (there must be none)."""

    def __init__(self, path=None):
        import jax

        self.events = []  # [wall_time, seconds]
        self.hits = self.misses = 0
        self._path, self._lock = path, threading.Lock()
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            with self._lock:
                self.events.append([time.time(), secs])
                self._write()

    def _event(self, name, **_):
        if name.endswith("/cache_hits"):
            self.hits += 1
        elif name.endswith("/cache_misses"):
            self.misses += 1

    def snapshot(self) -> dict:
        return {"events": list(self.events), "cache_hits": self.hits,
                "cache_misses": self.misses}

    def _write(self):
        if self._path is not None:
            common.write_json(self._path, self.snapshot())


class Tracer:
    """A profiler trace of a slice of the window, taken by the process that
    holds the chip, and its reduction (``trace/reduce.py``)."""

    def __init__(self, out_dir: str):
        self.dir = out_dir
        self.wall_start = self.wall_started = self.wall_stop = None

    def start(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0  # per-call Python events slow the host
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        self.wall_start = time.time()
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.wall_started = time.time()

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        self.wall_stop = time.time()

    def reduce(self) -> dict:
        from benchmarks.trace import reduce

        paths = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise FileNotFoundError(f"no .xplane.pb under {self.dir}")
        out = reduce.reduce_file(paths[-1], common.rehearsing())
        out.update(wall_start=self.wall_start,
                   wall_started=self.wall_started, wall_stop=self.wall_stop,
                   xplane=paths[-1])
        return out


# --------------------------------------------------------------------------
# serving: LLMConfig.model_loader

def make_loader(spec: dict):
    """``spec``: config (the configuration file), seed, notes_dir,
    trace_slice_s, check {prompts, steps, pad_to}.  Returns the callable
    the replica runs before it builds its engine."""

    def load():
        import jax  # noqa: F401 - first use of the chip in this process

        notes, pid = spec["notes_dir"], os.getpid()
        t0 = time.time()
        clock = CompileClock(os.path.join(notes, f"compile-{pid}.json"))
        c = spec["config"]
        family = common.module("families", c["family"])
        reference = common.module("reference", c["family"])
        params = family.make_params(c, spec["seed"], c["dtype"])
        jax.block_until_ready(params)
        t1 = time.time()
        chk = spec["check"]
        candidates, gaps = reference.greedy(
            c, params, chk["prompts"], chk["steps"], chk["pad_to"])
        common.write_json(os.path.join(notes, f"replica-{pid}.json"), {
            **devices_note(), "weights_s": t1 - t0,
            "reference_s": time.time() - t1,
            "weight_bytes": sum(x.nbytes for x in jax.tree.leaves(params)),
            "reference": {"candidates": candidates, "gaps": gaps}})
        threading.Thread(target=_side_channel, args=(spec, clock),
                         name="bench-side", daemon=True).start()
        return params, family.model_config(c)

    return load


def _side_channel(spec: dict, clock: CompileClock):
    """Serves the driver's requests, made by dropping a file into the notes
    directory: ``cmd-trace`` (trace a slice now), ``cmd-finish`` (reduce the
    trace if any, report peak memory and compilations, and stop)."""
    notes, pid = spec["notes_dir"], os.getpid()
    tracer = None
    while True:
        time.sleep(0.05)
        if tracer is None and os.path.exists(os.path.join(notes, "cmd-trace")):
            tracer = Tracer(os.path.join(notes, "trace"))
            tracer.start()
            time.sleep(spec["trace_slice_s"])
            tracer.stop()
        if os.path.exists(os.path.join(notes, "cmd-finish")):
            out = {"memory_peak_bytes": memory_peak_bytes(),
                   "compile": clock.snapshot()}
            try:
                if tracer is not None:
                    out["trace"] = tracer.reduce()
            except Exception as e:  # noqa: BLE001 - the driver reports it
                out["trace_error"] = f"{type(e).__name__}: {e}"
            common.write_json(os.path.join(notes, f"finish-{pid}.json"), out)
            return


# --------------------------------------------------------------------------
# training: the train loop JaxTrainer runs in the worker that holds the chips

def train_loop(config: dict):
    """``config``: model (the configuration file), mix, seed, seconds,
    trace, trace_slice_s, run_dir, t_start.  Reports one record: the steps
    with their times relative to the window's first instant, the losses,
    the reference check, memory, compilations and the reduced trace."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu import train
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.train.step import (create_train_state, data_sharding,
                                    default_optimizer, make_train_step)

    clock = CompileClock()
    c, mix = config["model"], config["mix"]
    family = common.module("families", c["family"])
    reference = common.module("reference", c["family"])
    model, cfg = family.model_module(), family.model_config(c)
    key = jax.random.PRNGKey(config["seed"])
    rows, seq = mix["global_batch_tokens"] // mix["seq_len"], mix["seq_len"]
    batches = iter(train.get_dataset_shard("train").iter_batches(
        batch_size=rows, batch_format="numpy", drop_last=True,
        prefetch_batches=mix["prefetch_batches"]))

    # The check, before the sharded state exists (the unsharded fp32
    # weights alone fill half a chip): the float32 reference's loss of the
    # same seeded weights on two rows of the first batch, on one device.
    # The mesh's first step then runs on those two rows, repeated to the
    # batch's size, so that its loss is the loss of the same sample.
    first = np.asarray(next(batches)["tokens"], np.int32)
    sample = first[:2]
    t = time.time()
    params = jax.jit(lambda k: model.init(cfg, k))(key)
    ref_loss = float(jax.jit(lambda p, x: reference.loss(c, p, x))(
        params, jnp.asarray(sample)))
    del params
    reference_s = time.time() - t

    mesh = create_mesh(MeshConfig(**c["train"]["mesh"]))
    opt = default_optimizer()
    steps, losses = [], []
    tracer, traced = None, None
    with mesh:
        state = create_train_state(model, cfg, mesh, opt, key)
        layout = jax.tree.map(lambda x: x.sharding, state)
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        step = make_train_step(model, cfg, mesh, opt,
                               attn_impl=c["train"]["attn_impl"],
                               out_shardings=(layout, replicated))
        sharding = data_sharding(mesh)
        put = lambda x: jax.device_put(jnp.asarray(x, jnp.int32), sharding)  # noqa: E731
        check_batch = put(np.tile(sample, (rows // 2, 1)))
        compiled = step.lower(state, check_batch).compile()
        m = compiled.memory_analysis()
        planned = (m.temp_size_in_bytes + m.argument_size_in_bytes
                   + m.output_size_in_bytes - m.alias_size_in_bytes)
        limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        if limit and planned > c["memory_headroom"] * limit:
            raise MemoryError(f"the step plans {planned} of {limit} bytes a "
                              f"device: over {c['memory_headroom']}")
        text = compiled.as_text()
        state, out = compiled(state, check_batch)
        mesh_loss = float(out["loss"])
        for _ in range(int(mix["warmup_steps"])):
            state, out = compiled(state, put(next(batches)["tokens"]))
            float(out["loss"])
        seconds, slice_s = config["seconds"], config["trace_slice_s"]
        t0_wall, t0 = time.time(), time.monotonic()
        while True:
            a = time.monotonic() - t0
            if a >= seconds:
                break
            if (config["trace"] and tracer is None
                    and a >= (seconds - slice_s) / 2.0):
                tracer = Tracer(os.path.join(config["run_dir"], "trace"))
                tracer.start()
            host = next(batches)["tokens"]
            b = time.monotonic() - t0
            batch = put(host)
            state, out = compiled(state, batch)
            loss = float(jax.block_until_ready(out["loss"]))
            e = time.monotonic() - t0
            steps.append({"start": a, "got_batch": b, "end": e,
                          "wait_s": b - a, "tokens": rows * seq,
                          "loss": loss})
            losses.append(loss)
            if (tracer is not None and traced is None
                    and time.time() - tracer.wall_started >= slice_s):
                tracer.stop()
                traced = True
    record = {
        "devices": devices_note(), "steps": steps, "t0_wall": t0_wall,
        "setup_s": t0_wall - config["t_start"],
        "reference_loss": ref_loss, "mesh_first_loss": mesh_loss,
        "reference_s": reference_s, "planned_bytes": planned,
        "bytes_limit": limit, "memory_peak_bytes": memory_peak_bytes(),
        "has_kernel": "tpu_custom_call" in text,
        "collectives": sorted(k for k in (
            "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute") if k in text),
        "n_params": sum(x.size for x in jax.tree.leaves(state["params"])),
        "compile": clock.snapshot()}
    if tracer is not None:
        if traced is None:
            tracer.stop()
        try:
            record["trace"] = tracer.reduce()
        except Exception as e:  # noqa: BLE001 - the driver reports it
            record["trace_error"] = f"{type(e).__name__}: {e}"
    train.report(record)
