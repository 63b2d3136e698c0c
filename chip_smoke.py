#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, in a
cluster started by ``ray_tpu.init()``, each in a worker process that the
scheduler granted the chip:

  python chip_smoke.py             one chip: serve, then train
  python chip_smoke.py --chips 4   one host of four: sharded training against
                                   one device, then two replicas behind the
                                   router (and nothing else)

* serve: ``build_openai_app`` -> ``serve.run`` -> HTTP ``/v1/completions``
  through proxy, OpenAI router, handle, replica and engine, on Llama-3-8B at
  its full widths with the depth cut to fit one chip, weights from ``--seed``.
* train: ``JaxTrainer.fit()`` on GPT-2 124M at its published size
  (``create_train_state`` + ``make_train_step`` over the default mesh), with
  a checkpointed ``train.report``.

One JSON object per phase on its own line, and as the LAST line
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``, the
device as JAX reports it inside the worker that holds it.  Any failure, a
missing TPU included, exits non-zero without that line.  This is a smoke and
not a benchmark: it prints no rate and no utilization.

This process never imports jax.  A chip belongs to one process at a time, a
parent that touched JAX would hold it, and the worker that is granted it
would then fail.  Every wait has a limit; on a limit or a worker's death the
tail of the workers' stderr logs is printed (they go to files the driver
otherwise never shows).
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

LLAMA_LAYERS_SERVE = 16  # of 32: 9.1 GB of bf16 weights + 1.1 GB of pages
LLAMA_LAYERS_TRAIN = 4   # of 32: fp32 weights + adam over four chips
PROMPT_LENS = {"short": 24, "long": 100}  # prefill buckets 32 and 128
MAX_TOKENS = 16
VOCAB = 128256
HEADROOM = 0.8  # share of device memory a compiled step may plan to use


class SmokeFailure(Exception):
    pass


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def emit(obj: dict):
    print(json.dumps(obj), flush=True)


# ---------------------------------------------------------------------------
# code that runs inside the worker that holds the chip (shipped by value)

def _devices() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "ids": [d.id for d in devs],
            "granted_chips": os.environ.get("TPU_VISIBLE_CHIPS", "all"),
            "pid": os.getpid()}


def _compile_clock(path=None) -> dict:
    """Sum what JAX itself reports of this process's compilations (a cache
    hit counts its retrieval time); mirrored into ``path`` when given."""
    import jax

    total = {"compile_s": 0.0, "cache_hits": 0, "cache_misses": 0}
    lock = threading.Lock()

    def note(key, amount):
        with lock:
            total[key] += amount
            if path is not None:
                with open(path + ".tmp", "w") as f:
                    json.dump(total, f)
                os.replace(path + ".tmp", path)

    def on_duration(name, secs, **_):
        if name == "/jax/core/compile/backend_compile_duration":
            note("compile_s", secs)

    def on_event(name, **_):
        if name.endswith("/cache_hits") or name.endswith("/cache_misses"):
            note(name.rsplit("/", 1)[1], 1)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return total


def _llama_cfg(n_layers: int, **changes):
    import dataclasses

    from ray_tpu.models import llama

    return dataclasses.replace(llama.LlamaConfig.llama3_8b(),
                               n_layers=n_layers, **changes)


def _gpt2_cfg():
    from ray_tpu.models import gpt2

    # 124M fits the chip without remat or the chunked loss
    return gpt2.GPT2Config(remat=False, loss_chunk=0)


def _device_bytes() -> int:
    import jax

    return jax.devices()[0].memory_stats()["bytes_limit"]


def _barriers() -> dict:
    """One long matmul chain, timed to ``block_until_ready`` and to a value
    fetch.  Timings here trust the first; an earlier runtime returned from
    it before the chain had run."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def chain(a):
        return jax.lax.scan(lambda x, _: ((x @ a) * 1e-3, None), a, None,
                            length=60)[0]

    a = jnp.ones((8192, 8192), jnp.bfloat16)
    float(chain(a)[0, 0])  # compile, and settle
    out = {}
    for name in ("block_until_ready", "value_fetch") * 2:
        t0 = time.perf_counter()
        y = chain(a)
        if name == "value_fetch":
            float(y[0, 0])
        else:
            y.block_until_ready()
        out[name + "_s"] = time.perf_counter() - t0
    return out


def _llama_loader(seed: int, notes_dir: str, prompts: dict):
    """``LLMConfig.model_loader``: seeded bf16 weights, made on the device.
    It also leaves a note for the driver: the devices this replica sees, and
    the next token the TRAINING-side forward (models/llama.py, flash kernel)
    predicts for each prompt, which the engine's own forward (llm/model.py)
    must then find."""
    def load():
        import jax
        import jax.numpy as jnp

        from ray_tpu.models import llama

        pid = os.getpid()
        _compile_clock(os.path.join(notes_dir, f"compile-{pid}.json"))
        cfg = _llama_cfg(LLAMA_LAYERS_SERVE, remat=False)
        params = jax.jit(lambda k: jax.tree.map(
            lambda x: x.astype(jnp.bfloat16), llama.init(cfg, k)))(
                jax.random.PRNGKey(seed))
        forward = jax.jit(lambda p, t: llama.apply(p, t, cfg)[0, -1])
        top = {}
        for name, tokens in prompts.items():
            logits = forward(params, jnp.asarray([tokens], jnp.int32))
            if not bool(jnp.isfinite(logits).all()):
                raise FloatingPointError(f"reference logits for {name}")
            top[name] = [int(t) for t in jax.lax.top_k(logits, 8)[1]]
        note = {**_devices(), "reference_top8": top,
                "weight_bytes": sum(x.nbytes for x in jax.tree.leaves(params))}
        with open(os.path.join(notes_dir, f"replica-{pid}.json"), "w") as f:
            json.dump(note, f)
        return params, cfg

    return load


def _footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def _save_and_report(state, report: dict):
    """One ``train.report`` with a checkpoint that was read back."""
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.train.checkpoint import Checkpoint, load_pytree, save_pytree

    ctx = train.get_context()
    ckpt_dir = os.path.join(ctx.experiment_dir, "smoke-ckpt",
                            f"worker-{ctx.get_world_rank()}")
    os.makedirs(ckpt_dir, exist_ok=True)
    save_pytree(ckpt_dir, state)
    back = load_pytree(ckpt_dir)
    first = lambda tree: np.asarray(jax.tree.leaves(tree)[0])
    report["checkpoint_read_back"] = bool(
        int(back["step"]) == int(state["step"])
        and np.array_equal(first(back["params"]), first(state["params"])))
    train.report(report, checkpoint=Checkpoint.from_directory(ckpt_dir))


def _gpt2_loop(config: dict):
    """GPT-2 124M at its published size on a batch that leaves the chip
    headroom."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import gpt2
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.train.step import (create_train_state, data_sharding,
                                    default_optimizer, make_train_step)

    clock = _compile_clock()
    barriers = _barriers()
    cfg = _gpt2_cfg()
    mesh = create_mesh(MeshConfig())
    opt = default_optimizer()
    limit = _device_bytes()
    with mesh:
        state = create_train_state(gpt2, cfg, mesh, opt,
                                   jax.random.PRNGKey(config["seed"]))
        step = make_train_step(gpt2, cfg, mesh, opt)
        # a batch of 12 plans 15.4 of the chip's 16 GB: take the largest
        # batch whose compiled step leaves headroom
        for batch in (8, 6, 4, 2, 1):
            tokens = jax.device_put(jax.random.randint(
                jax.random.PRNGKey(config["seed"] + 1),
                (batch, cfg.max_seq_len + 1), 0, cfg.vocab_size, jnp.int32),
                data_sharding(mesh))
            compiled = step.lower(state, tokens).compile()
            planned = _footprint(compiled)
            if planned <= HEADROOM * limit:
                break
        else:
            raise MemoryError(f"no batch fits: {planned} of {limit} bytes")
        losses = []
        for _ in range(config["steps"]):  # one batch, repeated
            state, metrics = compiled(state, tokens)
            losses.append(float(metrics["loss"]))
    _save_and_report(state, {
        "devices": _devices(), "losses": losses, "batch": batch,
        "seq": cfg.max_seq_len, "planned_bytes": planned,
        "device_bytes": limit, "barriers": barriers,
        "n_params": sum(x.size for x in jax.tree.leaves(state["params"])),
        "has_kernel": "tpu_custom_call" in compiled.as_text(), **clock})


def _llama_sharded_loop(config: dict):
    """Llama-3-8B widths over a 2x2 ``fsdp x tp`` mesh with the flash
    kernel, against the same seeded model and batch on ONE device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.train.step import (create_train_state, data_sharding,
                                    default_optimizer, make_train_step)

    clock = _compile_clock()
    cfg = _llama_cfg(LLAMA_LAYERS_TRAIN)
    key = jax.random.PRNGKey(config["seed"])
    tokens = jax.random.randint(
        jax.random.PRNGKey(config["seed"] + 1), (2, config["seq"] + 1), 0,
        cfg.vocab_size, jnp.int32)

    # what it is compared with: the loss of the unsharded model on the
    # first device, kernel included, before any update (the weights alone
    # fill half a chip, so they go before the sharded state comes)
    params = jax.jit(lambda k: llama.init(cfg, k))(key)
    loss_one = float(jax.jit(lambda p, t: llama.loss_fn(
        p, t, cfg, attn_impl="flash"))(params, tokens))
    del params

    mesh = create_mesh(MeshConfig(fsdp=2, tp=2))
    opt = default_optimizer()
    with mesh:
        state = create_train_state(llama, cfg, mesh, opt, key)
        layout = jax.tree.map(lambda x: x.sharding, state)
        replicated = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec())
        step = make_train_step(llama, cfg, mesh, opt, attn_impl="flash",
                               out_shardings=(layout, replicated))
        batch = jax.device_put(tokens, data_sharding(mesh))
        compiled = step.lower(state, batch).compile()
        per_device = {d.id: 0 for d in mesh.devices.flat}
        whole, replicated_bytes = 0, 0
        for leaf in jax.tree.leaves(state["params"]):
            whole += leaf.nbytes
            if leaf.sharding.is_fully_replicated:
                replicated_bytes += leaf.nbytes
            for shard in leaf.addressable_shards:
                per_device[shard.device.id] += shard.data.nbytes
        losses = []
        for _ in range(config["steps"]):
            state, metrics = compiled(state, batch)
            losses.append(float(metrics["loss"]))
    text = compiled.as_text()
    train.report({
        "devices": _devices(), "losses": losses, "loss_one_device": loss_one,
        "mesh": {"fsdp": 2, "tp": 2}, "seq": config["seq"],
        "param_bytes": whole, "param_bytes_replicated": replicated_bytes,
        "param_bytes_per_device": per_device,
        "planned_bytes_per_device": _footprint(compiled),
        "has_kernel": "tpu_custom_call" in text,
        "collectives": sorted(c for c in (
            "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute") if c in text), **clock})


# ---------------------------------------------------------------------------
# the driver: never touches jax

def _post(url: str, body: dict, timeout_s: float):
    """(status, parsed body); a streamed body comes back as its SSE events."""
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout_s) as resp:
            raw = resp.read().decode()
            status = resp.status
    except urllib.error.HTTPError as e:
        return e.code, e.read().decode(errors="replace")
    if not body.get("stream"):
        return status, json.loads(raw)
    events = [line[len("data: "):] for line in raw.splitlines()
              if line.startswith("data: ")]
    return status, [e if e == "[DONE]" else json.loads(e) for e in events]


def _completion(base: str, prompt: list, stream: bool = False) -> str:
    """One greedy completion of exactly MAX_TOKENS tokens; returns its text."""
    status, out = _post(f"{base}/v1/completions", {
        "prompt": prompt, "max_tokens": MAX_TOKENS, "temperature": 0.0,
        "ignore_eos": True, "stream": stream}, timeout_s=600)
    check(status == 200, f"/v1/completions answered {status}: {out!r:.500}")
    if not stream:
        usage = out.get("usage", {})
        check(usage.get("completion_tokens") == MAX_TOKENS
              and usage.get("prompt_tokens") == len(prompt),
              f"asked for {MAX_TOKENS} tokens, got {out!r:.500}")
        return out["choices"][0]["text"]
    check(out and out[-1] == "[DONE]" and not any(
        "error" in e for e in out[:-1]), f"stream broke: {out!r:.500}")
    pieces = [e["choices"][0] for e in out[:-1]]
    check(len(pieces) == MAX_TOKENS + 1
          and pieces[-1]["finish_reason"] == "length",
          f"asked for {MAX_TOKENS} streamed tokens, got {len(pieces) - 1}")
    return "".join(p["text"] for p in pieces)


def _read_notes(notes_dir: str, kind: str) -> list:
    notes = []
    for path in sorted(glob.glob(os.path.join(notes_dir, f"{kind}-*.json"))):
        with open(path) as f:
            notes.append(json.load(f))
    return notes


def serve_phase(seed: int, notes_dir: str, replicas: int) -> dict:
    import random

    import ray_tpu
    from ray_tpu import serve
    from ray_tpu.llm import EngineConfig, LLMConfig, build_openai_app
    from ray_tpu.llm.tokenizer import ByteTokenizer
    from ray_tpu.serve.handle import CONTROLLER_NAME, DeploymentHandle

    rng = random.Random(seed)
    prompts = {name: [rng.randrange(VOCAB) for _ in range(n)]
               for name, n in PROMPT_LENS.items()}
    engine = EngineConfig(max_slots=8, num_pages=1024, page_size=16,
                          max_seq_len=1024)
    # K and V, bf16, Llama-3-8B's 8 KV heads of 128
    pool_bytes = (2 * LLAMA_LAYERS_SERVE * engine.num_pages
                  * engine.page_size * 8 * 128 * 2)
    model_id = "llama3-8b-widths"
    app = build_openai_app(LLMConfig(
        model_id=model_id,
        model_loader=_llama_loader(seed, notes_dir, prompts),
        engine_config=engine, num_replicas=replicas,
        ray_actor_options={"num_cpus": 1, "num_tpus": 1},
        default_max_tokens=MAX_TOKENS))
    t0 = time.monotonic()
    serve.run(app, name="llm", route_prefix="/", _blocking_timeout_s=900)
    start_s = time.monotonic() - t0
    base = f"http://127.0.0.1:{serve.http_port()}"
    notes = _read_notes(notes_dir, "replica")
    check(len(notes) == replicas, f"{len(notes)} of {replicas} replicas "
          f"left a note")

    # requests: cold short prompt; two long prompts at once (a second
    # prefill bucket); then the short prompt again, plain and streamed
    cold = _completion(base, prompts["short"])
    others = [[rng.randrange(VOCAB) for _ in range(PROMPT_LENS["long"])]
              for _ in range(1 if replicas == 1 else 5)]
    errors: list = []

    def concurrent(prompt):
        try:
            _completion(base, prompt)
        except Exception as e:  # noqa: BLE001 - re-raised below
            errors.append(e)

    threads = [threading.Thread(target=concurrent, args=(p,))
               for p in [prompts["long"], *others]]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900)
        check(not t.is_alive(), "a concurrent request never came back")
    if errors:
        raise errors[0]
    again = _completion(base, prompts["short"])
    streamed = _completion(base, prompts["short"], stream=True)
    n_http = 3 + len(threads)

    # token ids, which the OpenAI body does not carry, through the same
    # handle -> router -> replica -> engine path
    server = DeploymentHandle("llm", f"LLMServer:{model_id}")
    ids = {}
    for name, prompt in prompts.items():
        # routed as the OpenAI router routes: to the replica whose cache
        # holds the prompt
        home = server.options(
            routing_hint=",".join(str(t) for t in prompt)[:512])
        runs = [home.generate_tokens.remote(
            prompt, max_tokens=MAX_TOKENS).result(timeout_s=600)
            for _ in range(2)]
        check(runs[0] == runs[1], f"greedy decoding of the {name} prompt "
              f"gave {runs[0]} and then {runs[1]}")
        check(len(runs[0]) == MAX_TOKENS
              and all(0 <= t < VOCAB for t in runs[0]),
              f"bad tokens for the {name} prompt: {runs[0]}")
        ids[name] = runs[0]
        for note in notes:
            check(runs[0][0] in note["reference_top8"][name],
                  f"engine continued the {name} prompt with {runs[0][0]}, "
                  f"the training-side forward's top 8 are "
                  f"{note['reference_top8'][name]}")
    text = ByteTokenizer().decode(ids["short"])
    check(again == streamed == text, "the same prompt, sent three times "
          f"to a warm cache, gave {again!r}, {streamed!r} and {text!r}")

    # each replica's own engine must have done the work it answered for
    controller = ray_tpu.get_actor(CONTROLLER_NAME)
    table = ray_tpu.get(controller.get_replicas.remote(
        "llm", f"LLMServer:{model_id}"), timeout=60)
    stats = [ray_tpu.get(r.handle_request.remote("engine_stats", (), {}),
                         timeout=60) for r in table["replicas"]]
    check(len(stats) == replicas, f"{len(stats)} replicas, not {replicas}")
    n_requests = n_http + 2 * len(prompts)
    check(sum(s["admitted"] for s in stats) == n_requests
          and sum(s["tokens_generated"] for s in stats)
          == n_requests * MAX_TOKENS
          and all(s["admitted"] > 0 and s["prefills"] > 0
                  and s["decode_steps"] > 0 for s in stats),
          f"engines did not do what was answered for: {stats}")
    chips = [n["granted_chips"] for n in notes]
    check(len(set(chips)) == replicas and len({n["pid"] for n in notes})
          == replicas, f"replicas share a chip or a process: {notes}")
    serve.delete("llm")
    serve.shutdown()
    compiles = _read_notes(notes_dir, "compile")
    return {
        "phase": "serve", "model": "Llama-3-8B widths", "d_model": 4096,
        "d_ff": 14336, "heads": 32, "kv_heads": 8, "head_dim": 128,
        "vocab": VOCAB, "n_layers": LLAMA_LAYERS_SERVE,
        "weight_bytes": notes[0]["weight_bytes"], "pool_bytes": pool_bytes,
        "replicas": [{k: n[k] for k in ("platform", "kind", "count", "ids",
                                        "granted_chips", "pid")}
                     for n in notes],
        "http_200": n_http, "streamed": 1, "tokens_each": MAX_TOKENS,
        "prefill_buckets": [engine.bucket_for(n)
                            for n in PROMPT_LENS.values()],
        "cold_equals_warm": cold == again,
        "admitted_per_replica": [s["admitted"] for s in stats],
        "start_s": round(start_s, 1),
        "compile_s": round(sum(c["compile_s"] for c in compiles), 1),
        "cache_hits": sum(c["cache_hits"] for c in compiles),
        "cache_misses": sum(c["cache_misses"] for c in compiles),
    }


def train_phase(seed: int, storage: str, chips: int) -> dict:
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig

    sharded = chips > 1
    trainer = JaxTrainer(
        _llama_sharded_loop if sharded else _gpt2_loop,
        train_loop_config={"seed": seed, "steps": 3 if sharded else 8,
                           "seq": 2048},
        scaling_config=ScalingConfig(
            num_workers=1,
            resources_per_worker={"CPU": 1, "TPU": chips}),
        run_config=RunConfig(name="chip-smoke", storage_path=storage))
    result = trainer.fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    losses = m["losses"]
    check(all(x == x and abs(x) != float("inf") for x in losses),
          f"loss is not finite: {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(m["has_kernel"], "no tpu_custom_call in the compiled step: the "
          "attention kernel was not on the path")
    out = {"phase": "train", **m, "compile_s": round(m["compile_s"], 1)}
    if not sharded:
        check(m["checkpoint_read_back"] and result.checkpoint is not None
              and os.listdir(result.checkpoint.path),
              "the reported checkpoint was not saved or did not read back")
        check(m["planned_bytes"] <= HEADROOM * m["device_bytes"],
              f"the step plans {m['planned_bytes']} bytes")
        b = m["barriers"]
        check(b["block_until_ready_s"] >= 0.9 * b["value_fetch_s"],
              f"block_until_ready came back before the work was done: {b}")
        return {**out, "model": "GPT-2 124M", "n_layers": 12, "d_model": 768,
                "checkpoint": os.path.basename(result.checkpoint.path)}
    # bf16 compute, two orders of summation: the first loss (before any
    # update) of the sharded step against the unsharded model
    check(abs(losses[0] - m["loss_one_device"]) <= 0.05,
          f"sharded loss {losses[0]} vs one device {m['loss_one_device']}")
    share = (m["param_bytes"] - m["param_bytes_replicated"]) / chips \
        + m["param_bytes_replicated"]
    worst = max(m["param_bytes_per_device"].values())
    check(len(m["param_bytes_per_device"]) == chips
          and worst <= 1.02 * share,
          f"a device holds {worst} parameter bytes, a fair share is "
          f"{share}: {m['param_bytes_per_device']}")
    check("all-gather" in m["collectives"] and (
        "all-reduce" in m["collectives"]
        or "reduce-scatter" in m["collectives"]),
        f"an fsdp x tp step without its collectives: {m['collectives']}")
    return {**out, "phase": "train_sharded", "model": "Llama-3-8B widths",
            "n_layers": LLAMA_LAYERS_TRAIN, "d_model": 4096}


def _stderr_tails(session_dir: str, lines: int = 25):
    """Worker output goes to files the driver never shows: show their ends."""
    for path in sorted(glob.glob(os.path.join(session_dir, "logs",
                                              "worker-*.err"))):
        with open(path, errors="replace") as f:
            tail = f.readlines()[-lines:]
        if tail:
            print(f"--- {os.path.basename(path)}\n{''.join(tail)}",
                  file=sys.stderr, flush=True)


def _bounded(fn, limit_s: float, *args):
    """Run a phase with a time limit on the whole of it."""
    box: dict = {}

    def run():
        try:
            box["out"] = fn(*args)
        except BaseException as e:  # noqa: BLE001 - re-raised by the caller
            box["error"] = e

    t = threading.Thread(target=run, daemon=True)
    t.start()
    t.join(limit_s)
    if t.is_alive():
        raise SmokeFailure(f"{fn.__name__} passed its limit of {limit_s:.0f}s")
    if "error" in box:
        raise box["error"]
    return box["out"]


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args()

    import ray_tpu
    from ray_tpu._private import direct
    from ray_tpu.native import build
    from ray_tpu.util import compile_cache

    # The native components are built from their sources on first use (a
    # checkout holds no binaries).  A build that fails stops here, and does
    # not leave the run on the Python lane.
    t0 = time.monotonic()
    for name in ("shm_store", "gcs_server", "_rtpu_core",
                 "libmutable_channel"):
        build.binary_path(name)
    if direct.native_core() is None:
        print("chip_smoke FAILED: the native transport did not load",
              file=sys.stderr)
        return 1
    native_build_s = time.monotonic() - t0
    deadline = time.monotonic() + 1140  # the whole run: 1200 s, start-up too
    # stdout is for the phases' lines; what the workers print stays in their
    # log files, whose ends are shown on a failure
    os.environ.setdefault("RTPU_LOG_TO_DRIVER", "0")
    cache_dir = compile_cache.enable()  # the workers inherit it
    scratch = tempfile.mkdtemp(prefix="chip_smoke_")
    node = ray_tpu.init(resources={"CPU": 8.0}, min_workers=2, max_workers=8,
                        object_store_memory=1 << 29)
    devices = None
    try:
        found = int(node.resources.get("TPU", 0))
        check(found >= args.chips, f"this needs {args.chips} TPU chip(s) "
              f"and the machine shows {found} (no /dev/accel* or "
              f"/dev/vfio/N device file)")
        # a task that is granted the chips says what JAX finds there, in a
        # process that ends with it: the first of the hand-overs
        seen = ray_tpu.get(ray_tpu.remote(num_tpus=args.chips)(
            _devices).remote(), timeout=300)
        check(seen["platform"] == "tpu" and seen["count"] == args.chips,
              f"JAX found no TPU in the worker that was granted "
              f"{args.chips} chip(s): it sees {seen} (JAX_PLATFORMS="
              f"{os.environ.get('JAX_PLATFORMS')!r})")
        emit({"phase": "start", "chips": args.chips, "tpu_found": found,
              "devices": seen, "compile_cache": cache_dir,
              "native_build_s": round(native_build_s, 1),
              "seed": args.seed})
        phases = ([(serve_phase, args.seed, scratch, 1),
                   (train_phase, args.seed, scratch, 1)]
                  if args.chips == 1 else
                  [(train_phase, args.seed, scratch, 4),
                   (serve_phase, args.seed, scratch, 2)])
        for fn, *phase_args in phases:
            line = _bounded(fn, deadline - time.monotonic(), *phase_args)
            for holder in line.get("replicas") or [line["devices"]]:
                check(holder["platform"] == "tpu",
                      f"{fn.__name__} ran on {holder}")
            emit(line)
        check("jax" not in sys.modules, "the driver imported jax")
        devices = {k: seen[k] for k in ("platform", "kind", "count")}
    except BaseException as e:  # noqa: BLE001 - report, clean up, fail
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", file=sys.stderr,
              flush=True)
        _stderr_tails(node.session_dir)
        return 1
    finally:
        ray_tpu.shutdown()
        shutil.rmtree(scratch, ignore_errors=True)
    emit({"ok": True, "device": devices})
    return 0


if __name__ == "__main__":
    sys.exit(main())
