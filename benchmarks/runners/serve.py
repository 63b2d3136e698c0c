"""Runs a serving cell: cluster -> ``build_openai_app`` -> ``serve.run`` ->
HTTP, one replica on one granted chip, load from ``loadgen.py`` in its own
process.  This process never imports JAX.
"""

from __future__ import annotations

import glob
import json
import os
import subprocess
import sys
import time

from benchmarks import common
from benchmarks.runners import _cluster

MODEL_ID = "bench"
COUNTERS = ("prefills", "decode_steps", "tokens_generated", "admitted",
            "preempted", "page_evictions", "prefill_tokens_saved",
            "cow_copies")
CHECK = {"n_prompts": 12, "steps": 16, "pad_to": 96, "min_len": 20,
         "max_len": 60,
         # At every step the engine's greedy token must be the reference's
         # best, or one whose reference logit lies less than this under the
         # best; where it is such a near-tie the prompt's comparison ends
         # (every later token then legitimately differs).  Reason for the
         # number: the engine computes in bf16 (8 bits of mantissa), the
         # reference in float32; with the seeded weights the logits are O(1)
         # and the two differ by a few hundredths at these widths: the
         # flips measured on the chip all lie under 0.03 (PERF.md), so 0.1
         # leaves room and a path computed in a lower precision still fails.
         "margin": 0.1, "min_compared": 48}


class Stack:
    """The cluster with the application deployed on it."""

    def __init__(self, cell: dict, seed: int, trace: bool, run_dir: str,
                 trace_slice_s: float = 4.0):
        self.cell, self.seed, self.trace = cell, seed, trace
        self.cfg = cell["config_file"]
        self.run_dir = run_dir
        self.trace_slice_s = trace_slice_s
        self.node = None

    # -- set-up -----------------------------------------------------------
    def start(self):
        import random

        from ray_tpu import serve
        from ray_tpu.llm import EngineConfig, LLMConfig, build_openai_app

        # what the workers inherit: spans on in a traced run and off
        # otherwise (RTPU_TRACE_SAMPLE is the program's own head-sampling
        # flag), and room for a window's traces
        os.environ["RTPU_TRACE_SAMPLE"] = "1.0" if self.trace else "0.0"
        os.environ["RTPU_TRACE_CAP"] = "16384"
        self.node = _cluster.start(self.cell, self.run_dir, 1 << 29)
        rng = random.Random(self.seed)
        vocab = self.cfg["vocab_size"]
        # first tokens distinct, and apart from the traffic's and the
        # warm-up's (generators/_common.first_tokens says why)
        firsts = rng.sample(range(vocab // 32, vocab // 16),
                            CHECK["n_prompts"])
        self.check_prompts = [
            [first] + [rng.randrange(3, vocab) for _ in range(
                rng.randint(CHECK["min_len"], CHECK["max_len"]) - 1)]
            for first in firsts]
        from benchmarks import in_worker

        loader = in_worker.make_loader({
            "config": self.cfg, "seed": self.seed, "notes_dir": self.run_dir,
            "trace_slice_s": self.trace_slice_s,
            "check": {"prompts": self.check_prompts, "steps": CHECK["steps"],
                      "pad_to": CHECK["pad_to"]}})
        eng = dict(self.cfg["engine"])
        eng["prefill_buckets"] = tuple(eng["prefill_buckets"])
        app = build_openai_app(LLMConfig(
            model_id=MODEL_ID, model_loader=loader,
            engine_config=EngineConfig(**eng), num_replicas=1,
            ray_actor_options={"num_cpus": 1, "num_tpus": 1},
            default_max_tokens=16))
        t = time.monotonic()
        serve.run(app, name="llm", route_prefix="/", _blocking_timeout_s=1000)
        self.serve_run_s = time.monotonic() - t
        self.url = f"http://127.0.0.1:{serve.http_port()}/v1/completions"
        notes = glob.glob(os.path.join(self.run_dir, "replica-*.json"))
        if len(notes) != 1:
            raise RuntimeError(f"{len(notes)} replica notes, expected 1")
        with open(notes[0]) as f:
            self.note = json.load(f)
        if self.note["platform"] != "tpu" and not common.rehearsing():
            raise RuntimeError(f"the replica runs on {self.note['platform']}")

    def engine_stats(self) -> dict:
        import ray_tpu
        from ray_tpu.serve.handle import CONTROLLER_NAME

        controller = ray_tpu.get_actor(CONTROLLER_NAME)
        table = ray_tpu.get(controller.get_replicas.remote(
            "llm", f"LLMServer:{MODEL_ID}"), timeout=60)
        (replica,) = table["replicas"]
        stats = ray_tpu.get(replica.handle_request.remote(
            "engine_stats", (), {}), timeout=60)
        # the cumulative counters only: the percentile fields come from
        # rings of the last 128 requests, the wrong window for a run
        return {k: stats[k] for k in COUNTERS if k in stats}

    def check_correct(self) -> dict:
        """Greedy tokens through ``LLMServer.generate_tokens`` against the
        reference's, computed in the replica before the engine existed."""
        from ray_tpu.serve.handle import DeploymentHandle

        server = DeploymentHandle("llm", f"LLMServer:{MODEL_ID}")
        steps = CHECK["steps"]
        calls = [server.generate_tokens.remote(p, max_tokens=steps)
                 for p in self.check_prompts]
        got = [c.result(timeout_s=600) for c in calls]
        # the first prompt again: now a prefix hit (resident-prefix prefill)
        got.append(server.generate_tokens.remote(
            self.check_prompts[0], max_tokens=steps).result(timeout_s=600))
        ref = self.note["reference"]
        compared, strict, flips, wrong = 0, 0, [], []
        for i, out in enumerate(got):
            j = i if i < len(self.check_prompts) else 0
            for s in range(steps):
                tok = out[s] if s < len(out) else None
                cands, gaps = ref["candidates"][j][s], ref["gaps"][j][s]
                compared += 1
                if tok == cands[0]:
                    strict += gaps[1] >= CHECK["margin"]
                    continue
                if tok in cands and gaps[cands.index(tok)] < CHECK["margin"]:
                    flips.append(round(gaps[cands.index(tok)], 4))
                else:
                    wrong.append({"prompt": j, "step": s, "engine": tok,
                                  "reference": cands, "gaps": gaps})
                break
        flat = sorted(g[1] for row in ref["gaps"] for g in row)
        return {"ok": not wrong and compared >= CHECK["min_compared"],
                "positions_compared": compared,
                "of_them_above_tolerance": int(strict),
                "flips_below_tolerance": flips, "mismatches": wrong[:4],
                "margin_tolerance": CHECK["margin"],
                "margin_p10": flat[len(flat) // 10],
                "margin_p50": flat[len(flat) // 2],
                "repeat_equals_first": got[-1] == got[0]}

    # -- one measured window ------------------------------------------------
    def run_load(self, schedule: dict, seconds: float, tag: str = "w") -> dict:
        """Start the load generator on ``schedule`` and read the engine's
        counters at both ends of its window.  Returns the client records,
        the window and the counter deltas."""
        out_dir = os.path.join(self.run_dir, tag)
        os.makedirs(out_dir)
        plan = os.path.join(out_dir, "plan.json")
        with open(plan, "w") as f:
            json.dump({"schedule": schedule, "url": self.url,
                       "model": MODEL_ID, "seconds": seconds,
                       "out_dir": out_dir}, f)
        with open(os.path.join(out_dir, "loadgen.err"), "w") as err:
            proc = subprocess.Popen(
                [sys.executable, os.path.join(common.HERE, "loadgen.py"),
                 plan], stdout=err, stderr=err)
        try:
            alive = lambda: proc.poll() is None  # noqa: E731
            window = common.read_json_when_there(
                os.path.join(out_dir, "window.json"),
                time.monotonic() + 900, alive)
            t0 = window["t0_wall"]
            _sleep_until(t0)
            before = self.engine_stats()
            if self.trace:
                _sleep_until(t0 + (seconds - self.trace_slice_s) / 2.0)
                open(os.path.join(self.run_dir, "cmd-trace"), "w").close()
            _sleep_until(t0 + seconds)
            after = self.engine_stats()
            proc.wait(timeout=schedule.get("drain_s", 0.0) + 120)
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait()
        if proc.returncode != 0:
            with open(os.path.join(out_dir, "loadgen.err")) as f:
                raise RuntimeError(f"the load generator failed:\n"
                                   f"{f.read()[-2000:]}")
        with open(os.path.join(out_dir, "loadgen.json")) as f:
            load = json.load(f)
        return {"window": window, "records": load["records"],
                "setup_records": load["setup_records"],
                "counters": {k: after[k] - before[k] for k in after
                             if k in before},
                "counters_at_end": after}

    def finish(self) -> dict:
        """Peak memory, compilations and the reduced trace, from the
        replica; the engine's spans, from the node."""
        open(os.path.join(self.run_dir, "cmd-finish"), "w").close()
        pid = self.note["pid"]
        out = common.read_json_when_there(
            os.path.join(self.run_dir, f"finish-{pid}.json"),
            time.monotonic() + 300)
        out["spans"] = []
        if self.trace:
            from ray_tpu._private import worker as worker_mod

            time.sleep(2.5)  # the span flushers' period is 2 s
            out["spans"] = worker_mod.global_worker().rpc(
                "spans_window", {"since_ts": 0.0, "name_prefix": ""})
        return out

    def stop(self):
        import ray_tpu
        from ray_tpu import serve

        if self.node is None:
            return
        try:
            serve.delete("llm")
            serve.shutdown()
        finally:
            ray_tpu.shutdown()
            self.node = None

    def stderr_tails(self) -> str:
        return "" if self.node is None else _cluster.stderr_tails(self.node)


def _sleep_until(wall: float):
    delay = wall - time.time()
    if delay > 0:
        time.sleep(delay)


def run(cell: dict, seed: int, seconds: float, trace: bool,
        t_start: float) -> dict:
    """The whole of one run; returns the context the metric readers take."""
    mix = cell["mix"]
    gen = common.module("generators", mix["kind"])
    stack = Stack(cell, seed, trace, _cluster.run_dir(cell, seed, trace))
    try:
        stack.start()
        schedule = gen.generate(mix, seed, seconds, stack.cfg["engine"],
                                stack.cfg["vocab_size"])
        correct = stack.check_correct()
        load = stack.run_load(schedule, seconds)
        fin = stack.finish()
    except BaseException:
        sys.stderr.write(stack.stderr_tails() + "\n")
        raise
    finally:
        stack.stop()
    if "jax" in sys.modules:
        raise RuntimeError("the driver imported jax")
    window = load["window"]
    compiles = [e for e in fin["compile"]["events"]
                if window["t0_wall"] <= e[0] < window["t0_wall"] + seconds]
    ctx = {
        "kind": "serve", "cell": cell, "config": stack.cfg, "mix": mix,
        "seconds": seconds, "trace": trace, "schedule_mode": schedule["mode"],
        "records": load["records"], "counters": load["counters"],
        "window": window, "setup_s": window["t0_wall"] - t_start,
        "setup_parts": {"serve_run_s": stack.serve_run_s,
                        "weights_s": stack.note["weights_s"],
                        "reference_s": stack.note["reference_s"],
                        "warmup_s": window["warmup_s"],
                        "prime_s": window["prime_s"],
                        "cache_hits": fin["compile"]["cache_hits"],
                        "cache_misses": fin["compile"]["cache_misses"],
                        "compile_s": sum(e[1] for e in
                                         fin["compile"]["events"])},
        "correct": correct, "compiles_in_window": len(compiles),
        "device": {"platform": stack.note["platform"],
                   "kind": stack.note["kind"], "count": stack.note["count"],
                   "memory_peak_bytes": fin["memory_peak_bytes"]},
        "spans": fin["spans"], "device_trace": fin.get("trace"),
        "trace_error": fin.get("trace_error"),
        "max_slots": stack.cfg["engine"]["max_slots"],
        "drain_s": schedule.get("drain_s", 0.0),
    }
    ctx["notes"] = ["engine counters over the window: " + ", ".join(
        f"{k} {v}" for k, v in sorted(load["counters"].items()) if v)]
    in_window = common.window_records(ctx)
    if schedule["mode"] == "open":  # unbounded, but what a user feels first
        ttft = [r["first"] - r["due"] for r in in_window if r["ok"]]
        ctx["notes"].append(
            f"ttft over {len(ttft)} requests: median "
            f"{(common.median(ttft) or 0) * 1e3:.1f} ms, p90 "
            f"{(common.percentile(ttft, 0.9) or 0) * 1e3:.1f} ms")
    ctx["attempted"] = len(in_window)
    # a closed loop's requests still in flight when the window ends are cut
    # off, not failed; an open loop's still open after the drain are failed
    ctx["failed"] = sum(1 for r in in_window if not r["ok"] and not (
        schedule["mode"] == "closed" and r.get("cancelled")))
    return ctx
