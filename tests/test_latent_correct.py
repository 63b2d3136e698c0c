"""The comparison that decides ``correct`` in a serving cell whose model
caches latent rows (``benchmarks/runners/serve_latent.py``), driven as the
harness drives it but in this process and at a tiny size: the loader the
replica runs, an ``LLMEngine`` over what it returns, the runner's own
``check_correct`` over that engine's answers and the rows they left in its
pool.  Clean it reads true; with the rows WRITTEN to the pool kept in 3 bits
of mantissa (an fp8 page), with a decode step's rows written a slot off (a
page fault), or with the decode kernel walking only a slot's first block,
false, and by the limit meant for it."""

import json
import os
import random

import jax
import jax.numpy as jnp
import pytest

from benchmarks import in_worker, in_worker_latent
from benchmarks.runners import serve, serve_latent
from ray_tpu.llm import model as lm
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.ops import paged_attention

CONFIG = {
    "family": "glm4_moe_lite", "dtype": "float32", "vocab_size": 640,
    "hidden_size": 64, "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 32, "qk_rope_head_dim": 16, "v_head_dim": 24,
    "n_routed_experts": 8, "num_experts_per_tok": 2, "n_shared_experts": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 1.8, "n_group": 1,
    "topk_group": 1, "max_position_embeddings": 1024, "rope_theta": 1e6,
    "rms_norm_eps": 1e-5,
    "engine": {"max_slots": 4, "num_pages": 256, "page_size": 16,
               "max_seq_len": 1024, "prefill_buckets": [64, 1024]},
}
SEED = 2147484041
BLOCK = paged_attention.LATENT_BLOCK_TOKENS


def _cut(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=3)


def _rows_in_three_bits(pool, li, pages, slots, row):
    return _WRITE(pool, li, pages, slots, _cut(row))


def _decode_rows_a_slot_off(pool, li, pages, slots, row):
    """A decode step's rows (one a slot of the engine: 4) land in the next
    position of their page; a prefill's (a bucket's) where they belong."""
    if row.shape[0] == CONFIG["engine"]["max_slots"]:
        slots = (slots + 1) % pool.shape[2]
    return _WRITE(pool, li, pages, slots, row)


def _first_block_only(q, pool, tables, lengths, layer, **kw):
    return _KERNEL(q, pool, tables, jnp.minimum(lengths, BLOCK), layer, **kw)


_WRITE, _KERNEL = lm._write_rows, lm.paged_latent_decode_attention
PLANTS = {"clean": {},
          "rows_in_three_bits": {"_write_rows": _rows_in_three_bits},
          "decode_rows_a_slot_off": {"_write_rows": _decode_rows_a_slot_off},
          "first_block_only": {
              "paged_latent_decode_attention": _first_block_only}}


class _NoClock:
    """``in_worker.CompileClock`` without its listeners, which would
    outlive the test's directory."""

    def __init__(self, path=None):
        pass

    def snapshot(self):
        return {"events": [], "cache_hits": 0, "cache_misses": 0}


class _Handle:
    """``DeploymentHandle`` as ``check_correct`` uses it, over an engine in
    this process: a call is submitted at once, so calls made together are
    live together."""

    engine = None

    def __init__(self, *names):
        self.generate_tokens = self

    def remote(self, prompt, max_tokens):
        out = _Handle()
        out.request = self.engine.submit(list(prompt), SamplingParams(
            max_tokens=max_tokens, temperature=0.0))
        return out

    def result(self, timeout_s):
        tokens = []
        while True:
            item = self.request.out_queue.get(timeout=timeout_s)
            if item is None:
                return tokens
            if isinstance(item, Exception):
                raise item
            tokens.append(item)


@pytest.fixture
def harness(tmp_path, monkeypatch):
    """``verdict(plant)``: the runner's ``check_correct`` over an engine
    built from the replica's loader with ``plant`` in the served path."""
    import ray_tpu.serve.handle as handle_mod

    # three prompts that cross the kernel's first block, 24 steps each
    check = {**serve_latent.CHECK, "n_prompts": 3, "min_len": BLOCK + 10,
             "max_len": BLOCK + 60, "steps": 24, "pad_to": BLOCK + 96}
    monkeypatch.setattr(serve, "CHECK", check)
    monkeypatch.setattr(serve_latent, "CHECK", check)
    monkeypatch.setattr(in_worker, "CompileClock", _NoClock)
    monkeypatch.setattr(handle_mod, "DeploymentHandle", _Handle)
    engines = []

    def verdict(plant):
        for name, fn in PLANTS[plant].items():
            monkeypatch.setattr(lm, name, fn)
        jax.clear_caches()  # programs traced over the clean forms
        run_dir = str(tmp_path)
        rng = random.Random(SEED)
        stack = serve_latent.Stack(
            {"config_file": CONFIG}, SEED, False, run_dir)
        firsts = rng.sample(range(20, 40), check["n_prompts"])
        stack.check_prompts = [
            [f] + [rng.randrange(3, CONFIG["vocab_size"]) for _ in range(
                rng.randint(check["min_len"], check["max_len"]) - 1)]
            for f in firsts]
        params, model_cfg = in_worker_latent.make_loader({
            "config": CONFIG, "seed": SEED, "notes_dir": run_dir,
            "trace_slice_s": 1.0,
            "check": {"prompts": stack.check_prompts, "steps": check["steps"],
                      "pad_to": check["pad_to"]}})()
        engine = LLMEngine(params, model_cfg, EngineConfig(**{
            **CONFIG["engine"],
            "prefill_buckets": tuple(CONFIG["engine"]["prefill_buckets"])}))
        engine.start()
        engines.append(engine)
        monkeypatch.setattr(_Handle, "engine", engine)
        monkeypatch.setattr(in_worker_latent, "_engine", lambda: engine)
        monkeypatch.setattr(stack, "engine_stats", engine.stats)
        with open(os.path.join(run_dir, f"replica-{os.getpid()}.json")) as f:
            stack.note = json.load(f)
        return stack.check_correct()

    yield verdict
    open(tmp_path / "cmd-finish", "w").close()  # the side channels end
    for engine in engines:
        engine.stop()
    monkeypatch.undo()
    jax.clear_caches()  # nothing traced over a plant outlives it


def test_the_clean_served_path_reads_correct(harness):
    v = harness("clean")
    assert v["ok"], v
    assert v["repeat_equals_first"] and v["prefix_hit_tokens"] > BLOCK
    assert v["tokens_missing"] == 0 and v["within_margin_share"] == 1.0
    rows = v["rows"]
    # every sequence's full pages were found through the prefix index, rows
    # written by the prefill and rows written by decode steps among them
    assert len(rows["sequences"]) == 4
    assert all(s["resident"] >= s["tokens"] - 16 and s["decode_rows"] >= 8
               for s in rows["sequences"])
    # float32 against float32: far under limits that are set for bf16
    assert max(v["pinned"]["logit_rms_error"].values()) < 1e-4
    assert max(rows[f"{k}_{part}"] for k in ("first", "second", "all")
               for part in ("prefill", "decode")) < 1e-4


@pytest.mark.parametrize("plant, by", [
    ("rows_in_three_bits", "first_layer_rows"),
    ("decode_rows_a_slot_off", "all_layers_rows"),
    ("first_block_only", "second_layer_rows")])
def test_a_planted_fault_in_the_served_path_reads_not_correct(harness, plant,
                                                              by):
    v = harness(plant)
    assert not v["ok"], v
    rows, lim = v["rows"], v["limits"]
    # the comparison under the routing pinned runs no pool and no kernel:
    # it stays clean whatever is planted there
    assert max(v["pinned"]["logit_rms_error"].values()) < 1e-4
    if by == "first_layer_rows":  # an fp8 page: 3e-2, between the limits
        for part in ("first_prefill", "first_decode"):
            assert lim["first_layer_rows_rel_rms_max"] < rows[part] < 0.1
    elif by == "all_layers_rows":  # wrong rows where decode steps wrote
        assert rows["all_decode"] > lim["all_layers_rows_rel_rms_max"]
        assert rows["all_decode"] > 5 * rows["all_prefill"]
    else:  # layer 0's rows are right, the walk over them is not: the rows
        # of layer 1 that decode steps wrote past the block say so, and so
        # do the tokens
        assert max(rows["first_prefill"], rows["first_decode"]) < 1e-4
        assert rows["second_prefill"] < 1e-4
        assert rows["second_decode"] > lim["second_layer_rows_rel_rms_max"]
        assert v["within_margin_share"] < 1.0
