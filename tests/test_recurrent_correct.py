"""The comparison that decides ``correct`` in a serving cell whose model has
recurrent layers (``benchmarks/runners/serve_recurrent.py``), driven as the
harness drives it but in this process and at a tiny size: the loader the
replica runs, an ``LLMEngine`` over what it returns, the runner's own
``check_correct`` over that engine's answers and rows.  Clean it reads true;
with the state ROUNDED to bf16 in the served path (after every decode update
and between the chunks of a prefill) or the delta rule's correction dropped,
false, and by the limit meant for it."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

from benchmarks import in_worker, in_worker_recurrent
from benchmarks.runners import serve, serve_recurrent
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.ops import gated_delta

CONFIG = {
    "family": "olmo_hybrid", "dtype": "float32", "vocab_size": 640,
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 8,
    "num_attention_heads": 6, "num_key_value_heads": 6, "head_dim": 16,
    "linear_num_key_heads": 4, "linear_num_value_heads": 4,
    "linear_key_head_dim": 16, "linear_value_head_dim": 32,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "layer_types": ["linear_attention"] * 3 + ["full_attention"]
    + ["linear_attention"] * 3 + ["full_attention"],
    "max_position_embeddings": 512, "rope_theta": 500000.0,
    "rms_norm_eps": 1e-6,
    "engine": {"max_slots": 8, "num_pages": 256, "page_size": 16,
               "max_seq_len": 512, "prefill_buckets": [256, 512]},
}
SEED = 2147484038


def _bf16(x):
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _rounded_update(state, layer, *a, **kw):
    o, state = _UPDATE(state, layer, *a, **kw)
    row = jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=True)
    return o, jax.lax.dynamic_update_slice_in_dim(state, _bf16(row), layer, 0)


def _rounded_chunked(q, k, v, g, beta, S0, chunk=gated_delta.CHUNK):
    outs, S = [], S0
    for i in range(0, q.shape[0], chunk):
        o, S = _CHUNKED(*(x[i:i + chunk] for x in (q, k, v, g, beta)),
                        _bf16(S), chunk)
        outs.append(o)
    return jnp.concatenate(outs, 0), _bf16(S)


def _uncorrected_update(state, layer, q, k, v, g, beta, active, *, pack):
    S = gated_delta.unpack_state(
        jax.lax.dynamic_index_in_dim(state, layer, 0, keepdims=False), pack)
    S2 = (S * jnp.exp(g)[..., None, None]
          + (beta[..., None] * v)[..., None] * k[:, :, None, :])
    S2 = jnp.where(active[:, None, None, None], S2, S)
    o = jnp.einsum("bhvk,bhk->bhv", S2, q, precision="highest")
    return (jnp.where(active[:, None, None], o, 0.0),
            jax.lax.dynamic_update_index_in_dim(
                state, gated_delta.pack_state(S2, pack), layer, 0))


def _uncorrected_chunked(q, k, v, g, beta, S0, chunk=gated_delta.CHUNK):
    def step(S, x):
        q, k, v, g, b = x
        S = (S * jnp.exp(g)[:, None, None]
             + (b[:, None] * v)[:, :, None] * k[:, None, :])
        return S, jnp.einsum("hvk,hk->hv", S, q, precision="highest")

    S, o = jax.lax.scan(step, S0.astype(jnp.float32), tuple(
        x.astype(jnp.float32) for x in (q, k, v, g, beta)))
    return o, S


_UPDATE, _CHUNKED = gated_delta.decode_update, gated_delta.chunked
PLANTS = {"clean": None,
          "state_rounded_to_bf16": (_rounded_update, _rounded_chunked),
          "correction_dropped": (_uncorrected_update, _uncorrected_chunked)}


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
        self.request = self.engine.submit(list(prompt), SamplingParams(
            max_tokens=max_tokens, temperature=0.0))
        out = _Handle()
        out.request = self.request
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
    import random

    import ray_tpu.serve.handle as handle_mod

    check = {**serve_recurrent.CHECK, "n_prompts": 3, "min_len": 150,
             "max_len": 200, "pad_to": 232, "min_compared": 6}
    monkeypatch.setattr(serve, "CHECK", check)
    monkeypatch.setattr(serve_recurrent, "CHECK", check)
    monkeypatch.setattr(in_worker, "CompileClock", _NoClock)
    monkeypatch.setattr(handle_mod, "DeploymentHandle", _Handle)
    engines = []

    def verdict(plant):
        if PLANTS[plant] is not None:
            update, chunked = PLANTS[plant]
            monkeypatch.setattr(gated_delta, "decode_update", update)
            monkeypatch.setattr(gated_delta, "chunked", chunked)
            jax.clear_caches()  # programs traced over the clean forms
        run_dir = str(tmp_path)
        rng = random.Random(SEED)
        stack = serve_recurrent.Stack(
            {"config_file": CONFIG}, SEED, False, run_dir)
        stack.check_prompts = [
            [rng.randrange(3, CONFIG["vocab_size"]) for _ in range(
                rng.randint(check["min_len"], check["max_len"]))]
            for _ in range(check["n_prompts"])]
        params, model_cfg = in_worker_recurrent.make_loader({
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
        monkeypatch.setattr(in_worker_recurrent, "_engine", lambda: engine)
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
    rows = v["rows"]
    # every prompt's two copies found, each in a slot of its own, after the
    # prompt and ``row_steps`` - 1 of the engine's tokens, or up to 7 more
    slots = [s for r in rows["rows"] for s in r["slots"]]
    assert len(set(slots)) == 2 * len(rows["rows"]) == 6
    n = serve_recurrent.CHECK["row_steps"]
    assert all(n - 1 <= past <= n + 6 for r in rows["rows"]
               for past in r["tokens_past_the_prompt"])
    assert rows["replays_equal"]
    # and no other slot's row comes near
    assert rows["nearest_other_rel_rms"] > 10 * v["row_rel_rms_max"]


@pytest.mark.parametrize("plant", ["state_rounded_to_bf16",
                                   "correction_dropped"])
def test_a_planted_fault_in_the_served_path_reads_not_correct(harness, plant):
    v = harness(plant)
    assert not v["ok"], v
    # the engine's own rows show it, not only the pinned recurrence
    assert v["rows"]["worst_rel_rms"] > v["row_rel_rms_max"]
    if plant == "state_rounded_to_bf16":
        # which the tokens cannot: the first comparison passes
        assert not v["mismatches"]
