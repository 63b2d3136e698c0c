"""Serve LLM + Data LLM tests (reference: python/ray/llm tests +
release/llm_tests/serve/run_llm_serve_test_and_bms.py shape)."""

import sys

import cloudpickle
import numpy as np
import pytest
import requests

# Module-level functions here (tiny_loader) ship inside configs to worker
# processes that cannot import this test module — pickle them by value.
cloudpickle.register_pickle_by_value(sys.modules[__name__])

import ray_tpu
from ray_tpu import serve
from ray_tpu.llm import (
    EngineConfig,
    LLMConfig,
    ProcessorConfig,
    build_llm_processor,
    build_openai_app,
)


def tiny_loader():
    import jax

    from ray_tpu.models import llama

    cfg = llama.LlamaConfig(
        vocab_size=259, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=512, dtype="float32", remat=False)
    return llama.init(cfg, jax.random.PRNGKey(7)), cfg


def tiny_sdar_loader():
    """A block-diffusion MoE (models/sdar_moe.py) over the byte
    tokenizer's vocabulary."""
    import jax

    from ray_tpu.models import sdar_moe

    cfg = sdar_moe.SDARMoEConfig.tiny(259, max_seq_len=512)
    return sdar_moe.init(cfg, jax.random.PRNGKey(7)), cfg


def tiny_hybrid_loader():
    """Linear-attention layers, three to every full one
    (models/olmo_hybrid.py), over the byte tokenizer's vocabulary."""
    import jax

    from ray_tpu.models import olmo_hybrid

    cfg = olmo_hybrid.OlmoHybridConfig.tiny(259)
    return olmo_hybrid.init(cfg, jax.random.PRNGKey(7)), cfg


def tiny_latent_loader():
    """Latent attention over a latent page pool, routed experts beside a
    shared one after a leading dense layer (models/glm_moe_lite.py), over
    the byte tokenizer's vocabulary."""
    import jax

    from ray_tpu.models import glm_moe_lite

    cfg = glm_moe_lite.GLMMoELiteConfig.tiny(259)
    return glm_moe_lite.init(cfg, jax.random.PRNGKey(7)), cfg


def tiny_windowed_loader():
    """Window and full attention layers over a page pool a kind, gated
    attention, routed experts beside a shared one after a leading dense
    layer (models/afmoe.py), over the byte tokenizer's vocabulary."""
    import jax

    from ray_tpu.models import afmoe

    cfg = afmoe.AfmoeConfig.tiny(259)
    return afmoe.init(cfg, jax.random.PRNGKey(7)), cfg


@pytest.fixture(scope="module", autouse=True)
def _cluster(ray_cluster):
    # join the session cluster (conftest.ray_cluster owns the
    # canonical config); never shut down here
    yield
    serve.shutdown()


def test_openai_endpoints():
    app = build_openai_app(LLMConfig(
        model_id="tiny", model_loader=tiny_loader,
        engine_config=EngineConfig(max_slots=4, num_pages=128, page_size=8,
                                   max_seq_len=256,
                                   prefill_buckets=(32, 64, 128)),
        default_max_tokens=8))
    serve.run(app, name="llm", route_prefix="/llm", _blocking_timeout_s=120)
    port = serve.http_port()
    base = f"http://127.0.0.1:{port}/llm/v1"

    r = requests.get(f"{base}/models", timeout=60)
    assert r.json()["data"][0]["id"] == "tiny"

    r = requests.post(f"{base}/completions",
                      json={"prompt": "hello", "max_tokens": 6},
                      timeout=300)
    body = r.json()
    assert body["object"] == "text_completion", body
    assert body["usage"]["completion_tokens"] <= 6
    assert isinstance(body["choices"][0]["text"], str)

    r = requests.post(f"{base}/chat/completions",
                      json={"messages": [
                          {"role": "user", "content": "hi"}],
                          "max_tokens": 4},
                      timeout=300)
    body = r.json()
    assert body["object"] == "chat.completion", body
    assert body["choices"][0]["message"]["role"] == "assistant"
    serve.delete("llm")


def test_block_diffusion_model_streams_over_http():
    """The same path, chosen by the model configuration's type alone:
    build_openai_app -> serve.run -> HTTP -> LLMServer -> LLMEngine, whose
    passes fill a block of 4 at a time; the stream carries every token, a
    sampled request is refused by name."""
    import json

    app = build_openai_app(LLMConfig(
        model_id="tiny-sdar", model_loader=tiny_sdar_loader,
        engine_config=EngineConfig(max_slots=4, num_pages=128, page_size=8,
                                   max_seq_len=256,
                                   prefill_buckets=(32, 64, 128)),
        default_max_tokens=8))
    serve.run(app, name="sdar", route_prefix="/sdar",
              _blocking_timeout_s=120)
    base = f"http://127.0.0.1:{serve.http_port()}/sdar/v1"
    body = {"prompt": [7, 8, 9, 10, 11, 12, 13], "max_tokens": 10,
            "ignore_eos": True}
    whole = requests.post(f"{base}/completions", json=body,
                          timeout=300).json()
    assert whole["usage"]["completion_tokens"] == 10, whole
    events = []
    with requests.post(f"{base}/completions", json={**body, "stream": True},
                       stream=True, timeout=300) as r:
        for line in r.iter_lines():
            if line.startswith(b"data: ") and line != b"data: [DONE]":
                events.append(json.loads(line[6:]))
    text = "".join(e["choices"][0]["text"] for e in events)
    assert text == whole["choices"][0]["text"]
    r = requests.post(f"{base}/completions",
                      json={**body, "temperature": 0.7}, timeout=300)
    assert "diffusion over blocks" in r.text
    serve.delete("sdar")


def test_hybrid_model_answers_over_http():
    """The same path again, chosen by what the model says it caches:
    build_openai_app -> serve.run -> HTTP -> LLMServer -> LLMEngine, pages
    for the full layers and a state row a slot for the linear ones; the
    stream carries what the whole answer holds, the same prompt asked
    again is computed again and answers the same, and the engine's
    counters say whose state was updated."""
    import json

    app = build_openai_app(LLMConfig(
        model_id="tiny-hybrid", model_loader=tiny_hybrid_loader,
        engine_config=EngineConfig(max_slots=4, num_pages=128, page_size=16,
                                   max_seq_len=512,
                                   prefill_buckets=(64, 128, 256)),
        default_max_tokens=8))
    serve.run(app, name="hybrid", route_prefix="/hybrid",
              _blocking_timeout_s=120)
    base = f"http://127.0.0.1:{serve.http_port()}/hybrid/v1"
    body = {"prompt": list(range(7, 157)), "max_tokens": 12,
            "ignore_eos": True}
    whole = requests.post(f"{base}/completions", json=body,
                          timeout=300).json()
    assert whole["usage"]["completion_tokens"] == 12, whole
    events = []
    with requests.post(f"{base}/completions", json={**body, "stream": True},
                       stream=True, timeout=300) as r:
        for line in r.iter_lines():
            if line.startswith(b"data: ") and line != b"data: [DONE]":
                events.append(json.loads(line[6:]))
    text = "".join(e["choices"][0]["text"] for e in events)
    assert text == whole["choices"][0]["text"]
    from ray_tpu.serve.handle import DeploymentHandle

    stats = DeploymentHandle(
        "hybrid", "LLMServer:tiny-hybrid").engine_stats.remote().result(
            timeout_s=60)
    assert stats["state_resets"] == 2 and stats["prefill_tokens_saved"] == 0
    assert stats["state_slot_steps"] >= 2 * 11 and stats["scan_chunks"] > 0
    serve.delete("hybrid")


def test_latent_model_answers_over_http():
    """The same path once more, chosen by what the model says it caches:
    build_openai_app -> serve.run -> HTTP -> LLMServer -> LLMEngine over ONE
    pool of latent rows; the answer streams, the same prompt asked again
    (twice more) is a PREFIX HIT (latent pages are pages)
    and answers the same, sampling works as for any token-at-a-time model,
    and the engine's counters say what its steps read."""
    import json

    app = build_openai_app(LLMConfig(
        model_id="tiny-latent", model_loader=tiny_latent_loader,
        engine_config=EngineConfig(max_slots=4, num_pages=128, page_size=8,
                                   max_seq_len=256,
                                   prefill_buckets=(32, 64, 128)),
        default_max_tokens=8))
    serve.run(app, name="latent", route_prefix="/latent",
              _blocking_timeout_s=120)
    base = f"http://127.0.0.1:{serve.http_port()}/latent/v1"
    body = {"prompt": list(range(7, 57)), "max_tokens": 12,
            "ignore_eos": True}
    whole = requests.post(f"{base}/completions", json=body,
                          timeout=300).json()
    assert whole["usage"]["completion_tokens"] == 12, whole
    events = []
    with requests.post(f"{base}/completions", json={**body, "stream": True},
                       stream=True, timeout=300) as r:
        for line in r.iter_lines():
            if line.startswith(b"data: ") and line != b"data: [DONE]":
                events.append(json.loads(line[6:]))
    # (not compared as text: the byte tokenizer's multi-byte sequences that
    # a chunk's end splits decode to other characters than the whole does)
    assert events and all(e["choices"][0]["text"] is not None for e in events)
    again = requests.post(f"{base}/completions", json=body,
                          timeout=300).json()
    assert again["choices"][0]["text"] == whole["choices"][0]["text"]
    sampled = requests.post(f"{base}/completions",
                            json={**body, "temperature": 0.7},
                            timeout=300).json()
    assert sampled["usage"]["completion_tokens"] == 12, sampled
    from ray_tpu.serve.handle import DeploymentHandle

    stats = DeploymentHandle(
        "latent", "LLMServer:tiny-latent").engine_stats.remote().result(
            timeout_s=60)
    assert stats["prefill_tokens_saved"] >= 3 * 48  # six pages of eight
    assert stats["latent_pages_read"] == stats["decode_pages_read"] > 0
    assert stats["experts_read"] > 0
    serve.delete("latent")


def test_windowed_model_answers_a_long_prompt_over_http():
    """The same path a fifth time, chosen by what the model says it caches:
    build_openai_app -> serve.run -> HTTP -> LLMServer -> LLMEngine over TWO
    pools.  A prompt of 150 tokens over buckets of 64 at most is computed
    in three chunks, with a window of 32 most of its window pages are given
    back before it ends, the answer streams, asked again it answers the
    same (no prefix index: computed again), sampling works as for any
    token-at-a-time model, and the counters say what the steps walked."""
    import json

    app = build_openai_app(LLMConfig(
        model_id="tiny-windowed", model_loader=tiny_windowed_loader,
        engine_config=EngineConfig(max_slots=4, num_pages=128, page_size=8,
                                   max_seq_len=256,
                                   prefill_buckets=(32, 64)),
        default_max_tokens=8))
    serve.run(app, name="windowed", route_prefix="/windowed",
              _blocking_timeout_s=120)
    base = f"http://127.0.0.1:{serve.http_port()}/windowed/v1"
    body = {"prompt": [7 + i % 200 for i in range(150)], "max_tokens": 12,
            "ignore_eos": True}
    whole = requests.post(f"{base}/completions", json=body,
                          timeout=300).json()
    assert whole["usage"]["completion_tokens"] == 12, whole
    events = []
    with requests.post(f"{base}/completions", json={**body, "stream": True},
                       stream=True, timeout=300) as r:
        for line in r.iter_lines():
            if line.startswith(b"data: ") and line != b"data: [DONE]":
                events.append(json.loads(line[6:]))
    assert events and all(e["choices"][0]["text"] is not None for e in events)
    again = requests.post(f"{base}/completions", json=body,
                          timeout=300).json()
    assert again["choices"][0]["text"] == whole["choices"][0]["text"]
    sampled = requests.post(f"{base}/completions",
                            json={**body, "temperature": 0.7},
                            timeout=300).json()
    assert sampled["usage"]["completion_tokens"] == 12, sampled
    from ray_tpu.serve.handle import DeploymentHandle

    stats = DeploymentHandle(
        "windowed", "LLMServer:tiny-windowed").engine_stats.remote().result(
            timeout_s=60)
    assert stats["prefill_chunks"] == 4 * 3 and stats["admitted"] == 4
    assert stats["prefill_tokens_saved"] == 0  # no prefix index
    assert stats["window_pages_freed"] >= 4 * 14
    assert stats["window_pages_skipped"] > stats["window_pages_read"] > 0
    assert stats["experts_read"] > 0
    assert stats["full_pages_in_use"] == stats["window_pages_in_use"] == 0
    serve.delete("windowed")


@pytest.mark.parametrize("loader", [tiny_loader, tiny_sdar_loader,
                                    tiny_hybrid_loader, tiny_latent_loader,
                                    tiny_windowed_loader])
def test_batch_processor_over_dataset(loader):
    from ray_tpu import data as rd

    processor = build_llm_processor(ProcessorConfig(
        model_loader=loader,
        engine_config=EngineConfig(max_slots=4, num_pages=128, page_size=8,
                                   max_seq_len=256,
                                   prefill_buckets=(32, 64)),
        concurrency=1, batch_size=4,
        sampling={"max_tokens": 4}))
    ds = rd.from_items([{"prompt": f"item {i}"} for i in range(8)])
    out = processor(ds).take_all()
    assert len(out) == 8
    assert all(isinstance(r["generated_text"], str) for r in out)
