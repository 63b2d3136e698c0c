"""Prefill/decode disaggregation + multiplexing + prefix routing.

The core invariant: a PD-split generation must produce EXACTLY the tokens a
single engine would (the KV handoff is lossless). Mirrors the reference's
prefill_decode_disagg tests in shape.
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")

from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams  # noqa: E402
from ray_tpu.models import llama  # noqa: E402


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, dtype="float32", remat=False)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return params, cfg


def _engine(tiny_model):
    params, cfg = tiny_model
    return LLMEngine(params, cfg, EngineConfig(
        max_slots=4, num_pages=64, page_size=8, max_seq_len=256,
        prefill_buckets=(16, 32, 64, 128)))


def test_pd_handoff_matches_single_engine(tiny_model):
    prompt = [1, 17, 42, 99, 5, 23, 77]
    sp = SamplingParams(max_tokens=12, temperature=0.0)

    single = _engine(tiny_model)
    expected = single.generate(list(prompt), sp)
    single.stop()

    prefill_engine = _engine(tiny_model)
    decode_engine = _engine(tiny_model)
    first, kv_k, kv_v, n = prefill_engine.prefill_extract(list(prompt), sp)
    assert n == len(prompt)
    assert first == expected[0]
    req = decode_engine.submit_with_kv(list(prompt), first, kv_k, kv_v, sp)
    toks = [first]
    while True:
        item = req.out_queue.get(timeout=120)
        if item is None:
            break
        if isinstance(item, Exception):
            raise item
        toks.append(item)
    assert toks == expected, (toks, expected)
    prefill_engine.stop()
    decode_engine.stop()


@pytest.mark.parametrize("n", [33, 75, 96])
def test_pd_prefill_of_a_prompt_over_the_largest_bucket_is_the_whole_one(
        tiny_model, n):
    """A prompt over the largest bucket is prefilled in chunks before its
    pages ship: first token and every shipped row as one program leaves
    them, and a later prompt that hits the registered pages answers as an
    engine that never saw them (one chunk alone would ship, and index,
    rows nobody wrote)."""
    params, cfg = tiny_model
    prompt = [int(t) for t in
              np.random.default_rng(n).integers(1, 128, size=n)]
    sp = SamplingParams(max_tokens=6, temperature=0.0)
    whole = _engine(tiny_model)
    chunks = LLMEngine(params, cfg, EngineConfig(
        max_slots=4, num_pages=64, page_size=8, max_seq_len=256,
        prefill_buckets=(16, 32)))
    try:
        first, kv_k, kv_v, _ = whole.prefill_extract(list(prompt), sp)
        got, got_k, got_v, m = chunks.prefill_extract(list(prompt), sp)
        assert m == n and got == first
        assert chunks.stats()["prefill_chunks"] == -(-n // 32)
        rows = np.arange(kv_k.shape[1] * 8) < n  # the last page's tail: any
        for a, b in ((kv_k, got_k), (kv_v, got_v)):
            a = a.reshape(a.shape[0], -1, *a.shape[3:])[:, rows]
            b = b.reshape(b.shape[0], -1, *b.shape[3:])[:, rows]
            np.testing.assert_allclose(b, a, rtol=2e-5, atol=2e-5)
        # the registered pages, hit by the same prompt with a tail
        again = prompt + [3, 4, 5]
        expected = whole.generate(list(again), sp)
        assert chunks.generate(list(again), sp) == expected
        assert chunks.stats()["prefill_tokens_saved"] >= n - n % 8
    finally:
        whole.stop()
        chunks.stop()


def test_pd_serve_app(ray_cluster, tiny_model):
    import ray_tpu.serve as serve
    from ray_tpu.llm import LLMConfig, build_pd_openai_app

    params, cfg = tiny_model

    def loader(params=params, cfg=cfg):
        return params, cfg

    llm_config = LLMConfig(
        model_id="tiny-pd", model_loader=loader,
        engine_config=EngineConfig(max_slots=4, num_pages=64, page_size=8,
                                   max_seq_len=256,
                                   prefill_buckets=(16, 32, 64, 128)),
        default_max_tokens=8)
    app = build_pd_openai_app(llm_config)
    serve.run(app, name="pd_app", route_prefix="/pd")
    try:
        handle = serve.get_app_handle("pd_app")
        resp = handle.handle_http.remote({
            "path": "/v1/completions",
            "body": {"prompt": "hello", "max_tokens": 6},
        }).result(timeout_s=300)
        assert resp["object"] == "text_completion"
        assert resp["usage"]["completion_tokens"] >= 1
        assert isinstance(resp["choices"][0]["text"], str)
    finally:
        serve.delete("pd_app")


def test_multiplexed_lru(ray_cluster):
    import ray_tpu.serve as serve

    @serve.deployment
    class Adapters:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id: str):
            self.loads.append(model_id)
            return f"model::{model_id}"

        def __call__(self, _body=None):
            mid = serve.get_multiplexed_model_id()
            return {"model": self.get_model(mid), "loads": list(self.loads)}

    serve.run(Adapters.bind(), name="mux_app", route_prefix="/mux")
    try:
        h = serve.get_app_handle("mux_app")
        r1 = h.options(multiplexed_model_id="a").remote().result(
            timeout_s=60)
        assert r1["model"] == "model::a"
        h.options(multiplexed_model_id="b").remote().result(timeout_s=60)
        # "a" again: cached, no new load
        r3 = h.options(multiplexed_model_id="a").remote().result(
            timeout_s=60)
        assert r3["loads"].count("a") == 1
        # "c" evicts LRU ("b"); "b" again must reload
        h.options(multiplexed_model_id="c").remote().result(timeout_s=60)
        r5 = h.options(multiplexed_model_id="b").remote().result(
            timeout_s=60)
        assert r5["loads"].count("b") == 2
    finally:
        serve.delete("mux_app")


def test_prefix_affinity_routing(ray_cluster):
    import ray_tpu.serve as serve

    # hint stickiness moved from the old per-handle hash into the
    # prefix_aware router policy; the default pow2 ignores hints
    @serve.deployment(num_replicas=2, request_router_policy="prefix_aware")
    class Echo:
        def __init__(self):
            import os
            self.pid = os.getpid()

        def __call__(self, _body=None):
            return self.pid

    serve.run(Echo.bind(), name="aff_app", route_prefix="/aff")
    try:
        h = serve.get_app_handle("aff_app")
        pids = {h.options(routing_hint="prefix-X").remote().result(
            timeout_s=60) for _ in range(6)}
        # same hint -> same replica every time
        assert len(pids) == 1
        other = {h.options(routing_hint=f"h{i}").remote().result(
            timeout_s=60) for i in range(8)}
        assert len(other) >= 1  # smoke: different hints spread or not
    finally:
        serve.delete("aff_app")
