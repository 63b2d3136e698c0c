"""Serve streaming + ASGI: generator deployments, SSE token streaming,
raw-ASGI ingress (reference: serve/_private/proxy.py:709 streaming,
replica.py ASGI wrapper, @serve.ingress)."""

import json
import queue
import sys
import threading
import time
import types

import cloudpickle
import pytest
import requests

import ray_tpu
from ray_tpu import serve

# _ScriptedEngine ships inside a deployment to a replica process that
# cannot import this test module: pickle it by value.
cloudpickle.register_pickle_by_value(sys.modules[__name__])


@pytest.fixture(scope="module", autouse=True)
def cluster(ray_cluster):
    yield ray_cluster
    serve.shutdown()


def test_generator_deployment_streams(cluster):
    @serve.deployment
    class Streamer:
        def __call__(self, n):
            def gen():
                for i in range(int(n)):
                    yield f"chunk-{i};"
            return serve.StreamingResponse(gen(), content_type="text/plain")

    serve.run(Streamer.bind(), name="streamer", route_prefix="/stream")
    port = serve.http_port()
    r = requests.post(f"http://127.0.0.1:{port}/stream", json=5, timeout=60,
                      stream=True)
    assert r.status_code == 200
    body = b"".join(r.iter_content(64)).decode()
    assert body == "".join(f"chunk-{i};" for i in range(5))
    serve.delete("streamer")


def test_bare_generator_and_incremental_delivery(cluster):
    @serve.deployment
    class Slow:
        def __call__(self, arg=None):
            def gen():
                for i in range(3):
                    time.sleep(0.3)
                    yield f"t{i}|"
            return gen()  # bare generators stream too

    serve.run(Slow.bind(), name="slowgen", route_prefix="/slow")
    port = serve.http_port()
    t0 = time.monotonic()
    first_at = None
    chunks = []
    with requests.post(f"http://127.0.0.1:{port}/slow", timeout=60,
                       stream=True) as r:
        for chunk in r.iter_content(16):
            if first_at is None:
                first_at = time.monotonic() - t0
            chunks.append(chunk.decode())
    total = time.monotonic() - t0
    assert "".join(chunks) == "t0|t1|t2|"
    # the first chunk must arrive well before the stream completes —
    # i.e. delivery is incremental, not buffered
    assert first_at < total - 0.25, (first_at, total)
    serve.delete("slowgen")


def test_asgi_ingress(cluster):
    async def asgi_app(scope, receive, send):
        assert scope["type"] == "http"
        msg = await receive()
        body = msg.get("body", b"")
        if scope["path"].endswith("/echo"):
            payload = json.dumps({
                "path": scope["path"], "method": scope["method"],
                "echo": json.loads(body) if body else None,
                "q": scope["query_string"].decode()}).encode()
            status = 200
        else:
            payload, status = b"nope", 404
        await send({"type": "http.response.start", "status": status,
                    "headers": [(b"content-type", b"application/json"),
                                (b"x-served-by", b"asgi")]})
        await send({"type": "http.response.body", "body": payload})

    App = serve.deployment(serve.ingress(asgi_app))
    serve.run(App.bind(), name="asgiapp", route_prefix="/api")
    port = serve.http_port()
    r = requests.post(f"http://127.0.0.1:{port}/api/echo?who=me",
                      json={"x": 1}, timeout=60)
    assert r.status_code == 200
    assert r.headers.get("x-served-by") == "asgi"
    data = r.json()
    assert data["echo"] == {"x": 1}
    assert data["path"] == "/echo"
    assert data["q"] == "who=me"
    r2 = requests.get(f"http://127.0.0.1:{port}/api/missing", timeout=60)
    assert r2.status_code == 404
    serve.delete("asgiapp")


def test_openai_sse_token_streaming(cluster):
    """/v1/chat/completions with stream:true yields SSE chunks end to end
    (proxy -> router -> LLMServer replica -> engine token queues)."""
    from ray_tpu.llm.server import LLMConfig, build_openai_app
    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.models import llama

    def loader():
        import jax

        cfg = llama.LlamaConfig.tiny(vocab_size=384)
        return llama.init(cfg, jax.random.PRNGKey(0)), cfg

    app = build_openai_app(LLMConfig(
        model_id="tiny", model_loader=loader,
        engine_config=EngineConfig(max_slots=2, num_pages=64, page_size=8,
                                   max_seq_len=128,
                                   prefill_buckets=(16, 32, 64)),
        default_max_tokens=8))
    serve.run(app, name="llm", route_prefix="/llm",
              _blocking_timeout_s=240.0)
    port = serve.http_port()
    with requests.post(
            f"http://127.0.0.1:{port}/llm/v1/chat/completions",
            json={"messages": [{"role": "user", "content": "hi"}],
                  "max_tokens": 4, "stream": True},
            timeout=240, stream=True) as r:
        assert r.status_code == 200
        assert "text/event-stream" in r.headers.get("Content-Type", "")
        events = []
        for line in r.iter_lines():
            if line:
                events.append(line.decode())
    assert events[-1] == "data: [DONE]"
    payloads = [json.loads(e[len("data: "):]) for e in events[:-1]]
    # role preamble + >=1 content delta + finish chunk
    assert payloads[0]["choices"][0]["delta"].get("role") == "assistant"
    assert any(p["choices"][0]["delta"].get("content")
               for p in payloads[1:-1])
    assert payloads[-1]["choices"][0]["finish_reason"] in ("stop", "length")
    assert all(p["object"] == "chat.completion.chunk" for p in payloads)
    serve.delete("llm")


# ------------- the SSE producer: one chunk per pull, every ready token -----

class _ScriptedEngine:
    """The part of LLMEngine that LLMServer's stream path touches.  It
    takes the place of `params` and `model_cfg` with a script (tokens, then
    None or an Exception) and a gap: every request's out_queue gets the
    script, whole before submit returns or one item every `gap_s`."""

    def __init__(self, script, gap_s, engine_config, kv_tier=None):
        self._script, self._gap_s = tuple(script), gap_s
        self._thread = None
        self._stop = threading.Event()
        self._put_at = []  # wall clock of each put, for engine_stats()

    def start(self):
        pass

    def submit(self, tokens, params):
        req = types.SimpleNamespace(out_queue=queue.Queue())

        def produce():
            for item in self._script:
                time.sleep(self._gap_s)
                self._put_at.append(time.time())
                req.out_queue.put(item)

        if self._gap_s:
            threading.Thread(target=produce, daemon=True).start()
        else:
            produce()
        return req

    def stats(self):
        return {"put_at": list(self._put_at)}


def _scripted_server(script, gap_s=0.0):
    """An LLMServer over a _ScriptedEngine, in whatever process calls."""
    import ray_tpu.llm.engine as engine_mod
    from ray_tpu.llm.server import LLMConfig, LLMServer

    real = engine_mod.LLMEngine
    engine_mod.LLMEngine = _ScriptedEngine
    try:
        return LLMServer(LLMConfig(model_id="scripted",
                                   model_loader=lambda: (script, gap_s)))
    finally:
        engine_mod.LLMEngine = real


_CREATED = 1_700_000_000


def _per_token_framing(script, *, chat, max_tokens, rid, model):
    """The byte stream as the server framed it when every token was a
    chunk of its own (PR 25's _sse_stream), written out again here."""
    from ray_tpu.llm.tokenizer import get_tokenizer

    tok = get_tokenizer(None)
    obj = "chat.completion.chunk" if chat else "text_completion"

    def event(choice):
        return "data: " + json.dumps(
            {"id": rid, "object": obj, "created": _CREATED, "model": model,
             "choices": [choice]}) + "\n\n"

    out = []
    if chat:
        out.append(event({"index": 0, "delta": {"role": "assistant"},
                          "finish_reason": None}))
    n = 0
    for item in script:
        if isinstance(item, Exception):
            out.append("data: " + json.dumps(
                {"error": {"message": str(item)}}) + "\n\n")
            break
        if item is None:
            delta = {"delta": {}} if chat else {"text": ""}
            out.append(event({
                "index": 0, **delta,
                "finish_reason": "length" if n >= max_tokens else "stop"}))
            break
        n += 1
        piece = tok.decode([item])
        payload = {"delta": {"content": piece}} if chat else {"text": piece}
        out.append(event({"index": 0, **payload, "finish_reason": None}))
    out.append("data: [DONE]\n\n")
    return out


def _letters(k):
    return [3 + ord("a") + i % 26 for i in range(k)]


@pytest.mark.parametrize("script,max_tokens,chat", [
    pytest.param(_letters(1) + [None], 1, False, id="k1"),
    pytest.param(_letters(8) + [None], 8, False, id="k8"),
    pytest.param(_letters(40) + [None], 40, False, id="k40"),
    pytest.param(_letters(8) + [None], 8, True, id="k8-chat"),
    pytest.param(_letters(3) + [RuntimeError("boom")], 8, False,
                 id="error-after-3"),
    pytest.param(_letters(5) + [None], 64, False, id="stop-before-max"),
])
def test_sse_chunk_carries_every_ready_token(monkeypatch, script,
                                             max_tokens, chat):
    """A preloaded out_queue leaves in ONE chunk, and the bytes are the
    per-token framing's: only their cut into chunks differs."""
    from ray_tpu.llm import server as server_mod
    from ray_tpu.llm.config import SamplingParams

    monkeypatch.setenv("RTPU_KV_TIER", "0")
    monkeypatch.setattr(server_mod, "time", types.SimpleNamespace(
        time=lambda: _CREATED + 0.5, monotonic=time.monotonic))
    srv = _scripted_server(script)
    chunks = list(srv._sse_stream(
        [1], SamplingParams(max_tokens=max_tokens), "cmpl-x", "scripted",
        chat=chat))
    want = _per_token_framing(script, chat=chat, max_tokens=max_tokens,
                              rid="cmpl-x", model="scripted")
    assert "".join(chunks) == "".join(want)
    k = sum(isinstance(t, int) for t in script)
    if chat:  # the role preamble goes out before any token is waited for
        assert chunks.pop(0) == want.pop(0)
    # every token event, then the finish event (or the framed error) and
    # [DONE], in the one chunk that the one pull found ready
    assert chunks == ["".join(want)]
    final = want[k]
    if isinstance(script[-1], Exception):
        assert json.loads(final[len("data: "):]) == {
            "error": {"message": "boom"}}
    else:
        reason = json.loads(final[len("data: "):])["choices"][0][
            "finish_reason"]
        assert reason == ("stop" if k < max_tokens else "length")
    # k events left in one chunk: events exceed chunks wherever k > 1
    stats = srv.engine_stats()
    assert (stats["stream_events"], stats["stream_chunks"]) == (k, 1)


def test_sse_stream_counters_under_concurrent_streams(monkeypatch):
    """Streams run on the proxy's pull threads, several at once: no
    update of the server's two counters may be lost."""
    from ray_tpu.llm.config import SamplingParams

    monkeypatch.setenv("RTPU_KV_TIER", "0")
    srv = _scripted_server(_letters(1) + [None])
    streams, each = 16, 150

    def consume():
        for _ in range(each):
            for _ in srv._sse_stream([1], SamplingParams(max_tokens=1),
                                     "cmpl-x", "scripted", chat=False):
                pass

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=consume) for _ in range(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    stats = srv.engine_stats()
    assert stats["stream_events"] == stats["stream_chunks"] == streams * each
    # the same counts ride the metrics plane, over every server of the
    # process (so at least this one's)
    from ray_tpu.util import metrics

    pushed = {m["name"]: sum(m["values"].values())
              for m in metrics.snapshot() if m["kind"] == "counter"}
    assert pushed["llm_stream_events_total"] >= streams * each
    assert pushed["llm_stream_chunks_total"] >= streams * each


def test_sse_paced_tokens_are_not_held_back(cluster):
    """A token every half second: each leaves alone, and the first
    reaches the HTTP client before the engine has made the second (the
    drain never waits)."""
    gap_s, script = 0.5, _letters(3) + [None]

    @serve.deployment
    class Paced:
        def __init__(self):
            self._srv = _scripted_server(script, gap_s)

        def __call__(self, body):
            return self._srv.completions_stream(body)

        def engine_stats(self):
            return self._srv.engine_stats()

    serve.run(Paced.bind(), name="paced", route_prefix="/paced",
              _blocking_timeout_s=240.0)
    port = serve.http_port()
    seen_at = []
    with requests.post(f"http://127.0.0.1:{port}/paced",
                       json={"prompt": "hi", "max_tokens": 3},
                       timeout=240, stream=True) as r:
        assert r.status_code == 200
        assert "text/event-stream" in r.headers.get("Content-Type", "")
        events = []
        for line in r.iter_lines():
            if line:
                seen_at.append(time.time())
                events.append(line.decode())
    assert events[-1] == "data: [DONE]"
    payloads = [json.loads(e[len("data: "):]) for e in events[:-1]]
    assert [p["choices"][0]["text"] for p in payloads] == ["a", "b", "c", ""]
    assert payloads[-1]["choices"][0]["finish_reason"] == "length"
    stats = serve.get_app_handle("paced").engine_stats.remote().result(
        timeout_s=60)
    put_at = stats["put_at"]
    assert len(put_at) == 4
    # same host, same wall clock: the client had the first event in hand
    # before the producer put the second token
    assert put_at[0] <= seen_at[0] < put_at[1], (put_at, seen_at)
    assert stats["stream_events"] == stats["stream_chunks"] == 3
    serve.delete("paced")
