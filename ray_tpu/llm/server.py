"""OpenAI-compatible LLM serving on ray_tpu.serve.

Counterpart of the reference's Serve LLM stack
(/root/reference/python/ray/llm/_internal/serve/deployments/llm/
llm_server.py:410 LLMServer, configs/openai_api_models.py router,
builders/application_builders.py build_openai_app): an LLMServer deployment
owns a continuous-batching engine (llm/engine.py); the path-aware ingress
implements /v1/completions, /v1/chat/completions, and /v1/models.
"""

from __future__ import annotations

import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

from ray_tpu import serve
from ray_tpu.llm import kv_tier as kv_tier_mod
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.tokenizer import get_tokenizer


@dataclass
class LLMConfig:
    """Reference: llm/_internal/serve/configs/server_models.py LLMConfig
    (model_loading_config + engine_kwargs + deployment_config)."""

    model_id: str = "llama-tiny"
    # callable returning (params, model config) — checkpoint loading hook;
    # the engine dispatches on the configuration (models/llama.py,
    # models/sdar_moe.py)
    model_loader: Optional[Callable] = None
    tokenizer: Optional[str] = None  # None/"byte" or HF name
    engine_config: EngineConfig = field(default_factory=EngineConfig)
    num_replicas: int = 1
    ray_actor_options: Dict[str, Any] = field(default_factory=dict)
    default_max_tokens: int = 64


# Shared by every LLMServer of the process, like the engine's instruments
# (llm/engine.py _engine_metrics); engine_stats() carries each server's own.
_METRICS = None
_metrics_lock = threading.Lock()


def _stream_metrics():
    global _METRICS
    with _metrics_lock:
        if _METRICS is None:
            from ray_tpu.util.metrics import Counter

            _METRICS = {
                "stream_events": Counter(
                    "llm_stream_events_total", "SSE token events yielded "
                    "to streaming callers"),
                "stream_chunks": Counter(
                    "llm_stream_chunks_total", "Stream chunks (one proxy "
                    "pull each) that carried at least one token event; "
                    "events / chunks is how many tokens a pull moves"),
            }
        return _METRICS


class LLMServer:
    """The engine-owning deployment (one engine per replica)."""

    def __init__(self, llm_config: LLMConfig):
        # JAX comes in here, in the replica that holds the chip, and not
        # with the module: a driver describes the app without it
        from ray_tpu.llm.engine import LLMEngine

        self._config = llm_config
        if llm_config.model_loader is None:
            raise ValueError("LLMConfig.model_loader is required")
        params, model_cfg = llm_config.model_loader()
        self._tok = get_tokenizer(llm_config.tokenizer)
        # Store-backed KV tier (ISSUE 16): in a ray_tpu worker the engine
        # seals hot family spines into the shm store and pulls them back
        # on sheds/failover instead of cold-prefilling.
        self._tier = kv_tier_mod.default_tier()
        self._engine = LLMEngine(params, model_cfg,
                                 llm_config.engine_config,
                                 kv_tier=self._tier)
        self._engine.start()
        # streams run on the proxy's pull threads, several at once
        self._m = _stream_metrics()
        self._stream_lock = threading.Lock()
        self._stream_events = 0
        self._stream_chunks = 0
        if self._tier is not None:
            # Warm restart: a replica the controller just restarted (or a
            # fresh scale-up) re-hydrates the cluster's hottest families
            # from the store before traffic arrives, instead of starting
            # from zero hits.  Best-effort and async (scheduler thread
            # drains the queue); an empty directory is a no-op.
            try:
                roots = self._tier.hottest(8)
            except Exception:  # noqa: BLE001
                roots = []
            # (an engine over recurrent layers took no tier: it has no
            # pages a spine could be pulled into, and refuses by name)
            if roots and self._engine.kv_tier is not None:
                self._engine.kv_prehydrate(roots)

    def _params_from(self, body: dict) -> SamplingParams:
        stop_ids = tuple(body.get("stop_token_ids", ()))
        eos = getattr(self._tok, "eos_id", None)
        if eos is not None and not body.get("ignore_eos"):
            stop_ids = stop_ids + (eos,)
        return SamplingParams(
            max_tokens=int(body.get("max_tokens",
                                    self._config.default_max_tokens)),
            temperature=float(body.get("temperature", 0.0)),
            top_p=float(body.get("top_p", 1.0)),
            stop_token_ids=stop_ids,
            seed=body.get("seed"))

    def _encode_prompt(self, prompt) -> List[int]:
        return (list(prompt) if isinstance(prompt, list)
                and prompt and isinstance(prompt[0], int)
                else self._tok.encode(str(prompt)))

    def _sse_stream(self, tokens: List[int], params: SamplingParams,
                    rid: str, model: str, chat: bool, trace_ctx=None):
        """Token stream -> OpenAI SSE chunks (reference gets this from
        vLLM; the engine already streams per-request token queues).

        A yielded chunk is one pull of the proxy (one actor round trip,
        serve/proxy.py stream_to_client), and pulls are what that path
        runs out of, not events.  So a chunk carries the events of every
        token the engine had emitted when the pull arrived: it waits for
        one and never for a second."""
        import json as _json
        import queue as _queue

        from ray_tpu.util import tracing

        obj = "chat.completion.chunk" if chat else "text_completion"

        def event(choice: dict) -> str:
            return "data: " + _json.dumps(
                {"id": rid, "object": obj, "created": int(time.time()),
                 "model": model, "choices": [{"index": 0, **choice}]}
            ) + "\n\n"

        def error(message: str) -> str:
            return "data: " + _json.dumps(
                {"error": {"message": message}}) + "\n\n"

        try:
            # the generator body runs lazily on the proxy's pull thread,
            # where the registration-time task span is long gone: restore
            # the captured context so the engine request parents correctly
            with tracing.use_context(trace_ctx):
                req = self._engine.submit(tokens, params)
        except Exception as e:  # frame submit rejections as SSE errors
            yield error(f"{type(e).__name__}: {e}")
            yield "data: [DONE]\n\n"
            return
        if chat:
            yield event({"delta": {"role": "assistant"},
                         "finish_reason": None})
        n = 0
        deadline = time.monotonic() + 600.0
        while True:
            try:
                # bounded waits: a dead engine loop pushes no terminator,
                # and a stream must never hang its replica pull thread
                ready = [req.out_queue.get(timeout=5.0)]
            except _queue.Empty:
                thread = self._engine._thread
                if ((thread is not None and not thread.is_alive()
                     and not self._engine._stop.is_set())
                        or time.monotonic() > deadline):
                    yield error("engine stopped mid-stream") \
                        + "data: [DONE]\n\n"
                    return
                continue
            try:
                while True:
                    ready.append(req.out_queue.get_nowait())
            except _queue.Empty:
                pass
            out = []
            last = None  # the event that ends the stream, once drained
            for tok in ready:
                if isinstance(tok, Exception):
                    last = error(str(tok))
                    break
                if tok is None:
                    last = event({
                        **({"delta": {}} if chat else {"text": ""}),
                        "finish_reason": ("length" if n >= params.max_tokens
                                          else "stop")})
                    break
                n += 1
                piece = self._tok.decode([tok])
                out.append(event({
                    **({"delta": {"content": piece}} if chat
                       else {"text": piece}),
                    "finish_reason": None}))
            if out:
                self._m["stream_events"].inc(len(out))
                self._m["stream_chunks"].inc()
                with self._stream_lock:
                    self._stream_events += len(out)
                    self._stream_chunks += 1
            if last is not None:
                yield "".join(out) + last + "data: [DONE]\n\n"
                return
            yield "".join(out)

    def completions_stream(self, body: dict):
        from ray_tpu.serve import StreamingResponse
        from ray_tpu.util import tracing

        tokens = self._encode_prompt(body.get("prompt", ""))
        return StreamingResponse(
            self._sse_stream(tokens, self._params_from(body),
                             f"cmpl-{uuid.uuid4().hex[:24]}",
                             body.get("model", self._config.model_id),
                             chat=False,
                             trace_ctx=tracing.current_context()),
            content_type="text/event-stream")

    def chat_stream(self, body: dict):
        from ray_tpu.serve import StreamingResponse
        from ray_tpu.util import tracing

        prompt = self._tok.apply_chat_template(body.get("messages", []))
        return StreamingResponse(
            self._sse_stream(self._tok.encode(prompt),
                             self._params_from(body),
                             f"chatcmpl-{uuid.uuid4().hex[:24]}",
                             body.get("model", self._config.model_id),
                             chat=True,
                             trace_ctx=tracing.current_context()),
            content_type="text/event-stream")

    def completions(self, body: dict) -> dict:
        prompt = body.get("prompt", "")
        tokens = self._encode_prompt(prompt)
        params = self._params_from(body)
        out = self._engine.generate(tokens, params)
        text = self._tok.decode(out)
        return {
            "id": f"cmpl-{uuid.uuid4().hex[:24]}",
            "object": "text_completion",
            "created": int(time.time()),
            "model": body.get("model", self._config.model_id),
            "choices": [{"index": 0, "text": text,
                         "finish_reason": "stop"
                         if len(out) < params.max_tokens else "length"}],
            "usage": {"prompt_tokens": len(tokens),
                      "completion_tokens": len(out),
                      "total_tokens": len(tokens) + len(out)},
        }

    def chat(self, body: dict) -> dict:
        messages = body.get("messages", [])
        prompt = self._tok.apply_chat_template(messages)
        tokens = self._tok.encode(prompt)
        params = self._params_from(body)
        out = self._engine.generate(tokens, params)
        text = self._tok.decode(out)
        return {
            "id": f"chatcmpl-{uuid.uuid4().hex[:24]}",
            "object": "chat.completion",
            "created": int(time.time()),
            "model": body.get("model", self._config.model_id),
            "choices": [{"index": 0,
                         "message": {"role": "assistant", "content": text},
                         "finish_reason": "stop"
                         if len(out) < params.max_tokens else "length"}],
            "usage": {"prompt_tokens": len(tokens),
                      "completion_tokens": len(out),
                      "total_tokens": len(tokens) + len(out)},
        }

    def generate_tokens(self, prompt_tokens: List[int],
                        **params) -> List[int]:
        """Raw token API (used by data-plane batch inference)."""
        return self._engine.generate(list(prompt_tokens),
                                     SamplingParams(**params))

    def engine_stats(self) -> dict:
        """The engine's counters and, beside them, this server's stream
        counters: `stream_events` token events left in `stream_chunks`
        chunks, so events / chunks is what one pull of the proxy moves."""
        with self._stream_lock:
            streams = {"stream_events": self._stream_events,
                       "stream_chunks": self._stream_chunks}
        return {**self._engine.stats(), **streams}

    def kv_prehydrate(self, roots) -> int:
        """Controller KV replication fan-out: pull these family spines
        from the store tier (no-op without a tier)."""
        roots = list(roots)
        self._engine.kv_prehydrate(roots)
        return len(roots)

    def check_health(self):
        if self._engine._thread is not None \
                and not self._engine._thread.is_alive() \
                and not self._engine._stop.is_set():
            raise RuntimeError("engine loop died")


class OpenAIRouter:
    """Path-aware ingress translating OpenAI REST to LLMServer calls
    (reference: configs/openai_api_models.py OpenAI router deployment)."""

    def __init__(self, server_handle, model_id: str):
        self._server = server_handle
        self._model_id = model_id

    @staticmethod
    def _hint(body: dict, chat: bool) -> Optional[str]:
        """Routing hint for the prefix-aware router: the raw prompt text
        prefix (char-ngram keyed tree — no tokenizer needed here).  Chat
        requests hint on the concatenated message contents, so multi-turn
        conversations sharing a history keep landing on the replica whose
        engine holds their KV pages."""
        if chat:
            parts = []
            for m in body.get("messages", []) or []:
                parts.append(str(m.get("role", "")))
                parts.append(str(m.get("content", "")))
            text = "\x1f".join(parts)
        else:
            prompt = body.get("prompt", "")
            if isinstance(prompt, list):
                prompt = ",".join(str(t) for t in prompt)
            text = str(prompt)
        return text[:512] or None

    def handle_http(self, request: dict):
        path = request.get("path", "/")
        body = request.get("body") or {}
        if path.endswith("/v1/models") or path == "/models":
            return {"object": "list",
                    "data": [{"id": self._model_id, "object": "model"}]}
        # Trace root for the serving anatomy (ISSUE 20): every request
        # that survives RTPU_TRACE_SAMPLE renders as one connected tree —
        # openai.request -> serve.route -> replica task -> llm.request
        # (queue / kv_pull / prefill / decode phase spans under it).
        from ray_tpu.util import tracing

        if path.endswith("/chat/completions"):
            with tracing.serving_span("openai.request", path=path,
                                      stream=bool(body.get("stream"))):
                h = self._server.options(
                    routing_hint=self._hint(body, True))
                if body.get("stream"):
                    # the stream marker passes through untouched: the proxy
                    # pulls SSE chunks straight from the LLMServer replica
                    return h.chat_stream.remote(body).result(timeout_s=300)
                return h.chat.remote(body).result(timeout_s=300)
        if path.endswith("/completions"):
            with tracing.serving_span("openai.request", path=path,
                                      stream=bool(body.get("stream"))):
                h = self._server.options(
                    routing_hint=self._hint(body, False))
                if body.get("stream"):
                    return h.completions_stream.remote(body).result(
                        timeout_s=300)
                return h.completions.remote(body).result(timeout_s=300)
        return {"error": f"unknown endpoint {path}"}


def build_openai_app(llm_config: LLMConfig) -> serve.Application:
    """Reference: builders/application_builders.py build_openai_app."""
    server = serve.deployment(LLMServer).options(
        name=f"LLMServer:{llm_config.model_id}",
        num_replicas=llm_config.num_replicas,
        ray_actor_options=llm_config.ray_actor_options,
        max_ongoing_requests=llm_config.engine_config.max_slots * 2,
        # KV-locality routing: keep shared prompt prefixes (system prompts,
        # multi-turn histories) on the replica holding their warm pages
        request_router_policy="prefix_aware",
    ).bind(llm_config)
    router = serve.deployment(OpenAIRouter).options(
        name="OpenAIRouter").bind(server, llm_config.model_id)
    return router
