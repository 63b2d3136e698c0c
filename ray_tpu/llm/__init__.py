"""ray_tpu.llm: TPU-native LLM serving + batch inference.

Counterpart of the reference's Serve LLM / Data LLM
(/root/reference/python/ray/llm/): where the reference wraps vLLM, the
engine here is native — paged KV cache, bucketed prefill, one compiled
decode step, continuous batching (engine.py, model.py, paged_cache.py) —
served OpenAI-compatibly on ray_tpu.serve (server.py) and over Datasets
(batch.py).

Names resolve on first use: the configuration records and the application
builder need no JAX, so a driver that only describes a deployment never
imports it (the replica that is granted the chip does).
"""

import importlib

_HOME = {
    "ProcessorConfig": "batch", "build_llm_processor": "batch",
    "EngineConfig": "config", "SamplingParams": "config",
    "LLMEngine": "engine",
    "CacheConfig": "paged_cache", "PageAllocator": "paged_cache",
    "DecodeServer": "pd_disagg", "PDRouter": "pd_disagg",
    "PrefillServer": "pd_disagg", "build_pd_openai_app": "pd_disagg",
    "LLMConfig": "server", "LLMServer": "server",
    "build_openai_app": "server",
    "ByteTokenizer": "tokenizer", "get_tokenizer": "tokenizer",
}

__all__ = sorted(_HOME)


def __getattr__(name: str):
    home = _HOME.get(name)
    if home is None:
        raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
    value = getattr(importlib.import_module(f"{__name__}.{home}"), name)
    globals()[name] = value
    return value
