"""Plain configuration records of the LLM stack.

Kept apart from the engine so that a driver can describe an application
(``LLMConfig``, ``build_openai_app``) without importing JAX: the process
that describes a deployment is not the one that is granted its chip.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional


@dataclass
class EngineConfig:
    max_slots: int = 8  # concurrent sequences in the decode batch
    num_pages: int = 512
    page_size: int = 16
    max_seq_len: int = 1024
    prefill_buckets: tuple = (32, 64, 128, 256, 512, 1024)
    # pages of EACH window layer's pool, for a model with window layers
    # (paged_cache.py); 0: max_slots x (window // page_size + 4), a live
    # slot's window and a burst, and at least what one prompt in chunks
    # holds at once
    window_pages: int = 0

    def bucket_for(self, n: int) -> int:
        for b in self.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(f"prompt length {n} exceeds largest bucket "
                         f"{self.prefill_buckets[-1]}")


@dataclass
class SamplingParams:
    max_tokens: int = 64
    temperature: float = 0.0  # 0 => greedy
    top_p: float = 1.0
    stop_token_ids: tuple = ()
    seed: Optional[int] = None
