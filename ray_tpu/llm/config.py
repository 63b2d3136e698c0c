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
