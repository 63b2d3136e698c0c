"""A fine-tuning job's input: documents of lognormal length, concatenated
and cut into fixed-length sequences (packing).  Causal attention runs
across document boundaries: the flash kernel has no segment mask.

Parameters: ``doc`` {median, sigma, min, max}; ``seq_len``;
``global_batch_tokens``; ``shards`` x ``seqs_per_shard`` sequences are made
by ``ray_tpu.data`` map tasks, shard ``i`` a pure function of (seed, i).
The train loop takes batches of ``global_batch_tokens / seq_len`` rows.
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators import _common as g

RUNNER = "train"


def pack_shard(p: dict, seed: int, shard: int, vocab: int) -> np.ndarray:
    """``seqs_per_shard`` rows of ``seq_len + 1`` token ids (inputs and the
    shifted targets come from the same row)."""
    r = g.rng(seed, 1000 + int(shard))
    row, rows = int(p["seq_len"]) + 1, int(p["seqs_per_shard"])
    need = row * rows
    parts, have = [], 0
    while have < need:
        n = int(g.lognormal_clipped(r, p["doc"]))
        parts.append(r.integers(g.FIRST_TOKEN_ID, vocab, n, dtype=np.int32))
        have += n
    return np.concatenate(parts)[:need].reshape(rows, row)


def generate(p: dict, seed: int, seconds: float, engine: dict,
             vocab: int) -> dict:
    if p["global_batch_tokens"] % p["seq_len"]:
        raise ValueError("global_batch_tokens must be whole sequences")
    return {"mode": "train", "seed": int(seed), "vocab": int(vocab),
            "params": p, "shards": int(p["shards"]),
            "batch_rows": p["global_batch_tokens"] // p["seq_len"]}
