"""``open_loop_windowed``'s schedule and warm-up (a Poisson trace of prompts
LONGER than the largest prefill bucket, computed in chunks of it; a warm-up
request for every program a chunked prompt can reach: the first chunk's
``jit_prefill``, ``jit_prefill_with_prefix`` at every bucket a last chunk
pads to and at the largest for the chunks between, a cold prefill of every
bucket a prompt under it takes, and a burst of the decode step), for a model
with block-sparse and linear-attention layers: such a cell is run by
``runners/serve_sparse_linear.py``."""

from __future__ import annotations

from benchmarks.generators.open_loop_windowed import (generate,  # noqa: F401
                                                      warmup_requests)

RUNNER = "serve_sparse_linear"
