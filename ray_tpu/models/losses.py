"""Loss heads tuned for the TPU memory system.

The naive LM loss materializes fp32 logits of shape (batch, seq, vocab) —
for GPT-2 124M at batch 8 x seq 1024 that is a 1.6 GB tensor written to and
re-read from HBM, and the head matmul runs off the MXU's fast path when its
inputs are fp32.  ``chunked_softmax_xent`` instead:

- keeps the head matmul in bf16 with fp32 accumulation
  (``preferred_element_type``) — the MXU's native mode;
- scans over sequence chunks so only (batch, chunk, vocab) logits ever
  exist, with ``jax.checkpoint`` on the chunk so the backward pass
  recomputes chunk logits instead of storing them.

No reference counterpart: the reference delegates loss math to
torch/vLLM (SURVEY §2.4); this is TPU-native net-new.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def chunked_softmax_xent(x: jax.Array, head: jax.Array, targets: jax.Array,
                         chunk: int = 256) -> jax.Array:
    """Mean next-token cross-entropy without materializing full logits.

    x:       (batch, seq, d_model) activations (any float dtype; bf16 keeps
             the matmul on the MXU fast path)
    head:    (d_model, vocab) output projection (tied embeddings: pass
             ``wte.T`` — XLA folds the transpose into the dot)
    targets: (batch, seq) int32 gold next tokens
    """
    with jax.named_scope("loss"):  # models/llama.py PARTS
        return _chunked_softmax_xent(x, head, targets, chunk)


def _chunked_softmax_xent(x, head, targets, chunk):
    b, s, _ = x.shape

    def nll(xch, tch, mch):
        logits = jnp.dot(xch, head.astype(xch.dtype),
                         preferred_element_type=jnp.float32)
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, tch[..., None], axis=-1)[..., 0]
        return jnp.sum((logz - gold) * mch)

    if chunk <= 0 or chunk >= s:
        # single pass: no recompute; fine whenever (b, s, vocab) fits HBM
        return nll(x, targets, jnp.ones((b, s), x.dtype)) / (b * s)
    # pad the sequence up to a chunk multiple (LM losses see seq-1 tokens,
    # which is odd for every even seq — a divisibility requirement would
    # make the chunked path dead code); pads are masked out of the sum
    pad = (-s) % chunk
    mask = jnp.ones((b, s), x.dtype)
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (s + pad) // chunk
    xc = x.reshape(b, n, chunk, x.shape[-1]).swapaxes(0, 1)
    tc = targets.reshape(b, n, chunk).swapaxes(0, 1)
    mc = mask.reshape(b, n, chunk).swapaxes(0, 1)
    chunk_nll = jax.checkpoint(nll)

    def body(carry, xt):
        xch, tch, mch = xt
        return carry + chunk_nll(xch, tch, mch), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (xc, tc, mc))
    return total / (b * s)
