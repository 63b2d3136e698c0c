"""Plain reference of the dense decoder family: float32 ``jax.numpy``, no
kernel, no cache, no batching tricks, independent of ``ray_tpu.models`` and
``ray_tpu.llm``.  It follows the published description (pre-norm blocks,
RMSNorm, rotary embedding over split halves, grouped-query attention,
SwiGLU, untied output head).  Departure: none; weights come in the
program's parameter layout (layers stacked on a leading axis) and are upcast
one layer at a time, so that a model served in bf16 can be checked beside
its own weights on one chip.

On a TPU a float32 matmul runs in lower precision unless asked otherwise:
every entry point here runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [b, s, h, d]; rotate pairs (i, i + d/2) by position * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _block(c: dict, x, p):
    b, s, _ = x.shape
    nh, nkv = c["num_attention_heads"], c["num_key_value_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // nh
    p = jax.tree.map(lambda w: w.astype(F32), p)
    h = _rms_norm(x, p["attn_norm"], c["rms_norm_eps"])
    q = _rope((h @ p["attn"]["wq"]).reshape(b, s, nh, hd), c["rope_theta"])
    k = _rope((h @ p["attn"]["wk"]).reshape(b, s, nkv, hd), c["rope_theta"])
    v = (h @ p["attn"]["wv"]).reshape(b, s, nkv, hd)
    q = q.reshape(b, s, nkv, nh // nkv, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / jnp.sqrt(F32(hd))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v)
    x = x + out.reshape(b, s, nh * hd) @ p["attn"]["wo"]
    h = _rms_norm(x, p["mlp_norm"], c["rms_norm_eps"])
    gate = h @ p["mlp"]["w_gate"]
    return x + (gate * jax.nn.sigmoid(gate) * (h @ p["mlp"]["w_up"])) \
        @ p["mlp"]["w_down"]


def hidden(c: dict, params, tokens):
    """tokens [b, s] -> final-norm activations [b, s, d], float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        x, _ = jax.lax.scan(lambda x, p: (_block(c, x, p), None), x,
                            params["layers"])
        return _rms_norm(x, params["final_norm"].astype(F32),
                         c["rms_norm_eps"])


def logits(c: dict, params, tokens):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    with jax.default_matmul_precision("highest"):
        return hidden(c, params, tokens) @ params["lm_head"].astype(F32)


def loss(c: dict, params, tokens):
    """Mean next-token cross-entropy of tokens [b, s + 1]."""
    with jax.default_matmul_precision("highest"):
        lg = logits(c, params, tokens[:, :-1])
        logz = jax.scipy.special.logsumexp(lg, -1)
        gold = jnp.take_along_axis(lg, tokens[:, 1:, None], -1)[..., 0]
        return jnp.mean(logz - gold)


TOP_K = 4


def greedy(c: dict, params, prompts: list, steps: int, pad_to: int):
    """Greedy continuation of each prompt by FULL re-forward at every step
    (no cache).  Returns (candidates, gaps), each [n][steps][TOP_K]: the
    TOP_K tokens with the largest logits at that step, best first (the
    first continues the sequence), and how far each one's logit lies under
    the best.  All prompts run as one padded batch; causal attention makes
    padding to the right of a sequence invisible to it."""
    import numpy as np

    n = len(prompts)
    buf = np.zeros((n, pad_to), np.int32)
    lens = np.array([len(p) for p in prompts], np.int32)
    if int(lens.max()) + steps > pad_to:
        raise ValueError("pad_to is too short for the prompts and steps")
    for i, p in enumerate(prompts):
        buf[i, :len(p)] = p

    @jax.jit
    def step(params, buf, lens):
        with jax.default_matmul_precision("highest"):
            h = hidden(c, params, buf)
            last = h[jnp.arange(n), lens - 1]
            lg = last @ params["lm_head"].astype(F32)
        top = jax.lax.top_k(lg, TOP_K)
        return top[1], top[0][:, :1] - top[0]

    cands, gaps = [], []
    for _ in range(steps):
        t, g = (np.asarray(x) for x in step(
            params, jnp.asarray(buf), jnp.asarray(lens)))
        buf[np.arange(n), lens] = t[:, 0]
        lens = lens + 1
        cands.append(t)
        gaps.append(g)
    return (np.stack(cands, 1).tolist(),
            np.stack(gaps, 1).astype(float).tolist())
