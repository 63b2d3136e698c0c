"""GPT-2 decoder LM (BASELINE config 1: 124M single-chip trainer).

Same functional conventions as models/llama.py: dict pytrees, scan-stacked
layers, logical sharding specs.  Learned positional embeddings, pre-LN,
GELU MLP, untied LM head off the tied embedding (GPT-2 ties them).

The block shares no part with models/llama.py's: layer norm with a bias
(not RMS norm), one fused biased QKV product (not three without), learned
positions (not rotated q and k), GELU (not a gate).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import flash_attention
from ray_tpu.parallel.sharding import logical_spec as L


@dataclass(frozen=True)
class GPT2Config:
    vocab_size: int = 50257
    d_model: int = 768
    n_layers: int = 12
    n_heads: int = 12
    max_seq_len: int = 1024
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = False
    # sequence-chunked cross-entropy (models/losses.py): avoids the
    # (batch, seq, vocab) fp32 logits tensor; 0 disables chunking
    loss_chunk: int = 256

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_ff(self) -> int:
        return 4 * self.d_model

    @staticmethod
    def gpt2_124m() -> "GPT2Config":
        return GPT2Config()

    @staticmethod
    def tiny(vocab_size: int = 512) -> "GPT2Config":
        return GPT2Config(vocab_size=vocab_size, d_model=64, n_layers=2,
                          n_heads=2, max_seq_len=128)


def param_logical_specs(cfg: GPT2Config):
    layer = {
        "attn": {
            "wqkv": L("layers", "embed", "heads"),
            "bqkv": L("layers", "heads"),
            "wo": L("layers", "heads", "embed"),
            "bo": L("layers", "norm"),
        },
        "mlp": {
            "w_in": L("layers", "embed", "mlp"),
            "b_in": L("layers", "mlp"),
            "w_out": L("layers", "mlp", "embed"),
            "b_out": L("layers", "norm"),
        },
        "ln1_g": L("layers", "norm"),
        "ln1_b": L("layers", "norm"),
        "ln2_g": L("layers", "norm"),
        "ln2_b": L("layers", "norm"),
    }
    return {
        "wte": L("vocab", "embed"),
        "wpe": L(None, "embed"),
        "layers": layer,
        "lnf_g": L("norm",),
        "lnf_b": L("norm",),
    }


def init(cfg: GPT2Config, key: jax.Array):
    kte, kpe, kl = jax.random.split(key, 3)
    d, nl = cfg.d_model, cfg.n_layers

    def dense(key, shape, std=0.02):
        return jax.random.normal(key, shape, jnp.float32) * std

    ks = jax.random.split(kl, 4)
    # GPT-2 scales residual-out projections by 1/sqrt(2*n_layers).
    res_std = 0.02 / (2 * nl) ** 0.5
    layers = {
        "attn": {
            "wqkv": dense(ks[0], (nl, d, 3 * d)),
            "bqkv": jnp.zeros((nl, 3 * d), jnp.float32),
            "wo": dense(ks[1], (nl, d, d), res_std),
            "bo": jnp.zeros((nl, d), jnp.float32),
        },
        "mlp": {
            "w_in": dense(ks[2], (nl, d, cfg.d_ff)),
            "b_in": jnp.zeros((nl, cfg.d_ff), jnp.float32),
            "w_out": dense(ks[3], (nl, cfg.d_ff, d), res_std),
            "b_out": jnp.zeros((nl, d), jnp.float32),
        },
        "ln1_g": jnp.ones((nl, d), jnp.float32),
        "ln1_b": jnp.zeros((nl, d), jnp.float32),
        "ln2_g": jnp.ones((nl, d), jnp.float32),
        "ln2_b": jnp.zeros((nl, d), jnp.float32),
    }
    return {
        "wte": dense(kte, (cfg.vocab_size, d)),
        "wpe": dense(kpe, (cfg.max_seq_len, d), 0.01),
        "layers": layers,
        "lnf_g": jnp.ones((d,), jnp.float32),
        "lnf_b": jnp.zeros((d,), jnp.float32),
    }


def layer_norm(x, g, b, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    return (out * g + b).astype(x.dtype)


def _layer(cfg: GPT2Config, x, p, attn_impl, mesh, rules):
    b, s, d = x.shape
    h = layer_norm(x, p["ln1_g"], p["ln1_b"], cfg.norm_eps)
    qkv = h @ p["attn"]["wqkv"].astype(h.dtype) + p["attn"]["bqkv"].astype(
        h.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)
    shape = (b, s, cfg.n_heads, cfg.head_dim)
    attn = flash_attention(q.reshape(shape), k.reshape(shape),
                           v.reshape(shape), causal=True, impl=attn_impl,
                           mesh=mesh, rules=rules)
    attn = attn.reshape(b, s, d)
    x = x + attn @ p["attn"]["wo"].astype(h.dtype) + p["attn"]["bo"].astype(
        h.dtype)

    h = layer_norm(x, p["ln2_g"], p["ln2_b"], cfg.norm_eps)
    h = jax.nn.gelu(h @ p["mlp"]["w_in"].astype(h.dtype)
                    + p["mlp"]["b_in"].astype(h.dtype), approximate=True)
    x = x + h @ p["mlp"]["w_out"].astype(h.dtype) + p["mlp"]["b_out"].astype(
        h.dtype)
    return x


def trunk(params, tokens, cfg: GPT2Config, attn_impl: str = "auto",
          mesh=None, rules=None):
    """Embeddings -> final layer norm, WITHOUT the LM head: (b, s, d)."""
    dtype = jnp.dtype(cfg.dtype)
    s = tokens.shape[1]
    x = (params["wte"][tokens] + params["wpe"][:s][None]).astype(dtype)

    step = partial(_layer, cfg, attn_impl=attn_impl, mesh=mesh, rules=rules)
    if cfg.remat:
        step = jax.checkpoint(step)

    def scan_body(x, layer_params):
        return step(x, layer_params), None

    x, _ = jax.lax.scan(scan_body, x, params["layers"])
    return layer_norm(x, params["lnf_g"], params["lnf_b"], cfg.norm_eps)


def apply(params, tokens, cfg: GPT2Config, attn_impl: str = "auto",
          mesh=None, rules=None):
    x = trunk(params, tokens, cfg, attn_impl, mesh=mesh, rules=rules)
    # tied LM head: bf16 operands with fp32 accumulation — the MXU's
    # native mode (an fp32 matmul here halves the headline throughput)
    return jnp.dot(x, params["wte"].T.astype(x.dtype),
                   preferred_element_type=jnp.float32)


def loss_fn(params, tokens, cfg: GPT2Config, attn_impl: str = "auto",
            mesh=None, rules=None):
    from ray_tpu.models.losses import chunked_softmax_xent

    x = trunk(params, tokens[:, :-1], cfg, attn_impl, mesh=mesh,
              rules=rules)
    return chunked_softmax_xent(x, params["wte"].T, tokens[:, 1:],
                                chunk=cfg.loss_chunk, mesh=mesh, rules=rules)
