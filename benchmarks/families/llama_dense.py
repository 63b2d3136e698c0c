"""Dense decoder family (Llama/Mistral block): what the program is given for
a configuration of this family, and what the algorithm needs of the chip.

Two halves.  ``model_config`` and ``make_params`` turn a configuration file
(the published ``config.json`` keys) into what the program takes: a
``LlamaConfig`` and seeded weights made on the device in one jitted call.
Everything below them is plain arithmetic on the published sizes, the
benchmark's own count of the operations and bytes a call requires; it
imports nothing of the program, so no change to the program moves it.
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}


# --------------------------------------------------------------------------
# sizes (plain arithmetic; ``c`` is the configuration file as a dict)

def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def params_per_layer(c: dict) -> int:
    d, f = c["hidden_size"], c["intermediate_size"]
    hq = c["num_attention_heads"] * head_dim(c)
    hkv = c["num_key_value_heads"] * head_dim(c)
    return 2 * d * hq + 2 * d * hkv + 3 * d * f + 2 * d


def params_outside_layers(c: dict) -> int:
    return 2 * c["vocab_size"] * c["hidden_size"] + c["hidden_size"]


def n_params(c: dict) -> int:
    return (c["num_hidden_layers"] * params_per_layer(c)
            + params_outside_layers(c))


def matmul_params(c: dict) -> int:
    """Parameters a token is multiplied with: everything but the embedding
    table (a lookup) and the norms."""
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * (params_per_layer(c) - 2 * d)
            + c["vocab_size"] * d)


def kv_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * head_dim(c) * BYTES[dtype])


def weight_bytes(c: dict, dtype: str = "bfloat16") -> int:
    return n_params(c) * BYTES[dtype]


# --------------------------------------------------------------------------
# required operations and bytes

def attention_flops(c: dict, q_len: int, kv_len: int,
                    causal_within: bool) -> float:
    """QK^T and PV for ``q_len`` queries against ``kv_len`` keys, all
    layers.  ``causal_within``: the queries are the last ``q_len`` of the
    keys and see only what precedes them, which halves their own square."""
    hq = c["num_attention_heads"] * head_dim(c)
    pairs = q_len * kv_len
    if causal_within:
        pairs -= q_len * (q_len - 1) / 2
    return 4.0 * pairs * hq * c["num_hidden_layers"]


def prefill_flops(c: dict, new_tokens: int, cached_tokens: int = 0) -> float:
    """Forward pass over ``new_tokens`` prompt tokens whose first
    ``cached_tokens`` predecessors are already in the cache.  The output
    head runs for the last position only."""
    d = c["hidden_size"]
    body = 2.0 * new_tokens * (matmul_params(c) - c["vocab_size"] * d)
    head = 2.0 * c["vocab_size"] * d
    return body + head + attention_flops(
        c, new_tokens, cached_tokens + new_tokens, causal_within=True)


def decode_step_bytes(c: dict, context_tokens: int,
                      dtype: str = "bfloat16") -> float:
    """HBM bytes one decode step has to read: every weight a token is
    multiplied with, once, plus the K and V of the tokens actually present
    over all running sequences (``context_tokens`` is their sum)."""
    return (matmul_params(c) * BYTES[dtype]
            + context_tokens * kv_bytes_per_token(c, dtype))


def decode_step_flops(c: dict, batch: int, context_tokens: int) -> float:
    hq = c["num_attention_heads"] * head_dim(c)
    return (2.0 * batch * matmul_params(c)
            + 4.0 * context_tokens * hq * c["num_hidden_layers"])


def train_flops_per_token(c: dict, seq_len: int) -> float:
    """Forward and backward, no recomputation: 6 per matmul parameter and
    causal attention at 3 x its forward cost."""
    attn = 3.0 * attention_flops(c, seq_len, seq_len, True) / seq_len
    return 6.0 * matmul_params(c) + attn


def flash_kernel_cost(c: dict, kernel: str, batch: int, seq_len: int,
                      heads: int, dtype_bytes: int) -> dict:
    """Required work of one call of a flash attention kernel on ONE device
    holding ``batch`` sequences and ``heads`` query heads (causal): fwd is
    two matmuls over the lower triangle, dK/dV and dQ recompute the scores
    (fwd 2, dkv 4 of which 2 recompute, dq 3 matmuls' worth)."""
    hd = head_dim(c)
    tri = seq_len * (seq_len + 1) / 2
    matmuls = {"flash_attention_fwd": 2, "flash_attention_bwd_dkv": 4,
               "flash_attention_bwd_dq": 3}[kernel]
    flops = 2.0 * matmuls * tri * hd * heads * batch
    kv_heads = max(1, heads * c["num_key_value_heads"]
                   // c["num_attention_heads"])
    qo = batch * seq_len * heads * hd * dtype_bytes
    kv = batch * seq_len * kv_heads * hd * dtype_bytes
    reads = {"flash_attention_fwd": qo + 2 * kv,
             "flash_attention_bwd_dkv": 3 * qo + 2 * kv,
             "flash_attention_bwd_dq": 3 * qo + 2 * kv}[kernel]
    writes = {"flash_attention_fwd": qo, "flash_attention_bwd_dkv": 2 * kv,
              "flash_attention_bwd_dq": qo}[kernel]
    return {"flops": flops, "bytes": float(reads + writes)}


# --------------------------------------------------------------------------
# what the program is given (imports the program; runs in the chip's holder)

def model_config(c: dict, **overrides):
    from ray_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=c.get("dtype", "bfloat16"),
        **{**c.get("model", {}), **overrides})


def model_module():
    from ray_tpu.models import llama

    return llama


def make_params(c: dict, seed: int, dtype: str):
    """Seeded weights in the type they are served in, made on the device in
    one jitted call (the fp32 draw and the cast fuse: no fp32 copy of the
    model ever exists).  The key uses the ``rbg`` generator, which draws
    billions of numbers several times faster on a TPU than the default;
    the same seed gives the same weights on the same device."""
    import jax
    import jax.numpy as jnp

    llama = model_module()
    cfg = model_config(c)
    return jax.jit(lambda k: jax.tree.map(
        lambda x: x.astype(jnp.dtype(dtype)), llama.init(cfg, k)))(
            jax.random.key(seed, impl="rbg"))
