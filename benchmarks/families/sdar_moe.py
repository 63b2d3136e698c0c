"""SDAR-MoE family (Qwen3-MoE layer, generation by diffusion over blocks):
what the program is given for a configuration of this family, and what the
algorithm needs of the chip.

Two halves, as ``llama_dense``.  ``model_config`` and ``make_params`` turn a
configuration file (the published ``config.json`` keys, and under
``sampler`` what that file lacks) into what the program takes.  Everything
above them is plain arithmetic on the published sizes, the benchmark's own
count of the operations and bytes a call requires; it imports nothing of the
program, so no change to the program moves it.

Names the metric readers use: a denoising pass is the program
``jit_block_step`` in the device trace, and the routed experts' grouped
product is the operation ``moe_grouped_mlp`` (a Pallas kernel's call is
named after the kernel).
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}
PASS_MODULE = "jit_block_step"
EXPERT_KERNEL = "moe_grouped_mlp"


# --------------------------------------------------------------------------
# sizes (plain arithmetic; ``c`` is the configuration file as a dict)

def attention_params_per_layer(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return 2 * d * hq + 2 * d * hkv + 2 * hd  # q, o, k, v, q_norm, k_norm


def expert_params(c: dict) -> int:
    """One expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def params_per_layer(c: dict) -> int:
    d = c["hidden_size"]
    return (attention_params_per_layer(c) + d * c["num_experts"]
            + c["num_experts"] * expert_params(c) + 2 * d)


def n_params(c: dict) -> int:
    d = c["hidden_size"]
    return (c["num_hidden_layers"] * params_per_layer(c)
            + 2 * c["vocab_size"] * d + d)


def active_matmul_params_per_layer(c: dict) -> int:
    """Parameters ONE token is multiplied with in a layer."""
    d, hd = c["hidden_size"], c["head_dim"]
    return (attention_params_per_layer(c) - 2 * hd + d * c["num_experts"]
            + c["num_experts_per_tok"] * expert_params(c))


def kv_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    return (2 * c["num_hidden_layers"] * c["num_key_value_heads"]
            * c["head_dim"] * BYTES[dtype])


def weight_bytes(c: dict, dtype: str = "bfloat16") -> int:
    return n_params(c) * BYTES[dtype]


# --------------------------------------------------------------------------
# required operations and bytes

def expected_experts_hit(c: dict, assignments: int) -> float:
    """Experts of one layer that at least one of ``assignments`` (token,
    expert) pairs reaches, the router taken as uniform (seeded weights)."""
    e = c["num_experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** assignments)


# What the REFERENCE's float32 router reaches with seeded weights at the
# published widths, 8 layers (benchmarks/reference/sdar_moe.py on the CPU,
# seed 2147484001; PERF.md section 6, PR 34): tokens of one sequence choose
# alike, so a count that takes the router as uniform is a fifth too high.
# Experts of one layer that the 4 rows of ONE open block reach: in a pass
# that fills masks (12 blocks at a time reached 85.8-94.5 of 128, which is
# what 12 independent blocks of 11.3-13.5 experts reach; a block alone
# 11.7-13.3) and in a pass over a block with no mask left (93.4-100.1 of
# 128: 13.2-15.3 a block).
EXPERTS_A_BLOCK = {"fill": 12.4, "final": 14.3}
# ...that the first m tokens of one prompt reach (4 prompts of 512 tokens)
EXPERTS_A_PROMPT = ((0, 0.0), (32, 36.16), (64, 43.41), (128, 51.47),
                    (192, 55.75), (256, 58.97), (384, 62.41), (512, 65.09))


def experts_reached_by_blocks(c: dict, fill: float, final: float) -> float:
    """Experts of one layer that ``fill`` blocks with masks and ``final``
    blocks without reach between them, each block's set taken as drawn
    independently of the others' (which the 12-block counts bear out)."""
    e = c["num_experts"]
    miss = ((1.0 - EXPERTS_A_BLOCK["fill"] / e) ** fill
            * (1.0 - EXPERTS_A_BLOCK["final"] / e) ** final)
    return e * (1.0 - miss)


def experts_reached_by_prompt(tokens: float) -> float:
    """...that ``tokens`` tokens of one prompt reach (interpolated)."""
    pts = EXPERTS_A_PROMPT
    for (m0, e0), (m1, e1) in zip(pts, pts[1:]):
        if tokens <= m1:
            return e0 + (e1 - e0) * (tokens - m0) / (m1 - m0)
    return pts[-1][1]


def expert_bytes_per_call(c: dict, rows: float, dtype: str = "bfloat16",
                          experts_hit: float = None) -> float:
    """HBM bytes ONE call of the grouped product (one layer) has to read and
    write for ``rows`` tokens: the three matrices of every expert a token
    reaches, once, plus each assignment's input and output row.
    ``experts_hit``: how many experts that is (``experts_reached_by_*``);
    else what uniform routing would reach, an upper estimate."""
    assignments = rows * c["num_experts_per_tok"]
    if experts_hit is None:
        experts_hit = expected_experts_hit(c, assignments)
    weights = experts_hit * expert_params(c)
    rows_io = 2 * assignments * c["hidden_size"]
    return (weights + rows_io) * BYTES[dtype]


def expert_flops_per_call(c: dict, rows: int) -> float:
    return 2.0 * rows * c["num_experts_per_tok"] * expert_params(c)


def pass_bytes(c: dict, slots: int, context_tokens: float,
               dtype: str = "bfloat16") -> float:
    """HBM bytes one denoising pass has to read: per layer the attention
    and router weights and the experts reached by ``slots`` blocks of
    ``block_length`` rows, the output head, and the K and V of the tokens
    present (read once for a block's rows)."""
    rows = slots * c["sampler"]["block_length"]
    d = c["hidden_size"]
    per_layer = ((attention_params_per_layer(c) + d * c["num_experts"])
                 * BYTES[dtype] + expert_bytes_per_call(c, rows, dtype))
    return (c["num_hidden_layers"] * per_layer
            + c["vocab_size"] * d * BYTES[dtype]
            + context_tokens * kv_bytes_per_token(c, dtype))


def attention_flops(c: dict, q_len: int, kv_len: int,
                    causal_within: bool) -> float:
    """QK^T and PV, all layers.  ``causal_within``: the queries are the
    last ``q_len`` of the keys; causal over blocks, so a query sees its
    predecessors and the rest of its own block."""
    hq = c["num_attention_heads"] * c["head_dim"]
    pairs = q_len * kv_len
    if causal_within:
        B = c["sampler"]["block_length"]
        pairs -= q_len * (q_len - B) / 2
    return 4.0 * pairs * hq * c["num_hidden_layers"]


def prefill_flops(c: dict, new_tokens: int, cached_tokens: int = 0) -> float:
    """Forward pass over ``new_tokens`` prompt tokens behind
    ``cached_tokens`` resident ones.  No output head: a prefill of this
    family emits nothing."""
    body = 2.0 * new_tokens * c["num_hidden_layers"] \
        * active_matmul_params_per_layer(c)
    return body + attention_flops(c, new_tokens, cached_tokens + new_tokens,
                                  causal_within=True)


# --------------------------------------------------------------------------
# what the program is given (imports the program; runs in the chip's holder)

def model_module():
    from ray_tpu.models import sdar_moe

    return sdar_moe


def model_config(c: dict, **overrides):
    sdar_moe = model_module()  # a program without this family fails here
    return sdar_moe.SDARMoEConfig(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_expert=c["moe_intermediate_size"], n_experts=c["num_experts"],
        experts_per_token=c["num_experts_per_tok"],
        norm_topk_prob=bool(c["norm_topk_prob"]),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=c.get("dtype", "bfloat16"),
        **{**c["sampler"], **overrides})


def make_params(c: dict, seed: int, dtype: str):
    """Seeded weights in the type they are served in, made on the device in
    one jitted call (``rbg`` keys, as ``llama_dense.make_params``); the
    model's own ``init`` draws and casts the experts a layer at a time."""
    import jax
    import jax.numpy as jnp

    sdar_moe, cfg = model_module(), model_config(c)
    return jax.jit(lambda k: sdar_moe.init(cfg, k, jnp.dtype(dtype)))(
        jax.random.key(seed, impl="rbg"))


def pinned_logits(c: dict, params, tokens, rows, weights, chosen):
    """The PROGRAM's layer (its attention half, its dropless ``dispatch``,
    its grouped kernel, its head) over tokens [b, s] with the routing
    HANDED IN: weights and experts [layers, b * s, k], the reference's.
    Returns logits [b, r, vocab] float32 at ``rows`` [b, r].

    Why the routing is pinned: a bf16 stream moves a router logit by
    0.005-0.01, the 8th and 9th of 128 lie 0.04 apart, so the served model
    takes another 8th expert than float32 in 6-32 % of tokens a layer,
    each swap moving the logits by some 0.05.  That is no fault, and it
    buries what IS one (an expert's weights misread, fp8, a dropped
    assignment: 0.03 and up) unless both sides take the same experts.
    Attention is the cacheless one of ``sdar_moe.apply``; the pages, the
    sampler and the engine are checked on tokens (runners/serve_routed)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama, moe

    sdar_moe, cfg = model_module(), model_config(c)
    positions = jnp.arange(tokens.shape[1])
    mask = sdar_moe.block_causal(positions, positions, cfg.block_length)
    rep = cfg.n_heads // cfg.n_kv_heads

    def attend(q, k, v, cache):  # (b, s, heads, d), as sdar_moe.apply's
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / (cfg.head_dim ** 0.5)
        attn = jax.nn.softmax(jnp.where(mask, scores, -1e30).astype(
            jnp.float32), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", attn.astype(v.dtype), v), cache

    experts = params["layers"]["experts"]

    def body(x, p, li, _routed):
        def pinned(p, h):
            out, _ = moe.dispatch(h.reshape(-1, h.shape[-1]), weights[li],
                                  chosen[li], experts, li)
            return out.reshape(h.shape)

        return llama.layer(cfg, p, x, positions[None, :], attend, None,
                           pinned)[0]

    x, _ = sdar_moe.scan_layers(cfg, params, body,
                                llama.embed(params, tokens, cfg))
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return llama.head(params, x, cfg)
