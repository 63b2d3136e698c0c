"""GLM-4.7-Flash family (``glm4_moe_lite``: latent attention in every layer,
leading dense layers, then sigmoid-scored routed experts beside a shared
one): what the program is given for a configuration of this family, and
what the algorithm needs of the chip.

Two halves, as ``sdar_moe``.  ``model_config``, ``make_params`` and
``pinned_logits`` turn a configuration file (the published ``config.json``
keys) into what the program takes.  Everything above them is plain
arithmetic on the published sizes, the benchmark's own count of the
operations and bytes a call requires; it imports nothing of the program, so
no change to the program moves it.

Names the metric readers use: a decode step is a program ``jit_decode_step*``
in the device trace; the latent decode kernel's time lies under the part
``mla/attend`` and the routed experts' grouped product under ``moe/experts``
of those programs (``trace/device_parts.py`` puts an operation under the
program whose run contains it).
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}
DECODE_MODULE = "jit_decode_step"
LATENT_PARTS = ("mla/absorb", "mla/attend", "mla/unabsorb")
LATENT_KERNEL_PART = "mla/attend"
EXPERT_KERNEL_PART = "moe/experts"
MOE_PARTS_PREFIX = "moe/"
LANES = 128  # a pool's row is whole lane tiles


# --------------------------------------------------------------------------
# sizes (plain arithmetic; ``c`` is the configuration file as a dict)

def attention_params_per_layer(c: dict) -> int:
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    q, nope, dr, dv = (c["q_lora_rank"], c["qk_nope_head_dim"],
                       c["qk_rope_head_dim"], c["v_head_dim"])
    return (d * q + q + q * H * (nope + dr)  # W_qa, its norm, W_qb
            + d * (r + dr) + r + r * H * (nope + dv)  # W_kva, norm, W_kvb
            + H * dv * d)  # W_o


def expert_params(c: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: dict) -> int:
    return c["n_shared_experts"] * expert_params(c)


def dense_layer_params(c: dict) -> int:
    d = c["hidden_size"]
    return (attention_params_per_layer(c) + 3 * d * c["intermediate_size"]
            + 2 * d)


def sparse_layer_params(c: dict, experts: float = None) -> float:
    """A sparse layer; ``experts``: counting only so many of the routed."""
    d, e = c["hidden_size"], c["n_routed_experts"]
    n = e if experts is None else experts
    return (attention_params_per_layer(c) + d * e + e  # router and its bias
            + shared_params(c) + n * expert_params(c) + 2 * d)


def n_layers(c: dict) -> tuple:
    """(dense, sparse)."""
    k = c["first_k_dense_replace"]
    return k, c["num_hidden_layers"] - k


def n_params(c: dict) -> int:
    d = c["hidden_size"]
    dense, sparse = n_layers(c)
    return int(dense * dense_layer_params(c) + sparse * sparse_layer_params(c)
               + 2 * c["vocab_size"] * d + d)


def weight_bytes(c: dict, dtype: str = "bfloat16") -> int:
    return n_params(c) * BYTES[dtype]


def latent_row_values(c: dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def latent_row_lanes(c: dict) -> int:
    """What a row takes in the pool: whole lane tiles (576 -> 640)."""
    return -(-latent_row_values(c) // LANES) * LANES


def latent_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    """Page bytes a cached token takes over all layers."""
    return c["num_hidden_layers"] * latent_row_lanes(c) * BYTES[dtype]


def kv_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    """What K and V of these head counts would take instead."""
    return (c["num_hidden_layers"] * c["num_attention_heads"]
            * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
               + c["v_head_dim"]) * BYTES[dtype])


# --------------------------------------------------------------------------
# required operations and bytes

def expected_experts_hit(c: dict, assignments: float) -> float:
    """Experts of one layer that at least one of ``assignments`` (token,
    expert) pairs reaches, the router taken as uniform (seeded weights)."""
    e = c["n_routed_experts"]
    return e * (1.0 - (1.0 - 1.0 / e) ** assignments)


def expert_bytes_per_call(c: dict, rows: float, experts_hit: float,
                          dtype: str = "bfloat16") -> float:
    """HBM bytes ONE call of the grouped product (one layer) has to move
    for ``rows`` tokens: the three matrices of the ``experts_hit`` experts
    some token reaches, once, plus each assignment's input and output row."""
    rows_io = 2 * rows * c["num_experts_per_tok"] * c["hidden_size"]
    return (experts_hit * expert_params(c) + rows_io) * BYTES[dtype]


def latent_attend_bytes(c: dict, slots: float, pages: float, page_size: int,
                        dtype: str = "bfloat16") -> float:
    """HBM bytes ONE call of the latent decode kernel (one layer) has to
    move: ``pages`` pages of latent rows walked, each row once, plus a
    query in and an output out for every head of ``slots`` live slots."""
    H = c["num_attention_heads"]
    rows = pages * page_size * latent_row_lanes(c)
    q_and_o = slots * H * (latent_row_lanes(c) + c["kv_lora_rank"])
    return (rows + q_and_o) * BYTES[dtype]


def decode_step_bytes(c: dict, slots: float, context_tokens: float,
                      experts_hit: float = None,
                      dtype: str = "bfloat16") -> float:
    """HBM bytes one decode step has to read: every layer's attention
    weights, the dense layers' MLPs, the sparse layers' routers, shared
    experts and the routed experts ``slots`` tokens reach (``experts_hit``
    a layer, else what uniform routing gives), the output head, and the
    latent rows of the tokens present."""
    dense, sparse = n_layers(c)
    if experts_hit is None:
        experts_hit = expected_experts_hit(
            c, slots * c["num_experts_per_tok"])
    d = c["hidden_size"]
    weights = (dense * dense_layer_params(c)
               + sparse * sparse_layer_params(c, experts_hit)
               + c["vocab_size"] * d + d)
    return (weights * BYTES[dtype]
            + context_tokens * latent_bytes_per_token(c, dtype))


def active_matmul_params(c: dict) -> float:
    """Parameters ONE token is multiplied with, all layers (no head)."""
    d = c["hidden_size"]
    dense, sparse = n_layers(c)
    attn = attention_params_per_layer(c) - c["q_lora_rank"] \
        - c["kv_lora_rank"]
    return (dense * (attn + 3 * d * c["intermediate_size"])
            + sparse * (attn + d * c["n_routed_experts"] + shared_params(c)
                        + c["num_experts_per_tok"] * expert_params(c)))


def attention_flops(c: dict, q_len: int, kv_len: int) -> float:
    """The REBUILT form, all layers: QK^T over nope + rope and PV over v,
    the queries being the last ``q_len`` of ``kv_len`` keys (causal), and
    K and V rebuilt from the ``kv_len - q_len`` resident latent rows (the
    new rows' up-projection is in ``active_matmul_params``)."""
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dqk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    pairs = q_len * kv_len - q_len * (q_len - 1) / 2
    up = (kv_len - q_len) * r * H * (c["qk_nope_head_dim"] + c["v_head_dim"])
    return (2.0 * pairs * H * (dqk + c["v_head_dim"]) + 2.0 * up) \
        * c["num_hidden_layers"]


def prefill_flops(c: dict, new_tokens: int, cached_tokens: int = 0) -> float:
    """Forward pass over ``new_tokens`` prompt tokens behind
    ``cached_tokens`` resident ones, and the head for the last token."""
    return (2.0 * new_tokens * active_matmul_params(c)
            + attention_flops(c, new_tokens, cached_tokens + new_tokens)
            + 2.0 * c["hidden_size"] * c["vocab_size"])


# --------------------------------------------------------------------------
# what the program is given (imports the program; runs in the chip's holder)

def model_module():
    from ray_tpu.models import glm_moe_lite

    return glm_moe_lite


def model_config(c: dict, **overrides):
    glm = model_module()  # a program without this family fails here
    return glm.GLMMoELiteConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["intermediate_size"], n_dense_layers=c["first_k_dense_replace"],
        d_expert=c["moe_intermediate_size"], n_experts=c["n_routed_experts"],
        experts_per_token=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        norm_topk_prob=bool(c["norm_topk_prob"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=c.get("dtype", "bfloat16")), **overrides})


def make_params(c: dict, seed: int, dtype: str):
    """Seeded weights in the type they are served in, made on the device in
    one jitted call (``rbg`` keys, as ``llama_dense.make_params``); the
    model's own ``init`` draws and casts the experts a layer at a time and
    draws the non-zero ``router_bias`` (sd ``assumed.router_bias_sd``)."""
    import jax
    import jax.numpy as jnp

    glm, cfg = model_module(), model_config(c)
    sd = float(c.get("router_bias_sd", 0.05))
    return jax.jit(lambda k: glm.init(cfg, k, jnp.dtype(dtype), sd))(
        jax.random.key(seed, impl="rbg"))


def pinned_logits(c: dict, params, tokens, rows, weights, chosen,
                  absorbed: bool, fault=None):
    """The PROGRAM's layers (its latent attention in the prefills' REBUILT
    form or, ``absorbed``, the decode step's; its dropless ``dispatch``,
    grouped kernel and shared expert; its head) over tokens [b, s] with the
    sparse layers' routing HANDED IN: weights and experts [sparse layers,
    b * s, k], the reference's.  Returns logits [b, r, vocab] float32 at
    ``rows`` [b, r].

    Why the routing is pinned (``families/sdar_moe.py`` has SDAR's
    numbers): a bf16 stream moves a router score a little, the 4th and 5th
    of 64 lie close, so the served model takes another 4th expert than
    float32 in a share of tokens a layer, each swap moving the logits by
    more than a misread weight or a page kept in fewer bits would.  That is
    no fault, and it buries what IS one unless both sides take the same
    experts.  Attention is the cacheless ``glm_moe_lite.batch_attend``; the
    pages, the kernel over them and the engine are checked on the rows they
    leave and on tokens (runners/serve_latent).

    ``fault``: None, or a function of a layer's latent rows applied before
    they are attended to (the builder's readings: rows kept in fewer bits)."""
    import jax.numpy as jnp

    from ray_tpu.models import llama, moe

    glm, cfg = model_module(), model_config(c)
    params = glm.serving_layout(params)  # the products as they are served
    positions = jnp.arange(tokens.shape[1])
    inner = glm.batch_attend(
        cfg, positions[None, :] <= positions[:, None], absorbed)

    def attend(q_nope, q_rope, row, a, cache):
        return inner(q_nope, q_rope, row if fault is None else fault(row),
                     a, cache)

    experts = params["layers"]["experts"]

    def body(x, p, li, ffn):
        if "router" in p:  # a sparse layer: the routing is the reference's
            i = li - cfg.n_dense_layers

            def ffn(p, h):
                hf = h.reshape(-1, h.shape[-1])
                out, _ = moe.dispatch(hf, weights[i], chosen[i], experts, i)
                return (out + moe.shared_mlp(p["shared"], hf)).reshape(
                    h.shape)

        return llama.layer(cfg, p, x, positions[None, :], attend, None, ffn,
                           glm.latent_attention_block)[0]

    x, _ = glm.scan_layers(cfg, params, body,
                           llama.embed(params, tokens, cfg))
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return llama.head(params, x, cfg)


def engine_rows(engine, pages: list):
    """The latent rows the engine's pool holds in ``pages``: [layers,
    len(pages) x page_size, values], the zero tail cut."""
    import jax.numpy as jnp

    c = engine.model_cfg
    got = engine.cache_k[:, jnp.asarray(pages, jnp.int32)]
    return got.reshape(got.shape[0], -1, got.shape[-1])[..., :c.latent_dim]
