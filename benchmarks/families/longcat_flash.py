"""LongCat-Flash-Chat family (``longcat_flash``: shortcut-connected double
layers of latent attention and dense feed-forwards, softmax-routed experts
of which a chip HOLDS A SHARE, a third of the router's columns identity
experts): what the program is given for a configuration of this family,
and what the algorithm needs of the chip.

Two halves, as ``glm4_moe_lite``.  ``model_config``, ``make_params``,
``pinned_logits``, ``held_part`` and ``engine_rows`` turn a configuration
file (the published ``config.json`` keys) into what the program takes.
Everything above them is plain arithmetic on the published sizes and the
share, the benchmark's own count of the operations and bytes a call
requires; it imports nothing of the program.

The file's ``n_routed_experts`` is what THIS CHIP HOLDS (16); the router's
columns are ``published.n_routed_experts`` (512) + ``zero_expert_num``
(256), and ``vocab_size`` is the share's slice of the vocabulary.

Names the metric readers use: a decode step is a program
``jit_decode_step*`` in the device trace; the latent decode kernel's time
lies under the part ``mla/attend``, the held experts' grouped product under
``moe/experts`` and the identity picks' weighted add under ``moe/zero``
(``trace/device_parts.py`` puts an operation under the program whose run
contains it).
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}
DECODE_MODULE = "jit_decode_step"
LATENT_PARTS = ("mla/absorb", "mla/attend", "mla/unabsorb")
LATENT_KERNEL_PART = "mla/attend"
EXPERT_KERNEL_PART = "moe/experts"
MOE_PARTS_PREFIX = "moe/"
LANES = 128  # a pool's row is whole lane tiles
SUBLAYERS = 2  # attention sublayers (and dense feed-forwards) a layer


# --------------------------------------------------------------------------
# sizes (plain arithmetic; ``c`` is the configuration file as a dict)

def router_columns(c: dict) -> tuple:
    """(columns that are experts with weights, identity columns)."""
    return (c.get("published", {}).get("n_routed_experts",
                                       c["n_routed_experts"]),
            c["zero_expert_num"])


def attention_params(c: dict) -> int:
    """ONE latent attention sublayer."""
    d, H, r = c["hidden_size"], c["num_attention_heads"], c["kv_lora_rank"]
    q, nope, dr, dv = (c["q_lora_rank"], c["qk_nope_head_dim"],
                       c["qk_rope_head_dim"], c["v_head_dim"])
    return (d * q + q + q * H * (nope + dr)  # W_qa, its norm, W_qb
            + d * (r + dr) + r + r * H * (nope + dv)  # W_kva, norm, W_kvb
            + H * dv * d)  # W_o


def ffn_params(c: dict) -> int:
    """ONE dense feed-forward: gate, up and down."""
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def expert_params(c: dict) -> int:
    """One routed expert: gate, up and down."""
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def layer_params(c: dict, experts: float = None) -> float:
    """A double layer as this chip holds it: two attentions, two dense
    feed-forwards, four block norms, the router over ALL its columns with
    its bias, and the experts held (``experts``: counting only so many)."""
    d = c["hidden_size"]
    n = c["n_routed_experts"] if experts is None else experts
    cols = sum(router_columns(c))
    return (SUBLAYERS * (attention_params(c) + ffn_params(c) + 2 * d)
            + d * cols + cols + n * expert_params(c))


def n_layers(c: dict) -> tuple:
    """(dense, routed): every layer routes."""
    return 0, c["num_layers"]


def n_params(c: dict) -> int:
    d = c["hidden_size"]
    return int(c["num_layers"] * layer_params(c) + 2 * c["vocab_size"] * d
               + d)


def weight_bytes(c: dict, dtype: str = "bfloat16") -> int:
    return n_params(c) * BYTES[dtype]


def latent_row_values(c: dict) -> int:
    return c["kv_lora_rank"] + c["qk_rope_head_dim"]


def latent_row_lanes(c: dict) -> int:
    """What a row takes in the pool: whole lane tiles (576 -> 640)."""
    return -(-latent_row_values(c) // LANES) * LANES


def latent_layers(c: dict) -> int:
    """Pool layers: one an attention sublayer."""
    return SUBLAYERS * c["num_layers"]


def latent_bytes_per_token(c: dict, dtype: str = "bfloat16") -> int:
    """Page bytes a cached token takes over all attention sublayers."""
    return latent_layers(c) * latent_row_lanes(c) * BYTES[dtype]


# --------------------------------------------------------------------------
# required operations and bytes

def expected_experts_hit(c: dict, rows: float) -> float:
    """HELD experts of one layer that at least one of ``rows`` tokens
    reaches, the router taken as uniform over its columns (a token's picks
    are distinct columns)."""
    held, cols = c["n_routed_experts"], sum(router_columns(c))
    return held * (1.0 - (1.0 - c["moe_topk"] / cols) ** rows)


def expected_local_rows(c: dict, rows: float) -> float:
    """(token, expert) rows the held experts of one layer compute."""
    return rows * c["moe_topk"] * c["n_routed_experts"] \
        / sum(router_columns(c))


def expected_identity_share(c: dict) -> float:
    """Share of the picks that land on identity columns, uniform router."""
    real, zero = router_columns(c)
    return zero / (real + zero)


def expert_bytes_per_call(c: dict, local_rows: float, experts_hit: float,
                          dtype: str = "bfloat16") -> float:
    """HBM bytes ONE call of the grouped product (one layer) has to move:
    the three matrices of the ``experts_hit`` held experts some token
    reaches, once, plus each of the ``local_rows`` rows in and out."""
    rows_io = 2 * local_rows * c["hidden_size"]
    return (experts_hit * expert_params(c) + rows_io) * BYTES[dtype]


def latent_attend_bytes(c: dict, slots: float, pages: float, page_size: int,
                        dtype: str = "bfloat16") -> float:
    """HBM bytes ONE call of the latent decode kernel (one attention
    sublayer) has to move: ``pages`` pages of latent rows walked, each row
    once, plus a query in and an output out for every head of ``slots``
    live slots."""
    H = c["num_attention_heads"]
    rows = pages * page_size * latent_row_lanes(c)
    q_and_o = slots * H * (latent_row_lanes(c) + c["kv_lora_rank"])
    return (rows + q_and_o) * BYTES[dtype]


def latent_attend_flops(c: dict, slots: float, pages: float,
                        page_size: int) -> float:
    """The same call's operations: every head's score over the row's lanes
    and its weighted sum of the row's value."""
    H = c["num_attention_heads"]
    return 2.0 * pages * page_size * H * (latent_row_lanes(c)
                                          + c["kv_lora_rank"])


def decode_step_bytes(c: dict, slots: float, context_tokens: float,
                      experts_hit: float = None,
                      dtype: str = "bfloat16") -> float:
    """HBM bytes one decode step has to read: every layer's two attentions
    and dense feed-forwards, its router, the held experts ``slots`` tokens
    reach (``experts_hit`` a layer, else what uniform routing gives), the
    output head's slice, and the latent rows of the tokens present."""
    if experts_hit is None:
        experts_hit = expected_experts_hit(c, slots)
    d = c["hidden_size"]
    weights = (c["num_layers"] * layer_params(c, experts_hit)
               + c["vocab_size"] * d + d)
    return (weights * BYTES[dtype]
            + context_tokens * latent_bytes_per_token(c, dtype))


def active_matmul_params(c: dict) -> float:
    """Parameters ONE token is multiplied with ON THIS CHIP, all layers (no
    head): of its ``moe_topk`` picks the share that lands on held experts
    with a uniform router; an identity pick multiplies nothing."""
    d = c["hidden_size"]
    attn = attention_params(c) - c["q_lora_rank"] - c["kv_lora_rank"]
    return c["num_layers"] * (
        SUBLAYERS * (attn + ffn_params(c)) + d * sum(router_columns(c))
        + expected_local_rows(c, 1.0) * expert_params(c))


def attention_flops(c: dict, q_len: int, kv_len: int) -> float:
    """The REBUILT form, all attention sublayers: QK^T over nope + rope and
    PV over v, the queries being the last ``q_len`` of ``kv_len`` keys
    (causal), and K and V rebuilt from the ``kv_len - q_len`` resident
    latent rows (the new rows' up-projection is in
    ``active_matmul_params``)."""
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    dqk = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    pairs = q_len * kv_len - q_len * (q_len - 1) / 2
    up = (kv_len - q_len) * r * H * (c["qk_nope_head_dim"] + c["v_head_dim"])
    return (2.0 * pairs * H * (dqk + c["v_head_dim"]) + 2.0 * up) \
        * latent_layers(c)


def prefill_flops(c: dict, new_tokens: int, cached_tokens: int = 0) -> float:
    """Forward pass over ``new_tokens`` prompt tokens behind
    ``cached_tokens`` resident ones, and the head for the last token."""
    return (2.0 * new_tokens * active_matmul_params(c)
            + attention_flops(c, new_tokens, cached_tokens + new_tokens)
            + 2.0 * c["hidden_size"] * c["vocab_size"])


# --------------------------------------------------------------------------
# what the program is given (imports the program; runs in the chip's holder)

def model_module():
    from ray_tpu.models import longcat_flash

    return longcat_flash


def model_config(c: dict, **overrides):
    lc = model_module()  # a program without this family fails here
    real, zero = router_columns(c)
    if c.get("zero_expert_type", "identity") != "identity":
        raise ValueError(f"zero experts of type {c['zero_expert_type']!r} "
                         f"are not written: identity only")
    return lc.LongCatFlashConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_layers"], n_heads=c["num_attention_heads"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        d_ff=c["ffn_hidden_size"], d_expert=c["expert_ffn_hidden_size"],
        n_experts=real, n_identity_experts=zero,
        experts_per_token=c["moe_topk"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        n_experts_held=c["n_routed_experts"],
        first_expert_held=c.get("first_expert_held", 0),
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=c.get("dtype", "bfloat16")), **overrides})


def make_params(c: dict, seed: int, dtype: str):
    """Seeded weights in the type they are served in, made on the device in
    one jitted call (``rbg`` keys, as ``llama_dense.make_params``); the
    model's own ``init`` draws the router at ``router_logit_sd`` and the
    non-zero ``router_bias`` at ``router_bias_sd`` (both under ``assumed``
    in the file)."""
    import jax
    import jax.numpy as jnp

    lc, cfg = model_module(), model_config(c)
    return jax.jit(lambda k: lc.init(
        cfg, k, jnp.dtype(dtype), float(c["router_bias_sd"]),
        float(c["router_logit_sd"])))(jax.random.key(seed, impl="rbg"))


def pinned_logits(c: dict, params, tokens, rows, weights, chosen,
                  absorbed: bool, fault=None):
    """The PROGRAM's double layers (its latent attention in the prefills'
    REBUILT form or, ``absorbed``, the decode step's; its
    ``dispatch_share``, grouped kernel and identity add; its head) over
    tokens [b, s] with the routing HANDED IN: weights and columns [layers,
    b * s, k], the reference's (``families/glm4_moe_lite.py`` says why: a
    bf16 stream swaps near-tied columns, which is no fault and buries what
    is one).  Returns logits [b, r, vocab] float32 at ``rows`` [b, r].

    ``fault``: None, or a function of a sublayer's latent rows applied
    before they are attended to (the builder's readings)."""
    import jax.numpy as jnp

    from ray_tpu.models import glm_moe_lite as glm
    from ray_tpu.models import llama

    lc, cfg = model_module(), model_config(c)
    params = lc.serving_layout(params)  # the products as they are served
    positions = jnp.arange(tokens.shape[1])
    inner = glm.batch_attend(
        cfg, positions[None, :] <= positions[:, None], absorbed)

    def attend(q_nope, q_rope, row, a, cache):
        return inner(q_nope, q_rope, row if fault is None else fault(row),
                     a, cache)

    x, _, _ = lc.walk(cfg, params["layers"],
                      llama.embed(params, tokens, cfg), positions[None, :],
                      attend, pinned=(weights, chosen))
    x = jnp.take_along_axis(x, rows[:, :, None], axis=1)
    return llama.head(params, x, cfg)


def held_part(c: dict, params, m, weights, chosen):
    """The PROGRAM's ``dispatch_share`` (sort, grouped kernel, combine) of
    every layer on rows HANDED IN: m [layers, n, d] float32 (the
    reference's, cast to the served type), weights and columns [layers, n,
    k]; the identity picks' part taken off again.  Returns [layers, n, d]
    float32: what the held experts added."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe

    cfg = model_config(c)
    experts = params["layers"]["experts"]
    real, zero = router_columns(c)

    def one(i, m, w, e):
        mf = m.astype(jnp.dtype(c["dtype"]))
        out, _ = moe.dispatch_share(
            mf, w, e, experts, i, first=cfg.first_expert_held,
            columns=real + zero, identity=zero)
        ident = jnp.sum(jnp.where(e >= real, w, 0.0), -1, keepdims=True)
        return out.astype(jnp.float32) - ident * mf.astype(jnp.float32)

    return jnp.stack([one(i, m[i], weights[i], chosen[i])
                      for i in range(c["num_layers"])])


def engine_rows(engine, pages: list):
    """The latent rows the engine's pool holds in ``pages``: [pool layers,
    len(pages) x page_size, values], the zero tail cut."""
    import jax.numpy as jnp

    c = engine.model_cfg
    got = engine.cache_k[:, jnp.asarray(pages, jnp.int32)]
    return got.reshape(got.shape[0], -1, got.shape[-1])[..., :c.latent_dim]
