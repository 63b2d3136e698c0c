"""LongCat-Flash-Chat (``model_type`` ``longcat_flash``): shortcut-connected
DOUBLE layers of latent attention, a dense feed-forward and softmax-routed
experts, a third of the router's columns identity experts with no weights,
served as ONE CHIP'S SHARE of an expert-parallel deployment.

The block (``x`` a row of the stream; d = 6144, eps 1e-5, H = 64).  A layer
has TWO attention sublayers and TWO dense feed-forwards; the routed experts
are fed from behind the FIRST attention and added back after the SECOND
feed-forward, so the routed branch depends on neither the second attention
nor either dense feed-forward, and XLA may order it beside them freely
(in the deployment that is what hides the experts' exchange across chips):

    h1 = x  + MLA_0(norm_a0(x))
    m  = norm_f0(h1)
    s  = MoE(m)                      # the shortcut branch, used only at the end
    h2 = h1 + FFN_0(m)               # SiLU-gated, width 12288
    h3 = h2 + MLA_1(norm_a1(h2))
    y  = h3 + FFN_1(norm_f1(h3)) + s

``MLA_j(u)`` is models/glm_moe_lite.py's latent attention, its functions
imported and not copied, at 64 heads of 128 + 64 (score) / 128 (value) with
the two rank scales (``mla_scale_q_lora``, ``mla_scale_kv_lora``):
``c_q = RMSNorm(u W_qa) * sqrt(6144 / 1536)``; ``[q_nope | q_rope]_h = c_q
W_qb``, ``q_rope`` rotated (theta 1e7); ``[c | k_r] = u W_kva`` (512 | 64);
``c_kv = RMSNorm(c) * sqrt(6144 / 512)``; ``k_rope = RoPE(k_r)``, one key
for the 64 heads, not scaled; ``[k_nope | v]_h = c_kv W_kvb``; ``score_h =
(q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(192)``, causal softmax,
``concat_h(P_h v_h) W_o`` (8192 -> 6144).  The cached row is ``[c_kv |
k_rope]`` AFTER norm, scale and rotation: 576 values in the family's
640-lane page row.  Decode attends in the absorbed form, the prefills
rebuild K and V.  A scanned layer writes TWO pool layers: sublayer j of
layer i is pool layer ``2 i + j`` (``cache_layout()`` declares ``2 x
n_layers``).

``MoE(m)``: ``p = softmax(m W_r)`` in float32 over ``n_experts +
n_identity_experts`` columns (768: 0-511 experts with weights, 512-767
identity); the 12 columns are the top 12 of ``p + b``
(``e_score_correction_bias``, the choice only); ``w_i = 6 p_i`` of the
chosen, NOT renormalised.  ``s = sum_{i real} w_i E_i(m) + (sum_{i
identity} w_i) m``, ``E_i`` a SiLU-gated MLP of width 2048.

THE SHARE.  The published model splits a layer's 512 experts over the chips
of an expert-parallel group; this chip holds ``n_experts_held`` of them
from ``first_expert_held`` on (16 from 0: one of 32 chips) and a slice of
the vocabulary (``vocab_size`` rows of the embedding and the head).  It sums
over the chosen experts it HOLDS and over every identity pick of its own
tokens (an identity expert needs no weights and no exchange, so it is
computed where the token lives); a pick on an expert another chip holds
adds nothing here (models/moe.py ``dispatch_share``): no code stands in for
the absent chips or their traffic, and what the share computes is what the
reference computes given the same share.  Dropless: every pick on a held
expert is computed, whatever the split.

Parameters: ``layers`` = {``first``, ``second``: a sublayer each (``attn``
as glm_moe_lite's, ``attn_norm``, ``mlp_norm``, ``mlp``), ``router`` [L, d,
768], ``router_bias`` [L, 768], ``experts`` (the held ones, [L, held, ...])},
every leaf stacked over the layers.  ``serving_layout`` lays each
sublayer's ``attn`` out as glm_moe_lite's does (``w_a``, ``wq_up``,
``w_uk``, ``w_uv``).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models import glm_moe_lite as glm
from ray_tpu.models import moe
from ray_tpu.models.llama import embed, gated_mlp, head, rms_norm

SUBLAYERS = ("first", "second")


@dataclass(frozen=True)
class LongCatFlashConfig:
    vocab_size: int = 131072  # the share's slice where the head is split
    d_model: int = 6144
    n_layers: int = 28
    n_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    d_ff: int = 12288
    d_expert: int = 2048
    n_experts: int = 512  # the router's columns with weights, on ANY chip
    n_identity_experts: int = 256  # zero_expert_num, the trailing columns
    experts_per_token: int = 12
    routed_scaling_factor: float = 6.0
    # the share: the experts this chip holds (all of them: no share)
    n_experts_held: int = 512
    first_expert_held: int = 0
    max_seq_len: int = 131072
    rope_theta: float = 10_000_000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not (0 < self.n_experts_held and 0 <= self.first_expert_held
                <= self.n_experts - self.n_experts_held):
            raise ValueError(
                f"experts {self.first_expert_held}.."
                f"{self.first_expert_held + self.n_experts_held - 1} are "
                f"not among the {self.n_experts} the router sends to")

    head_dim = glm.GLMMoELiteConfig.head_dim
    n_kv_heads = glm.GLMMoELiteConfig.n_kv_heads
    latent_dim = glm.GLMMoELiteConfig.latent_dim
    latent_width = glm.GLMMoELiteConfig.latent_width

    @property
    def q_lora_scale(self) -> float:
        return (self.d_model / self.q_lora_rank) ** 0.5

    @property
    def kv_lora_scale(self) -> float:
        return (self.d_model / self.kv_lora_rank) ** 0.5

    @property
    def router_columns(self) -> int:
        return self.n_experts + self.n_identity_experts

    # What the engine and the served programs ask of a family (llm/model.py
    # says who owns which decision): latent rows, as glm_moe_lite's, and
    # refused what it refuses, by the same sentences.
    block_length = 0  # it generates a token at a time
    window = 0  # every sublayer sees every position
    refuses = glm.GLMMoELiteConfig.refuses

    def serving_layout(self, params):
        return serving_layout(params)

    def served_walk(self, params, x, caches, positions, via):
        pool, _, state = caches
        x, pool, counted = walk(self, params["layers"], x, positions,
                                via["attend_latent"], pool)
        return x, (pool, None, state), {moe.SHARE_COUNTED: counted}, None

    def cache_layout(self) -> dict:
        """ONE pool of latent rows as glm_moe_lite's ``cache_layout`` says,
        a pool layer an ATTENTION SUBLAYER: two a model layer."""
        return {"n_layers": len(SUBLAYERS) * self.n_layers,
                "latent_dim": self.latent_width}

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "LongCatFlashConfig":
        """For tests: two double layers, 16 experts with weights and 8
        identity columns, top 4, every expert held."""
        return LongCatFlashConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=24, d_ff=96, d_expert=32,
            n_experts=16, n_identity_experts=8, experts_per_token=4,
            n_experts_held=16, max_seq_len=256, dtype="float32"), **kw})


def init(cfg: LongCatFlashConfig, key: jax.Array, dtype=jnp.float32,
         bias_sd: float = 0.001, router_logit_sd: float = 2.5):
    """Seeded parameters in ``dtype``: every matrix normal with variance
    1 / fan_in, norms 1, but (1) the two UP-PROJECTIONS behind the latent
    norms, ``wq_b`` and ``wkv_b``, drawn at variance 1 / d_model: the
    initialisation whose variance mismatch the two rank scales exist to
    correct (a latent of rank r through weights of variance 1 / d_model
    comes out at variance r / d_model, and ``sqrt(d_model / r)`` restores
    1).  At 1 / fan_in the scales would make a head's scores sqrt(4 x 12) =
    6.9 times too wide, the softmax an argmax over a thousand keys, and the
    bf16 rounding of a score would reshuffle which key wins: logits then
    differ from a float32 pass by 0.3 of their rms with nothing wrong (my
    chip run, PR 54); and (2) the ROUTER, drawn so that its logits (of a
    normed row, unit rms) have a standard deviation of ``router_logit_sd``
    (with sd 1 the softmax over 768 columns is near uniform, a pick weighs
    6 / 768 and the whole routed branch is invisible), and a NON-ZERO
    ``router_bias`` (normal, sd ``bias_sd``: at the 12th of 768 softmax
    scores the neighbours lie ~0.001 apart).  The router's columns are the
    WHOLE model's, whichever experts are held; the experts are drawn and
    cast a layer at a time (models/sdar_moe.py ``init``)."""
    k_embed, k_first, k_second, k_moe, k_head = jax.random.split(key, 5)
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.v_head_dim)
    nl, ne, f = cfg.n_layers, cfg.n_experts_held, cfg.d_expert

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def sublayer(key):
        ks = jax.random.split(key, 8)
        return {
            "attn": {
                "wq_a": dense(ks[0], (nl, d, cfg.q_lora_rank), d),
                "q_norm": jnp.ones((nl, cfg.q_lora_rank), dtype),
                "wq_b": dense(ks[1], (nl, cfg.q_lora_rank, H * (nope + dr)),
                              d),
                "wkv_a": dense(ks[2], (nl, d, r + dr), d),
                "kv_norm": jnp.ones((nl, r), dtype),
                "wkv_b": dense(ks[3], (nl, r, H * (nope + dv)), d),
                "wo": dense(ks[4], (nl, H * dv, d), H * dv)},
            "mlp": {"w_gate": dense(ks[5], (nl, d, cfg.d_ff), d),
                    "w_up": dense(ks[6], (nl, d, cfg.d_ff), d),
                    "w_down": dense(ks[7], (nl, cfg.d_ff, d), cfg.d_ff)},
            "attn_norm": jnp.ones((nl, d), dtype),
            "mlp_norm": jnp.ones((nl, d), dtype)}

    def experts(key, shape, fan_in):
        return jax.lax.map(lambda k: dense(k, shape, fan_in),
                           jax.random.split(key, nl))

    ks = jax.random.split(k_moe, 5)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d) * (d ** 0.5) * 0.02,
        "layers": {
            "first": sublayer(k_first), "second": sublayer(k_second),
            "router": (router_logit_sd * jax.random.normal(
                ks[0], (nl, d, cfg.router_columns), jnp.float32)
                * d ** -0.5).astype(dtype),
            "router_bias": bias_sd * jax.random.normal(
                ks[1], (nl, cfg.router_columns), jnp.float32),
            "experts": {"w_gate": experts(ks[2], (ne, d, f), d),
                        "w_up": experts(ks[3], (ne, d, f), d),
                        "w_down": experts(ks[4], (ne, f, d), f)}},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
    }


def serving_layout(params):
    """The tree as the served programs hold it: each sublayer's ``attn``
    laid out as ``glm_moe_lite.lay_out_attention`` says; every other leaf as
    it was; a tree already laid out comes back as it is."""
    layers = params["layers"]
    if "w_a" in layers["first"]["attn"]:
        return params
    return {**params, "layers": {**layers, **{
        sub: {**layers[sub],
              "attn": glm.lay_out_attention(layers[sub]["attn"])}
        for sub in SUBLAYERS}}}


def routed_branch(cfg, p, experts, i, m, pinned=None):
    """``MoE(m)`` of layer ``i`` for this chip's share: m (..., d) -> (s
    (..., d), counted [4] under ``moe.SHARE_COUNTED``).  ``pinned``:
    (weights, experts) (N, k) handed in, in the place of the router's
    own."""
    mf = m.reshape(-1, m.shape[-1])
    weights, chosen = pinned or moe.route(
        mf, p["router"], cfg.experts_per_token, renormalise=False,
        bias=p["router_bias"], scale=cfg.routed_scaling_factor,
        scoring="softmax")
    s, counted = moe.dispatch_share(
        mf, weights, chosen, experts, i, first=cfg.first_expert_held,
        columns=cfg.router_columns, identity=cfg.n_identity_experts)
    return s.reshape(m.shape), counted


def double_layer(cfg, p, experts, i, x, positions, attend, pool,
                 pinned=None):
    """One shortcut-connected double layer (the block above): (y, pool,
    counted).  ``p``: the layer's leaves, the experts apart (they stay
    stacked and ``i`` is read); ``pool``: the latent pool or None, sublayer
    j writing layer ``2 i + j`` of it."""
    a, b = p["first"], p["second"]
    # (``attend`` hands the pool back first: written, or None as it came)
    h1, (pool, *_) = glm.latent_attention_block(
        cfg, a, x, positions, attend, (pool, None, 2 * i))
    with jax.named_scope("mlp/norm"):
        m = rms_norm(h1, a["mlp_norm"], cfg.norm_eps)
    s, counted = routed_branch(cfg, p, experts, i, m, pinned)
    h2 = h1 + gated_mlp(a, m)
    h3, (pool, *_) = glm.latent_attention_block(
        cfg, b, h2, positions, attend, (pool, None, 2 * i + 1))
    with jax.named_scope("mlp/norm"):
        n = rms_norm(h3, b["mlp_norm"], cfg.norm_eps)
    return h3 + gated_mlp(b, n) + s, pool, counted


def walk(cfg, layers, x, positions, attend, pool=None, pinned=None):
    """ONE ``lax.scan`` over the double layers, the pool in the carry, the
    experts held out of what the scan slices (``moe.scan_routed_layers``
    says why).  ``pinned``: routing handed in, (weights, experts) each
    [layers, N, k].  Returns (x, pool, counted [4] summed over the layers,
    under ``moe.SHARE_COUNTED``)."""
    stacked = dict(layers)
    experts = stacked.pop("experts")
    index = jnp.arange(cfg.n_layers, dtype=jnp.int32)

    def step(carry, per_layer):
        x, pool, counted = carry
        p, i, pin = per_layer
        x, pool, n = double_layer(cfg, p, experts, i, x, positions, attend,
                                  pool, pin)
        return (x, pool, counted + n), None

    with jax.named_scope("layers"):
        (x, pool, counted), _ = jax.lax.scan(
            step, (x, pool, jnp.zeros(len(moe.SHARE_COUNTED), jnp.int32)),
            (stacked, index, pinned))
    return x, pool, counted


@partial(jax.jit, static_argnames=("cfg", "absorbed"))
def apply(params, tokens, cfg: LongCatFlashConfig, absorbed: bool = False):
    """Cacheless forward: tokens (batch, seq) -> logits (batch, seq, vocab)
    float32, causal, attending in either form (``glm.batch_attend``)."""
    positions = jnp.arange(tokens.shape[1])
    attend = glm.batch_attend(cfg, positions[None, :] <= positions[:, None],
                              absorbed)
    x, _, _ = walk(cfg, params["layers"], embed(params, tokens, cfg),
                   positions[None, :], attend)
    return head(params, x, cfg)
