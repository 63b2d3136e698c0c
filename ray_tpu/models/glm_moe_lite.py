"""GLM-4.7-Flash (``model_type`` ``glm4_moe_lite``): latent attention (MLA)
in every layer, one leading dense layer, then sparse layers of sigmoid-scored
top-4-of-64 routed experts beside a shared expert.

The block is the Llama family's pre-norm one, ``h = x + Attn(norm(x));
y = h + FFN(norm(h))`` (models/llama.py ``layer``), with both halves its
own, read off the parameters and not off a flag:

The latent functions below (``latent_qkv``, ``rebuild_kv``, ``absorb``,
``unabsorb``, ``latent_attention_block``, ``batch_attend``, the layout of a
layer's ``attn`` leaves) are SHARED: models/longcat_flash.py attends with
them at 64 heads of 128 + 64 / 128 and with both rank scales (``c_q`` and
``c_kv`` times ``cfg.q_lora_scale`` / ``cfg.kv_lora_scale`` behind their
norms; 1.0 here, where no product is emitted).  The head counts and widths
in what follows are THIS model's.

Latent attention (H = 20 heads; ``q_lora_rank`` 768, ``kv_lora_rank``
r = 512, ``qk_nope_head_dim`` 192, ``qk_rope_head_dim`` 64, ``v_head_dim``
256).  For a normed row x:

1. ``c_q = RMSNorm(x W_qa)`` [768]; ``[q_nope | q_rope]_h = c_q W_qb``
   [H x (192 + 64)], ``q_rope`` rotated.
2. ``[c_kv | k_r] = x W_kva`` [512 | 64]; ``c_kv = RMSNorm(c_kv)``;
   ``k_rope = RoPE(k_r)``: ONE rotary key, shared by the H heads.
3. ``[k_nope | v]_h = c_kv W_kvb`` [H x (192 + 256)];
   ``score_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(256)``,
   causal softmax, ``o = concat_h(P_h v_h) W_o`` [H x 256 -> d].

What is CACHED a token a layer is the LATENT ROW ``[c_kv | k_rope]`` (after
the norm, after the rotation): 576 values, where K and V of these head
counts would be 10,240.  Two forms attend over such rows (``rebuild_kv``,
``absorb`` / ``unabsorb`` below), and they are the same function:

- REBUILT (both prefills, the cacheless pass): per-head K and V are made
  from the rows by step 3's product and dense masked attention runs over
  them, 20 heads of 256.
- ABSORBED (the decode step): K and V are never made.  With ``W_kvb`` split
  a head into ``W_uk`` [192 x 512] and ``W_uv`` [512 x 256],
  ``q~_h = q_nope_h W_uk,h`` [512], ``score_h = q~_h . c_kv + q_rope_h .
  k_rope`` (one product of width 576 against the row), ``o_h = (P_h c_kv)
  W_uv,h``: 20 query heads against ONE cached row whose first 512 values
  are also the "value" (ops/paged_attention.py
  ``paged_latent_decode_attention``).

Feed-forward.  Layer 0 (``first_k_dense_replace`` 1) is a SiLU-gated MLP of
10,240.  Every other layer routes (``topk_method`` ``noaux_tc``, ``n_group``
1 = ``topk_group`` 1, so the group limit is the identity and is not
written): ``s = sigmoid(h W_r)`` in float32; the 4 experts are the top 4 of
``s + b`` (``e_score_correction_bias``, for the CHOICE only); their weights
are ``s`` of the chosen, divided by their sum, times 1.8
(``routed_scaling_factor``); ``FFN(h) = sum_i w_i E_i(h) + Shared(h)``,
every expert and the shared one a SiLU-gated MLP of 1536
(models/moe.py ``route``, ``routed_mlp``, dropless).

The published model has a 48th, multi-token-prediction layer
(``num_nextn_predict_layers`` 1) that drafts and is no part of the forward
pass that defines the logits; it is not built here.

Rotary embedding: the rotation is the family's rotate-half over split
halves (i, i + 32) of the 64 rope dimensions (models/llama.py ``rope``).
The published DeepSeek-V3-family checkpoints hold those dimensions as
interleaved pairs (2i, 2i + 1) and the published modelling code
de-interleaves ACTIVATIONS every call (``rope_interleave``); the same
permutation applied once to the rope COLUMNS of ``W_qb`` and ``W_kva`` when
a checkpoint is loaded gives these programs' layout, scores unchanged.

Parameters: ``dense`` (the leading dense layers, leaves stacked over them)
and ``layers`` (the sparse ones); both hold ``attn`` = ``wq_a``, ``q_norm``,
``wq_b``, ``wkv_a``, ``kv_norm``, ``wkv_b``, ``wo``.  ``serving_layout``
stacks what one product reads (``w_a`` = ``wq_a | wkv_a``, 768 + 576
columns: every split falls on a lane tile), reorders ``wq_b``'s columns
into ``wq_up`` (every head's nope part, then every head's rope part: the
split falls on a lane tile, 3,840 | 1,280, and XLA reads the weight where
it lies) and splits ``wkv_b`` into ``w_uk`` [H, 192, 512] and ``w_uv``
[H, 512, 256], heads leading, as the absorbed form's batched products read
them; every function here takes either layout.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import (embed, gated_mlp, head, layer, rms_norm,
                                  rope)
from ray_tpu.models.moe import scan_routed_layers, served_routed_walk

LANES = 128  # a pool's rows are whole lane tiles

_LATENT_ROWS = ("{cfg.__class__.__name__} caches latent rows, one pool of "
                "{cfg.latent_width} values a token a layer and no V pool, "
                "which this engine does not serve with %s ({where}): that "
                "ships K and V pages")


@dataclass(frozen=True)
class GLMMoELiteConfig:
    vocab_size: int = 154880
    d_model: int = 2048
    n_layers: int = 47
    n_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    d_ff: int = 10240
    n_dense_layers: int = 1  # first_k_dense_replace
    d_expert: int = 1536
    n_experts: int = 64
    experts_per_token: int = 4
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.8
    max_seq_len: int = 202752
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # a factor on c_q and on c_kv behind their norms (a model that scales
    # its ranks to the stream's width, models/longcat_flash.py): none here
    q_lora_scale: float = 1.0
    kv_lora_scale: float = 1.0

    def __post_init__(self):
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError(
                f"{self.n_dense_layers} leading dense layers leave no sparse "
                f"layer of {self.n_layers}")

    @property
    def head_dim(self) -> int:
        """Width of a head's SCORE (nope + rope); a value head is
        ``v_head_dim``."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_kv_heads(self) -> int:
        """Rebuilt K and V have a head a query head."""
        return self.n_heads

    @property
    def latent_dim(self) -> int:
        """Values of a cached row: ``c_kv`` then ``k_rope``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_width(self) -> int:
        """Lanes a row takes in the pool: ``latent_dim`` up to whole lane
        tiles (576 -> 640), the tail zeros."""
        return -(-self.latent_dim // LANES) * LANES

    # What the engine and the served programs ask of a family (llm/model.py
    # says who owns which decision), beside ``cache_layout`` below.
    block_length = 0  # it generates a token at a time
    window = 0  # every layer sees every position
    refuses = {"pd": _LATENT_ROWS % "prefill/decode disaggregation",
               "kv_tier": _LATENT_ROWS % "the KV tier",
               "chunked_prompt": (
                   "{cfg.__class__.__name__} caches latent rows and its "
                   "suffix prefill rebuilds K and V from every row the "
                   "whole page table reaches, a chunk of a long prompt as "
                   "dearly as a prefix hit of its length: {where} is not "
                   "computed in chunks")}

    def serving_layout(self, params):
        return serving_layout(params)

    def served_walk(self, params, x, caches, positions, via):
        return served_routed_walk(scan_layers, self, params, x, caches,
                                  positions, via["attend_latent"],
                                  latent_attention_block)

    def cache_layout(self) -> dict:
        """What the served programs cache (``llm/model.py cache_layout``):
        ONE pool of latent rows, ``[n_layers, pages, page_size,
        latent_width]``, and no V pool.  How the 576 values lie: as they
        are, ``c_kv`` in lanes 0-511 (four whole tiles, which the decode
        kernel also reads as the value), ``k_rope`` in 512-575, and 64
        lanes of zeros to the tile's end.  HBM rows are whole 128-lane
        tiles whatever the array says, so a 576-wide pool would occupy the
        same 640; said out loud, the kernel's page copies and its score
        product run over whole tiles and a query's zero tail meets the
        zeros.  A token-layer therefore costs 640 x 2 = 1,280 bytes in
        bf16 (1,152 of them values), against 20,480 for K and V of 20 heads
        of 256."""
        return {"n_layers": self.n_layers, "latent_dim": self.latent_width}

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "GLMMoELiteConfig":
        """For tests: a value head (24) that is neither the score's width
        (32 + 16) nor the nope part's; one dense layer, two sparse."""
        return GLMMoELiteConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=4,
            q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=32,
            qk_rope_head_dim=16, v_head_dim=24, d_ff=96, d_expert=32,
            n_experts=8, experts_per_token=2, max_seq_len=256,
            dtype="float32"), **kw})


def init(cfg: GLMMoELiteConfig, key: jax.Array, dtype=jnp.float32,
         bias_sd: float = 0.05):
    """Seeded parameters in ``dtype``: every matrix normal with variance
    1 / fan_in, norms 1, and a NON-ZERO ``router_bias`` (normal, sd
    ``bias_sd``: a trained checkpoint's is not zero, and with zero the
    choice could not differ from the weights' order).  The experts are
    drawn and cast a layer at a time (models/sdar_moe.py ``init``)."""
    k_embed, k_dense, k_sparse, k_head = jax.random.split(key, 4)
    d, H, r = cfg.d_model, cfg.n_heads, cfg.kv_lora_rank
    nope, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                    cfg.v_head_dim)
    n_dense, n_sparse = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    ne, f, fs = cfg.n_experts, cfg.d_expert, (cfg.n_shared_experts
                                              * cfg.d_expert)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def attn(key, nl):
        ks = jax.random.split(key, 5)
        return {"wq_a": dense(ks[0], (nl, d, cfg.q_lora_rank), d),
                "q_norm": jnp.ones((nl, cfg.q_lora_rank), dtype),
                "wq_b": dense(ks[1], (nl, cfg.q_lora_rank, H * (nope + dr)),
                              cfg.q_lora_rank),
                "wkv_a": dense(ks[2], (nl, d, r + dr), d),
                "kv_norm": jnp.ones((nl, r), dtype),
                "wkv_b": dense(ks[3], (nl, r, H * (nope + dv)), r),
                "wo": dense(ks[4], (nl, H * dv, d), H * dv)}

    def mlp(key, nl, width):
        ks = jax.random.split(key, 3)
        return {"w_gate": dense(ks[0], (nl, d, width), d),
                "w_up": dense(ks[1], (nl, d, width), d),
                "w_down": dense(ks[2], (nl, width, d), width)}

    def norms(nl):
        return {"attn_norm": jnp.ones((nl, d), dtype),
                "mlp_norm": jnp.ones((nl, d), dtype)}

    def experts(key, shape, fan_in):
        return jax.lax.map(lambda k: dense(k, shape, fan_in),
                           jax.random.split(key, n_sparse))

    kd, ks = jax.random.split(k_dense, 2), jax.random.split(k_sparse, 7)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d) * (d ** 0.5) * 0.02,
        "dense": {"attn": attn(kd[0], n_dense),
                  "mlp": mlp(kd[1], n_dense, cfg.d_ff), **norms(n_dense)},
        "layers": {
            "attn": attn(ks[0], n_sparse),
            "router": dense(ks[1], (n_sparse, d, ne), d),
            "router_bias": (bias_sd * jax.random.normal(
                ks[2], (n_sparse, ne), jnp.float32)),
            "experts": {"w_gate": experts(ks[3], (ne, d, f), d),
                        "w_up": experts(ks[4], (ne, d, f), d),
                        "w_down": experts(ks[5], (ne, f, d), f)},
            "shared": mlp(ks[6], n_sparse, fs), **norms(n_sparse)},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
    }


def serving_layout(params):
    """The tree as the served programs hold it: in ``dense`` and in
    ``layers``, ``wq_a`` and ``wkv_a`` side by side as ONE ``w_a``
    [layers, d_model, q_lora_rank + kv_lora_rank + rope] (one product a
    layer from the normed stream, read out of the stacked parameter inside
    its fusion: PERF.md section 6, PR 37); ``wq_b``'s columns reordered
    into ``wq_up`` [layers, q_lora_rank, H nope + H rope], every head's
    nope part before every head's rope part (split a head at 192 of 256,
    inside a lane tile, the product became a convolution for which XLA
    sliced the weight into fast memory and transposed it every layer: the
    described v5e's compile shows the copy gone); and ``wkv_b`` split a
    head into ``w_uk`` [layers, H, nope, r] and ``w_uv`` [layers, H, r, v],
    as the absorbed form's two batched products contract them.  The four
    are dropped, every other leaf is as it was; a tree that already has
    ``w_a`` comes back as it is."""
    if "w_a" in params["layers"]["attn"]:
        return params
    return {**params, **{
        name: {**params[name],
               "attn": lay_out_attention(params[name]["attn"])}
        for name in ("dense", "layers")}}


def lay_out_attention(a):
    """One stack of layers' ``attn`` leaves (leading axis the layers) as
    ``serving_layout`` describes them."""
    a = dict(a)
    (nl, q_rank), r = a["q_norm"].shape, a["kv_norm"].shape[-1]
    dr = a["wkv_a"].shape[-1] - r
    # wq_b: H (nope + dr) columns; wkv_b: H (nope + dv); wo: H dv rows
    H = (a["wq_b"].shape[-1] - a["wkv_b"].shape[-1]
         + a["wo"].shape[1]) // dr
    dv = a["wo"].shape[1] // H
    up = a.pop("wkv_b").reshape(nl, r, H, -1)
    a["w_uk"] = up[..., :-dv].transpose(0, 2, 3, 1)
    a["w_uv"] = up[..., -dv:].transpose(0, 2, 1, 3)
    q = a.pop("wq_b").reshape(nl, q_rank, H, -1)
    a["wq_up"] = jnp.concatenate(
        [q[..., :-dr].reshape(nl, q_rank, -1),
         q[..., -dr:].reshape(nl, q_rank, -1)], axis=-1)
    a["w_a"] = jnp.concatenate([a.pop("wq_a"), a.pop("wkv_a")], axis=-1)
    return a


# ---------------------------------------------------------------------------
# The attention half, as parts.  ``a`` is one layer's ``attn`` parameters.

def latent_qkv(cfg, p, h, positions):
    """The normed stream h (..., d_model) -> (q_nope (..., H, nope), q_rope
    (..., H, rope) rotated, row (..., latent_dim): ``c_kv`` normed, then
    ``k_rope`` rotated, what the pages cache)."""
    a, r = p["attn"], cfg.kv_lora_rank
    with jax.named_scope("mla/kv_down"):  # both products from the stream
        if "w_a" in a:  # serving_layout: one product, then split
            c_q, c_kv, k_r = jnp.split(
                h @ a["w_a"].astype(h.dtype),
                (cfg.q_lora_rank, cfg.q_lora_rank + r), axis=-1)
        else:
            c_q = h @ a["wq_a"].astype(h.dtype)
            c_kv, k_r = jnp.split(h @ a["wkv_a"].astype(h.dtype), (r,),
                                  axis=-1)
        c_kv = rms_norm(c_kv, a["kv_norm"], cfg.norm_eps)
        if cfg.kv_lora_scale != 1.0:
            c_kv = c_kv * jnp.asarray(cfg.kv_lora_scale, c_kv.dtype)
    with jax.named_scope("mla/q_proj"):
        c_q = rms_norm(c_q, a["q_norm"], cfg.norm_eps)
        if cfg.q_lora_scale != 1.0:
            c_q = c_q * jnp.asarray(cfg.q_lora_scale, c_q.dtype)
        if "wq_up" in a:  # serving_layout: every head's nope, then the ropes
            q_nope, q_rope = (y.reshape(*h.shape[:-1], cfg.n_heads, -1)
                              for y in jnp.split(
                                  c_q @ a["wq_up"].astype(h.dtype),
                                  (cfg.n_heads * cfg.qk_nope_head_dim,),
                                  axis=-1))
        else:
            q = (c_q @ a["wq_b"].astype(h.dtype)).reshape(
                *h.shape[:-1], cfg.n_heads, cfg.head_dim)
            q_nope, q_rope = jnp.split(q, (cfg.qk_nope_head_dim,), axis=-1)
    q_rope = rope(q_rope, positions, cfg.rope_theta)
    k_rope = rope(k_r[..., None, :], positions, cfg.rope_theta)[..., 0, :]
    return q_nope, q_rope, jnp.concatenate([c_kv, k_rope], axis=-1)


def _up_weights(cfg, a):
    """(w_uk [H, nope, r], w_uv [H, r, v]) of either layout."""
    if "w_uk" in a:
        return a["w_uk"], a["w_uv"]
    w = a["wkv_b"].reshape(cfg.kv_lora_rank, cfg.n_heads, -1)
    return (w[..., :cfg.qk_nope_head_dim].transpose(1, 2, 0),
            w[..., cfg.qk_nope_head_dim:].transpose(1, 0, 2))


def rebuild_kv(cfg, a, rows):
    """The REBUILT form's K and V from latent rows (..., T, >= latent_dim;
    a pool's rows come with their zero tail): k (..., T, H, nope + rope),
    the one rotary key repeated to the heads, and v (..., T, H, v)."""
    with jax.named_scope("mla/kv_up"):
        r = cfg.kv_lora_rank
        c, k_rope = rows[..., :r], rows[..., r:cfg.latent_dim]
        w_uk, w_uv = _up_weights(cfg, a)
        k_nope = jnp.einsum("...tr,hnr->...thn", c, w_uk.astype(c.dtype))
        v = jnp.einsum("...tr,hrv->...thv", c, w_uv.astype(c.dtype))
        k_rope = jnp.broadcast_to(
            k_rope[..., None, :], (*k_nope.shape[:-1], k_rope.shape[-1]))
        return jnp.concatenate([k_nope, k_rope], axis=-1), v


def absorb(cfg, a, q_nope, q_rope, width: int = 0):
    """The ABSORBED form's query: ``[q_nope W_uk | q_rope]`` (..., H,
    latent_dim), which scores against a latent row directly; ``width``
    pads it with zeros to a pool's row."""
    with jax.named_scope("mla/absorb"):
        w_uk, _ = _up_weights(cfg, a)
        q = jnp.concatenate(
            [jnp.einsum("...hn,hnr->...hr", q_nope,
                        w_uk.astype(q_nope.dtype)), q_rope], axis=-1)
        short = max(0, width - q.shape[-1])
        return jnp.pad(q, ((0, 0),) * (q.ndim - 1) + ((0, short),))


def unabsorb(cfg, a, o):
    """The ABSORBED form's output: attention's weighted sum of ``c_kv``
    (..., H, r) through ``W_uv``, a head's own: (..., H, v)."""
    with jax.named_scope("mla/unabsorb"):
        _, w_uv = _up_weights(cfg, a)
        return jnp.einsum("...hr,hrv->...hv", o, w_uv.astype(o.dtype))


def latent_attention_block(cfg, p, x, positions, attend, cache=None):
    """x + attention(norm(x)), latent: ``llama.attention_block``'s twin.
    ``attend(q_nope, q_rope, row, a, cache) -> (out (..., H, v), cache)``
    is the one thing that differs between forward passes: it caches the
    row where it has a cache and attends in either form."""
    with jax.named_scope("attn/norm"):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    out, cache = attend(*latent_qkv(cfg, p, h, positions), p["attn"], cache)
    with jax.named_scope("attn/out"):
        out = out.reshape(*x.shape[:-1], cfg.n_heads * cfg.v_head_dim)
        return x + out @ p["attn"]["wo"].astype(x.dtype), cache


def scan_layers(cfg, params, body, carry):
    """``body(carry, layer_params, li, feed_forward)`` over every layer in
    order: the leading dense ones unrolled (their feed-forward the gated
    MLP, ``li`` a Python int), then ``lax.scan`` over the sparse ones
    (``moe.scan_routed_layers``), whose ``li`` runs on from theirs.
    Returns (carry, experts read, summed over the sparse layers)."""
    with jax.named_scope("layers"):
        for i in range(cfg.n_dense_layers):
            carry = body(carry, jax.tree.map(lambda w: w[i], params["dense"]),
                         i, gated_mlp)
    return scan_routed_layers(cfg, params["layers"], body, carry,
                              first=cfg.n_dense_layers)


def batch_attend(cfg, mask, absorbed: bool = False):
    """The cacheless passes' ``attend`` over a batch (b, s, ...), ``mask``
    [s, s]: the prefills' REBUILT form or, ``absorbed``, the decode step's
    (over the latent rows themselves), in plain ``jax.numpy``."""
    scale = cfg.head_dim ** -0.5

    def probs(scores):
        return jax.nn.softmax(jnp.where(mask, scores * scale, -1e30).astype(
            jnp.float32), axis=-1)

    def attend(q_nope, q_rope, row, a, cache):
        if absorbed:
            q = absorb(cfg, a, q_nope, q_rope)
            with jax.named_scope("mla/attend"):
                p = probs(jnp.einsum("bqhw,bkw->bhqk", q, row))
                o = jnp.einsum("bhqk,bkr->bqhr", p.astype(row.dtype),
                               row[..., :cfg.kv_lora_rank])
            return unabsorb(cfg, a, o), cache
        k, v = rebuild_kv(cfg, a, row)
        with jax.named_scope("attn/attend"):
            q = jnp.concatenate([q_nope, q_rope], axis=-1)
            p = probs(jnp.einsum("bqhd,bkhd->bhqk", q, k))
            return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v), cache

    return attend


@partial(jax.jit, static_argnames=("cfg", "absorbed"))
def apply(params, tokens, cfg: GLMMoELiteConfig, absorbed: bool = False):
    """Cacheless forward: tokens (batch, seq) -> logits (batch, seq, vocab)
    float32, causal, attending in either form (``batch_attend``)."""
    positions = jnp.arange(tokens.shape[1])
    attend = batch_attend(cfg, positions[None, :] <= positions[:, None],
                          absorbed)

    def body(x, p, li, ffn):
        return layer(cfg, p, x, positions[None, :], attend, None, ffn,
                     latent_attention_block)[0]

    x, _ = scan_layers(cfg, params, body, embed(params, tokens, cfg))
    return head(params, x, cfg)
