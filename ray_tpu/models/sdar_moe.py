"""SDAR-MoE: a Qwen3-MoE decoder that generates by diffusion over blocks.

The layer is the Llama family's (models/llama.py parts) with two changes,
both read off the parameters and the configuration and not off a flag:
an RMS norm a head on q and k before the rotary embedding (``q_norm`` /
``k_norm`` in ``attn``), and, in every layer, top-k routed experts with no
shared expert for the feed-forward (models/moe.py ``routed_mlp``,
dropless).  Attention is causal over BLOCKS of ``block_length`` positions
and bidirectional inside one: position i sees j iff j // B <= i // B.  The
logits at position i are for the token AT i (no shift): a masked position
holds the mask token, and the model says what belongs there.

Generation (the sampler of the model's published ``generate.py``) lives
with the engine: ``llm/model.py`` ``block_step`` is one denoising pass over
every slot's open block through the page pool, ``llm/engine.py`` runs the
passes.  This file holds what both sides of that share, and the cacheless
forward pass the tests compare with.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import LlamaConfig, embed, head, layer
from ray_tpu.models.moe import scan_routed_layers, served_routed_walk

STRATEGIES = ("sequential", "low_confidence_static", "low_confidence_dynamic")

_BY_BLOCKS = ("{cfg.__class__.__name__} generates by diffusion over blocks of "
              "{cfg.block_length} positions, which this engine does not "
              "serve with ")


@dataclass(frozen=True)
class SDARMoEConfig:
    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    d_expert: int = 768
    n_experts: int = 128
    experts_per_token: int = 8
    norm_topk_prob: bool = True
    max_seq_len: int = 32768
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"
    # the sampler: none of it is in the published config.json
    block_length: int = 4
    mask_token_id: int = 151669
    denoising_steps: int = 4
    remasking_strategy: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9

    def __post_init__(self):
        if self.remasking_strategy not in STRATEGIES:
            raise ValueError(
                f"remasking_strategy {self.remasking_strategy!r} is not one "
                f"of {STRATEGIES}")
        if not 1 <= self.denoising_steps <= self.block_length:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} must lie in 1.."
                f"block_length ({self.block_length})")

    # What the engine and the served programs ask of a family (llm/model.py):
    # ``block_length`` and ``mask_token_id`` are the fields above; the
    # chosen experts' weights are not scaled; the tree and the pools are
    # the Llama family's.
    routed_scaling_factor = None
    window = 0  # every layer sees every position a block may see
    cache_layout = LlamaConfig.cache_layout
    serving_layout = LlamaConfig.serving_layout
    refuses = {
        "sampling": _BY_BLOCKS + "sampling at {where} (greedy only)",
        "pd": _BY_BLOCKS + "prefill/decode disaggregation ({where}): a "
              "prefill of this model yields no first token to ship, and a "
              "slot opens on a block, not on a shipped token",
    }

    def served_walk(self, params, x, caches, positions, via):
        return served_routed_walk(scan_layers, self, params, x, caches,
                                  positions, via["attend"])

    @staticmethod
    def sdar_30b_a3b() -> "SDARMoEConfig":
        return SDARMoEConfig()

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "SDARMoEConfig":
        """For tests: head_dim is not d_model / n_heads here either."""
        return SDARMoEConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
            n_kv_heads=2, head_dim=32, d_expert=32, n_experts=8,
            experts_per_token=2, max_seq_len=256, dtype="float32",
            mask_token_id=vocab_size - 1, denoising_steps=2,
            remasking_strategy="sequential"), **kw})


def num_transfer_tokens(block_length: int, steps: int) -> tuple:
    """Masks a denoising step fills at least: block_length // steps, the
    first block_length % steps steps one more (``generate.py``)."""
    base, extra = divmod(block_length, steps)
    return tuple(base + (i < extra) for i in range(steps))


def init(cfg: SDARMoEConfig, key: jax.Array, dtype=jnp.float32):
    """Seeded parameters in ``dtype``.  The experts are drawn and cast a
    layer at a time: at the published widths one leaf of them is 1.6 G
    numbers, whose random bits alone would take 6.4 GB drawn at once."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    d, nl, ne, f = cfg.d_model, cfg.n_layers, cfg.n_experts, cfg.d_expert
    hq = cfg.n_heads * cfg.head_dim
    hkv = cfg.n_kv_heads * cfg.head_dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def experts(key, shape, fan_in):
        return jax.lax.map(lambda k: dense(k, shape, fan_in),
                           jax.random.split(key, nl))

    ks = jax.random.split(k_layers, 8)
    layers = {
        "attn": {
            "wq": dense(ks[0], (nl, d, hq), d),
            "wk": dense(ks[1], (nl, d, hkv), d),
            "wv": dense(ks[2], (nl, d, hkv), d),
            "wo": dense(ks[3], (nl, hq, d), hq),
            "q_norm": jnp.ones((nl, cfg.head_dim), dtype),
            "k_norm": jnp.ones((nl, cfg.head_dim), dtype),
        },
        "router": dense(ks[4], (nl, d, ne), d),
        "experts": {
            "w_gate": experts(ks[5], (ne, d, f), d),
            "w_up": experts(ks[6], (ne, d, f), d),
            "w_down": experts(ks[7], (ne, f, d), f),
        },
        "attn_norm": jnp.ones((nl, d), dtype),
        "mlp_norm": jnp.ones((nl, d), dtype),
    }
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d) * (d ** 0.5) * 0.02,
        "layers": layers,
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
    }


def scan_layers(cfg, params, body, carry):
    """``lax.scan`` of ``body(carry, layer_params, li, feed_forward)`` over
    the layers, every one routed (``moe.scan_routed_layers``: the experts
    held out of what the scan slices).  Returns (carry, experts read,
    summed over the layers)."""
    return scan_routed_layers(cfg, params["layers"], body, carry)


def block_causal(qpos, kpos, block_length: int):
    """[q, k] bool: the key position's block is not after the query's."""
    return kpos[None, :] // block_length <= qpos[:, None] // block_length


@partial(jax.jit, static_argnames=("cfg",))
def apply(params, tokens, cfg: SDARMoEConfig):
    """Cacheless forward: tokens (batch, seq) -> logits (batch, seq, vocab)
    float32, under the block-causal mask."""
    positions = jnp.arange(tokens.shape[1])
    mask = block_causal(positions, positions, cfg.block_length)
    rep = cfg.n_heads // cfg.n_kv_heads

    def attend(q, k, v, cache):  # (b, s, heads, d)
        with jax.named_scope("attn/attend"):
            k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
            scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k)
                      / (cfg.head_dim ** 0.5))
            attn = jax.nn.softmax(jnp.where(mask, scores, -1e30).astype(
                jnp.float32), axis=-1)
            return (jnp.einsum("bhqk,bkhd->bqhd", attn.astype(v.dtype), v),
                    cache)

    def body(x, p, li, ffn):
        return layer(cfg, p, x, positions[None, :], attend, None, ffn)[0]

    x, _ = scan_layers(cfg, params, body, embed(params, tokens, cfg))
    return head(params, x, cfg)
