"""Llama-family decoder LM, TPU-first.

Plain functional JAX: params are nested-dict pytrees, layers are stacked on a
leading axis and iterated with ``lax.scan`` (O(1) compile time in depth), and
every parameter carries a *logical* sharding spec (parallel/sharding.py) so
the same definition runs single-chip, FSDP, TP, or any mesh combination.
The reference delegates this entire layer to torch/vLLM engines; here it is
native (SURVEY.md §2.4, §7 step 7).

The decoder block is written here once, as parts (``embed`` ... ``head``
below), and every Llama-family forward pass composes them around the one
thing that differs, how it attends: this file's training forward,
``models/moe.py`` (its own feed-forward after ``attention_block``) and the
engine's cache-aware programs (``llm/model.py``).  ``models/gpt2.py`` shares
none of them, and says why.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.ops.attention import FLASH_RESIDUALS, flash_attention
from ray_tpu.parallel.sharding import logical_spec as L


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    max_seq_len: int = 8192
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    # checkpoint each layer: the backward pass makes a layer's activations
    # again from its input, but for the flash kernel's output and row
    # statistics, which are kept (``REMAT_KEEPS``)
    remat: bool = True
    # sequence-chunked cross-entropy (models/losses.py): avoids the
    # (batch, seq, vocab) fp32 logits tensor; 0 disables chunking
    loss_chunk: int = 256

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    # What the engine and the served programs ask of a family (llm/model.py
    # says who owns which decision).  Class members, not fields: a
    # configuration hashes and compares as before.
    block_length = 0  # positions a block; 0: it generates a token at a time
    window = 0  # positions a window layer sees; 0: every layer sees them all
    refuses = {}  # feature -> why the engine cannot serve the model with it

    def cache_layout(self) -> dict:
        """What is cached, as ``paged_cache.CacheConfig`` takes it: one K/V
        page pool a layer."""
        return {"n_layers": self.n_layers, "n_kv_heads": self.n_kv_heads,
                "head_dim": self.head_dim}

    def serving_layout(self, params):
        return serving_layout(params)

    def served_walk(self, params, x, caches, positions, via):
        return served_walk(self, params, x, caches, positions, via)

    @staticmethod
    def llama3_8b() -> "LlamaConfig":
        return LlamaConfig()

    @staticmethod
    def llama3_70b() -> "LlamaConfig":
        return LlamaConfig(d_model=8192, n_layers=80, n_heads=64,
                           n_kv_heads=8, d_ff=28672)

    @staticmethod
    def tiny(vocab_size: int = 512) -> "LlamaConfig":
        """For tests and multichip dry runs."""
        return LlamaConfig(vocab_size=vocab_size, d_model=128, n_layers=2,
                           n_heads=4, n_kv_heads=2, d_ff=256,
                           max_seq_len=256, remat=False)

    @staticmethod
    def llama3_8b_dry(vocab_size: int = 512) -> "LlamaConfig":
        """8B-SHAPED dry config: the llama3_8b geometry ratios (4:1 GQA,
        3.5x FFN, head_dim 32) at tiny scale, so a dry run exercises the
        EXACT sharding structure of the v5e-16 8B recipe
        (train/llama3.py) without 8B of parameters."""
        return LlamaConfig(vocab_size=vocab_size, d_model=256, n_layers=4,
                           n_heads=8, n_kv_heads=2, d_ff=896,
                           max_seq_len=512, remat=True, loss_chunk=128)


def decoder_logical_specs(feed_forward: dict):
    """Logical sharding spec tree of a Llama-family decoder around its
    feed-forward's own entries (``{"mlp": ...}`` here, router and experts
    in models/moe.py), mirroring init()'s param tree."""
    layer = {
        "attn": {
            "wq": L("layers", "embed", "heads"),
            "wk": L("layers", "embed", "kv_heads"),
            "wv": L("layers", "embed", "kv_heads"),
            "wo": L("layers", "heads", "embed"),
        },
        **feed_forward,
        "attn_norm": L("layers", "norm"),
        "mlp_norm": L("layers", "norm"),
    }
    return {
        "embed": L("vocab", "embed"),
        "layers": layer,
        "final_norm": L("norm",),
        "lm_head": L("embed", "vocab"),
    }


def param_logical_specs(cfg: LlamaConfig):
    return decoder_logical_specs({"mlp": {
        "w_gate": L("layers", "embed", "mlp"),
        "w_up": L("layers", "embed", "mlp"),
        "w_down": L("layers", "mlp", "embed"),
    }})


def init(cfg: LlamaConfig, key: jax.Array):
    """Initialize parameters (fp32 master weights)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    d, nl = cfg.d_model, cfg.n_layers
    hq = cfg.n_heads * cfg.head_dim
    hkv = cfg.n_kv_heads * cfg.head_dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5))

    ks = jax.random.split(k_layers, 7)
    layers = {
        "attn": {
            "wq": dense(ks[0], (nl, d, hq), d),
            "wk": dense(ks[1], (nl, d, hkv), d),
            "wv": dense(ks[2], (nl, d, hkv), d),
            "wo": dense(ks[3], (nl, hq, d), hq),
        },
        "mlp": {
            "w_gate": dense(ks[4], (nl, d, cfg.d_ff), d),
            "w_up": dense(ks[5], (nl, d, cfg.d_ff), d),
            "w_down": dense(ks[6], (nl, cfg.d_ff, d), cfg.d_ff),
        },
        "attn_norm": jnp.ones((nl, d), jnp.float32),
        "mlp_norm": jnp.ones((nl, d), jnp.float32),
    }
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d) * (d ** 0.5) * 0.02,
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
    }


# ---------------------------------------------------------------------------
# The block, as parts.  ``p`` is one layer's slice of params["layers"].

# The names the parts give their operations (``jax.named_scope``; metadata
# on the traced operations, nothing at run time).  A part opens its scope
# where it is written, below and in models/moe.py, models/losses.py,
# llm/model.py (the ``attend`` strategies, token selection) and
# train/step.py, so every composition inherits them and a device profile
# of any program reads ``.../attn/qkv/dot_general`` where it read
# ``fusion.121``.  ``layers`` is the layer scan itself: what lies under it
# and under no deeper part is the scan's own work, the slices of the stacked
# norm weights, the loop's counter and, for a tree with ``wq``, ``wk``,
# ``wv``, their slices into fast memory and the transposes XLA makes of
# them (``serving_layout`` below is the cure where it cost most).
# Whoever reads a profile by part (benchmarks/trace/device_parts.py) takes
# the names from here, the innermost that a path holds; none is a name a
# JAX primitive or transform uses.
PARTS = (
    "embed", "layers",
    "attn/norm", "attn/qkv", "attn/rope", "attn/kv_write", "attn/attend",
    "attn/attend/repeat_kv", "attn/out",
    "mlp/norm", "mlp/gate_up", "mlp/down",
    "moe/route", "moe/dispatch", "moe/experts", "moe/combine",
    "head", "sample", "loss", "optim",
    # models/olmo_hybrid.py's linear-attention layer: the stacked input
    # product, the short convolution, norms and gates, the recurrence (a
    # decode step's update, a prefill's chunked scan), gated norm and W_o
    "lin_attn/proj", "lin_attn/conv", "lin_attn/gates", "lin_attn/state",
    "lin_attn/out",
    # models/glm_moe_lite.py's latent attention: the stacked down-projections
    # and the latent norm, the query's norm and up-projection, K and V
    # rebuilt from latent rows (prefills), the decode step's absorption,
    # its paged kernel over the rows and its un-absorption; the routed
    # layers' shared expert (models/moe.py)
    "mla/kv_down", "mla/q_proj", "mla/kv_up", "mla/absorb", "mla/attend",
    "mla/unabsorb", "moe/shared",
    # models/moe.py ``dispatch_share``: the identity experts' picks, their
    # summed weight times the layer's own input (no product)
    "moe/zero",
    # models/afmoe.py's block: the RMS norm a head on q and k (a part of
    # its own there; the Qwen3 family's lies inside ``attn/qkv``), the
    # sigmoid gate on attention's output, and the sandwich block's two
    # norms BEHIND attention and the feed-forward
    "attn/qk_norm", "attn/gate", "norm/post",
    # models/minicpm_sala.py: a sparse layer's products (q, k, v and gate
    # with their norms; the gate and W_o), its pooled keys and the choice
    # of blocks, its attention over the chosen ones; a linear layer's
    # products and norms, its recurrence, its output norm, gate and W_o
    "sparse_attn/proj", "sparse_attn/index", "sparse_attn/attend",
    "lightning/proj", "lightning/state", "lightning/out",
    # models/falcon_h1.py's Mamba-2 mixer: W_in and the muP vector, the
    # short convolution, softplus / decay / dt x, the recurrence (a decode
    # step's update, a prefill's chunked scan, with D x), gated norm and
    # W_out
    "ssm/proj", "ssm/conv", "ssm/gates", "ssm/state", "ssm/out",
    # models/nemotron_h.py's routed layer: the experts live in a LATENT
    # narrower than the stream, one product into it before the dispatch and
    # one back out of it behind the combine, both shared by every expert
    "moe/latent_in", "moe/latent_out",
    # parallel/tp_stream.py: the links of a training trunk whose stream is
    # split over ``tp`` between the products: a group's rows passed round
    # the ring into ``attn/qkv`` and ``mlp/gate_up``, the partial sums of
    # ``attn/out`` and ``mlp/down`` passed home (and backward the reverse:
    # a scatter inside ``attn/qkv``, a gather inside ``attn/out``); the
    # slice that splits the stream before the first layer and the gather
    # that makes it whole after the last
    "tp/gather", "tp/scatter",
)


def embed(params, tokens, cfg):
    with jax.named_scope("embed"):
        x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
        # a family whose stream begins as a multiple of the table's rows
        # says so (models/afmoe.py ``embed_scale``); no other has the name
        scale = getattr(cfg, "embed_scale", None)
        return x if scale is None else x * jnp.asarray(scale, x.dtype)


def rms_norm(x, weight, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * jax.lax.rsqrt(var + eps)).astype(x.dtype) * weight.astype(
        x.dtype)


def rope(x, positions, theta):
    """Rotary embedding; x: (..., heads, head_dim), positions: (...) or
    anything that broadcasts against x's leading axes."""
    head_dim = x.shape[-1]
    with jax.named_scope("attn/rope"):
        freqs = theta ** (-jnp.arange(0, head_dim // 2, dtype=jnp.float32)
                          / (head_dim // 2))
        angles = positions[..., None].astype(jnp.float32) * freqs  # (.., d/2)
        cos = jnp.cos(angles)[..., None, :]  # broadcast over heads
        sin = jnp.sin(angles)[..., None, :]
        x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
        out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                              axis=-1)
        return out.astype(x.dtype)


def serving_layout(params):
    """The parameter tree as the served programs (llm/model.py) hold it:
    every layer's ``wq``, ``wk`` and ``wv`` side by side in ONE stacked
    ``wqkv`` [n_layers, d_model, (n_heads + 2 n_kv_heads) head_dim], the
    three dropped, every other leaf as it was.  ``qkv_rope`` then makes one
    product a layer, and XLA reads the layer's weight out of the stacked
    parameter inside that product's fusion, as it reads the MLP's; the three
    products' reshape to heads it folds into a convolution that wants each
    weight sliced out and transposed first (PERF.md section 6, PR 37).  A
    tree that already has ``wqkv`` comes back as it is.  Training keeps the
    three: their logical specs shard ``heads`` and ``kv_heads`` apart."""
    attn = params["layers"]["attn"]
    if "wqkv" in attn:
        return params
    attn = dict(attn)
    attn["wqkv"] = jnp.concatenate(
        [attn.pop("wq"), attn.pop("wk"), attn.pop("wv")], axis=-1)
    return {**params, "layers": {**params["layers"], "attn": attn}}


def _qk_norm(cfg, x, weight):
    """RMS norm of q or k, x (..., heads, head_dim): a [head_dim] weight
    norms each head (Qwen3 family), a wider one the whole row of heads
    before they are split (OLMo 2 / 3)."""
    if weight.shape[-1] == cfg.head_dim:
        return rms_norm(x, weight, cfg.norm_eps)
    flat = x.reshape(*x.shape[:-2], -1)
    return rms_norm(flat, weight, cfg.norm_eps).reshape(x.shape)


def qkv(cfg, p, h, ring=None):
    """The normed stream h (..., d_model) projected and split into heads:
    q (..., n_heads, head_dim), k and v at KV-head width, NOT rotated (a
    family whose attention carries no position, models/nemotron_h.py, takes
    them so).  The layer's parameters say how: three weights (training), or
    the one ``wqkv`` of ``serving_layout``, whose product is split after.
    ``ring`` (parallel/tp_stream.py): h is a device's rows of a stream
    split over ``tp``, and q, k, v come back for the whole group's rows at
    the device's heads."""
    def heads(w, n):
        return (h @ w.astype(h.dtype)).reshape(*h.shape[:-1], n, cfg.head_dim)

    with jax.named_scope("attn/qkv"):
        if "wqkv" in p["attn"]:  # serving_layout: one product, then split
            nq = cfg.n_heads * cfg.head_dim
            nkv = cfg.n_kv_heads * cfg.head_dim
            q, k, v = (y.reshape(*h.shape[:-1], -1, cfg.head_dim)
                       for y in jnp.split(
                           h @ p["attn"]["wqkv"].astype(h.dtype),
                           (nq, nq + nkv), axis=-1))
        elif ring is not None:
            q, k, v = (y.reshape(*y.shape[:-1], -1, cfg.head_dim)
                       for y in ring.into(h, p["attn"]["wq"], p["attn"]["wk"],
                                          p["attn"]["wv"]))
        else:
            q = heads(p["attn"]["wq"], cfg.n_heads)
            k = heads(p["attn"]["wk"], cfg.n_kv_heads)
            v = heads(p["attn"]["wv"], cfg.n_kv_heads)
        if "q_norm" in p["attn"]:  # RMS norm pre-rope, by the weight's width
            q = _qk_norm(cfg, q, p["attn"]["q_norm"])
            k = _qk_norm(cfg, k, p["attn"]["k_norm"])
    return q, k, v


def qkv_rope(cfg, p, h, positions, ring=None):
    """``qkv`` with q and k rotated."""
    q, k, v = qkv(cfg, p, h, ring)
    return (rope(q, positions, cfg.rope_theta),
            rope(k, positions, cfg.rope_theta), v)


def attention_block(cfg, p, x, positions, attend, cache=None, ring=None):
    """x + attention(norm(x)).  ``attend(q, k, v, cache) -> (out, cache)``
    is the one thing that differs between forward passes: training attends
    within the batch and has no cache; the engine's programs write k and v
    into the layer's pages and attend through them.  ``cache`` is whatever
    the caller's layer scan hands its strategy, and comes back with x.
    The strategy names its own parts (``attn/attend``, and ``attn/kv_write``
    where it has a cache to write).  ``ring``: x is a device's rows of a
    stream split over ``tp`` (``qkv_rope``); the strategy attends over the
    group's rows at the device's heads."""
    with jax.named_scope("attn/norm"):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    out, cache = attend(*qkv_rope(cfg, p, h, positions, ring), cache)
    with jax.named_scope("attn/out"):
        out = out.reshape(*out.shape[:-2], -1)
        if ring is not None:
            return x + ring.back(out, p["attn"]["wo"]), cache
        return x + out @ p["attn"]["wo"].astype(x.dtype), cache


def gated_mlp(p, h, ring=None):
    """``ring``: h is a device's rows of a stream split over ``tp``, and so
    is what comes back; between them the group's rows at the device's
    columns."""
    if ring is not None:
        with jax.named_scope("mlp/gate_up"):
            gate, up = ring.into(h, p["mlp"]["w_gate"], p["mlp"]["w_up"])
            gate = jax.nn.silu(gate)
        with jax.named_scope("mlp/down"):
            return ring.back(gate * up, p["mlp"]["w_down"])
    with jax.named_scope("mlp/gate_up"):
        gate = jax.nn.silu(h @ p["mlp"]["w_gate"].astype(h.dtype))
        up = h @ p["mlp"]["w_up"].astype(h.dtype)
    with jax.named_scope("mlp/down"):
        return (gate * up) @ p["mlp"]["w_down"].astype(h.dtype)


def layer(cfg, p, x, positions, attend, cache=None, feed_forward=gated_mlp,
          attention=attention_block):
    """One decoder layer: (x, cache).  ``feed_forward(p, h)`` is the dense
    gated MLP unless the caller hands another (models/moe.py's routed
    experts), ``attention`` this file's block unless the layer attends over
    latent rows (models/glm_moe_lite.py ``latent_attention_block``, whose
    ``attend`` takes other things)."""
    x, cache = attention(cfg, p, x, positions, attend, cache)
    with jax.named_scope("mlp/norm"):
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    return x + feed_forward(p, h), cache


def head(params, x, cfg, true_len=None):
    """Final norm, then ``lm_head`` in float32 (what sampling and the MoE
    loss take).  ``true_len``: x [L, d_model] is one padded sequence of
    which only the last real token's logits are wanted."""
    with jax.named_scope("head"):
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if true_len is not None:
            x = jnp.take(x, jnp.maximum(true_len - 1, 0), axis=0)
        x = x.astype(jnp.float32)
        # a family whose head reads a multiple of the normed stream says
        # so (models/minicpm_sala.py ``logit_scale``), as ``embed`` above
        scale = getattr(cfg, "logit_scale", None)
        return (x if scale is None else x * scale) @ params["lm_head"]


def served_walk(cfg, params, x, caches, positions, via):
    """The layers as the served programs (llm/model.py) walk them, one
    signature a family: ``caches`` = (cache_k, cache_v, state) ride in the
    scan's carry whole, ``via`` is what the program attends with
    (``attend``, ``attend_latent``, ``recur``: a family takes what it
    uses).  Returns (x, caches, what the walk counted by name, the rows a
    program is to write once the scan is over).  Here: a plain scan, K/V
    ``attend``, nothing counted."""
    cache_k, cache_v, state = caches

    def body(carry, per_layer):
        x, ck, cv = carry
        p, li = per_layer
        x, (ck, cv) = layer(cfg, p, x, positions, via["attend"], (ck, cv, li))
        return (x, ck, cv), None

    with jax.named_scope("layers"):
        (x, cache_k, cache_v), _ = jax.lax.scan(
            body, (x, cache_k, cache_v),
            (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    return x, (cache_k, cache_v, state), {}, None


# ---------------------------------------------------------------------------
# The training forward: attention within the batch, no cache.

# What a remat'd layer keeps for its backward pass BESIDE the carry, by
# ``checkpoint_name``; everything else is made again from the carry.  The
# flash kernel's output and row statistics (68 MB a layer a device in the
# benchmark's train cell, against the carry's 67): its second run was the
# dearest recompute a kept byte (22 ms of an 836 ms step for 0.48 GB; the
# MLP's ``gate`` and ``up`` cost 72 ms for 3.3 GB).  The set is fixed, one
# for every mesh: it grows with what was kept already, and a trunk whose
# attention is not the kernel (``impl="xla"``, the sequence-parallel
# forms) names nothing and keeps the carry alone, as before.  q, k, v
# after rope and the stream after attention were tried beside these and
# dropped: the cell's step then plans over 0.85 of a v5e's memory
# (PERF.md section 6, PR 44).
REMAT_KEEPS = FLASH_RESIDUALS

_SEQUENCE_PARALLEL = ("ring", "zigzag", "ulysses")


def batch_attend(attn_impl, mesh, rules=None):
    """The training passes' ``attend``: dense flash or sequence-parallel
    attention (ring / zigzag-balanced ring / ulysses); no cache."""
    def attend(q, k, v, cache):
        with jax.named_scope("attn/attend"):
            if attn_impl in _SEQUENCE_PARALLEL:
                from ray_tpu.ops.ring_attention import (
                    sequence_parallel_attention)

                if mesh is None:
                    raise ValueError(
                        f"attn_impl={attn_impl!r} requires a mesh")
                return sequence_parallel_attention(
                    q, k, v, mesh, impl=attn_impl, causal=True,
                    rules=rules), cache
            return flash_attention(q, k, v, causal=True, impl=attn_impl,
                                   mesh=mesh, rules=rules), cache
    return attend


def _layer(cfg: LlamaConfig, x, layer_params, positions, attn_impl, mesh,
           rules, ring=None):
    return layer(cfg, layer_params, x, positions,
                 batch_attend(attn_impl, mesh, rules),
                 feed_forward=partial(gated_mlp, ring=ring),
                 attention=partial(attention_block, ring=ring))[0]


def _tp_split(cfg: LlamaConfig, batch: int, attn_impl, mesh, rules):
    """How to split the stream over ``tp`` between a layer's products
    (parallel/tp_stream.py), or None: no mesh, no ``tp`` axis on it, a
    sequence-parallel attention, or rows that do not divide."""
    if mesh is None or attn_impl in _SEQUENCE_PARALLEL:
        return None
    from ray_tpu.parallel.tp_stream import stream_split

    return stream_split(
        mesh, rules, jax.tree.map(lambda spec: spec[1:],
                                  param_logical_specs(cfg)["layers"],
                                  is_leaf=lambda s: isinstance(s, tuple)),
        {"batch": batch, "heads": cfg.n_heads, "kv_heads": cfg.n_kv_heads,
         "mlp": cfg.d_ff})


def trunk(params, tokens, cfg: LlamaConfig, attn_impl: str = "auto",
          mesh=None, rules=None):
    """Embeddings -> final RMS norm, WITHOUT the LM head: (b, s, d).

    Layers run under lax.scan over the stacked layer params.  With
    ``cfg.remat`` each step is a ``jax.checkpoint`` whose policy keeps the
    layer's input (the carry) and ``REMAT_KEEPS``, the flash kernel's output
    and row statistics, and makes everything else again in the backward
    pass: the products and the norms are cheap for their bytes, the kernel
    is not, so it runs once a layer a step.
    attn_impl "ring"/"ulysses" (with a mesh) enables sequence-parallel
    attention over the sp axis for long-context training.

    On a mesh with a ``tp`` axis the stream is split over it between the
    layers' products (``_tp_split``): each layer runs under one shard_map on
    a ``tp``-th of a group's rows, and the rows are gathered once, after
    the final norm.
    """
    x = embed(params, tokens, cfg)
    positions = jnp.arange(tokens.shape[1])[None, :]

    split = _tp_split(cfg, tokens.shape[0], attn_impl, mesh, rules)
    if split is None:
        step = partial(_layer, cfg, positions=positions, attn_impl=attn_impl,
                       mesh=mesh, rules=rules)
    else:
        # the lookup and its gradient's scatter-add keep the layout they
        # had (a group's rows whole): the split is a slice of it, and its
        # backward one gather a step
        with jax.named_scope("tp/scatter"):
            x = split.split_rows(split.whole_rows(x))
        step = split.layer(
            lambda ring, x, p, positions: _layer(
                cfg, x, p, positions, attn_impl, None, None, ring),
            jnp.dtype(cfg.dtype), positions)
    if cfg.remat:
        step = jax.checkpoint(
            step, policy=jax.checkpoint_policies.save_only_these_names(
                *REMAT_KEEPS))

    def scan_body(x, layer_params):
        return step(x, layer_params), None

    with jax.named_scope("layers"):
        x, _ = jax.lax.scan(scan_body, x, params["layers"])
    with jax.named_scope("head"):  # the final norm is the head's
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    if split is None:
        return x
    with jax.named_scope("tp/gather"):
        return split.whole_rows(x)


def apply(params, tokens, cfg: LlamaConfig, attn_impl: str = "auto",
          mesh=None, rules=None):
    """Forward pass: tokens (batch, seq) int32 -> logits (batch, seq, vocab)."""
    x = trunk(params, tokens, cfg, attn_impl, mesh=mesh, rules=rules)
    # bf16 operands, fp32 accumulation (preferred_element_type) — the
    # MXU's native mode; logits come out fp32 for a stable softmax.
    with jax.named_scope("head"):
        return jnp.dot(x, params["lm_head"].astype(x.dtype),
                       preferred_element_type=jnp.float32)


def loss_fn(params, tokens, cfg: LlamaConfig, attn_impl: str = "auto",
            mesh=None, rules=None):
    """Next-token cross-entropy; tokens (batch, seq)."""
    from ray_tpu.models.losses import chunked_softmax_xent

    x = trunk(params, tokens[:, :-1], cfg, attn_impl, mesh=mesh, rules=rules)
    return chunked_softmax_xent(x, params["lm_head"], tokens[:, 1:],
                                chunk=cfg.loss_chunk, mesh=mesh, rules=rules)
