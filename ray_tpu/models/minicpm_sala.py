"""MiniCPM-SALA (``model_type`` ``minicpm_sala``, openbmb): block-sparse
attention layers (``minicpm4``: InfLLM-v2, arXiv:2506.07900) and fixed-decay
linear-attention layers (``lightning-attn``: Lightning Attention,
arXiv:2401.04658) in one stack, ``mixer_types`` says which is which.

What ``config.json`` has no key for is marked (+) and is listed under
``assumed`` in the benchmark's configuration.

- Stream (MiniCPM's muP keys): ``x0 = scale_emb E[token]``; each sublayer
  ``x <- x + (scale_depth / sqrt(depth_base)) f(RMSNorm(x))`` with
  ``depth_base`` the PUBLISHED depth whatever depth is run; logits
  ``W_head (RMSNorm(x) / (d_model / dim_model_base))``; a SiLU-gated MLP; no
  biases.
- ``lightning-attn``: q, k, v and the gate from the normed stream, H heads
  of d each; an RMS norm a head on q and k ((+) learned [d] weights);
  rotary embedding on q and k ((+) rotate-half over the whole head,
  absolute position); a head's state ``S_t = lambda_h S_{t-1} + k_t^T v_t``
  (float32 (+)), ``o_t = q_t S_t / sqrt(d)``, ``lambda_h = exp(-2^(-8 (h +
  1) / H))`` (+); an RMS norm a head on ``o_t``; ``o_t * sigmoid(W_g h)``
  (+ the gate's form); ``W_o``.
- ``minicpm4``: q (H heads), k and v (G KV heads) and the gate; the same
  norm on q and k; NO rotary embedding; scale 1/sqrt(d); which cached
  blocks a query attends to is ops/block_sparse.py's (+ sizes: MiniCPM4.1's
  ``sparse_config``; the switch between the dense and the sparse rule is on
  the QUERY's context, so a prompt whole, in chunks and decode steps agree);
  ``o * sigmoid(W_g h)``; ``W_o``.

What is cached (``cache_layout``): K/V pages over the sparse layers; beside
each page a row of POOLED KEYS a sparse layer (``page_rows``: the mean of
the page's keys and the next page's, what the selection scores against,
complete when both pages are full); a float32 state row a slot a linear
layer (``state_rows``).  The engine keeps the pooled keys TWICE
(llm/paged_cache.py ``CacheConfig``): ``pooled_k`` a row a page, found by
page id, and ``pooled_k_by_slot``, each slot's rows in the order of its
page table, which the decode step and the suffix prefill read where they
lie (the walk below carries the pair; the three programs write both).  No
layer here finds out which program it is in: ``attend_sparse`` and
``recur_fixed`` are the program's (llm/model.py).

Parameters: ``layers`` = ``{"sparse": leaves stacked over the sparse
layers, "lin": over the linear ones}``.  ``serving_layout`` makes
``layers`` a tuple of RUNS of like layers (``cfg.runs()``), each run's
leaves stacked over its own layers, the products of the normed stream side
by side in one weight (``wqkvg`` / ``w_in``): a run of linear layers is one
``lax.scan`` over its leaves as they lie, a sparse layer is unrolled (its
pool and its rows are indexed by a static layer), and no weight is sliced
out of a stack that holds another run's.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import gated_mlp, rms_norm, rope
from ray_tpu.ops import lightning

SPARSE, LINEAR = "minicpm4", "lightning-attn"

PUBLISHED_MIXERS = (
    (SPARSE,) + (LINEAR,) * 8 + (SPARSE,) + (LINEAR,) * 6 + (SPARSE,) * 2
    + (LINEAR,) * 4 + (SPARSE,) + (LINEAR,) * 6 + (SPARSE,) * 3)

_ROWS_BESIDE = ("{cfg.__class__.__name__} keeps a state row a slot for its "
                "linear layers and a row of pooled keys a page for its "
                "sparse layers beside the K/V pages, which this engine does "
                "not serve with %s ({where}): pages alone carry neither")


@dataclass(frozen=True)
class MiniCPMSALAConfig:
    vocab_size: int = 73448
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    d_ff: int = 16384
    lightning_heads: int = 32
    lightning_head_dim: int = 128
    mixer_types: tuple = None  # one entry a layer; None: the published 32
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    depth_base: int = 32  # the published depth, under the residual's root
    # the sparse layers' sizes (ops/block_sparse.py)
    kernel_size: int = 32
    kernel_stride: int = 16
    block_size: int = 64
    init_blocks: int = 1
    window_size: int = 2048
    topk: int = 64
    dense_len: int = 8192
    max_seq_len: int = 524288
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "mixer_types", tuple(
            self.mixer_types or PUBLISHED_MIXERS))
        if (len(self.mixer_types) != self.n_layers
                or set(self.mixer_types) != {SPARSE, LINEAR}):
            raise ValueError(
                f"mixer_types names {SPARSE!r} or {LINEAR!r} for each of "
                f"{self.n_layers} layers, one of each kind at least; got "
                f"{self.mixer_types}")

    # What the engine and the served programs ask of a family (llm/model.py
    # says who owns which decision), beside ``cache_layout`` below.  It
    # TAKES a prompt in chunks and the suffix prefill: the state a chunk
    # left is the next one's initial state.
    block_length = 0  # it generates a token at a time
    window = 0  # no layer of it sees a fixed window of pages alone
    state_part = "lightning/state"  # where the programs' recurrence shows
    refuses = {
        "pd": _ROWS_BESIDE % "prefill/decode disaggregation",
        "kv_tier": _ROWS_BESIDE % "the KV tier",
        "prefix_cache": _ROWS_BESIDE % "a prefix hit",
    }

    @property
    def embed_scale(self) -> float:
        return self.scale_emb

    @property
    def logit_scale(self) -> float:
        return self.dim_model_base / self.d_model

    @property
    def residual_scale(self) -> float:
        return self.scale_depth / self.depth_base ** 0.5

    def count(self, kind: str) -> int:
        return sum(t == kind for t in self.mixer_types)

    def runs(self) -> tuple:
        """The stack as runs of like layers: (kind, index of the run's
        first layer AMONG ITS KIND, layers in the run)."""
        out, seen = [], {SPARSE: 0, LINEAR: 0}
        for t in self.mixer_types:
            if out and out[-1][0] == t:
                out[-1][2] += 1
            else:
                out.append([t, seen[t], 1])
            seen[t] += 1
        return tuple(tuple(r) for r in out)

    def cache_layout(self) -> dict:
        """What the served programs cache (``paged_cache.CacheConfig``):
        K/V pages over the SPARSE layers; ``page_rows``, a row of pooled
        keys a page a sparse layer, [n_kv_heads, head_dim]; ``state_rows``,
        the linear layers' state, float32 whatever the model is served
        in."""
        n_lin = self.count(LINEAR)
        return {"n_layers": self.count(SPARSE),
                "n_kv_heads": self.n_kv_heads, "head_dim": self.head_dim,
                "state_layers": n_lin, "scan_chunk": lightning.CHUNK,
                "state_rows": {"S": (n_lin, (
                    self.lightning_heads, self.lightning_head_dim,
                    self.lightning_head_dim), jnp.float32)},
                "page_rows": {"pooled_k": (self.count(SPARSE), (
                    self.n_kv_heads, self.head_dim), jnp.dtype(self.dtype))}}

    def serving_layout(self, params):
        return serving_layout(self, params)

    def served_walk(self, params, x, caches, positions, via):
        return served_walk(self, params, x, caches, positions, via)

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "MiniCPMSALAConfig":
        """For tests: both kinds of layer with a sparse layer neither first
        nor alone, 2 KV heads, pages of 8, blocks of 4 pages, a
        ``dense_len`` of 8 blocks under a context of 16."""
        return MiniCPMSALAConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=5, n_heads=4,
            n_kv_heads=2, head_dim=16, d_ff=96, lightning_heads=4,
            lightning_head_dim=16,
            mixer_types=(SPARSE, LINEAR, LINEAR, SPARSE, LINEAR),
            dim_model_base=32, depth_base=8, kernel_size=16, kernel_stride=8,
            block_size=32, init_blocks=1, window_size=64, topk=6,
            dense_len=256, max_seq_len=512, dtype="float32"), **kw})


def init(cfg: MiniCPMSALAConfig, key: jax.Array, dtype=jnp.float32):
    """Seeded parameters in ``dtype``: every matrix normal with variance
    1 / fan_in, norms 1, the embedding's rows of variance 1 / scale_emb^2
    so that the stream BEGINS at 1 rms."""
    k_embed, k_sparse, k_lin, k_head = jax.random.split(key, 4)
    d, f, hd = cfg.d_model, cfg.d_ff, cfg.head_dim
    hq, hkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    hl = cfg.lightning_heads * cfg.lightning_head_dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def block(key, n, widths, head_dims):
        """A kind's layers: the mixer's matrices ``widths`` (name -> (in,
        out)), its norms a head, the MLP and the block's two norms."""
        names = sorted(widths)
        ks = jax.random.split(key, len(names) + 3)
        mix = {w: dense(k, (n, *widths[w]), widths[w][0])
               for w, k in zip(names, ks)}
        mix.update({w: jnp.ones((n, width), dtype)
                    for w, width in head_dims.items()})
        return {"mix": mix,
                "mlp": {"w_gate": dense(ks[-3], (n, d, f), d),
                        "w_up": dense(ks[-2], (n, d, f), d),
                        "w_down": dense(ks[-1], (n, f, d), f)},
                "attn_norm": jnp.ones((n, d), dtype),
                "mlp_norm": jnp.ones((n, d), dtype)}

    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), cfg.scale_emb ** 2),
        "layers": {
            "sparse": block(k_sparse, cfg.count(SPARSE), {
                "wq": (d, hq), "wk": (d, hkv), "wv": (d, hkv),
                "wg": (d, hq), "wo": (hq, d)},
                {"q_norm": hd, "k_norm": hd}),
            "lin": block(k_lin, cfg.count(LINEAR), {
                "wq": (d, hl), "wk": (d, hl), "wv": (d, hl), "wg": (d, hl),
                "wo": (hl, d)}, {"q_norm": cfg.lightning_head_dim,
                                 "k_norm": cfg.lightning_head_dim,
                                 "o_norm": cfg.lightning_head_dim})},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
    }


_IN = ("wq", "wk", "wv", "wg")  # the products of the normed stream


def serving_layout(cfg: MiniCPMSALAConfig, params):
    """The tree as the served programs hold it: ``layers`` a tuple of the
    stack's runs (``cfg.runs()``), a run's leaves stacked over its own
    layers, with ``wq``, ``wk``, ``wv`` and ``wg`` side by side as ONE
    ``w_in`` (every split on a head, a lane tile at 128).  A tree that is
    laid out so comes back as it is."""
    layers = params["layers"]
    if isinstance(layers, tuple):
        return params
    by_kind = {SPARSE: layers["sparse"], LINEAR: layers["lin"]}

    def run(kind, first, n):
        p = jax.tree.map(lambda w: w[first:first + n], by_kind[kind])
        mix = dict(p["mix"])
        mix["w_in"] = jnp.concatenate([mix.pop(w) for w in _IN], axis=-1)
        return {**p, "mix": mix}

    return {**params, "layers": tuple(run(*r) for r in cfg.runs())}


# ---------------------------------------------------------------------------
# The two layers, as parts.  ``p`` is one layer's parameters.

def _heads(cfg, mix, h, widths, head_dim):
    """The normed stream's products, split and shaped to heads: one for
    each of ``widths`` (q, k, v, gate), from ``w_in`` or the four."""
    if "w_in" in mix:
        edges = [sum(widths[:i + 1]) for i in range(len(widths) - 1)]
        ys = jnp.split(h @ mix["w_in"].astype(h.dtype), edges, axis=-1)
    else:
        ys = [h @ mix[w].astype(h.dtype) for w in _IN]
    q, k, v, g = (y.reshape(*h.shape[:-1], -1, head_dim) for y in ys)
    return (rms_norm(q, mix["q_norm"], cfg.norm_eps),
            rms_norm(k, mix["k_norm"], cfg.norm_eps), v, g)


def _mlp_block(cfg, p, x):
    with jax.named_scope("mlp/norm"):
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    return x + cfg.residual_scale * gated_mlp(p, h)


def sparse_layer(cfg, p, x, attend, cache):
    """One ``minicpm4`` layer: (x, cache).  ``attend(q, k, v, cache) ->
    (out [..., H, d], cache)`` caches, selects and attends its own way."""
    mix, hd = p["mix"], cfg.head_dim
    with jax.named_scope("attn/norm"):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    with jax.named_scope("sparse_attn/proj"):
        hq, hkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
        q, k, v, g = _heads(cfg, mix, h, (hq, hkv, hkv, hq), hd)
    out, cache = attend(q, k, v, cache)
    with jax.named_scope("sparse_attn/proj"):
        out = out.astype(x.dtype) * jax.nn.sigmoid(g)
        y = out.reshape(*out.shape[:-2], -1) @ mix["wo"].astype(x.dtype)
        x = x + cfg.residual_scale * y
    return _mlp_block(cfg, p, x), cache


def lightning_layer(cfg, p, x, positions, recur, cache):
    """One ``lightning-attn`` layer: (x, cache).  ``recur(q, k, v, g,
    cache) -> (o [..., H, d] float32, cache)`` runs the recurrence its own
    way: over a sequence from an initial state, or one token a slot from
    the slot's row; g [H] is the heads' log decay."""
    mix, hd, H = p["mix"], cfg.lightning_head_dim, cfg.lightning_heads
    with jax.named_scope("attn/norm"):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    with jax.named_scope("lightning/proj"):
        q, k, v, g = _heads(cfg, mix, h, (H * hd,) * 4, hd)
    q = rope(q, positions, cfg.rope_theta)
    k = rope(k, positions, cfg.rope_theta)
    o, cache = recur(q, k, v, lightning.log_decays(H), cache)
    with jax.named_scope("lightning/out"):
        o = rms_norm(o * hd ** -0.5, mix["o_norm"].astype(jnp.float32),
                     cfg.norm_eps)
        o = o.astype(x.dtype) * jax.nn.sigmoid(g)
        y = o.reshape(*o.shape[:-2], -1) @ mix["wo"].astype(x.dtype)
        x = x + cfg.residual_scale * y
    return _mlp_block(cfg, p, x), cache


def served_walk(cfg, params, x, caches, positions, via):
    """``llama.served_walk`` over the stack's RUNS.  ``caches`` = (K pool,
    V pool, state), the state a dict of the linear layers' rows ``S`` (or
    None where the program writes them itself, once) and the pooled keys'
    rows ``pooled_k`` and ``pooled_k_by_slot``, handed on as the pair
    ``pooled``.  ``via["attend_sparse"](q, k, v, (ck, cv, pooled,
    li)) -> (out, (ck, cv, pooled), counted)``, ``counted`` what the call
    counted on the device by name, summed here over the sparse layers (they
    are unrolled) into the third thing returned, ONE vector under the tuple
    of its names (a transfer a step, not one a name); ``via["recur_fixed"](q, k,
    v, g, (S, li)) -> (o, (S, left))`` updates a layer's rows in place, or
    leaves them be and hands back as ``left`` the row the program is to
    write: the fourth thing returned, ``{"S": [linear layers, ...]}``."""
    cache_k, cache_v, state = caches
    S = state["S"]
    pooled = state["pooled_k"], state["pooled_k_by_slot"]
    left, counted = [], {}

    def attend(q, k, v, pools):
        out, pools, did = via["attend_sparse"](q, k, v, pools)
        for name, n in did.items():
            counted[name] = counted.get(name, 0) + n
        return out, pools

    def linear(carry, per_layer):
        x, S = carry
        p, li = per_layer
        x, (S, out) = lightning_layer(cfg, p, x, positions,
                                      via["recur_fixed"], (S, li))
        return (x, S), out

    with jax.named_scope("layers"):
        for p, (kind, first, n) in zip(params["layers"], cfg.runs()):
            if kind == LINEAR:
                (x, S), out = jax.lax.scan(
                    linear, (x, S),
                    (p, first + jnp.arange(n, dtype=jnp.int32)))
                left.append(out)
                continue
            for i in range(n):
                x, (cache_k, cache_v, pooled) = sparse_layer(
                    cfg, jax.tree.map(lambda w: w[i], p), x, attend,
                    (cache_k, cache_v, pooled, first + i))
    left = (None if left[0] is None
            else {"S": jnp.concatenate(left, axis=0)})
    names = tuple(sorted(counted))
    by_page, by_slot = pooled
    return (x, (cache_k, cache_v, {"S": S, "pooled_k": by_page,
                                   "pooled_k_by_slot": by_slot}),
            {names: jnp.stack([counted[n] for n in names])}, left)
