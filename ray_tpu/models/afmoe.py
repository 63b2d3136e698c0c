"""Trinity-Mini (``model_type`` ``afmoe``): sliding-window and full
attention layers in one stack (three window layers of 2,048 to every full
one), gated attention with a norm a head on q and k, sandwich norms, leading
dense layers, then sigmoid-scored top-8-of-128 routed experts beside a
shared expert.

What ``config.json`` has no key for is marked (+): it is as ``afmoe``'s
published modelling code has it, not read off the configuration.

- Embedding: ``x0 = E[token] * sqrt(d_model)`` (``mup_enabled`` (+):
  ``embed_scale``, which ``llama.embed`` applies).
- Block, every layer (+ sandwich norm: four RMS norms a layer):
  ``h = x + N_post_attn(Attn(N_in(x)))``;
  ``y = h + N_post_mlp(FFN(N_pre_mlp(h)))``.  Final norm, then the head.
- Attention, every layer, with ``u = N_in(x)``: ``q = RMSNorm_d(u W_q)`` a
  head, ``k = RMSNorm_d(u W_k)`` a KV head (+ QK norm, learned [head_dim]
  weights), ``v = u W_v``, ``g = sigmoid(u W_g)`` [H x head_dim] (+ output
  gate, a fifth matrix from the layer's normed input).  Scores ``q_h .
  k_kv(h) / sqrt(head_dim)``, causal softmax, ``o = (concat_h(P_h v_kv(h))
  * g) W_o``.
  - ``layer_types[i] == "sliding_attention"``: rotary embedding on q and k
    (rotate-half over the whole head, ``llama.rope``), and a query at
    position i sees keys j with ``0 <= i - j < sliding_window`` (+ the
    convention: the window holds ``sliding_window`` keys, the query's own
    among them).
  - ``"full_attention"``: (+) NO positional embedding, q and k are not
    rotated; every key ``j <= i``.
- The first ``n_dense_layers`` layers a SiLU-gated MLP of ``d_ff``; every
  other ``moe.routed_mlp`` as GLM-4.7-Flash's: ``s = sigmoid(h W_r)`` in
  float32, the experts the top k of ``s + expert_bias`` (+, for the CHOICE
  only), their weights ``s`` of the chosen over their sum (``route_norm``)
  times ``route_scale``; ``n_group`` 1 = ``topk_group`` 1, so the group
  limit is the identity and is not written.

What is cached: K and V pages, in TWO pools, one a kind of layer
(``cache_layout``; llm/paged_cache.py says how a sequence holds the window
layers' pages).  Which layer is of which kind is ``layer_types``, said here
once: ``walk_layers`` unrolls the layers in Python and hands each its
kind, so no program and no scan body finds it out (and compile time grows
with depth: a scan over whole periods of the pattern is what a deeper cut
would want).

Parameters: ``dense`` (the leading dense layers, leaves stacked over them)
and ``layers`` (the sparse ones); both hold ``attn`` = ``wq``, ``wk``,
``wv``, ``wg``, ``wo``, ``q_norm``, ``k_norm`` and the four norms
``attn_norm``, ``post_attn_norm``, ``mlp_norm``, ``post_mlp_norm``.
``serving_layout`` holds every layer's leaves APART (``dense`` a tuple of
layers, ``layers`` = ``{"each": a tuple of layers, "experts": as they
were}``: the walk is unrolled, so each weight is a parameter of its own
and nothing is sliced out of a stack) with the four products from the
normed stream as ONE ``wqkvg`` (every split on a lane tile); every function
here takes either.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import embed, gated_mlp, head, rms_norm, rope
from ray_tpu.models.moe import routed_mlp

SLIDING, FULL = "sliding_attention", "full_attention"
KIND = {SLIDING: "window", FULL: "full"}  # a layer type's pool

_TWO_POOLS = ("{cfg.__class__.__name__} keeps a window layer's pages only "
              "while they reach into the last {cfg.sliding_window} "
              "positions and gives the rest back while the sequence lives, "
              "so %s ({where}) would find the window layers' rows gone: "
              "not served")


@dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 200192
    d_model: int = 2048
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 6144
    n_dense_layers: int = 2
    d_expert: int = 1024
    n_experts: int = 128
    experts_per_token: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True  # route_norm
    routed_scaling_factor: float = 2.826  # route_scale
    # one entry a layer; None: ``global_attn_every_n_layers`` 4, the fourth
    layer_types: tuple = None
    sliding_window: int = 2048
    mup_enabled: bool = True
    max_seq_len: int = 131072
    rope_theta: float = 10_000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(
            self.layer_types or (FULL if (i + 1) % 4 == 0 else SLIDING
                                 for i in range(self.n_layers))))
        if (len(self.layer_types) != self.n_layers
                or set(self.layer_types) - set(KIND)):
            raise ValueError(
                f"layer_types names one of {sorted(KIND)} for each of "
                f"{self.n_layers} layers; got {self.layer_types}")
        if not 0 <= self.n_dense_layers < self.n_layers:
            raise ValueError(
                f"{self.n_dense_layers} leading dense layers leave no sparse "
                f"layer of {self.n_layers}")
        if not {SLIDING, FULL} <= set(self.layer_types):
            raise ValueError(
                "this family is served over a pool a kind of layer and "
                "wants one layer of each kind at least")

    # What the engine and the served programs ask of a family (llm/model.py
    # says who owns which decision), beside ``cache_layout`` below.
    block_length = 0  # it generates a token at a time
    refuses = {
        "prefix_cache": _TWO_POOLS % "a prefix hit",
        "pd": _TWO_POOLS % "prefill/decode disaggregation, which ships a "
                           "prompt's pages of one kind",
        "kv_tier": _TWO_POOLS % "the KV tier, which seals pages of one kind",
    }

    @property
    def window(self) -> int:
        """Positions a window layer sees, the query's own among them."""
        return self.sliding_window

    @property
    def embed_scale(self):
        return self.d_model ** 0.5 if self.mup_enabled else None

    def kinds(self) -> tuple:
        """A layer's (pool, index among the layers of its pool)."""
        seen = {"full": 0, "window": 0}
        out = []
        for t in self.layer_types:
            out.append((KIND[t], seen[KIND[t]]))
            seen[KIND[t]] += 1
        return tuple(out)

    def cache_layout(self) -> dict:
        """What the served programs cache (``paged_cache.CacheConfig``): K/V
        pages of ``n_kv_heads`` x ``head_dim`` in two pools, ``n_layers``
        FULL layers that keep every token and ``window_layers`` that keep
        the last ``window``."""
        n_window = sum(t == SLIDING for t in self.layer_types)
        return {"n_layers": self.n_layers - n_window,
                "n_kv_heads": self.n_kv_heads, "head_dim": self.head_dim,
                "window_layers": n_window, "window": self.sliding_window}

    def serving_layout(self, params):
        return serving_layout(params)

    def served_walk(self, params, x, caches, positions, via):
        """``llama.served_walk`` over pools by kind: ``caches`` holds a
        dict of two pools for K and one for V, ``via["attend_by_kind"]``
        the program's closure a kind; a layer gets its kind's."""
        cache_k, cache_v, state = caches
        attends, kinds = via["attend_by_kind"], self.kinds()

        def body(carry, p, li, feed_forward):
            x, ck, cv = carry
            kind, i = kinds[li]
            x, (k, v) = layer(self, p, x, positions, attends[kind],
                              (ck[kind], cv[kind], i), feed_forward,
                              rotate=kind == "window")
            return x, {**ck, kind: k}, {**cv, kind: v}

        (x, cache_k, cache_v), hit = walk_layers(
            self, params, body, (x, cache_k, cache_v))
        return x, (cache_k, cache_v, state), {"experts_read": hit}, None

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "AfmoeConfig":
        """For tests: a window (32) that is four pages of 8, a full layer
        that is neither first nor last, one dense layer, 8 experts top 2."""
        return AfmoeConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=5, n_heads=4,
            n_kv_heads=2, head_dim=16, d_ff=96, n_dense_layers=1,
            d_expert=32, n_experts=8, experts_per_token=2,
            layer_types=(SLIDING, SLIDING, SLIDING, FULL, SLIDING),
            sliding_window=32, max_seq_len=256, dtype="float32"), **kw})


def init(cfg: AfmoeConfig, key: jax.Array, dtype=jnp.float32,
         bias_sd: float = 0.05):
    """Seeded parameters in ``dtype``: every matrix normal with variance
    1 / fan_in, norms 1, the embedding's rows of variance 1 / d_model so
    that the stream BEGINS at 1 rms with the sqrt(d_model) factor, and a
    NON-ZERO ``router_bias`` (normal, sd ``bias_sd``, float32: a trained
    checkpoint's is not zero, and with zero the choice could not differ
    from the weights' order).  The experts are drawn and cast a layer at a
    time (models/sdar_moe.py ``init``)."""
    k_embed, k_dense, k_sparse, k_head = jax.random.split(key, 4)
    d, hd = cfg.d_model, cfg.head_dim
    hq, hkv = cfg.n_heads * hd, cfg.n_kv_heads * hd
    n_dense, n_sparse = cfg.n_dense_layers, cfg.n_layers - cfg.n_dense_layers
    ne, f, fs = cfg.n_experts, cfg.d_expert, (cfg.n_shared_experts
                                              * cfg.d_expert)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def attn(key, nl):
        ks = jax.random.split(key, 5)
        return {"wq": dense(ks[0], (nl, d, hq), d),
                "wk": dense(ks[1], (nl, d, hkv), d),
                "wv": dense(ks[2], (nl, d, hkv), d),
                "wg": dense(ks[3], (nl, d, hq), d),
                "wo": dense(ks[4], (nl, hq, d), hq),
                "q_norm": jnp.ones((nl, hd), dtype),
                "k_norm": jnp.ones((nl, hd), dtype)}

    def mlp(key, nl, width):
        ks = jax.random.split(key, 3)
        return {"w_gate": dense(ks[0], (nl, d, width), d),
                "w_up": dense(ks[1], (nl, d, width), d),
                "w_down": dense(ks[2], (nl, width, d), width)}

    def norms(nl):
        return {name: jnp.ones((nl, d), dtype) for name in (
            "attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm")}

    def experts(key, shape, fan_in):
        return jax.lax.map(lambda k: dense(k, shape, fan_in),
                           jax.random.split(key, n_sparse))

    kd, ks = jax.random.split(k_dense, 2), jax.random.split(k_sparse, 7)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d),
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
    """The tree as the served programs hold it: ``dense`` a tuple of its
    layers and ``layers`` = ``{"each": a tuple of the sparse layers,
    "experts": stacked as they were (the kernel indexes them where they
    lie)}``, a layer's leaves its own arrays (``walk_layers`` says what
    that changed on the chip);
    in each ``wq``, ``wk``, ``wv`` and ``wg`` side by side as ONE ``wqkvg``
    [d_model, (2 n_heads + 2 n_kv_heads) head_dim] (one product a layer
    from the normed stream, as ``llama.serving_layout``'s ``wqkv``; every
    split falls on a head, a lane tile at head_dim 128), the four dropped.
    A tree that is laid out so comes back as it is."""
    if "each" in params["layers"]:
        return params

    def apart(stack):
        n = jax.tree.leaves(stack)[0].shape[0]
        out = []
        for i in range(n):
            p = jax.tree.map(lambda w: w[i], stack)
            a = dict(p["attn"])
            a["wqkvg"] = jnp.concatenate(
                [a.pop("wq"), a.pop("wk"), a.pop("wv"), a.pop("wg")],
                axis=-1)
            out.append({**p, "attn": a})
        return tuple(out)

    sparse = {k: v for k, v in params["layers"].items() if k != "experts"}
    return {**params, "dense": apart(params["dense"]),
            "layers": {"each": apart(sparse),
                       "experts": params["layers"]["experts"]}}


# ---------------------------------------------------------------------------
# The block, as parts.  ``p`` is one layer's parameters.

def qkvg(cfg, p, h, positions, rotate: bool):
    """The normed stream h (..., d_model) -> q (..., H, d) and k (..., Hkv,
    d), each normed a head and, ``rotate`` (a window layer), rotated; v
    (..., Hkv, d); the gate's pre-activation (..., H, d)."""
    a = p["attn"]
    nq, nkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    with jax.named_scope("attn/qkv"):
        if "wqkvg" in a:  # serving_layout: one product, then split
            q, k, v, g = jnp.split(h @ a["wqkvg"].astype(h.dtype),
                                   (nq, nq + nkv, nq + 2 * nkv), axis=-1)
        else:
            q, k, v, g = (h @ a[w].astype(h.dtype)
                          for w in ("wq", "wk", "wv", "wg"))
        q, k, v, g = (y.reshape(*h.shape[:-1], -1, cfg.head_dim)
                      for y in (q, k, v, g))
    with jax.named_scope("attn/qk_norm"):
        q = rms_norm(q, a["q_norm"], cfg.norm_eps)
        k = rms_norm(k, a["k_norm"], cfg.norm_eps)
    if rotate:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    return q, k, v, g


def attention_block(cfg, p, x, positions, attend, cache, rotate: bool):
    """x + N_post(gated attention(N_in(x))): ``llama.attention_block``'s
    twin.  ``attend(q, k, v, cache) -> (out, cache)`` is the caller's, of
    the layer's kind."""
    with jax.named_scope("attn/norm"):
        h = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    q, k, v, g = qkvg(cfg, p, h, positions, rotate)
    out, cache = attend(q, k, v, cache)
    with jax.named_scope("attn/gate"):
        out = out * jax.nn.sigmoid(g)
    with jax.named_scope("attn/out"):
        out = out.reshape(*out.shape[:-2], -1) @ p["attn"]["wo"].astype(
            x.dtype)
    with jax.named_scope("norm/post"):
        return x + rms_norm(out, p["post_attn_norm"], cfg.norm_eps), cache


def layer(cfg, p, x, positions, attend, cache, feed_forward, rotate: bool):
    """One sandwich-norm decoder layer: (x, cache)."""
    x, cache = attention_block(cfg, p, x, positions, attend, cache, rotate)
    with jax.named_scope("mlp/norm"):
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    out = feed_forward(p, h)
    with jax.named_scope("norm/post"):
        return x + rms_norm(out, p["post_mlp_norm"], cfg.norm_eps), cache


def walk_layers(cfg, params, body, carry):
    """``body(carry, layer_params, li, feed_forward)`` over every layer in
    order, UNROLLED (a layer's kind is static, and its pool's shape with
    it): the leading dense ones with the gated MLP, then the sparse ones
    with ``routed_mlp`` as ``cfg`` has it, their experts left stacked over
    the sparse layers and indexed where they are read.  A layer's other
    leaves are taken where ``serving_layout`` put them apart, or sliced
    out of the stacked tree (``init``'s).  On the chip either way XLA
    PREFETCHES the layers' weights into fast memory beside the compute
    (``copy-start`` / ``slice-start`` into ``S(1)``, which carry no part's
    name: 38 of them a decode step over the stacked tree, 133, in 512-row
    slices, over leaves held apart); apart, the attention products and
    those waits together read 455 us a step where they read 525 (PERF.md
    section 6, PR 46).  Returns (carry, experts read, summed over the
    sparse layers)."""
    dense, sparse = params["dense"], params["layers"]
    experts = sparse["experts"]
    if "each" in sparse:
        of = {"dense": dense.__getitem__, "sparse": sparse["each"].__getitem__}
    else:
        sparse = {k: v for k, v in sparse.items() if k != "experts"}
        of = {"dense": lambda i: jax.tree.map(lambda w: w[i], dense),
              "sparse": lambda i: jax.tree.map(lambda w: w[i], sparse)}
    hits = []
    with jax.named_scope("layers"):
        for li in range(cfg.n_layers):
            i = li - cfg.n_dense_layers
            if i < 0:
                carry = body(carry, of["dense"](li), li, gated_mlp)
                continue

            def routed(p, h, i=i):
                out, n = routed_mlp(
                    h, p["router"], experts, i, top_k=cfg.experts_per_token,
                    renormalise=cfg.norm_topk_prob, bias=p["router_bias"],
                    scale=cfg.routed_scaling_factor, shared=p["shared"])
                hits.append(n)
                return out

            carry = body(carry, of["sparse"](i), li, routed)
    return carry, sum(hits, jnp.int32(0))


def batch_attend(cfg, positions, window: int = 0):
    """The cacheless pass's ``attend`` over a batch (b, s, ...): dense
    masked attention in plain ``jax.numpy``, causal and, ``window`` > 0,
    over the last ``window`` keys."""
    seen = positions[None, :] <= positions[:, None]
    if window:
        seen &= positions[:, None] - positions[None, :] < window
    rep = cfg.n_heads // cfg.n_kv_heads

    def attend(q, k, v, cache):
        with jax.named_scope("attn/attend"):
            k, v = (jnp.repeat(y, rep, axis=2) for y in (k, v))
            scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) * cfg.head_dim ** -0.5
            p = jax.nn.softmax(jnp.where(seen, scores, -1e30).astype(
                jnp.float32), axis=-1)
            return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v), cache

    return attend


@partial(jax.jit, static_argnames=("cfg",))
def apply(params, tokens, cfg: AfmoeConfig):
    """Cacheless forward: tokens (batch, seq) -> logits (batch, seq, vocab)
    float32."""
    positions = jnp.arange(tokens.shape[1])
    attends = {"full": batch_attend(cfg, positions),
               "window": batch_attend(cfg, positions, cfg.sliding_window)}
    kinds = cfg.kinds()

    def body(x, p, li, feed_forward):
        kind, _ = kinds[li]
        return layer(cfg, p, x, positions[None, :], attends[kind], None,
                     feed_forward, rotate=kind == "window")[0]

    x, _ = walk_layers(cfg, params, body, embed(params, tokens, cfg))
    return head(params, x, cfg)
