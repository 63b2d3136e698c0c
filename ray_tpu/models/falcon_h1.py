"""Falcon-H1 (``model_type`` ``falcon_h1``, tiiuae): a Mamba-2 mixer BESIDE
grouped-query attention in every block, both fed from one normed input and
summed into the stream, then a SiLU-gated MLP; muP multipliers on every
branch (fourteen published scalars).

Published keys in backticks; ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``.
What ``config.json`` has no key for is marked (+) and listed under
``assumed`` in the benchmark's configuration.

- Stream: ``h = E[token] * embedding_multiplier``; a block
  ``u = rms(h; w_1)``, ``h <- h + ssm_out_multiplier * Mixer(u) +
  attention_out_multiplier * Attn(u * attention_in_multiplier)``,
  ``h <- h + MLP(rms(h; w_2))``; ``logits = (rms(h; w_f) W_head) *
  lm_head_multiplier``; no biases but the convolution's.
- Attn: q (``num_attention_heads`` of ``head_dim``), ``k = (u W_k) *
  key_multiplier`` and v (``num_key_value_heads``); rotary embedding ((+)
  rotate-half over the whole head) at ``rope_theta``; causal softmax at
  head_dim^-0.5; ``W_o``.
- Mixer (Mamba-2, Dao and Gu, arXiv:2405.21060): ``p = ((u *
  ssm_in_multiplier) W_in) * m``, ``W_in`` d -> z (``mamba_d_ssm``) | x
  (``mamba_d_ssm``) | B and C (``mamba_n_groups`` x ``mamba_d_state`` each)
  | dt (``mamba_n_heads``), ``m`` the vector of ``ssm_multipliers`` over
  those five segments ((+) in that order).  ``x | B | C`` pass a causal
  depthwise convolution of width ``mamba_d_conv`` with a bias, then SiLU.
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)`` a head.  Head h of
  group g = h // (heads / groups): ``S_t = exp(dt_t A) S_{t-1} + B_t^g
  (dt_t x_t)^T`` (S [d_state, d_head], float32 (+)), ``y_t = C_t^g S_t +
  D_h x_t``.  ``y = rms_G(y * silu(z); w_n)``, the mean over each GROUP's
  columns (+) (``mamba_norm_before_gate`` false: the gate first);
  ``Mixer(u) = y W_out``.
- MLP: ``a = silu((x W_g) * mlp_multipliers[0]) * (x W_u)``; ``(a W_d) *
  mlp_multipliers[1]``.

The recurrence is ops/lightning.py's with ``k = B``, ``v = dt x``, ``q = C``
and ``g = dt A``: the decay's log a token a head, keys and queries a group.
The short convolution is models/olmo_hybrid.py's ``short_conv``.

What is cached (``cache_layout``): layer i owns POOL layer i (K/V pages of
its attention) AND STATE layer i (a float32 row [heads, d_state, d_head] a
slot and the convolution's last ``mamba_d_conv - 1`` inputs, a row each, a
layer's one behind the other as Olmo-Hybrid keeps them).  No layer here
finds out which program it is in: ``attend`` and ``recur_fixed`` are the
program's (llm/model.py); the walk below is ONE ``lax.scan`` whose body
hands both the same ``u`` and adds what they return.

Parameters: ``layers`` = ``{"attn": wq wk wv wo, "ssm": w_in conv conv_bias
dt_bias A_log D norm w_out, "mlp": w_gate w_up w_down, "attn_norm",
"mlp_norm"}``, every leaf stacked over the layers.  ``serving_layout`` is
``llama.serving_layout`` (one ``wqkv``).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.models.llama import embed, head, qkv_rope, rms_norm
from ray_tpu.models.olmo_hybrid import short_conv
from ray_tpu.ops import lightning

CONV_PART = "ssm/conv"  # the short convolution's, here and in llm/model.py
_STATE_BESIDE = ("{cfg.__class__.__name__} has a recurrent mixer in every "
                 "block whose state is a row a slot beside the pages, which "
                 "this engine does not serve with %s ({where}): pages alone "
                 "carry nothing of the state at their end")
_NO_STATE_IN_PAGES = ("{where} serves no model with a recurrent mixer beside "
                      "its attention: the pages of a prefix hold nothing of "
                      "the state at its end")


@dataclass(frozen=True)
class FalconH1Config:
    vocab_size: int = 261120
    d_model: int = 5120
    n_layers: int = 72
    n_heads: int = 20
    n_kv_heads: int = 4
    head_dim: int = 128
    d_ff: int = 21504
    ssm_heads: int = 32  # mamba_n_heads
    ssm_head_dim: int = 128  # mamba_d_head
    ssm_state: int = 256  # mamba_d_state
    ssm_groups: int = 2  # mamba_n_groups
    conv_width: int = 4  # mamba_d_conv
    # the muP multipliers, as published
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # over z | x | B | C | dt of the mixer's input product
    ssm_multipliers: tuple = (0.3535533905932738, 0.25, 0.1767766952966369,
                              0.5, 0.3535533905932738)
    mlp_multipliers: tuple = (0.1767766952966369, 0.011160714285714284)
    max_seq_len: int = 262144
    rope_theta: float = 1e11
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        object.__setattr__(self, "ssm_multipliers",
                           tuple(self.ssm_multipliers))
        object.__setattr__(self, "mlp_multipliers",
                           tuple(self.mlp_multipliers))
        if (self.ssm_heads % self.ssm_groups or len(self.ssm_multipliers) != 5
                or len(self.mlp_multipliers) != 2):
            raise ValueError(
                f"{self.ssm_heads} mixer heads in {self.ssm_groups} groups, "
                f"{len(self.ssm_multipliers)} ssm_multipliers (five) and "
                f"{len(self.mlp_multipliers)} mlp_multipliers (two)")

    # What the engine and the served programs ask of a family (llm/model.py
    # says who owns which decision), beside ``cache_layout`` below.  It
    # TAKES a prompt in chunks: the state and the convolution's tail that a
    # chunk left are the next one's, through ``recur_fixed``.
    block_length = 0  # it generates a token at a time
    window = 0  # its attention sees every position
    state_part = "ssm/state"  # where the programs' recurrence shows
    refuses = {
        "pd": _STATE_BESIDE % "prefill/decode disaggregation",
        "kv_tier": _STATE_BESIDE % "the KV tier",
        "prefix_cache": _NO_STATE_IN_PAGES,
    }

    @property
    def embed_scale(self) -> float:
        return self.embedding_multiplier

    @property
    def logit_scale(self) -> float:
        return self.lm_head_multiplier

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        """x | B | C: what passes the short convolution."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def in_segments(self) -> tuple:
        """Columns of z | x | B | C | dt in the mixer's input product."""
        gn = self.ssm_groups * self.ssm_state
        return (self.d_ssm, self.d_ssm, gn, gn, self.ssm_heads)

    def cache_layout(self) -> dict:
        """What the served programs cache (``paged_cache.CacheConfig``):
        K/V pages over EVERY layer, and beside them every layer's state: a
        float32 row [heads, d_state, d_head] a slot whatever the model is
        served in, and the convolution's last ``conv_width - 1`` inputs,
        a row each, a layer's one behind the other."""
        n, taps = self.n_layers, self.conv_width - 1
        return {"n_layers": n, "n_kv_heads": self.n_kv_heads,
                "head_dim": self.head_dim, "state_layers": n,
                "scan_chunk": lightning.CHUNK,
                "state_rows": {
                    "S": (n, (self.ssm_heads, self.ssm_state,
                              self.ssm_head_dim), jnp.float32),
                    "conv": (n * taps, (self.conv_channels,),
                             jnp.dtype(self.dtype))}}

    def serving_layout(self, params):
        return llama.serving_layout(params)

    def served_walk(self, params, x, caches, positions, via):
        return served_walk(self, params, x, caches, positions, via)

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "FalconH1Config":
        """For tests: three layers, five query heads to each of two KV
        heads, mixer heads in two groups with d_state unequal to d_head."""
        return FalconH1Config(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=3, n_heads=4,
            n_kv_heads=2, head_dim=16, d_ff=96, ssm_heads=4, ssm_head_dim=16,
            ssm_state=32, ssm_groups=2, max_seq_len=512, dtype="float32"),
            **kw})


def init(cfg: FalconH1Config, key: jax.Array, dtype=jnp.float32):
    """Seeded parameters in ``dtype``.  Matrices of variance 1 / fan_in
    would leave the published multipliers muting whole branches (keys x
    0.011: a uniform attention; logits x 0.0078: a flat head), and a fault
    in a muted branch shows nowhere.  So every matrix that a multiplier
    FOLLOWS is drawn at 1 / (fan_in multiplier^2): what follows the
    multiplier is then of variance 1 (the keys, the five segments of the
    mixer's input, the MLP's gate, both branches' outputs, the MLP's, the
    logits; W_o three times wider still), with the multipliers applied as
    published.  The embedding's
    rows have variance 1 / embedding_multiplier^2: the stream begins at 1
    rms.  ``A_log = log A``, A uniform in [1, 16]; ``dt_bias`` such that
    ``softplus(dt_bias)`` is log-uniform in [0.001, 0.1] (Mamba-2's own
    initialisation); ``D`` 1; taps normal / 2 and the convolution's bias
    normal / 10; norms 1.  ``A_log``, ``dt_bias`` and ``D`` stay float32."""
    d, f, n = cfg.d_model, cfg.d_ff, cfg.n_layers
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    ks = jax.random.split(key, 14)

    def dense(key, shape, fan_in, follows=1.0):
        return (jax.random.normal(key, shape, jnp.float32)
                / (fan_in ** 0.5 * follows)).astype(dtype)

    m = _in_multipliers(cfg, jnp.float32) * cfg.ssm_in_multiplier
    dt = jnp.exp(jax.random.uniform(
        ks[8], (n, cfg.ssm_heads), jnp.float32, jnp.log(1e-3), jnp.log(0.1)))
    return {
        "embed": dense(ks[0], (cfg.vocab_size, d), 1.0,
                       cfg.embedding_multiplier),
        "layers": {
            "attn": {
                "wq": dense(ks[1], (n, d, hq), d,
                            cfg.attention_in_multiplier),
                "wk": dense(ks[2], (n, d, hkv), d,
                            cfg.attention_in_multiplier * cfg.key_multiplier),
                "wv": dense(ks[3], (n, d, hkv), d,
                            cfg.attention_in_multiplier),
                # (a softmax over n keys at unit scores averages their
                # values down to sqrt(e / n) rms: three times wider, so
                # that attention enters the stream within a factor of 3 of
                # the mixer at contexts of 100 to 1,500)
                "wo": dense(ks[4], (n, hq, d), hq,
                            cfg.attention_out_multiplier / 3.0)},
            "ssm": {
                "w_in": (jax.random.normal(
                    ks[5], (n, d, sum(cfg.in_segments)), jnp.float32)
                    / (d ** 0.5 * m)).astype(dtype),
                "conv": (jax.random.normal(
                    ks[6], (n, cfg.conv_width, cfg.conv_channels),
                    jnp.float32) / 2).astype(dtype),
                "conv_bias": (jax.random.normal(
                    ks[7], (n, cfg.conv_channels), jnp.float32)
                    / 10).astype(dtype),
                # softplus(dt_bias) = dt
                "dt_bias": jnp.log(jnp.expm1(dt)),
                "A_log": jnp.log(jax.random.uniform(
                    ks[9], (n, cfg.ssm_heads), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((n, cfg.ssm_heads), jnp.float32),
                "norm": jnp.ones((n, cfg.d_ssm), dtype),
                "w_out": dense(ks[10], (n, cfg.d_ssm, d), cfg.d_ssm,
                               cfg.ssm_out_multiplier)},
            "mlp": {
                "w_gate": dense(ks[11], (n, d, f), d, cfg.mlp_multipliers[0]),
                "w_up": dense(ks[12], (n, d, f), d),
                "w_down": dense(ks[13], (n, f, d), f,
                                cfg.mlp_multipliers[1])},
            "attn_norm": jnp.ones((n, d), dtype),
            "mlp_norm": jnp.ones((n, d), dtype)},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(jax.random.fold_in(key, 99), (d, cfg.vocab_size), d,
                         cfg.lm_head_multiplier),
    }


# ---------------------------------------------------------------------------
# The block, as parts.  ``p`` is one layer's parameters.


def _in_multipliers(cfg, dtype):
    """``m``: ``ssm_multipliers`` over the five segments of the mixer's
    input product, [z + x + B + C + dt]."""
    return jnp.concatenate([jnp.full((n,), s, dtype) for s, n in zip(
        cfg.ssm_multipliers, cfg.in_segments)])


def mixer(cfg, p, u, recur, cache):
    """The Mamba-2 mixer of the normed stream u [..., d]: (``Mixer(u)``,
    cache).  ``recur(None, None, None, None, cache, conv=(taps, bias, xBC,
    gates)) -> (y [..., H, d_head] float32, cache)`` convolves from the rows
    that came before (zeros, or what its slot holds), makes the
    recurrence's inputs of the convolved rows through ``gates`` and runs it
    its own way: over a sequence from an initial state, or one token a slot
    from the slot's row."""
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    f32 = jnp.float32
    with jax.named_scope("ssm/proj"):
        y = ((u * jnp.asarray(cfg.ssm_in_multiplier, u.dtype))
             @ p["w_in"].astype(u.dtype)) * _in_multipliers(cfg, u.dtype)
        z, xbc, dt = jnp.split(
            y, (cfg.d_ssm, cfg.d_ssm + cfg.conv_channels), axis=-1)

    def gates(y):  # the convolved rows [..., x + B + C], float32
        with jax.named_scope("ssm/gates"):
            x, B, C = jnp.split(y, (cfg.d_ssm, cfg.d_ssm + G * N), axis=-1)
            x = x.reshape(*x.shape[:-1], H, P)
            B, C = (t.reshape(*t.shape[:-1], G, N) for t in (B, C))
            step = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
            g = -jnp.exp(p["A_log"].astype(f32)) * step
            skip = p["D"].astype(f32)[:, None] * x
            return C, B, x * step[..., None], g, skip

    y, cache = recur(None, None, None, None, cache,
                     conv=(p["conv"], p["conv_bias"], xbc, gates))
    with jax.named_scope("ssm/out"):
        y = gated_norm(cfg, p, y.reshape(*y.shape[:-2], cfg.d_ssm), z)
        return y.astype(u.dtype) @ p["w_out"].astype(u.dtype), cache


def gated_norm(cfg, p, y, z):
    """``rms_G(y * silu(z); w_n)``: the gate first (``mamba_norm_before_gate``
    false), the mean over each group's columns.  y [..., d_ssm] float32."""
    f32, G = jnp.float32, cfg.ssm_groups
    y = y * jax.nn.silu(z.astype(f32))
    y = rms_norm(y.reshape(*y.shape[:-1], G, cfg.d_ssm // G),
                 jnp.ones((), f32), cfg.norm_eps).reshape(y.shape)
    return y * p["norm"].astype(f32)


def attention(cfg, p, u, positions, attend, cache):
    """Grouped-query attention of the normed stream: (``Attn(u *
    attention_in_multiplier)``, cache); ``attend`` as in
    ``llama.attention_block``."""
    q, k, v = qkv_rope(
        cfg, p, u * jnp.asarray(cfg.attention_in_multiplier, u.dtype),
        positions)
    with jax.named_scope("attn/qkv"):
        k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
    out, cache = attend(q, k, v, cache)
    with jax.named_scope("attn/out"):
        return (out.reshape(*out.shape[:-2], -1).astype(u.dtype)
                @ p["attn"]["wo"].astype(u.dtype)), cache


def mlp(cfg, p, h):
    with jax.named_scope("mlp/gate_up"):
        gate = jax.nn.silu((h @ p["mlp"]["w_gate"].astype(h.dtype))
                           * jnp.asarray(cfg.mlp_multipliers[0], h.dtype))
        up = h @ p["mlp"]["w_up"].astype(h.dtype)
    with jax.named_scope("mlp/down"):
        return ((gate * up) @ p["mlp"]["w_down"].astype(h.dtype)
                * jnp.asarray(cfg.mlp_multipliers[1], h.dtype))


def block(cfg, p, x, positions, attend, pools, recur, rows):
    """One block: (x, pools, rows).  Both branches read the same ``u``."""
    with jax.named_scope("attn/norm"):
        u = rms_norm(x, p["attn_norm"], cfg.norm_eps)
    mixed, rows = mixer(cfg, p["ssm"], u, recur, rows)
    attended, pools = attention(cfg, p, u, positions, attend, pools)
    with jax.named_scope("attn/out"):
        x = (x + jnp.asarray(cfg.ssm_out_multiplier, x.dtype) * mixed
             + jnp.asarray(cfg.attention_out_multiplier, x.dtype) * attended)
    with jax.named_scope("mlp/norm"):
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    return x + mlp(cfg, p, h), pools, rows


def served_walk(cfg, params, x, caches, positions, via):
    """``llama.served_walk`` with the state rows beside the pools in the
    carry, whole: scanned layer i writes pool layer i through
    ``via["attend"]`` and state layer i through ``via["recur_fixed"]``,
    which updates the layer's rows in place, or leaves them be and hands
    back as ``left`` what the program is to write once the scan is over:
    the fourth thing returned, ``{"S": [layers, ...], "conv": [layers x
    taps, channels]}``."""
    cache_k, cache_v, state = caches

    def body(carry, per_layer):
        x, ck, cv, st = carry
        p, li = per_layer
        x, (ck, cv), (st, left) = block(
            cfg, p, x, positions, via["attend"], (ck, cv, li),
            via["recur_fixed"], (st, li))
        return (x, ck, cv, st), left

    with jax.named_scope("layers"):
        (x, cache_k, cache_v, state), left = jax.lax.scan(
            body, (x, cache_k, cache_v, state),
            (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)))
    if left is not None:
        S, tail = left
        left = {"S": S, "conv": tail.reshape(-1, tail.shape[-1])}
    return x, (cache_k, cache_v, state), {}, left


def apply(params, tokens, cfg: FalconH1Config):
    """Cacheless forward: tokens (batch, seq) -> logits (batch, seq, vocab)
    float32.  The convolution from zeros and the chunked recurrence from a
    zero state, a sequence at a time; dense causal attention."""
    s = tokens.shape[1]
    positions = jnp.arange(s)
    causal = positions[None, :] <= positions[:, None]
    rep = cfg.n_heads // cfg.n_kv_heads

    def attend(q, k, v, cache):  # (s, heads, d)
        with jax.named_scope("attn/attend"):
            k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
            scores = jnp.einsum("qhd,khd->hqk", q, k) / (cfg.head_dim ** 0.5)
            attn = jax.nn.softmax(jnp.where(causal, scores, -1e30).astype(
                jnp.float32), axis=-1)
            return jnp.einsum("hqk,khd->qhd", attn.astype(v.dtype), v), cache

    def recur(q, k, v, g, cache, conv):
        taps, bias, xbc, gates = conv
        y, _ = short_conv(taps, xbc, jnp.zeros(
            (cfg.conv_width - 1, xbc.shape[-1]), xbc.dtype), bias,
            CONV_PART)
        q, k, v, g, skip = gates(y)
        with jax.named_scope(cfg.state_part):
            o, _ = lightning.chunked(q, k, v, g, jnp.zeros(
                (cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim),
                jnp.float32))
            return o + skip, cache

    def one(tokens):
        def body(x, p):
            return block(cfg, p, x, positions, attend, None, recur,
                         None)[0], None

        with jax.named_scope("layers"):
            x, _ = jax.lax.scan(body, embed(params, tokens, cfg),
                                params["layers"])
        return head(params, x, cfg)

    return jax.vmap(one)(tokens)
