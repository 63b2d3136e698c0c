"""Olmo-Hybrid: gated delta-rule linear-attention layers, three to every
full-attention layer.

The model (``config.json`` of allenai/Olmo-Hybrid-7B, ``model_type``
``olmo_hybrid``): d = 3840, a SiLU-gated MLP of 11008, a vocabulary of
100,352 with an untied head, RMS norms (eps 1e-6), no biases, and
``layer_types`` = 3 x ``linear_attention`` then 1 x ``full_attention``,
repeated.  The block is OLMo 2 / 3's: the norm is on the OUTPUT of the mixer
and of the MLP, x + norm(f(x)); the mixer reads the stream as it is.

Linear-attention layer (Gated DeltaNet: Yang, Kautz, Hatamizadeh, "Gated
Delta Networks", 2024), H = 30 heads, d_k = 96, d_v = 192, input row x_t:

1. q~ = W_q x, k~ = W_k x (each H d_k = 2880), v~ = W_v x (H d_v = 5760).
2. A causal depthwise convolution of width 4 over time on each channel of
   q~, k~, v~ (the row itself and the three before it; zeros before the
   sequence starts), then SiLU.
3. Per head: q = q~ / |q~| d_k^(-1/2), k = k~ / |k~|.
4. b = 2 sigmoid(W_b x) in (0, 2)^H (the 2 is ``linear_allow_neg_eigval``);
   g = -exp(A_log) * softplus(W_a x + dt_bias) in R^H, a = exp(g) in (0, 1).
5. Per head S in R^{d_v x d_k}, S_0 = 0:
   S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T
       = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T;   o_t = S_t q_t.
6. y = W_o (RMSNorm_{d_v}(o_t; a learned [d_v] weight) * SiLU(W_g x)),
   W_g: d -> H d_v.

Full-attention layer: 30 query and 30 KV heads of 128, q and k RMS-normed
over their whole width (3840) before the heads are split, rotated
(rotate-half, whole head, theta 500,000), causal softmax attention at scale
128^(-1/2), ``wo`` 3840 x 3840.

Set by the family's convention, not by the published config (the benchmark's
configuration lists them under ``assumed``): the OLMo 2 / 3 block and q/k
norms, the rotary embedding and its theta, a float32 state.

A layer here is models/llama.py's parts (``qkv_rope``, ``rms_norm``,
``gated_mlp``, ``embed``, ``head``) around what is new: ``short_conv``,
``delta_inputs`` (steps 3 and 4), ``gated_out`` (step 6), and the
recurrence of step 5 in ops/gated_delta.py.  As ``attend`` is the one thing
the full layer's callers differ in, ``recur`` is the linear layer's: the
cacheless pass below runs the chunked form from a zero state; the engine's
programs (llm/model.py) keep a slot's state and its convolution's last
three inputs in a row of their own beside the page pools.

Parameters: ``layers`` holds ``lin`` (leaves lead with the linear layers,
in order) and ``full`` (leaves lead with the periods).  The layer scan is
over PERIODS and over ``full`` alone: ``lin`` stays stacked and a layer of
it is indexed where it is read, one dynamic slice a weight, which XLA
reads inside the product's fusion (sliced twice, a period and then a layer
of it, every weight was copied out and transposed first: 1.2 GB of
temporaries a prefill).  ``serving_layout`` stacks what one product reads
in place: a linear layer's six input projections (q, k, v, the output
gate, b, a: they share their input) as ``w_in``, b and a each padded to a
lane tile so that every split falls on one; a full layer's three as
``wqkv``.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import llama
from ray_tpu.models.llama import embed, gated_mlp, head, qkv_rope, rms_norm
from ray_tpu.ops import gated_delta

_STATE_BESIDE = ("{cfg.__class__.__name__} has recurrent layers whose state is "
                 "a row a slot beside the pages, which this engine does not "
                 "serve with %s ({where}): pages alone carry nothing of the "
                 "state at their end")
_NO_STATE_IN_PAGES = ("{where} serves no model with recurrent layers: the "
                      "pages of a prefix hold nothing of their state at its "
                      "end")


@dataclass(frozen=True)
class OlmoHybridConfig:
    vocab_size: int = 100352
    d_model: int = 3840
    n_layers: int = 32
    n_heads: int = 30
    n_kv_heads: int = 30
    head_dim: int = 128
    d_ff: int = 11008
    lin_heads: int = 30
    lin_key_dim: int = 96
    lin_value_dim: int = 192
    conv_width: int = 4
    period: int = 4  # layers a period: period - 1 linear, then one full
    max_seq_len: int = 65536
    rope_theta: float = 500_000.0
    norm_eps: float = 1e-6
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.n_layers % self.period:
            raise ValueError(
                f"{self.n_layers} layers are not whole periods of "
                f"{self.period} ({self.period - 1} linear, one full)")

    @property
    def n_periods(self) -> int:
        return self.n_layers // self.period

    @property
    def lin_per_period(self) -> int:
        return self.period - 1

    @property
    def conv_channels(self) -> int:
        return self.lin_heads * (2 * self.lin_key_dim + self.lin_value_dim)

    @property
    def state_pack(self) -> int:
        return gated_delta.head_pack(self.lin_heads, self.lin_value_dim)

    def state_rows(self, dtype=None) -> dict:
        """What a slot holds of the linear layers, by name: (rows a slot
        holds in all, shape of a row, dtype).  The recurrent state, one row
        a layer, is float32 whatever the model is served in (packed,
        ops/gated_delta.py); the convolution keeps its last ``conv_width -
        1`` inputs, a row each, a layer's one behind the other."""
        pack, layers = self.state_pack, self.n_periods * self.lin_per_period
        return {
            "S": (layers, (self.lin_heads // pack, self.lin_key_dim,
                           pack * self.lin_value_dim), jnp.float32),
            "conv": (layers * (self.conv_width - 1), (self.conv_channels,),
                     jnp.dtype(dtype or self.dtype)),
        }

    # What the engine and the served programs ask of a family (llm/model.py
    # says who owns which decision), beside ``cache_layout`` below.
    block_length = 0  # it generates a token at a time
    window = 0  # its attending layers see every position
    refuses = {
        "pd": _STATE_BESIDE % "prefill/decode disaggregation",
        "kv_tier": _STATE_BESIDE % "the KV tier",
        "prefix_cache": _NO_STATE_IN_PAGES,
        # (the program that would continue from a prefix's pages, and a
        # prompt longer than the largest prefill bucket, which would be
        # continued so chunk by chunk)
        "suffix_prefill": _NO_STATE_IN_PAGES,
        "chunked_prompt": _STATE_BESIDE % "a prompt computed in chunks",
    }

    def serving_layout(self, params):
        return serving_layout(params)

    def served_walk(self, params, x, caches, positions, via):
        return served_walk(self, params, x, caches, positions, via)

    def cache_layout(self) -> dict:
        """What the served programs cache (``llm/model.py cache_layout``):
        page pools over the FULL layers only, their pages at a head count
        the paged kernel's tiles take (30 KV heads ride in pages of 32, two
        of them zeros: 6.7 % more page bytes), and the state rows of the
        linear layers."""
        return {"n_layers": self.n_periods,
                "n_kv_heads": -(-self.n_kv_heads // 8) * 8,
                "head_dim": self.head_dim,
                "state_layers": self.n_periods * self.lin_per_period,
                "scan_chunk": gated_delta.CHUNK,
                "state_rows": self.state_rows()}

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "OlmoHybridConfig":
        """For tests: two periods, six KV heads in pages of eight, linear
        heads that pack by four."""
        return OlmoHybridConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, n_layers=8, n_heads=6,
            n_kv_heads=6, head_dim=16, d_ff=128, lin_heads=4,
            lin_key_dim=16, lin_value_dim=32, max_seq_len=512,
            dtype="float32"), **kw})


def init(cfg: OlmoHybridConfig, key: jax.Array, dtype=jnp.float32):
    """Seeded parameters in ``dtype``: matrices normal with variance
    1 / fan_in, norms 1, convolution taps normal / 2, and ``A_log`` /
    ``dt_bias`` such that a head's decay at W_a x = 0 is drawn log-uniform
    over about 0.9-0.999 (a decay of ~0 or ~1 everywhere would make the
    state trivial)."""
    k_embed, k_lin, k_full, k_head = jax.random.split(key, 4)
    d, f, P = cfg.d_model, cfg.d_ff, cfg.n_periods
    n = P * cfg.lin_per_period
    H, hk, hv = (cfg.lin_heads, cfg.lin_heads * cfg.lin_key_dim,
                 cfg.lin_heads * cfg.lin_value_dim)
    hq = cfg.n_heads * cfg.head_dim

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * (fan_in ** -0.5)).astype(dtype)

    def mlp(keys, lead):
        return {"w_gate": dense(keys[0], (*lead, d, f), d),
                "w_up": dense(keys[1], (*lead, d, f), d),
                "w_down": dense(keys[2], (*lead, f, d), f)}

    def ones(*shape):
        return jnp.ones(shape, dtype)

    kl = jax.random.split(k_lin, 12)
    # -log(decay) at W_a x = 0: log-uniform over -log(0.999) .. -log(0.9)
    rate = jnp.exp(jax.random.uniform(
        kl[8], (n, H), jnp.float32, jnp.log(1e-3), jnp.log(0.105)))
    lin = {
        "mix": {
            "wq": dense(kl[0], (n, d, hk), d),
            "wk": dense(kl[1], (n, d, hk), d),
            "wv": dense(kl[2], (n, d, hv), d),
            "wg": dense(kl[3], (n, d, hv), d),
            "wb": dense(kl[4], (n, d, H), d),
            "wa": dense(kl[5], (n, d, H), d),
            "conv": (jax.random.normal(
                kl[6], (n, cfg.conv_width, cfg.conv_channels),
                jnp.float32) / 2).astype(dtype),
            "A_log": jnp.log(rate),
            # softplus(dt_bias) = 1
            "dt_bias": jnp.full((n, H), jnp.log(jnp.e - 1), jnp.float32),
            "o_norm": ones(n, cfg.lin_value_dim),
            "wo": dense(kl[7], (n, hv, d), hv),
        },
        "mlp": mlp(kl[9:12], (n,)),
        "attn_norm": ones(n, d), "mlp_norm": ones(n, d),
    }
    kf = jax.random.split(k_full, 7)
    full = {
        "attn": {
            "wq": dense(kf[0], (P, d, hq), d),
            "wk": dense(kf[1], (P, d, hq), d),
            "wv": dense(kf[2], (P, d, hq), d),
            "wo": dense(kf[3], (P, hq, d), hq),
            "q_norm": ones(P, hq), "k_norm": ones(P, hq),
        },
        "mlp": mlp(kf[4:7], (P,)),
        "attn_norm": ones(P, d), "mlp_norm": ones(P, d),
    }
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d) * (d ** 0.5) * 0.02,
        "layers": {"lin": lin, "full": full},
        "final_norm": ones(d),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
    }


_LIN_IN = ("wq", "wk", "wv", "wg", "wb", "wa")
_LANES = 128


def _gate_width(cfg) -> int:
    """Columns b and a each take in ``w_in``: the heads, up to a lane tile."""
    return -(-cfg.lin_heads // _LANES) * _LANES


def serving_layout(params):
    """The tree as the served programs hold it: a linear layer's six input
    projections side by side in one ``w_in`` [linear layers, d, 17536] (b
    and a behind zeros up to a lane tile each), a full layer's three in one
    ``wqkv`` (``llama.serving_layout``); every other leaf as it was.  A
    tree already so laid out comes back as it is."""
    layers = params["layers"]
    mix = layers["lin"]["mix"]
    if "w_in" in mix:
        return params
    full = llama.serving_layout({"layers": layers["full"]})["layers"]
    mix = dict(mix)

    def tile(w):  # [layers, d, heads] -> zeros behind, up to a lane tile
        return jnp.pad(w, ((0, 0), (0, 0), (0, -w.shape[-1] % _LANES)))

    mix["w_in"] = jnp.concatenate(
        [mix.pop(w) for w in _LIN_IN[:4]]
        + [tile(mix.pop(w)) for w in _LIN_IN[4:]], axis=-1)
    return {**params, "layers": {"lin": {**layers["lin"], "mix": mix},
                                 "full": full}}


# ---------------------------------------------------------------------------
# The linear-attention layer, as parts.  ``p`` is one layer's ``mix``.


def lin_proj(cfg, p, x):
    """Step 1 and the other three products of the layer's input: (q~ k~ v~
    side by side [..., 11520], output gate [..., H d_v], b and a [..., H],
    the two as they come out of the product)."""
    hk = cfg.lin_heads * cfg.lin_key_dim
    hv = cfg.lin_heads * cfg.lin_value_dim
    with jax.named_scope("lin_attn/proj"):
        if "w_in" in p:  # serving_layout: one product, then split
            y = x @ p["w_in"].astype(x.dtype)
            wide = _gate_width(cfg)
            qkv, gate, b, a = jnp.split(
                y, (2 * hk + hv, 2 * hk + 2 * hv, 2 * hk + 2 * hv + wide),
                axis=-1)
            return [qkv, gate, b[..., :cfg.lin_heads], a[..., :cfg.lin_heads]]
        qkv = jnp.concatenate(
            [x @ p[w].astype(x.dtype) for w in ("wq", "wk", "wv")], axis=-1)
        return [qkv] + [x @ p[w].astype(x.dtype) for w in ("wg", "wb", "wa")]


def short_conv(taps, x, before, bias=None, part: str = "lin_attn/conv"):
    """Step 2, time on the FIRST axis.  x: [L, ..., C] a sequence's rows in
    order (a decode step: [1, slots, C]); before: [W - 1, ..., C] the rows
    that precede them (zeros at a sequence's start); taps: [W, C], the last
    tap on the row itself; ``bias`` [C] where the convolution has one
    (models/falcon_h1.py), ``part`` the name it runs under.  Returns (SiLU
    of the convolution [L, ..., C]
    in float32, as ``delta_inputs`` takes it: rounding q, k and v to the
    served type here would be one rounding more than the recurrence needs,
    the rows one behind the other [W - 1 + L, ..., C]: the next call's
    ``before`` is W - 1 of them)."""
    with jax.named_scope(part):
        L = x.shape[0]
        rows = jnp.concatenate([before.astype(x.dtype), x], axis=0)
        taps = taps.astype(jnp.float32)
        y = sum(rows[j:j + L].astype(jnp.float32) * taps[j]
                for j in range(taps.shape[0]))
        if bias is not None:
            y = y + bias.astype(jnp.float32)
        return jax.nn.silu(y), rows


def delta_inputs(cfg, p, y, b, a):
    """Steps 3 and 4 on the convolved rows y [..., 11520] and the two gate
    products: (q, k [..., H, d_k], v [..., H, d_v], g (the decay's log),
    beta [..., H]), float32."""
    H, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim
    f32 = jnp.float32
    with jax.named_scope("lin_attn/gates"):
        q, k, v = jnp.split(y.astype(f32), (H * dk, 2 * H * dk), axis=-1)
        q = q.reshape(*q.shape[:-1], H, dk)
        k = k.reshape(*k.shape[:-1], H, dk)
        v = v.reshape(*v.shape[:-1], H, dv)

        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)

        beta = 2.0 * jax.nn.sigmoid(b.astype(f32))
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            a.astype(f32) + p["dt_bias"].astype(f32))
        return unit(q) * dk ** -0.5, unit(k), v, g, beta


def gated_out(cfg, p, o, gate):
    """Step 6: o [..., H, d_v] float32, gate [..., H d_v] -> [..., d]."""
    with jax.named_scope("lin_attn/out"):
        o = rms_norm(o, p["o_norm"].astype(jnp.float32), cfg.norm_eps)
        o = o.reshape(gate.shape).astype(gate.dtype) * jax.nn.silu(gate)
        return o @ p["wo"].astype(gate.dtype)


def linear_layer(cfg, p, x, recur, cache=None):
    """One linear-attention layer: (x, cache).  ``recur(mix, qkv, b, a,
    cache) -> (o [..., H, d_v], cache)`` convolves and runs step 5 its own
    way: over a whole sequence from a zero state, or one token a slot from
    the slot's row."""
    mix = p["mix"]
    qkv, gate, b, a = lin_proj(cfg, mix, x)
    o, cache = recur(mix, qkv, b, a, cache)
    y = gated_out(cfg, mix, o, gate)
    with jax.named_scope("lin_attn/out"):
        x = x + rms_norm(y, p["attn_norm"], cfg.norm_eps)
    return _mlp_block(cfg, p, x), cache


def full_layer(cfg, p, x, positions, attend, cache=None):
    """One full-attention layer: (x, cache); ``attend`` as in
    ``llama.attention_block``."""
    out, cache = attend(*qkv_rope(cfg, p, x, positions), cache)
    with jax.named_scope("attn/out"):
        y = (out.reshape(*x.shape[:-1], cfg.n_heads * cfg.head_dim)
             @ p["attn"]["wo"].astype(x.dtype))
    with jax.named_scope("attn/norm"):
        x = x + rms_norm(y, p["attn_norm"], cfg.norm_eps)
    return _mlp_block(cfg, p, x), cache


def _mlp_block(cfg, p, x):
    y = gated_mlp(p, x)
    with jax.named_scope("mlp/norm"):
        return x + rms_norm(y, p["mlp_norm"], cfg.norm_eps)


def scan_periods(cfg, params, body, carry):
    """``lax.scan`` over the periods of ``body(carry, lin, full, period) ->
    (carry, out)``: ``full`` the period's full layer's parameters,
    ``lin(i)`` those of linear layer i of the model, sliced where they are
    asked for.  Returns (carry, the periods' ``out`` stacked)."""
    stacked = params["layers"]["lin"]

    def lin(i):
        return jax.tree.map(lambda a: a[i], stacked)

    def step(carry, per_period):
        full, period = per_period
        return body(carry, lin, full, period)

    with jax.named_scope("layers"):
        return jax.lax.scan(
            step, carry, (params["layers"]["full"],
                          jnp.arange(cfg.n_periods, dtype=jnp.int32)))


def served_walk(cfg, params, x, caches, positions, via):
    """``llama.served_walk`` over PERIODS: the recurrent layers of a period
    unrolled in the body, then its full layer, with the state rows beside
    the pools in the carry, whole.  ``via["recur"](mix, qkv, b, a, (state,
    li)) -> (o, (state, left))`` updates a layer's rows in place, or leaves
    them be and hands back as ``left`` what the program is to write once
    the scan is over: the fourth thing returned, [periods, ...] in a list by
    place in the period."""
    n = cfg.lin_per_period

    def body(carry, lin, full, period):
        x, ck, cv, st = carry
        left = []
        for j in range(n):
            li = period * n + j
            x, (st, out) = linear_layer(cfg, lin(li), x, via["recur"],
                                        (st, li))
            left.append(out)
        x, (ck, cv) = full_layer(cfg, full, x, positions, via["attend"],
                                 (ck, cv, period))
        return (x, ck, cv, st), left

    (x, *caches), left = scan_periods(cfg, params, body, (x, *caches))
    return x, tuple(caches), {}, left


def apply(params, tokens, cfg: OlmoHybridConfig):
    """Cacheless forward: tokens (batch, seq) -> logits (batch, seq, vocab)
    float32.  The chunked recurrence from a zero state, a sequence at a
    time; dense causal attention."""
    s = tokens.shape[1]
    positions = jnp.arange(s)
    causal = positions[None, :] <= positions[:, None]
    H, dk, dv = cfg.lin_heads, cfg.lin_key_dim, cfg.lin_value_dim

    def attend(q, k, v, cache):  # (b, s, heads, d)
        with jax.named_scope("attn/attend"):
            scores = (jnp.einsum("bqhd,bkhd->bhqk", q, k)
                      / (cfg.head_dim ** 0.5))
            attn = jax.nn.softmax(jnp.where(causal, scores, -1e30).astype(
                jnp.float32), axis=-1)
            return (jnp.einsum("bhqk,bkhd->bqhd", attn.astype(v.dtype), v),
                    cache)

    def recur(mix, qkv, b, a, cache):  # qkv: (b, s, channels)
        qkv = jnp.swapaxes(qkv, 0, 1)
        y, _ = short_conv(mix["conv"], qkv, jnp.zeros(
            (cfg.conv_width - 1, *qkv.shape[1:]), qkv.dtype))
        y = jnp.swapaxes(y, 0, 1)
        with jax.named_scope("lin_attn/state"):
            o, _ = jax.vmap(gated_delta.chunked, in_axes=(0,) * 5 + (None,))(
                *delta_inputs(cfg, mix, y, b, a),
                jnp.zeros((H, dv, dk), jnp.float32))
        return o, cache

    def body(x, lin, full, period):
        for j in range(cfg.lin_per_period):
            x, _ = linear_layer(cfg, lin(period * cfg.lin_per_period + j),
                                x, recur)
        return full_layer(cfg, full, x, positions[None, :], attend)[0], None

    x, _ = scan_periods(cfg, params, body, embed(params, tokens, cfg))
    return head(params, x, cfg)
