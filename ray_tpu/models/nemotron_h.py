"""Nemotron-H (``model_type`` ``nemotron_h``, NVIDIA: Nemotron-3-Super): a
stack in which a layer is ONE thing, a Mamba-2 mixer OR grouped-query
attention OR a routed feed-forward, chosen a layer by a pattern string, each
under one norm and one residual; the routed layer's experts live in a LATENT
narrower than the stream, two matrices each around a squared ReLU, beside a
shared expert at the stream's own width; served as ONE CHIP'S SHARE of an
expert-parallel deployment.

Published keys in backticks; ``rms(x; w) = x / sqrt(mean(x^2) + eps) * w``
(``layer_norm_epsilon``); d = ``hidden_size``.  No bias but the
convolution's.  What ``config.json`` has no key for is marked (+) and listed
under ``assumed`` in the benchmark's configuration.

- Stream: ``h = E[token]``; layer i of kind ``c = hybrid_override_pattern[i]``
  (``M``, ``*`` or ``E``): ``h <- h + Mix_c(rms(h; w_i))``; ``logits =
  rms(h; w_f) W_head``.
- ``M``, Mamba-2 (Dao and Gu, arXiv:2405.21060): ``p = u W_in``, ``W_in`` d ->
  z (H P) | x (H P) | B and C (``n_groups`` x ``ssm_state_size`` each) | dt
  (H), H = ``mamba_num_heads``, P = ``mamba_head_dim`` ((+) in that order).
  ``x | B | C`` pass a causal depthwise convolution of width ``conv_kernel``
  with a bias, then SiLU.  ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)`` a head.  Head h of group g = h // (H / groups): ``S_t =
  exp(dt_t A) S_{t-1} + B_t^g (dt_t x_t)^T`` (S [N, P], float32 (+)),
  ``y_t = C_t^g S_t + D_h x_t``.  ``y = rms_G(y * silu(z); w_n)``, the gate
  first and the mean over each GROUP's columns (+); ``Mix_M(u) = y W_out``.
- ``*``, attention: q (``num_attention_heads`` of ``head_dim``), k and v
  (``num_key_value_heads``), NO rotation and no q/k norm (+: the family's
  position lives in its Mamba layers); causal softmax at head_dim^-0.5;
  ``W_o``.
- ``E``, LatentMoE: ``s = sigmoid(u W_r)`` over ``n_routed_experts``
  columns, float32, on the UNPROJECTED input; chosen = the top
  ``num_experts_per_tok`` of ``s + b`` (``e_score_correction_bias``, the
  choice only); ``w = s[chosen] / sum s[chosen] * routed_scaling_factor``.
  ``l = u W_lin`` (d -> ``moe_latent_size``); ``r = sum_k w_k relu(l
  W_up^{e_k})^2 W_down^{e_k}`` (latent -> ``moe_intermediate_size`` ->
  latent, two matrices an expert and no gate); ``Mix_E(u) = r W_lout +
  relu(u W_up^s)^2 W_down^s``, the shared expert (d ->
  ``moe_shared_expert_intermediate_size`` -> d) on u itself, unweighted.

The recurrence is ops/lightning.py's with ``k = B``, ``v = dt x``, ``q = C``
and ``g = dt A``, as models/falcon_h1.py has it, at heads of HALF a lane
tile: the state is kept PACKED, ``state_pack`` heads that read one key side
by side in the lanes (``lightning.pack_state``), so that a slot's rows are
as many bytes moved as they are bytes of state.  The short convolution is
models/olmo_hybrid.py's ``short_conv``; the experts' product is
ops/grouped_matmul.py's two-matrix form through ``moe.dispatch_share``.

THE SHARE (as models/longcat_flash.py's).  This chip holds
``n_experts_held`` of a layer's ``n_experts`` from ``first_expert_held`` on
and ``vocab_size`` rows of the embedding and the head; the router keeps all
its columns; a pick on an expert another chip holds adds nothing here, and
nothing stands in for it.  ``W_lout`` is applied to the held experts'
weighted SUM (it is linear: a share's part of ``r W_lout`` is ``W_lout`` of
its own sum), and the router, both latent projections and the shared expert
are whole on every chip.

What is cached (``cache_layout``): a POOL layer for each ``*`` of the
pattern and a STATE layer for each ``M`` (the packed float32 rows a slot and
the convolution's last ``conv_kernel - 1`` inputs, a row each, a layer's one
behind the other as Falcon-H1 keeps them), each counted among its own kind.
The walk follows the pattern string, UNROLLED (a layer's kind and its place
among its kind are static; the published pattern is not periodic), over a
served tree that holds every layer's leaves apart (``serving_layout``), the
experts alone stacked over the ``E`` layers, where the grouped kernel
indexes them.

Parameters: ``layers`` = ``{"M": norm w_in conv conv_bias dt_bias A_log D
norm_gated w_out, "*": norm attn (wq wk wv wo), "E": norm router router_bias
w_lin w_lout shared (w_up w_down) experts (w_up w_down, [layers, held,
...])}``, every leaf stacked over the layers OF ITS KIND.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ray_tpu.models import llama, moe
from ray_tpu.models.falcon_h1 import CONV_PART, gated_norm
from ray_tpu.models.llama import embed, head, rms_norm
from ray_tpu.models.olmo_hybrid import short_conv
from ray_tpu.ops import lightning

KINDS = ("M", "*", "E")
PUBLISHED_PATTERN = ("MEMEMEM*E" + "MEMEMEM*E" + "MEMEMEM*E"
                     + "MEMEMEMEM*E" * 4 + "MEMEMEM*E" + "MEMEMEME")
_STATE_BESIDE = ("{cfg.__class__.__name__} has recurrent mixer layers whose "
                 "state is a row a slot beside the pages, which this engine "
                 "does not serve with %s ({where}): pages alone carry "
                 "nothing of the state at their end")
_NO_STATE_IN_PAGES = ("{where} serves no model with recurrent layers beside "
                      "its attention: the pages of a prefix hold nothing of "
                      "the state at its end")


@dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072  # the share's slice where the head is split
    d_model: int = 4096
    pattern: str = PUBLISHED_PATTERN  # hybrid_override_pattern
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    ssm_heads: int = 128  # mamba_num_heads
    ssm_head_dim: int = 64  # mamba_head_dim
    ssm_state: int = 128  # ssm_state_size
    ssm_groups: int = 8  # n_groups
    conv_width: int = 4  # conv_kernel
    n_experts: int = 512  # n_routed_experts: the router's columns
    experts_per_token: int = 22
    d_latent: int = 1024  # moe_latent_size
    d_expert: int = 2688  # moe_intermediate_size
    d_shared: int = 5376  # moe_shared_expert_intermediate_size
    routed_scaling_factor: float = 5.0
    # the share: the experts this chip holds (all of them: no share)
    n_experts_held: int = 512
    first_expert_held: int = 0
    # lanes a row of the state fills: heads narrower than it lie side by
    # side (``state_pack``); a test's stand-in is smaller
    state_lanes: int = 128
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"

    def __post_init__(self):
        if not self.pattern or set(self.pattern) - set(KINDS):
            raise ValueError(
                f"a layer is one of {KINDS} (a Mamba-2 mixer, attention, a "
                f"routed feed-forward); the pattern is {self.pattern!r}")
        if not (0 < self.n_experts_held and 0 <= self.first_expert_held
                <= self.n_experts - self.n_experts_held):
            raise ValueError(
                f"experts {self.first_expert_held}.."
                f"{self.first_expert_held + self.n_experts_held - 1} are "
                f"not among the {self.n_experts} the router sends to")
        if (self.ssm_heads % self.ssm_groups
                or (self.ssm_heads // self.ssm_groups) % self.state_pack):
            raise ValueError(
                f"{self.ssm_heads} mixer heads in {self.ssm_groups} groups, "
                f"{self.state_pack} of them side by side in a state row: "
                f"those read one key, so a group is a whole number of them")

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
    def n_layers(self) -> int:
        return len(self.pattern)

    def count(self, kind: str) -> int:
        return self.pattern.count(kind)

    @property
    def d_ssm(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        """x | B | C: what passes the short convolution."""
        return self.d_ssm + 2 * self.ssm_groups * self.ssm_state

    @property
    def state_pack(self) -> int:
        """Heads side by side in a row of the state: as many as fill its
        lanes (1 for a head that fills them itself)."""
        return max(1, self.state_lanes // self.ssm_head_dim)

    def cache_layout(self) -> dict:
        """What the served programs cache (``paged_cache.CacheConfig``):
        K/V pages over the ``*`` layers, and for each ``M`` layer a packed
        float32 row [heads / pack, d_state, pack x d_head] a slot whatever
        the model is served in, and the convolution's last ``conv_width -
        1`` inputs, a row each, a layer's one behind the other."""
        n, taps, pack = self.count("M"), self.conv_width - 1, self.state_pack
        return {"n_layers": self.count("*"), "n_kv_heads": self.n_kv_heads,
                "head_dim": self.head_dim, "state_layers": n,
                "scan_chunk": lightning.CHUNK,
                "state_rows": {
                    "S": (n, (self.ssm_heads // pack, self.ssm_state,
                              pack * self.ssm_head_dim), jnp.float32),
                    "conv": (n * taps, (self.conv_channels,),
                             jnp.dtype(self.dtype))}}

    def serving_layout(self, params):
        return serving_layout(params)

    def served_walk(self, params, x, caches, positions, via):
        return served_walk(self, params, x, caches, positions, via)

    @staticmethod
    def tiny(vocab_size: int = 512, **kw) -> "NemotronHConfig":
        """For tests: all three kinds in an irregular order, mixer heads of
        HALF the lane stand-in (two side by side a state row) in two groups
        with d_state unequal to d_head, 16 experts top 6 of which a quarter
        is held: more picks than held experts a token can reach."""
        return NemotronHConfig(**{**dict(
            vocab_size=vocab_size, d_model=64, pattern="MEM*EME", n_heads=4,
            n_kv_heads=2, head_dim=16, ssm_heads=8, ssm_head_dim=16,
            ssm_state=32, ssm_groups=2, state_lanes=32, n_experts=16,
            experts_per_token=6, d_latent=32, d_expert=48, d_shared=96,
            n_experts_held=4, first_expert_held=4, max_seq_len=512,
            dtype="float32"), **kw})


def init(cfg: NemotronHConfig, key: jax.Array, dtype=jnp.float32,
         bias_sd: float = 0.02, router_logit_sd: float = 1.0):
    """Seeded parameters in ``dtype``: every matrix normal with variance
    1 / fan_in, norms 1, the embedding's rows of unit variance (the stream
    begins at 1 rms).  ``A_log = log A``, A uniform in [1, 16]; ``dt_bias``
    such that ``softplus(dt_bias)`` is log-uniform in [0.001, 0.1]
    (``time_step_min``, ``time_step_max``: Mamba-2's own initialisation);
    ``D`` 1; taps normal / 2 and the convolution's bias normal / 10.  The
    ROUTER is drawn so that its logits (of a normed row, unit rms) have a
    standard deviation of ``router_logit_sd``: the sigmoid's scores then
    spread over (0.1, 0.9) and the 22 chosen are no coin toss; and a
    NON-ZERO ``router_bias`` (normal, sd ``bias_sd``, float32: around the
    22nd of 512 such scores the neighbours lie ~0.004 apart, so the bias
    changes the chosen set for most tokens).  The router's columns are the
    WHOLE model's, whichever experts are held; the experts are drawn and
    cast a layer at a time.  ``A_log``, ``dt_bias``, ``D`` and the bias
    stay float32."""
    d, N, G = cfg.d_model, cfg.ssm_state, cfg.ssm_groups
    hq, hkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    n = {kind: cfg.count(kind) for kind in KINDS}
    k_embed, k_m, k_a, k_e, k_head = jax.random.split(key, 5)

    def dense(key, shape, fan_in):
        return (jax.random.normal(key, shape, jnp.float32)
                * fan_in ** -0.5).astype(dtype)

    def experts(key, shape, fan_in):
        return jax.lax.map(lambda k: dense(k, shape, fan_in),
                           jax.random.split(key, n["E"]))

    km, ka, ke = (jax.random.split(k, 8) for k in (k_m, k_a, k_e))
    dt = jnp.exp(jax.random.uniform(
        km[3], (n["M"], cfg.ssm_heads), jnp.float32, jnp.log(1e-3),
        jnp.log(0.1)))
    held, f, r = cfg.n_experts_held, cfg.d_expert, cfg.d_latent
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), 1.0),
        "layers": {
            "M": {
                "norm": jnp.ones((n["M"], d), dtype),
                "w_in": dense(km[0], (n["M"], d, 2 * cfg.d_ssm + 2 * G * N
                                      + cfg.ssm_heads), d),
                "conv": (jax.random.normal(
                    km[1], (n["M"], cfg.conv_width, cfg.conv_channels),
                    jnp.float32) / 2).astype(dtype),
                "conv_bias": (jax.random.normal(
                    km[2], (n["M"], cfg.conv_channels), jnp.float32)
                    / 10).astype(dtype),
                # softplus(dt_bias) = dt
                "dt_bias": jnp.log(jnp.expm1(dt)),
                "A_log": jnp.log(jax.random.uniform(
                    km[4], (n["M"], cfg.ssm_heads), jnp.float32, 1.0, 16.0)),
                "D": jnp.ones((n["M"], cfg.ssm_heads), jnp.float32),
                # (``gated_norm``'s name for it)
                "norm_gated": jnp.ones((n["M"], cfg.d_ssm), dtype),
                "w_out": dense(km[5], (n["M"], cfg.d_ssm, d), cfg.d_ssm)},
            "*": {
                "norm": jnp.ones((n["*"], d), dtype),
                "attn": {"wq": dense(ka[0], (n["*"], d, hq), d),
                         "wk": dense(ka[1], (n["*"], d, hkv), d),
                         "wv": dense(ka[2], (n["*"], d, hkv), d),
                         "wo": dense(ka[3], (n["*"], hq, d), hq)}},
            "E": {
                "norm": jnp.ones((n["E"], d), dtype),
                "router": (router_logit_sd * jax.random.normal(
                    ke[0], (n["E"], d, cfg.n_experts), jnp.float32)
                    * d ** -0.5).astype(dtype),
                "router_bias": bias_sd * jax.random.normal(
                    ke[1], (n["E"], cfg.n_experts), jnp.float32),
                "w_lin": dense(ke[2], (n["E"], d, r), d),
                "w_lout": dense(ke[3], (n["E"], r, d), r),
                "shared": {
                    "w_up": dense(ke[4], (n["E"], d, cfg.d_shared), d),
                    "w_down": dense(ke[5], (n["E"], cfg.d_shared, d),
                                    cfg.d_shared)},
                "experts": {"w_up": experts(ke[6], (held, r, f), r),
                            "w_down": experts(ke[7], (held, f, r), f)}}},
        "final_norm": jnp.ones((d,), dtype),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
    }


def serving_layout(params):
    """The tree as the served programs hold it: ``layers[kind]`` a TUPLE of
    that kind's layers, a layer's leaves its own arrays (the walk is
    unrolled and reads layer j of a kind by a static index; out of a
    stacked leaf that would be a slice a product, as models/afmoe.py's
    ``serving_layout`` says), an attention layer's ``wq``, ``wk`` and
    ``wv`` as ONE ``wqkv`` (``llama.serving_layout``), and ``experts``
    beside them stacked over the ``E`` layers as they were (the kernel
    indexes them where they lie).  A tree laid out so comes back as it
    is."""
    layers = params["layers"]
    if "experts" in layers:
        return params

    def apart(stack):
        n = jax.tree.leaves(stack)[0].shape[0]
        return tuple(jax.tree.map(lambda w: w[i], stack) for i in range(n))

    attn = dict(layers["*"]["attn"])
    attn["wqkv"] = jnp.concatenate(
        [attn.pop("wq"), attn.pop("wk"), attn.pop("wv")], axis=-1)
    routed = {k: v for k, v in layers["E"].items() if k != "experts"}
    return {**params, "layers": {
        "M": apart(layers["M"]),
        "*": apart({**layers["*"], "attn": attn}),
        "E": apart(routed), "experts": layers["E"]["experts"]}}


# ---------------------------------------------------------------------------
# The three kinds of layer, as parts.  ``p`` is one layer's parameters, ``x``
# the stream; each returns ``x + Mix(rms(x))`` and what it cached or counted.


def mixer_layer(cfg, p, x, recur, cache):
    """A Mamba-2 layer: (x + ``Mix_M(rms(x))``, cache).  ``recur`` as
    ``falcon_h1.mixer`` takes it (llm/model.py ``recur_fixed`` with
    ``conv``): it convolves from the rows that came before, makes the
    recurrence's inputs through ``gates`` and runs it its own way."""
    H, P, N, G = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_groups
    f32 = jnp.float32
    with jax.named_scope("attn/norm"):
        u = rms_norm(x, p["norm"], cfg.norm_eps)
    with jax.named_scope("ssm/proj"):
        z, xbc, dt = jnp.split(
            u @ p["w_in"].astype(u.dtype),
            (cfg.d_ssm, cfg.d_ssm + cfg.conv_channels), axis=-1)

    def gates(y):  # the convolved rows [..., x + B + C], float32
        with jax.named_scope("ssm/gates"):
            xs, B, C = jnp.split(y, (cfg.d_ssm, cfg.d_ssm + G * N), axis=-1)
            xs = xs.reshape(*xs.shape[:-1], H, P)
            B, C = (t.reshape(*t.shape[:-1], G, N) for t in (B, C))
            step = jax.nn.softplus(dt.astype(f32) + p["dt_bias"].astype(f32))
            g = -jnp.exp(p["A_log"].astype(f32)) * step
            skip = p["D"].astype(f32)[:, None] * xs
            return C, B, xs * step[..., None], g, skip

    y, cache = recur(None, None, None, None, cache,
                     conv=(p["conv"], p["conv_bias"], xbc, gates))
    with jax.named_scope("ssm/out"):
        y = gated_norm(cfg, {"norm": p["norm_gated"]},
                       y.reshape(*y.shape[:-2], cfg.d_ssm), z)
        return x + y.astype(u.dtype) @ p["w_out"].astype(u.dtype), cache


def attention_layer(cfg, p, x, attend, cache):
    """An attention layer: (x + ``Mix_*(rms(x))``, cache); q and k as the
    products leave them, no position in them; ``attend`` as in
    ``llama.attention_block``."""
    with jax.named_scope("attn/norm"):
        u = rms_norm(x, p["norm"], cfg.norm_eps)
    out, cache = attend(*llama.qkv(cfg, p, u), cache)
    with jax.named_scope("attn/out"):
        return (x + out.reshape(*out.shape[:-2], -1).astype(u.dtype)
                @ p["attn"]["wo"].astype(u.dtype)), cache


def route(cfg, p, uf):
    """(weights, experts) (N, k) of the normed rows uf (N, d): sigmoid
    scores, the choice by score + bias, the chosen scores renormalised and
    scaled."""
    return moe.route(
        uf, p["router"], cfg.experts_per_token, renormalise=True,
        bias=p["router_bias"], scale=cfg.routed_scaling_factor,
        scoring="sigmoid")


def routed_part(cfg, p, experts, i, uf, pinned=None):
    """The held experts' part of ``Mix_E`` for this chip's share, ``r
    W_lout``: uf (N, d) -> ((N, d), counted [4] under
    ``moe.SHARE_COUNTED``).  The router reads uf itself; only the experts
    see the latent.  ``pinned``: (weights, experts) (N, k) handed in, in the
    place of the router's own."""
    weights, chosen = pinned or route(cfg, p, uf)
    with jax.named_scope("moe/latent_in"):
        latent = uf @ p["w_lin"].astype(uf.dtype)
    r, counted = moe.dispatch_share(
        latent, weights, chosen, experts, i, first=cfg.first_expert_held,
        columns=cfg.n_experts, identity=0)
    with jax.named_scope("moe/latent_out"):
        return r @ p["w_lout"].astype(uf.dtype), counted


def latent_moe(cfg, p, experts, i, x, pinned=None):
    """A routed layer: (x + ``Mix_E(rms(x))``, counted).  ``experts``: the
    held ones stacked over the ``E`` layers, of which ``i`` is read."""
    with jax.named_scope("mlp/norm"):
        u = rms_norm(x, p["norm"], cfg.norm_eps)
    uf = u.reshape(-1, u.shape[-1])
    r, counted = routed_part(cfg, p, experts, i, uf, pinned)
    shared = moe.shared_mlp(p["shared"], uf)
    with jax.named_scope("moe/shared"):
        return x + (r + shared).reshape(x.shape), counted


def walk(cfg, layers, x, attend, recur, caches=(None, None, None),
         pinned=None):
    """The layers in the pattern's order, unrolled: layer j OF ITS KIND
    writes pool layer j (``*``) or state layer j (``M``) or reads the
    experts of routed layer j (``E``).  ``layers`` as ``serving_layout``
    lays them out.  ``pinned``: routing handed in, (weights, experts) each
    [E layers, N, k].  Returns (x, caches, counted [4] summed over the
    routed layers, what the mixers handed back for the program to write: a
    list, an entry a mixer layer)."""
    cache_k, cache_v, state = caches
    counted = jnp.zeros(len(moe.SHARE_COUNTED), jnp.int32)
    seen, left = dict.fromkeys(KINDS, 0), []
    with jax.named_scope("layers"):
        for kind in cfg.pattern:
            j = seen[kind]
            seen[kind] += 1
            p = layers[kind][j]
            if kind == "M":
                x, (state, kept) = mixer_layer(cfg, p, x, recur, (state, j))
                left.append(kept)
            elif kind == "*":
                x, (cache_k, cache_v) = attention_layer(
                    cfg, p, x, attend, (cache_k, cache_v, j))
            else:
                x, n = latent_moe(
                    cfg, p, layers["experts"], j, x,
                    pinned and (pinned[0][j], pinned[1][j]))
                counted = counted + n
    return x, (cache_k, cache_v, state), counted, left


def served_walk(cfg, params, x, caches, positions, via):
    """``llama.served_walk`` for layers that are one thing each: ``*``
    layer j writes pool layer j through ``via["attend"]``, ``M`` layer j
    state layer j through ``via["recur_fixed"]``, which updates the layer's
    rows in place, or leaves them be and hands back what the program is to
    write once the walk is over: the fourth thing returned, ``{"S": [M
    layers, ...], "conv": [M layers x taps, channels]}``.  ``positions``
    are no layer's business here."""
    del positions
    x, caches, counted, left = walk(
        cfg, params["layers"], x, via["attend"], via["recur_fixed"], caches)
    if left and left[0] is not None:
        left = {"S": jnp.stack([S for S, _ in left]),
                "conv": jnp.concatenate([tail for _, tail in left])}
    else:
        left = None
    return x, caches, {moe.SHARE_COUNTED: counted}, left


def trunk(params, tokens, cfg: NemotronHConfig, pinned=None):
    """Cacheless: ONE sequence's tokens [seq] -> the stream behind the last
    layer [seq, d], before the final norm.  The convolution from zeros and
    the chunked recurrence from a zero state; dense causal attention.
    ``pinned``: routing handed in, (weights, experts) each [E layers, seq,
    k]."""
    positions = jnp.arange(tokens.shape[0])
    causal = positions[None, :] <= positions[:, None]
    rep = cfg.n_heads // cfg.n_kv_heads

    def attend(q, k, v, cache):  # (s, heads, d)
        with jax.named_scope("attn/attend"):
            k, v = (jnp.repeat(t, rep, axis=1) for t in (k, v))
            scores = jnp.einsum("qhd,khd->hqk", q, k) / (cfg.head_dim ** 0.5)
            attn = jax.nn.softmax(jnp.where(causal, scores, -1e30).astype(
                jnp.float32), axis=-1)
            return (jnp.einsum("hqk,khd->qhd", attn.astype(v.dtype), v),
                    cache[:2])

    def recur(q, k, v, g, cache, conv):
        taps, bias, xbc, gates = conv
        y, _ = short_conv(taps, xbc, jnp.zeros(
            (cfg.conv_width - 1, xbc.shape[-1]), xbc.dtype), bias, CONV_PART)
        q, k, v, g, skip = gates(y)
        with jax.named_scope(cfg.state_part):
            # (zeros laid out as a slot's rows are, ``cache_layout``: the
            # chunked scan takes narrow heads packed side by side alone)
            pack = cfg.state_pack
            o, _ = lightning.chunked(q, k, v, g, jnp.zeros(
                (cfg.ssm_heads // pack, cfg.ssm_state,
                 pack * cfg.ssm_head_dim), jnp.float32))
            return o + skip, (cache[0], None)

    return walk(cfg, cfg.serving_layout(params)["layers"],
                embed(params, tokens, cfg), attend, recur, pinned=pinned)[0]


def apply(params, tokens, cfg: NemotronHConfig):
    """Cacheless forward: tokens (batch, seq) -> logits (batch, seq, vocab)
    float32, a sequence at a time, unrolled (the grouped kernel's scalar
    tables are a sequence's own)."""
    return jnp.stack([head(params, trunk(params, t, cfg), cfg)
                      for t in tokens])
