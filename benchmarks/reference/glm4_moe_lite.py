"""Plain reference of the GLM-4.7-Flash family (``glm4_moe_lite``): float32
``jax.numpy``, NON-absorbed attention, no cache, no kernel, no sorting or
grouping, independent of ``ray_tpu.models`` and ``ray_tpu.llm``.

The layer, as the published description gives it: pre-norm blocks,
``h = x + Attn(norm(x)); y = h + FFN(norm(h))``, RMSNorm.

Attention (every layer; H heads): ``c_q = RMSNorm(x W_qa)``;
``[q_nope | q_rope]_h = c_q W_qb``; ``[c_kv | k_r] = x W_kva``,
``c_kv = RMSNorm(c_kv)``; ``[k_nope | v]_h = c_kv W_kvb``; ``q_rope`` and the
ONE ``k_r`` rotated; ``score_h = (q_nope_h . k_nope_h + q_rope_h . k_rope) /
sqrt(nope + rope)``; causal softmax; ``o = concat_h(P_h v_h) W_o``.  K and V
are built for every head: nothing is absorbed.  ``latent_rows`` hands out
``[c_kv | k_rope]`` (after the norm, after the rotation), which is what a
served model caches a token a layer.

Feed-forward: the first ``first_k_dense_replace`` layers a SiLU-gated MLP;
the others ``s = sigmoid(h W_r)``, the ``num_experts_per_tok`` experts of
largest ``s + b`` (``b`` = ``e_score_correction_bias``), their weights
``s`` of the chosen (WITHOUT ``b``) over their sum (``norm_topk_prob``)
times ``routed_scaling_factor``; ``FFN(h) = sum_i w_i E_i(h) + Shared(h)``.
EVERY expert is computed for every token and weighted (zero off the
chosen): no token can be dropped.

Departures from the published description: (1) ``n_group`` = ``topk_group``
= 1 makes the group-limited choice the identity, so it is not written (a
configuration with another value is refused by name); (2) the rotation is
rotate-half over split halves (i, i + rope / 2) of the rope dimensions, the
layout these programs hold; the published checkpoints hold interleaved
pairs and the published code de-interleaves before the same rotation (a
fixed permutation of the rope columns of ``W_qb`` and ``W_kva``, invisible
to seeded weights); (3) the multi-token-prediction layer
(``num_nextn_predict_layers``) is no part of the forward pass and is not
here; (4) weights come in the program's TRAINING parameter layout
(``dense`` and ``layers``, leaves stacked on a leading axis: ``wq_a``,
``wkv_a``, ``wkv_b``, ...) and are upcast a layer, and within it a block of
experts, at a time, so that the model served in bf16 can be checked beside
its own weights on one chip.

Every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
TOP_K = 4  # candidates a position
EXPERT_BLOCK = 8  # experts upcast and computed together


def _check(c: dict):
    if c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1:
        raise ValueError(
            f"this reference writes no group-limited choice: n_group "
            f"{c.get('n_group')} / topk_group {c.get('topk_group')} is not "
            f"1 / 1")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [b, s, ..., d]; rotate pairs (i, i + d/2) by position *
    theta^(-2i/d), the position being axis 1's index."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape(1, x.shape[1], *(1,) * (x.ndim - 3), half)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attention(c: dict, h, a):
    """h [b, s, d] normed -> (attention's output before W_o's residual,
    the latent rows [b, s, r + rope])."""
    b, s, _ = h.shape
    H, r = c["num_attention_heads"], c["kv_lora_rank"]
    nope, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                    c["v_head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    c_q = _rms_norm(h @ a["wq_a"], a["q_norm"], eps)
    q = (c_q @ a["wq_b"]).reshape(b, s, H, nope + dr)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], theta)], -1)
    kv = h @ a["wkv_a"]
    c_kv = _rms_norm(kv[..., :r], a["kv_norm"], eps)
    k_rope = _rope(kv[..., r:], theta)  # [b, s, rope]: one key, all heads
    up = (c_kv @ a["wkv_b"]).reshape(b, s, H, nope + dv)
    k = jnp.concatenate([up[..., :nope], jnp.broadcast_to(
        k_rope[:, :, None, :], (b, s, H, dr))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(nope + dr))
    causal = jnp.arange(s)[None, :] <= jnp.arange(s)[:, None]
    scores = jnp.where(causal, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1),
                     up[..., nope:])
    return (out.reshape(b, s, H * dv) @ a["wo"],
            jnp.concatenate([c_kv, k_rope], -1))


def _gated(h, w_gate, w_up, w_down):
    gate = h @ w_gate
    return (gate * jax.nn.sigmoid(gate) * (h @ w_up)) @ w_down


def choose(c: dict, scores, bias):
    """scores [n, E] = sigmoid(h W_r) -> (weights [n, k], experts [n, k]):
    the choice by scores + bias, the weights from the scores alone."""
    _, top_i = jax.lax.top_k(scores + bias, c["num_experts_per_tok"])
    top_w = jnp.take_along_axis(scores, top_i, -1)
    if c["norm_topk_prob"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    return top_w * c["routed_scaling_factor"], top_i


def _experts(c: dict, h, router, bias, experts):
    """h [n, d] -> (sum over ALL experts of weight x expert(h), 0 off the
    chosen; the chosen's weights [n, k]; the chosen [n, k])."""
    n_e = c["n_routed_experts"]
    scores = jax.nn.sigmoid(h @ router)  # [n, E]
    top_w, top_i = choose(c, scores, bias)
    weight = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_w)  # [n, E]
    blocks = n_e // EXPERT_BLOCK

    def part(acc, xs):
        w_gate, w_up, w_down, w = xs  # [EB, ...], w [EB, n]
        gate = jnp.einsum("nd,edf->enf", h, w_gate.astype(F32))
        up = jnp.einsum("nd,edf->enf", h, w_up.astype(F32))
        out = jnp.einsum("enf,efd->end", gate * jax.nn.sigmoid(gate) * up,
                         w_down.astype(F32))
        return acc + jnp.einsum("en,end->nd", w, out), None

    split = lambda a: a.reshape(blocks, EXPERT_BLOCK, *a.shape[1:])  # noqa: E731
    acc, _ = jax.lax.scan(part, jnp.zeros_like(h), (
        split(experts["w_gate"]), split(experts["w_up"]),
        split(experts["w_down"]), split(weight.T)))
    return acc, top_w, top_i


def _f32(p, without=()):
    return jax.tree.map(lambda w: w.astype(F32),
                        {k: v for k, v in p.items() if k not in without})


def _dense_block(c: dict, x, p):
    p = _f32(p)
    out, rows = _attention(c, _rms_norm(x, p["attn_norm"],
                                        c["rms_norm_eps"]), p["attn"])
    x = x + out
    h = _rms_norm(x, p["mlp_norm"], c["rms_norm_eps"])
    m = p["mlp"]
    return x + _gated(h, m["w_gate"], m["w_up"], m["w_down"]), rows


def _sparse_block(c: dict, x, p):
    experts = p["experts"]
    p = _f32(p, without=("experts",))
    out, rows = _attention(c, _rms_norm(x, p["attn_norm"],
                                        c["rms_norm_eps"]), p["attn"])
    x = x + out
    h = _rms_norm(x, p["mlp_norm"], c["rms_norm_eps"])
    flat = h.reshape(-1, h.shape[-1])
    routed, top_w, top_i = _experts(c, flat, p["router"], p["router_bias"],
                                    experts)
    sh = p["shared"]
    out = routed + _gated(flat, sh["w_gate"], sh["w_up"], sh["w_down"])
    return x + out.reshape(x.shape), (rows, top_w, top_i)


def _stack(c: dict, params, tokens):
    """tokens [b, s] -> (final-norm activations [b, s, d] float32; the
    latent rows [layers, b, s, r + rope]; the sparse layers' routing:
    weights and experts, each [sparse layers, b * s, k])."""
    _check(c)
    x = params["embed"][tokens].astype(F32)
    first = []
    for i in range(c["first_k_dense_replace"]):
        x, rows = _dense_block(
            c, x, jax.tree.map(lambda w: w[i], params["dense"]))
        first.append(rows)
    x, (rows, top_w, top_i) = jax.lax.scan(
        lambda x, p: _sparse_block(c, x, p), x, params["layers"])
    rows = jnp.concatenate([jnp.stack(first), rows]) if first else rows
    return (_rms_norm(x, params["final_norm"].astype(F32),
                      c["rms_norm_eps"]), rows, (top_w, top_i))


def hidden(c: dict, params, tokens):
    """tokens [b, s] -> final-norm activations [b, s, d], float32."""
    with jax.default_matmul_precision("highest"):
        return _stack(c, params, tokens)[0]


def logits(c: dict, params, tokens):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    with jax.default_matmul_precision("highest"):
        return hidden(c, params, tokens) @ params["lm_head"].astype(F32)


def latent_rows(c: dict, params, tokens):
    """tokens [b, s] -> [layers, b, s, kv_lora_rank + qk_rope_head_dim]
    float32: ``c_kv`` (after its norm) then ``k_rope`` (after rotation) of
    every token in every layer, what a served model's pages hold."""
    with jax.default_matmul_precision("highest"):
        return _stack(c, params, tokens)[1]


def logits_and_routing(c: dict, params, tokens, rows):
    """tokens [b, s], rows [b, r] positions -> (logits [b, r, vocab] float32
    at those positions, weights [sparse layers, b * s, k] float32, experts
    [sparse layers, b * s, k] int32): what this reference computed and the
    expert sets it took, for a comparison of LOGITS in which the other side
    is handed the same sets."""
    with jax.default_matmul_precision("highest"):
        h, _, (weights, chosen) = _stack(c, params, tokens)
        h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
        return (h @ params["lm_head"].astype(F32), weights,
                chosen.astype(jnp.int32))


def _padded(prompts: list, outputs: list, pad_to: int):
    n = len(prompts)
    buf = np.zeros((n, pad_to), np.int32)
    for i, p in enumerate(prompts):
        seq = list(p) + list(outputs[i] if outputs else ())
        if len(seq) > pad_to:
            raise ValueError("pad_to is too short for the prompts and steps")
        buf[i, :len(seq)] = seq
    return buf


def greedy(c: dict, params, prompts: list, steps: int, pad_to: int):
    """Greedy continuation of each prompt by FULL re-forward at every step
    (no cache).  Returns (candidates, gaps), each [n][steps][TOP_K]: the
    TOP_K tokens with the largest logits at that step, best first (the
    first continues the sequence), and how far each one's logit lies under
    the best.  Causal attention makes padding to the right invisible."""
    n = len(prompts)
    buf = _padded(prompts, None, pad_to)
    lens = np.array([len(p) for p in prompts], np.int32)
    if int(lens.max()) + steps > pad_to:
        raise ValueError("pad_to is too short for the prompts and steps")

    @jax.jit
    def step(params, buf, lens):
        with jax.default_matmul_precision("highest"):
            h = hidden(c, params, buf)[jnp.arange(n), lens - 1]
            lg = h @ params["lm_head"].astype(F32)
        top = jax.lax.top_k(lg, TOP_K)
        return top[1], top[0][:, :1] - top[0]

    cands, gaps = [], []
    for _ in range(steps):
        t, g = (np.asarray(x) for x in step(
            params, jnp.asarray(buf), jnp.asarray(lens)))
        buf[np.arange(n), lens] = t[:, 0]
        lens = lens + 1
        cands.append(t)
        gaps.append(g)
    return (np.stack(cands, 1).tolist(),
            np.stack(gaps, 1).astype(float).tolist())


def verify(c: dict, params, prompts: list, outputs: list, steps: int,
           pad_to: int, rows: bool = False):
    """Another generator's ``outputs`` [n][<= steps] held against this
    reference TOKEN BY TOKEN on that generator's own history: [n][steps]
    of how far the logit of its token lies under the reference's best at
    that position, every earlier position holding ITS tokens (None where it
    gave no token).  One causal forward pass over prompt + output; with
    ``rows`` also that pass's latent rows [layers, n, pad_to, r + rope]
    (``latent_rows`` of the same tokens), as a second result."""
    n = len(prompts)
    buf = _padded(prompts, [o[:steps] for o in outputs], pad_to)
    at = np.zeros((n, steps), np.int32)  # the position that predicts step t
    want = np.zeros((n, steps), np.int32)
    for i, (p, o) in enumerate(zip(prompts, outputs)):
        at[i] = np.minimum(len(p) - 1 + np.arange(steps), pad_to - 1)
        want[i, :len(o[:steps])] = o[:steps]

    @jax.jit
    def under_best(params, buf, at, want):
        with jax.default_matmul_precision("highest"):
            h, latent, _ = _stack(c, params, buf)
            h = jnp.take_along_axis(h, at[:, :, None], 1)
            lg = h @ params["lm_head"].astype(F32)
        theirs = jnp.take_along_axis(lg, want[:, :, None], -1)[..., 0]
        return jnp.max(lg, -1) - theirs, latent

    gaps, latent = under_best(params, jnp.asarray(buf), jnp.asarray(at),
                              jnp.asarray(want))
    gaps = np.asarray(gaps)
    gaps = [[float(gaps[i, t]) if t < len(outputs[i]) else None
             for t in range(steps)] for i in range(n)]
    return (gaps, latent) if rows else gaps
