"""Plain reference of Trinity-Mini's family (``model_type`` ``afmoe``):
float32 ``jax.numpy``, dense masks (causal, and the window's), no cache, no
chunks, no kernel, no sorting or grouping, independent of ``ray_tpu.models``
and ``ray_tpu.llm``.  ``c`` is the configuration file as a dict (the
published ``config.json`` keys).

What ``config.json`` has no key for is marked (+): "as ``model_type``
``afmoe``'s published modelling code has it; not read off config.json".

- Embedding: ``x0 = E[token] * sqrt(hidden_size)`` (``mup_enabled`` (+): the
  factor and where it is applied).
- Block, every layer (+ sandwich norm, four RMS norms a layer):
  ``h = x + N_post_attn(Attn(N_in(x)))``;
  ``y = h + N_post_mlp(FFN(N_pre_mlp(h)))``.  Final norm, then the head.
- Attention, every layer, ``u = N_in(x)``: ``q = RMSNorm_d(u W_q)`` a head,
  ``k = RMSNorm_d(u W_k)`` a KV head (+ QK norm, learned [head_dim]
  weights), ``v = u W_v``, ``g = sigmoid(u W_g)`` (+ output gate, a fifth
  matrix from the layer's normed input).  Scores ``q_h . k_kv(h) /
  sqrt(head_dim)`` (the softmax scale: assumed, the usual one), causal
  softmax, ``o = (concat_h(P_h v_kv(h)) * g) W_o``.
  - ``layer_types[i] == "sliding_attention"``: q and k rotated (rotate-half
    over the whole head, ``rope_theta``); a query at i sees keys j with
    ``0 <= i - j < sliding_window`` (+ the convention: the window holds
    ``sliding_window`` keys, the query's own among them).
  - ``"full_attention"``: (+) NO positional embedding; every key j <= i.
- Layers ``< num_dense_layers``: a SiLU-gated MLP of ``intermediate_size``.
- The others: ``s = sigmoid(h W_r)`` [num_experts]; the
  ``num_experts_per_tok`` experts of largest ``s + b`` (``b`` = the layer's
  ``expert_bias`` (+), for the CHOICE only); their weights ``s`` of the
  chosen over their sum (``route_norm``) times ``route_scale``;
  ``FFN(h) = sum_i w_i E_i(h) + Shared(h)``, each a SiLU-gated MLP of
  ``moe_intermediate_size``.  EVERY expert is computed for every token and
  weighted (zero off the chosen): no token can be dropped.

Departures from the published description: (1) ``n_group`` = ``topk_group``
= 1 makes the group-limited choice the identity, so it is not written (a
configuration with another value is refused by name); (2) weights come in
the program's TRAINING parameter layout (``dense`` and ``layers``, leaves
stacked on a leading axis: ``wq``, ``wk``, ``wv``, ``wg``, ``wo``, ...,
``router_bias`` for ``expert_bias``) and are upcast a layer, and within it
a block of experts, at a time, so that the model served in bf16 can be
checked beside its own weights on one chip; (3) the scores of a long
sequence are made a block of ``QUERY_BLOCK`` queries at a time (the same
numbers: a query's softmax is over its own row), so that 5,000 positions
fit beside them.

Every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
EXPERT_BLOCK = 8  # experts upcast and computed together
QUERY_BLOCK = 512  # queries whose scores are made together
SLIDING = "sliding_attention"


def _check(c: dict):
    if c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1:
        raise ValueError(
            f"this reference writes no group-limited choice: n_group "
            f"{c.get('n_group')} / topk_group {c.get('topk_group')} is not "
            f"1 / 1")
    if c.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError(f"score_func {c['score_func']!r} is not sigmoid")


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [b, s, heads, d]; rotate pairs (i, i + d/2) by position *
    theta^(-2i/d), the position being axis 1's index."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attention(c: dict, u, a, sliding: bool):
    """u [b, s, d] normed -> (attention's output through W_o, k and v
    [b, s, kv heads, head_dim] as a cache would hold them)."""
    b, s, _ = u.shape
    H, G, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                c["head_dim"])
    eps = c["rms_norm_eps"]
    q = _rms_norm((u @ a["wq"]).reshape(b, s, H, hd), a["q_norm"], eps)
    k = _rms_norm((u @ a["wk"]).reshape(b, s, G, hd), a["k_norm"], eps)
    v = (u @ a["wv"]).reshape(b, s, G, hd)
    gate = jax.nn.sigmoid(u @ a["wg"])  # [b, s, H * hd]
    if sliding:
        q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    kpos = jnp.arange(s)

    def rows(q_blk, qpos):  # q_blk [b, n, G, H / G, hd]
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", q_blk, k) / jnp.sqrt(F32(hd))
        seen = kpos[None, :] <= qpos[:, None]
        if sliding:
            seen &= qpos[:, None] - kpos[None, :] < c["sliding_window"]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), -1)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, v)

    qg = q.reshape(b, s, G, H // G, hd)
    if s <= 2 * QUERY_BLOCK:
        out = rows(qg, kpos)
    else:
        if s % QUERY_BLOCK:
            raise ValueError(f"a sequence of {s} positions is no whole "
                             f"number of query blocks of {QUERY_BLOCK}")
        n = s // QUERY_BLOCK
        out = jax.lax.map(
            lambda x: rows(x[0], x[1]),
            (qg.reshape(b, n, QUERY_BLOCK, G, H // G, hd).swapaxes(0, 1),
             kpos.reshape(n, QUERY_BLOCK)))
        out = out.swapaxes(0, 1).reshape(b, s, G, H // G, hd)
    return (out.reshape(b, s, H * hd) * gate) @ a["wo"], k, v


def _gated(h, w_gate, w_up, w_down):
    gate = h @ w_gate
    return (gate * jax.nn.sigmoid(gate) * (h @ w_up)) @ w_down


def choose(c: dict, scores, bias):
    """scores [n, E] = sigmoid(h W_r) -> (weights [n, k], experts [n, k]):
    the choice by scores + bias, the weights from the scores alone."""
    _, top_i = jax.lax.top_k(scores + bias, c["num_experts_per_tok"])
    top_w = jnp.take_along_axis(scores, top_i, -1)
    if c["route_norm"]:
        top_w = top_w / jnp.sum(top_w, -1, keepdims=True)
    return top_w * c["route_scale"], top_i


def _experts(c: dict, h, router, bias, experts, layer: int):
    """h [n, d] -> (sum over ALL experts of weight x expert(h), 0 off the
    chosen; the chosen's weights [n, k]; the chosen [n, k]).  ``experts``
    are every sparse layer's, stacked, of which ``layer`` is read a block
    at a time where they lie (a layer's slice of them would be a copy of
    1.6 GB at the published widths, one a layer of an unrolled stack)."""
    n_e = c["num_experts"]
    scores = jax.nn.sigmoid(h @ router)  # [n, E]
    top_w, top_i = choose(c, scores, bias)
    weight = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_w)  # [n, E]
    block = min(EXPERT_BLOCK, n_e)

    split = lambda a: a.reshape(  # noqa: E731
        a.shape[0], n_e // block, block, *a.shape[2:])
    w_gate, w_up, w_down = (split(experts[k]) for k in (
        "w_gate", "w_up", "w_down"))
    by_block = weight.T.reshape(n_e // block, block, -1)  # [blocks, EB, n]

    def part(acc, blk):
        gate = jnp.einsum("nd,edf->enf", h, w_gate[layer, blk].astype(F32))
        up = jnp.einsum("nd,edf->enf", h, w_up[layer, blk].astype(F32))
        out = jnp.einsum("enf,efd->end", gate * jax.nn.sigmoid(gate) * up,
                         w_down[layer, blk].astype(F32))
        return acc + jnp.einsum("en,end->nd", by_block[blk], out), None

    acc, _ = jax.lax.scan(part, jnp.zeros_like(h),
                          jnp.arange(n_e // block))
    return acc, top_w, top_i


def _f32(p):
    return jax.tree.map(lambda w: w.astype(F32), p)


def _layer(c: dict, x, p, sliding: bool, experts=None, layer: int = 0):
    """One block; ``experts``: the sparse layers', stacked, of which this
    is ``layer`` (never upcast whole).  Returns (x, (k, v), routing or
    None)."""
    eps = c["rms_norm_eps"]
    p = _f32(p)
    out, k, v = _attention(c, _rms_norm(x, p["attn_norm"], eps), p["attn"],
                           sliding)
    x = x + _rms_norm(out, p["post_attn_norm"], eps)
    h = _rms_norm(x, p["mlp_norm"], eps)
    routing = None
    if experts is None:
        m = p["mlp"]
        out = _gated(h, m["w_gate"], m["w_up"], m["w_down"])
    else:
        flat = h.reshape(-1, h.shape[-1])
        routed, top_w, top_i = _experts(c, flat, p["router"],
                                        p["router_bias"], experts, layer)
        sh = p["shared"]
        out = (routed + _gated(flat, sh["w_gate"], sh["w_up"],
                               sh["w_down"])).reshape(x.shape)
        routing = (top_w, top_i)
    return x + _rms_norm(out, p["post_mlp_norm"], eps), (k, v), routing


def _stack(c: dict, params, tokens):
    """tokens [b, s] -> (final-norm activations [b, s, d] float32; K and V
    by layer, each [layers, b, s, kv heads, head_dim]; the sparse layers'
    routing: weights and experts, each [sparse layers, b * s, k])."""
    _check(c)
    x = params["embed"][tokens].astype(F32)
    if c.get("mup_enabled"):
        x = x * jnp.sqrt(F32(c["hidden_size"]))
    n_dense = c["num_dense_layers"]
    sparse = {k: v for k, v in params["layers"].items() if k != "experts"}
    kv, routing = [], []
    for li, kind in enumerate(c["layer_types"]):
        i = li - n_dense
        if i < 0:
            x, rows, _ = _layer(c, x, jax.tree.map(lambda w: w[li],
                                                   params["dense"]),
                                kind == SLIDING)
        else:
            x, rows, chosen = _layer(
                c, x, jax.tree.map(lambda w: w[i], sparse), kind == SLIDING,
                params["layers"]["experts"], i)
            routing.append(chosen)
        kv.append(rows)
    x = _rms_norm(x, params["final_norm"].astype(F32), c["rms_norm_eps"])
    return (x, tuple(jnp.stack(r) for r in zip(*kv)),
            tuple(jnp.stack(r) for r in zip(*routing)))


def hidden(c: dict, params, tokens):
    """tokens [b, s] -> final-norm activations [b, s, d], float32."""
    with jax.default_matmul_precision("highest"):
        return _stack(c, params, tokens)[0]


def logits(c: dict, params, tokens):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    with jax.default_matmul_precision("highest"):
        return hidden(c, params, tokens) @ params["lm_head"].astype(F32)


def kv_rows(c: dict, params, tokens):
    """tokens [b, s] -> (K, V), each [layers, b, s, kv heads, head_dim]
    float32: what a served model's pages hold of a prompt, by layer (K
    after its norm and, in a window layer, its rotation)."""
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
    buf = np.zeros((len(prompts), pad_to), np.int32)
    for i, p in enumerate(prompts):
        seq = list(p) + list(outputs[i] if outputs else ())
        if len(seq) > pad_to:
            raise ValueError("pad_to is too short for the prompts and steps")
        buf[i, :len(seq)] = seq
    return buf


def verify(c: dict, params, prompts: list, outputs: list, steps: int,
           pad_to: int, rows: bool = False):
    """Another generator's ``outputs`` [n][<= steps] held against this
    reference TOKEN BY TOKEN on that generator's own history: [n][steps]
    of how far the logit of its token lies under the reference's best at
    that position, every earlier position holding ITS tokens (None where it
    gave no token).  One causal forward pass over prompt + output; with
    ``rows`` also that pass's K and V (``kv_rows`` of the same tokens), as
    a second result.  Causal attention makes padding to the right
    invisible."""
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
            h, kv, _ = _stack(c, params, buf)
            h = jnp.take_along_axis(h, at[:, :, None], 1)
            lg = h @ params["lm_head"].astype(F32)
        theirs = jnp.take_along_axis(lg, want[:, :, None], -1)[..., 0]
        return jnp.max(lg, -1) - theirs, kv

    gaps, kv = under_best(params, jnp.asarray(buf), jnp.asarray(at),
                          jnp.asarray(want))
    gaps = np.asarray(gaps)
    gaps = [[float(gaps[i, t]) if t < len(outputs[i]) else None
             for t in range(steps)] for i in range(n)]
    return (gaps, kv) if rows else gaps
