"""Plain reference for MiniCPM-SALA (``model_type`` ``minicpm_sala``): block
-sparse attention layers (InfLLM-v2, arXiv:2506.07900) and fixed-decay
linear-attention layers (Lightning Attention, arXiv:2401.04658), in plain
``jax.numpy`` at float32 under ``jax.default_matmul_precision("highest")``.
No cache, no chunks, no kernel; it imports nothing of ``ray_tpu``.  ``c`` is
the configuration file as a dict (the published ``config.json`` keys and
the sizes listed under ``sparse_config``), ``params`` the tree of
``ray_tpu.models.minicpm_sala.init`` (``layers`` = ``{"sparse": ...,
"lin": ...}``, leaves stacked over the layers of a kind).

The equations, with ``h = RMSNorm(x)`` (eps ``rms_norm_eps``):

- stream: ``x0 = scale_emb E[token]``; every sublayer ``x <- x +
  (scale_depth / sqrt(D)) f(h)``, D the PUBLISHED depth
  (``published.num_hidden_layers``); logits ``W_head (RMSNorm(x) /
  (hidden_size / dim_model_base))``; MLP ``W_down(silu(W_gate h) * W_up h)``.
- ``lightning-attn``: q, k, v = ``W_q h, W_k h, W_v h`` (H heads of d); RMS
  norm of q and of k over the head; rotary embedding (rotate-half, absolute
  position); ``o_t = sum_{i <= t} lambda^(t - i) (q_t . k_i) v_i / sqrt(d)``
  (the recurrence ``S_t = lambda S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t``
  in closed form), ``lambda_h = exp(-2^(-8 (h + 1) / H))``; RMS norm of
  ``o_t`` over the head; ``o_t * sigmoid(W_g h)``; ``W_o``.
- ``minicpm4``: q (H heads), k, v (G KV heads), no rotary embedding, the
  same norms; for the query at position t with n = t + 1 of context: n <=
  ``dense_len``: causal softmax attention over all n; else, a KV head at a
  time, pooled keys ``c_j = mean(k[s j : s j + w])`` for ``s j + w <= n``,
  ``p = sum over the group's heads of softmax_j(q . c_j / sqrt(d))``, a
  block of ``block_size`` positions scores the max of p over the rows that
  overlap it, and selected are the first ``init_blocks`` blocks, every
  block that overlaps the last ``window_size`` positions and the highest
  scores among the rest until ``topk`` in all (the lower index first among
  equals); causal softmax attention over the selected blocks;
  ``o * sigmoid(W_g h)``; ``W_o``.

Queries go ``QUERY_BLOCK`` at a time, so that 24k positions fit: a block's
scores against every key are [heads, QUERY_BLOCK, s].

``selection`` (what ``_stack`` returns and takes): [sparse layers, s, G,
blocks] bool, the blocks each query's KV heads attended to.  Handed back in
(``pinned``), the choice is not made again: top-k flips on rounding as a
router does, and a comparison of LOGITS wants both sides on one choice.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256
SPARSE, LINEAR = "minicpm4", "lightning-attn"


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [s, heads, d] at positions 0 .. s - 1: rotate-half."""
    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=F32) / (d // 2))
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _f32(p):
    return jax.tree.map(lambda w: w.astype(F32), p)


def _by_query_block(fn, s: int, *xs):
    """``fn(positions [b], *rows of xs [b, ...])`` over blocks of queries,
    stacked back to [s, ...]."""
    qb = int(np.gcd(s, QUERY_BLOCK))
    out = jax.lax.map(lambda a: fn(*a), (
        jnp.arange(s).reshape(s // qb, qb),
        *(x.reshape(s // qb, qb, *x.shape[1:]) for x in xs)))
    return jax.tree.map(lambda y: y.reshape(s, *y.shape[2:]), out)


def decays(c: dict):
    """lambda_h, float32 [H]."""
    H = c["lightning_nh"]
    return jnp.exp(-jnp.exp2(-8.0 * jnp.arange(1, H + 1, dtype=F32) / H))


def linear_attention(c: dict, q, k, v):
    """q, k, v [s, H, d] -> o [s, H, d]: the closed form of the
    recurrence, queries a block at a time."""
    s, _, d = q.shape
    log = jnp.log(decays(c))

    def rows(pos, qb):
        sc = jnp.einsum("qhd,khd->hqk", qb, k)
        age = pos[:, None] - jnp.arange(s)[None, :]
        w = jnp.where(age >= 0, jnp.exp(log[:, None, None]
                                        * jnp.maximum(age, 0)), 0.0)
        return jnp.einsum("hqk,khd->qhd", sc * w, v) * d ** -0.5

    return _by_query_block(rows, s, q)


def state_at(c: dict, k, v, n):
    """The recurrence's state after the first n tokens: [H, d, d]."""
    s = k.shape[0]
    age = n - 1 - jnp.arange(s)
    w = jnp.where(age >= 0, jnp.exp(jnp.log(decays(c))[:, None]
                                    * jnp.maximum(age, 0)), 0.0)  # [H, s]
    return jnp.einsum("hs,shk,shv->hkv", w, k, v)


def pooled_keys(c: dict, k):
    """k [s, G, d] -> [s / stride, G, d]: row j the mean of k[stride j :
    stride j + kernel_size] (rows that reach past s are not complete)."""
    sp = c["sparse_config"]
    st, w = sp["kernel_stride"], sp["kernel_size"]
    s = k.shape[0]
    kp = jnp.pad(k, ((0, w), (0, 0), (0, 0)))
    at = jnp.arange(s // st)[:, None] * st + jnp.arange(w)[None, :]
    return kp[at].mean(1)


def choose(c: dict, q, rows, n):
    """One query: q [H, d], rows [J, G, d] pooled keys, n its context (a
    traced scalar) -> [G, M] bool, M = ceil(J stride / block_size): the
    blocks each KV head's query heads attend to."""
    sp = c["sparse_config"]
    st, w, B = sp["kernel_stride"], sp["kernel_size"], sp["block_size"]
    H, d = q.shape
    J, G, _ = rows.shape
    M = -(-J * st // B)
    done = jnp.arange(J) * st + w <= n
    sc = jnp.einsum("ghd,jgd->ghj", q.reshape(G, H // G, d), rows) * d ** -0.5
    p = jax.nn.softmax(jnp.where(done, sc, -jnp.inf), -1)
    p = jnp.where(done, jnp.nan_to_num(p).sum(1), -1.0)  # [G, J]
    # the rows that overlap block m: j st < (m + 1) B and j st + w > m B
    m = jnp.arange(M)[:, None]
    j = jnp.arange(J)[None, :]
    over = (j * st < (m + 1) * B) & (j * st + w > m * B)  # [M, J]
    score = jnp.max(jnp.where(over, p[:, None, :], -1.0), -1)  # [G, M]
    m = jnp.arange(M)
    forced = (m < sp["init_blocks"]) | ((m + 1) * B > n - sp["window_size"])
    there = m * B < n
    score = jnp.where(forced, 1e4, score)
    # the highest first, the lower index first among equals
    order = jnp.argsort(jnp.where(there, -score, jnp.inf), -1, stable=True)
    rank = jnp.argsort(order, -1, stable=True)
    return ((rank < sp["topk"]) | (n <= sp["dense_len"])) & there


def sparse_attention(c: dict, q, k, v, pinned=None):
    """q [s, H, d], k, v [s, G, d] -> (o [s, H, d], the selection [s, G,
    M] bool).  ``pinned``: a selection to attend under instead."""
    sp = c["sparse_config"]
    s, H, d = q.shape
    G = k.shape[1]
    rows = pooled_keys(c, k)
    kpos = jnp.arange(s)

    def block(pos, qb, *pin):
        sel = (pin[0] if pin else jax.vmap(
            lambda q1, n: choose(c, q1, rows, n))(qb, pos + 1))  # [b, G, M]
        seen = jnp.repeat(sel, sp["block_size"], -1)[..., :s]
        seen &= kpos[None, None, :] <= pos[:, None, None]
        sc = jnp.einsum("qghd,kgd->qghk", qb.reshape(-1, G, H // G, d),
                        k) * d ** -0.5
        a = jax.nn.softmax(jnp.where(seen[:, :, None, :], sc, -jnp.inf), -1)
        return jnp.einsum("qghk,kgd->qghd", a, v).reshape(-1, H, d), sel

    return _by_query_block(block, s, q,
                           *(() if pinned is None else (pinned,)))


def _mlp(x, m):
    return (jax.nn.silu(x @ m["w_gate"]) * (x @ m["w_up"])) @ m["w_down"]


def _stack(c: dict, params, tokens, pinned=None, states=None, q_at=None):
    """tokens [s] -> (final-norm activations [s, d], what the layers made).
    ``pinned``: [sparse layers, s, G, M] selections to attend under.
    ``states``: a context length; the linear layers' states after so many
    tokens are handed back too.  ``q_at``: a position; the sparse layers'
    queries there and what their attention made of them are handed back
    too (``q``, ``o``: [sparse layers, H, d])."""
    eps, d = c["rms_norm_eps"], c["hidden_size"]
    rs = c["scale_depth"] / c["published"]["num_hidden_layers"] ** 0.5
    H, hd = c["num_attention_heads"], c["head_dim"]
    G = c["num_key_value_heads"]
    LH, lhd = c["lightning_nh"], c["lightning_head_dim"]
    x = params["embed"].astype(F32)[tokens] * c["scale_emb"]
    s = x.shape[0]
    seen = {SPARSE: 0, LINEAR: 0}
    made = {"k": [], "v": [], "pooled": [], "selection": [], "S": [],
            "q": [], "o": []}
    for kind in c["mixer_types"]:
        i = seen[kind]
        seen[kind] += 1
        p = _f32(jax.tree.map(lambda w: w[i], params["layers"][
            "sparse" if kind == SPARSE else "lin"]))
        m = p["mix"]
        h = _rms_norm(x, p["attn_norm"], eps)
        gate = jax.nn.sigmoid(h @ m["wg"])
        if kind == SPARSE:
            q = _rms_norm((h @ m["wq"]).reshape(s, H, hd), m["q_norm"], eps)
            k = _rms_norm((h @ m["wk"]).reshape(s, G, hd), m["k_norm"], eps)
            v = (h @ m["wv"]).reshape(s, G, hd)
            o, sel = sparse_attention(
                c, q, k, v, None if pinned is None else pinned[i])
            made["k"].append(k)
            made["v"].append(v)
            made["pooled"].append(pooled_keys(c, k))
            made["selection"].append(sel)
            if q_at is not None:
                made["q"].append(q[q_at])
                made["o"].append(o[q_at])
        else:
            q, k = (_rope(_rms_norm((h @ m[w]).reshape(s, LH, lhd), m[n], eps),
                          float(c["rope_theta"]))
                    for w, n in (("wq", "q_norm"), ("wk", "k_norm")))
            v = (h @ m["wv"]).reshape(s, LH, lhd)
            o = _rms_norm(linear_attention(c, q, k, v), m["o_norm"], eps)
            if states is not None:
                made["S"].append(state_at(c, k, v, states))
        x = x + rs * ((o.reshape(s, -1) * gate) @ m["wo"])
        x = x + rs * _mlp(_rms_norm(x, p["mlp_norm"], eps), p["mlp"])
    x = _rms_norm(x, params["final_norm"].astype(F32), eps)
    return x / (d / c["dim_model_base"]), {
        name: jnp.stack(rows) for name, rows in made.items() if rows}


def logits(c: dict, params, tokens):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    with jax.default_matmul_precision("highest"):
        return jnp.stack([_stack(c, params, t)[0] for t in tokens]) \
            @ params["lm_head"].astype(F32)


def rows_of(c: dict, params, tokens, states=None):
    """tokens [s] -> what a served model caches of them, float32: ``k``,
    ``v`` [sparse layers, s, G, d]; ``pooled`` [sparse layers, s / stride,
    G, d]; ``selection``; with ``states`` (a length) ``S`` [linear layers,
    H, d, d], the states after so many tokens."""
    with jax.default_matmul_precision("highest"):
        return _stack(c, params, tokens, states=states)[1]


def logits_and_selection(c: dict, params, tokens, rows):
    """tokens [s], rows [r] positions -> (logits [r, vocab] at those
    positions, the selection [sparse layers, s, G, M] bool this reference
    made), for a comparison of LOGITS under one choice of blocks."""
    with jax.default_matmul_precision("highest"):
        h, made = _stack(c, params, tokens)
        return h[rows] @ params["lm_head"].astype(F32), made["selection"]


def verify(c: dict, params, prompt: list, output: list, steps: int,
           pad_to: int, fed: list = None, q_at: int = 0):
    """Another generator's ``output`` [<= steps] held against this
    reference TOKEN BY TOKEN on that generator's own history: [steps] of
    how far the logit of its token lies under the reference's best at that
    position (None where it gave no token), and ``rows_of`` of the same
    pass, the states after its last token among them.  ``fed``: the tokens
    the pass runs over where they are not ``prompt + output`` (a served
    model's state may have taken a few more than it emitted); ``q_at``: the
    position whose sparse-layer queries and attention outputs come back
    with the rows.  Causal
    attention and a causal recurrence make padding to the right
    invisible."""
    seq = list(fed) if fed is not None else list(prompt) + list(output)
    if len(seq) > pad_to:
        raise ValueError("pad_to is too short for the prompt and steps")
    buf = np.zeros(pad_to, np.int32)
    buf[:len(seq)] = seq
    at = np.minimum(len(prompt) - 1 + np.arange(steps), len(seq) - 1)
    want = np.zeros(steps, np.int32)
    want[:len(output[:steps])] = output[:steps]

    @jax.jit
    def under_best(params, buf, at, want, states, q_at):
        with jax.default_matmul_precision("highest"):
            h, made = _stack(c, params, buf, states=states, q_at=q_at)
            lg = h[at] @ params["lm_head"].astype(F32)
        theirs = jnp.take_along_axis(lg, want[:, None], -1)[:, 0]
        return jnp.max(lg, -1) - theirs, made

    gaps, made = under_best(params, jnp.asarray(buf), jnp.asarray(at),
                            jnp.asarray(want), jnp.int32(len(seq)),
                            jnp.int32(q_at))
    gaps = np.asarray(gaps)
    return ([float(gaps[t]) if t < len(output) else None
             for t in range(steps)], made)
