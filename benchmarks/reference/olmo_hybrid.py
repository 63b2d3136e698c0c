"""Plain reference of the Olmo-Hybrid family: float32 ``jax.numpy``, no
kernel, no cache, no chunks, independent of ``ray_tpu.models``, ``ray_tpu.ops``
and ``ray_tpu.llm``.

The model (``config.json`` of allenai/Olmo-Hybrid-7B): RMS norms, no biases,
an untied head, a SiLU-gated MLP, and ``layer_types`` = 3 x
``linear_attention`` then 1 x ``full_attention``, repeated.

Linear-attention layer (Gated DeltaNet, Yang, Kautz, Hatamizadeh 2024; H
heads of d_k / d_v from the ``linear_*`` keys), input row x_t:

1. q~ = W_q x, k~ = W_k x (H d_k each), v~ = W_v x (H d_v).
2. A causal depthwise convolution of width ``linear_conv_kernel_dim`` over
   time on each channel of q~, k~, v~ (the row itself and the rows before
   it; zeros before the sequence starts), then SiLU.
3. Per head: q = q~ / |q~| d_k^(-1/2), k = k~ / |k~|.
4. b = 2 sigmoid(W_b x) (the 2 is ``linear_allow_neg_eigval``);
   g = -exp(A_log) * softplus(W_a x + dt_bias), a = exp(g).
5. Per head S [d_v, d_k], S_0 = 0, TOKEN BY TOKEN under ``lax.scan``:
   S_t = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T;   o_t = S_t q_t.
6. y = W_o (RMSNorm_{d_v}(o_t; a learned [d_v] weight) * SiLU(W_g x)).

Full-attention layer: ``num_attention_heads`` query and KV heads, causal
softmax attention at scale head_dim^(-1/2).

Not in the published config, set by the family's convention (the
configuration file's ``assumed``): (a) OLMo 2 / 3's block, x + norm(f(x))
with the norm on the mixer's and the MLP's OUTPUT, and q and k of the full
layers RMS-normed over their whole width before the heads are split; (b)
the full layers rotate q and k (rotate-half over the whole head) with theta
``rope_theta`` of the configuration file; (c) the state is float32 (here
everything is).  Departure: weights come in the program's parameter layout
(``layers.lin`` stacked [linear layers, ...], ``layers.full`` [periods, ...])
and are upcast a layer at a time, so that a model served in bf16 can be
checked beside its own weights on one chip.

Every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
# candidates a step: every token within the comparison's margin of the best
# has to be among them (runners/serve_recurrent.py: 0.5).  Over a vocabulary
# of 100,352 and logits of 1.0 rms the four best lie within 0.1 of each other
# and a dozen within 0.5 (my chip runs, PR 38: two runs of seven read
# ``correct`` false with four, the engine's token a fifth- or sixth-best
# 0.1 under the best).
TOP_K = 64


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: [b, s, h, d]; rotate pairs (i, i + d/2) by position * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(x.shape[1], dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mlp(c, x, p):
    """x + norm(MLP(x))."""
    y = (_silu(x @ p["mlp"]["w_gate"]) * (x @ p["mlp"]["w_up"])) \
        @ p["mlp"]["w_down"]
    return x + _rms_norm(y, p["mlp_norm"], c["rms_norm_eps"])


def _conv(x, taps):
    """x [b, s, C], taps [W, C] (the last on the row itself): causal
    depthwise convolution over s from zeros, then SiLU."""
    w, s = taps.shape[0], x.shape[1]
    rows = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    return _silu(sum(rows[:, j:j + s] * taps[j] for j in range(w)))


def _unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)


def _delta_step(S, x):
    """Step 5 for one token: S [H, d_v, d_k], x = (q, k, v, g, b)."""
    q, k, v, g, b = x
    # a = exp(g) as 1 + expm1(g): on a TPU exp is good to 1.5e-6 of its
    # result, and hundreds of such factors compound (7e-5 after 300)
    S = (1.0 + jnp.expm1(g))[:, None, None] * S
    S = S + (b[:, None] * (v - jnp.einsum("hvk,hk->hv", S, k))
             )[:, :, None] * k[:, None, :]
    return S, jnp.einsum("hvk,hk->hv", S, q)


def delta_rule(q, k, v, g, beta):
    """Step 5 for one sequence, token by token.  q, k [s, H, d_k], v
    [s, H, d_v], g, beta [s, H] -> o [s, H, d_v]."""
    H, dk, dv = q.shape[1], q.shape[2], v.shape[2]
    return jax.lax.scan(_delta_step, jnp.zeros((H, dv, dk), F32),
                        (q, k, v, g, beta))[1]


def _delta_inputs(c, x, m):
    """Steps 1-4: x [b, s, d], a layer's mixer weights -> q, k [b, s, H,
    d_k], v [b, s, H, d_v], g, beta [b, s, H]."""
    b, s, _ = x.shape
    H, dk, dv = (c["linear_num_value_heads"], c["linear_key_head_dim"],
                 c["linear_value_head_dim"])
    taps = jnp.split(m["conv"], (H * dk, 2 * H * dk), axis=-1)
    q = _conv(x @ m["wq"], taps[0]).reshape(b, s, H, dk)
    k = _conv(x @ m["wk"], taps[1]).reshape(b, s, H, dk)
    v = _conv(x @ m["wv"], taps[2]).reshape(b, s, H, dv)
    beta = (2.0 if c["linear_allow_neg_eigval"] else 1.0) \
        * jax.nn.sigmoid(x @ m["wb"])
    g = -jnp.exp(m["A_log"]) * jax.nn.softplus(x @ m["wa"] + m["dt_bias"])
    return _unit(q) * dk ** -0.5, _unit(k), v, g, beta


def _linear_block(c, x, p):
    b, s, _ = x.shape
    p = jax.tree.map(lambda w: w.astype(F32), p)
    m = p["mix"]
    o = jax.vmap(delta_rule)(*_delta_inputs(c, x, m))  # [b, s, H, d_v]
    o = _rms_norm(o, m["o_norm"], c["rms_norm_eps"]).reshape(b, s, -1)
    y = (o * _silu(x @ m["wg"])) @ m["wo"]
    x = x + _rms_norm(y, p["attn_norm"], c["rms_norm_eps"])
    return _mlp(c, x, p)


def first_layer_states(c: dict, params, tokens, lengths):
    """The FIRST layer's state S [H, d_v, d_k] once ONE sequence (tokens
    [s]) has been taken in as far as each of ``lengths`` [M] (ints, a
    traced array will do): [M, H, d_v, d_k] float32, token by token from
    zeros.  The first layer is a linear-attention layer whose mixer reads
    the embedding's rows as they are (the block norms its OUTPUT), so
    nothing lies between the tokens and this state but steps 1-5: of
    ``params`` only ``embed`` and the first row of ``layers.lin.mix`` are
    read."""
    with jax.default_matmul_precision("highest"):
        m = jax.tree.map(lambda w: w[0].astype(F32),
                         params["layers"]["lin"]["mix"])
        x = params["embed"][tokens].astype(F32)[None]
        q, k, v, g, beta = (a[0] for a in _delta_inputs(c, x, m))
        lengths = jnp.asarray(lengths, jnp.int32)

        def step(carry, x):
            S, kept, t = carry
            S, _ = _delta_step(S, x)
            kept = jnp.where((lengths == t + 1)[:, None, None, None], S, kept)
            return (S, kept, t + 1), None

        S0 = jnp.zeros((q.shape[1], v.shape[2], q.shape[2]), F32)
        kept0 = jnp.zeros((lengths.shape[0], *S0.shape), F32)
        return jax.lax.scan(step, (S0, kept0, jnp.int32(0)),
                            (q, k, v, g, beta))[0][1]


def _full_block(c, x, p):
    b, s, _ = x.shape
    nh = c["num_attention_heads"]
    hd = c.get("head_dim") or c["hidden_size"] // nh
    eps = c["rms_norm_eps"]
    p = jax.tree.map(lambda w: w.astype(F32), p)
    a = p["attn"]
    q = _rms_norm(x @ a["wq"], a["q_norm"], eps).reshape(b, s, nh, hd)
    k = _rms_norm(x @ a["wk"], a["k_norm"], eps).reshape(b, s, nh, hd)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    v = (x @ a["wv"]).reshape(b, s, nh, hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(hd))
    scores = jnp.where(jnp.tril(jnp.ones((s, s), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, -1), v)
    x = x + _rms_norm(out.reshape(b, s, nh * hd) @ a["wo"], p["attn_norm"],
                      eps)
    return _mlp(c, x, p)


def _period(c, x, p):
    n = jax.tree.leaves(p["lin"])[0].shape[0]
    for j in range(n):
        x = _linear_block(c, x, jax.tree.map(lambda w: w[j], p["lin"]))
    return _full_block(c, x, p["full"])


def _by_period(layers):
    """``lin`` [linear layers, ...] as [periods, linear layers a period,
    ...] beside ``full`` [periods, ...]."""
    periods = jax.tree.leaves(layers["full"])[0].shape[0]
    return {"full": layers["full"], "lin": jax.tree.map(
        lambda w: w.reshape(periods, -1, *w.shape[1:]), layers["lin"])}


def hidden(c: dict, params, tokens):
    """tokens [b, s] -> final-norm activations [b, s, d], float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"].astype(F32)[tokens]
        x, _ = jax.lax.scan(lambda x, p: (_period(c, x, p), None), x,
                            _by_period(params["layers"]))
        return _rms_norm(x, params["final_norm"].astype(F32),
                         c["rms_norm_eps"])


def logits(c: dict, params, tokens):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    with jax.default_matmul_precision("highest"):
        return hidden(c, params, tokens) @ params["lm_head"].astype(F32)


def greedy(c: dict, params, prompts: list, steps: int, pad_to: int):
    """Greedy continuation of each prompt by FULL re-forward at every step
    (no cache, no state kept).  Returns (candidates, gaps), each
    [n][steps][TOP_K]: the TOP_K tokens with the largest logits at that
    step, best first (the first continues the sequence), and how far each
    one's logit lies under the best.  All prompts run as one padded batch:
    what lies to the right of a sequence is invisible to it, in the causal
    attention and in the recurrence alike."""
    import numpy as np

    n = len(prompts)
    buf = np.zeros((n, pad_to), np.int32)
    lens = np.array([len(p) for p in prompts], np.int32)
    if int(lens.max()) + steps > pad_to:
        raise ValueError("pad_to is too short for the prompts and steps")
    for i, p in enumerate(prompts):
        buf[i, :len(p)] = p

    @jax.jit
    def step(params, buf, lens):
        with jax.default_matmul_precision("highest"):
            h = hidden(c, params, buf)
            last = h[jnp.arange(n), lens - 1]
            lg = last @ params["lm_head"].astype(F32)
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
