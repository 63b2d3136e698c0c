"""Plain reference of the SDAR-MoE family: float32 ``jax.numpy``, no kernel,
no cache, no sorting or grouping, independent of ``ray_tpu.models`` and
``ray_tpu.llm``.

The layer (Qwen3-MoE's, from which ``sdar_moe`` derives): pre-norm blocks,
RMSNorm, an RMS norm a head on q and k before the rotary embedding over
split halves, grouped-query attention that is causal over blocks of
``block_length`` positions and bidirectional inside one, and in every layer
a router (softmax over all experts in float32, top-k, the k weights
renormalised) over gated experts with no shared expert.  EVERY expert is
computed for every token and weighted by the renormalised top-k weight
(zero off the top-k): no token can be dropped.  Logits at position i are
for the token at i.

Generation is the model's published ``generate.py``: the prompt's whole
blocks are context, its tail opens the first block, and each block is
denoised by full forward passes over the whole sequence so far, filling
masks by ``remasking_strategy``.  A cacheless reference needs no pass to
make a block's K/V final: recomputing is what the engine's cache has to
reproduce.

Departures: (1) masked positions are tracked as state, not found by
comparing ids with the mask token's (the benchmark's traffic draws ids from
the whole vocabulary, the mask token's among them); (2) only masked
positions are ever filled: the published top-k can select an unmasked
position of a block that the prompt's tail opened; (3) weights come in the
program's parameter layout (layers stacked on a leading axis) and are
upcast a layer, and within it a block of experts, at a time, so that the
model served in bf16 can be checked beside its own weights on one chip.

Every entry point runs under ``jax.default_matmul_precision("highest")``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
TOP_K = 4  # candidates a position
EXPERT_BLOCK = 8  # experts upcast and computed together


def sampler(c: dict) -> dict:
    return c["sampler"]


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


def _experts(c: dict, h, router, experts):
    """h [n, d] -> (sum over ALL experts of weight x expert(h), the weight
    being the renormalised top-k softmax probability, 0 off the top-k;
    those weights [n, k]; their experts [n, k])."""
    n_e, k = c["num_experts"], c["num_experts_per_tok"]
    probs = jax.nn.softmax(h @ router, -1)  # [n, E]
    top_p, top_i = jax.lax.top_k(probs, k)
    if c["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
    weight = jnp.zeros_like(probs).at[
        jnp.arange(h.shape[0])[:, None], top_i].set(top_p)  # [n, E]
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
    return acc, top_p, top_i


def _block(c: dict, x, p):
    b, s, _ = x.shape
    nh, nkv, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["head_dim"])
    eps, B = c["rms_norm_eps"], sampler(c)["block_length"]
    experts = p["experts"]
    p = jax.tree.map(lambda w: w.astype(F32),
                     {k: v for k, v in p.items() if k != "experts"})
    a = p["attn"]
    h = _rms_norm(x, p["attn_norm"], eps)
    q = _rms_norm((h @ a["wq"]).reshape(b, s, nh, hd), a["q_norm"], eps)
    k = _rms_norm((h @ a["wk"]).reshape(b, s, nkv, hd), a["k_norm"], eps)
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    v = (h @ a["wv"]).reshape(b, s, nkv, hd)
    q = q.reshape(b, s, nkv, nh // nkv, hd)
    scores = jnp.einsum("bqgrd,bkgd->bgrqk", q, k) / jnp.sqrt(F32(hd))
    blk = jnp.arange(s) // B
    scores = jnp.where(blk[None, :] <= blk[:, None], scores, -jnp.inf)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", jax.nn.softmax(scores, -1), v)
    x = x + out.reshape(b, s, nh * hd) @ a["wo"]
    h = _rms_norm(x, p["mlp_norm"], eps)
    out, top_p, top_i = _experts(c, h.reshape(b * s, -1), p["router"],
                                 experts)
    return x + out.reshape(x.shape), (top_p, top_i)


def layer(c: dict, x, p):
    """One layer of the stack on x [b, s, d] float32; p: its parameters."""
    with jax.default_matmul_precision("highest"):
        return _block(c, x, p)[0]


def _stack(c: dict, params, tokens):
    """tokens [b, s] -> (final-norm activations [b, s, d] float32, the
    routing: weights and experts, each [layers, b * s, k])."""
    x = params["embed"].astype(F32)[tokens]
    x, routing = jax.lax.scan(lambda x, p: _block(c, x, p), x,
                              params["layers"])
    return _rms_norm(x, params["final_norm"].astype(F32),
                     c["rms_norm_eps"]), routing


def hidden(c: dict, params, tokens):
    """tokens [b, s] -> final-norm activations [b, s, d], float32."""
    with jax.default_matmul_precision("highest"):
        return _stack(c, params, tokens)[0]


def logits(c: dict, params, tokens):
    """tokens [b, s] -> logits [b, s, vocab], float32."""
    with jax.default_matmul_precision("highest"):
        return hidden(c, params, tokens) @ params["lm_head"].astype(F32)


def logits_and_routing(c: dict, params, tokens, rows):
    """tokens [b, s], rows [b, r] positions -> (logits [b, r, vocab] float32
    at those positions, weights [layers, b * s, k] float32, experts
    [layers, b * s, k] int32): what this reference computed and the expert
    sets it took, for a comparison of LOGITS in which the other side is
    handed the same sets (``families/sdar_moe.py`` ``pinned_logits``)."""
    with jax.default_matmul_precision("highest"):
        h, (weights, chosen) = _stack(c, params, tokens)
        h = jnp.take_along_axis(h, rows[:, :, None], axis=1)
        return (h @ params["lm_head"].astype(F32), weights,
                chosen.astype(jnp.int32))


def num_transfer_tokens(block_length: int, steps: int) -> list:
    base, extra = divmod(block_length, steps)
    return [base + (i < extra) for i in range(steps)]


def select(strategy: str, masked, conf, n_t: int, threshold: float):
    """The masked positions of one block that a pass fills.  masked, conf:
    [B].  ``sequential``: the leftmost n_t; ``low_confidence_static``: the
    n_t of highest confidence (a tie goes to the leftmost);
    ``low_confidence_dynamic``: every one over the threshold if those are
    at least n_t, else as static."""
    idx = [j for j in range(len(masked)) if masked[j]]
    if strategy == "sequential":
        return idx[:n_t]
    by_conf = sorted(idx, key=lambda j: (-conf[j], j))[:n_t]
    if strategy == "low_confidence_static":
        return by_conf
    high = [j for j in idx if conf[j] > threshold]
    return high if len(high) >= n_t else by_conf


def passes(c: dict, params, prompts: list, steps: int, pad_to: int,
           forced: list = None):
    """Generate ``steps`` tokens after each prompt; yields, a denoising pass
    of the padded batch, a list over the prompts of None (that prompt is
    done) or a dict: ``start`` of its open block, ``masked`` [B] before the
    pass, ``filled`` positions of the block, ``top`` [B][TOP_K] token ids,
    ``gaps`` [B][TOP_K] logit distances under the best, ``conf`` [B].
    Padding lies to the right of every open block, in later blocks, which
    the block-causal mask hides.

    ``forced`` [n][steps]: another generator's output tokens.  A position
    this reference fills then takes THAT token and not its own best, so
    every pass sees the history the other generator saw, and the dict
    gains ``forced_gaps`` [B]: how far the forced token's logit lies under
    the best where the pass fills it (``verify``)."""
    sm = sampler(c)
    B, T = sm["block_length"], sm["denoising_steps"]
    n_ts = num_transfer_tokens(B, T)
    n = len(prompts)
    lens = [len(p) for p in prompts]
    if max(-(-(ln + steps) // B) * B for ln in lens) > pad_to:
        raise ValueError("pad_to is too short for the prompts and steps")
    buf = np.full((n, pad_to), sm["mask_token_id"], np.int32)
    masked = np.ones((n, pad_to), bool)
    for i, p in enumerate(prompts):
        buf[i, :len(p)] = p
        masked[i, :len(p)] = False
    start = [ln // B * B for ln in lens]
    step = [0] * n

    want = np.zeros((n, pad_to), np.int32)  # the forced token a position
    for i, out in enumerate(forced or ()):
        want[i, lens[i]:lens[i] + len(out[:steps])] = out[:steps]

    @jax.jit
    def forward(params, buf, start, want):
        with jax.default_matmul_precision("highest"):
            h = hidden(c, params, buf)
            rows = start[:, None] + jnp.arange(B)[None, :]
            h = jnp.take_along_axis(h, rows[:, :, None], axis=1)  # [n, B, d]
            lg = h @ params["lm_head"].astype(F32)
        top = jax.lax.top_k(lg, TOP_K)
        conf = jnp.exp(top[0][..., 0] - jax.scipy.special.logsumexp(lg, -1))
        theirs = jnp.take_along_axis(
            lg, jnp.take_along_axis(want, rows, axis=1)[..., None], -1)
        return (top[1], top[0][..., :1] - top[0], conf,
                top[0][..., 0] - theirs[..., 0])

    def done(i):
        return start[i] >= lens[i] + steps

    while not all(done(i) for i in range(n)):
        top, gaps, conf, forced_gaps = (np.asarray(x) for x in forward(
            params, jnp.asarray(buf),
            jnp.asarray(np.minimum(start, pad_to - B), np.int32),
            jnp.asarray(want)))
        out = []
        for i in range(n):
            if done(i):
                out.append(None)
                continue
            s = start[i]
            m = masked[i, s:s + B].copy()
            filled = select(sm["remasking_strategy"], m, conf[i],
                            n_ts[min(step[i], T - 1)],
                            sm["confidence_threshold"])
            for j in filled:
                given = forced is not None and s + j - lens[i] < len(forced[i])
                buf[i, s + j] = want[i, s + j] if given else top[i, j, 0]
                masked[i, s + j] = False
            out.append({"start": s, "masked": m.tolist(), "filled": filled,
                        "top": top[i].tolist(), "gaps": gaps[i].tolist(),
                        "conf": conf[i].tolist(),
                        "forced_gaps": forced_gaps[i].tolist()})
            step[i] += 1
            if not masked[i, s:s + B].any():
                start[i], step[i] = s + B, 0
        yield out


def greedy(c: dict, params, prompts: list, steps: int, pad_to: int):
    """Returns (candidates, gaps), each [n][steps][TOP_K], one entry an
    OUTPUT POSITION: the TOP_K tokens with the largest logits there at the
    pass that filled it, best first (the first is the token generated), and
    how far each one's logit lies under the best."""
    n = len(prompts)
    cands = [[None] * steps for _ in range(n)]
    gaps = [[None] * steps for _ in range(n)]
    for out in passes(c, params, prompts, steps, pad_to):
        for i, o in enumerate(out):
            for j in (o["filled"] if o else ()):
                t = o["start"] + j - len(prompts[i])
                if t < steps:
                    cands[i][t] = o["top"][j]
                    gaps[i][t] = [float(g) for g in o["gaps"][j]]
    return cands, gaps


def verify(c: dict, params, prompts: list, outputs: list, steps: int,
           pad_to: int):
    """Another generator's ``outputs`` [n][<= steps] held against this
    reference TOKEN BY TOKEN on that generator's own history: [n][steps]
    of how far the logit of its token lies under the reference's best at
    the pass that fills the position, every earlier position holding ITS
    tokens (None where it gave no token).  Unlike ``greedy`` this does not
    end at the first token that differs: a served model that takes another
    expert than float32 on a near-tie of its router differs somewhere in
    most sequences, and what follows is then judged on what it saw.  With
    ``sequential`` remasking the positions a pass fills do not depend on
    the logits, so the passes here are the generator's; with the
    confidence strategies they are this reference's."""
    n = len(prompts)
    gaps = [[None] * steps for _ in range(n)]
    for out in passes(c, params, prompts, steps, pad_to, forced=outputs):
        for i, o in enumerate(out):
            for j in (o["filled"] if o else ()):
                t = o["start"] + j - len(prompts[i])
                if t < min(steps, len(outputs[i])):
                    gaps[i][t] = float(o["forced_gaps"][j])
    return gaps
