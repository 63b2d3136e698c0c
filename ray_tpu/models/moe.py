"""Mixtral-family sparse MoE decoder LM with expert parallelism, TPU-first.

The reference has no native MoE/expert-parallel implementation — it passes
``enable_expert_parallel`` through to vLLM engine kwargs (SURVEY.md §2.4).
Here EP is a mesh axis: expert weights are sharded over ``ep`` and token
dispatch/combine are einsums against a static-capacity one-hot dispatch
tensor (GShard-style), so XLA emits the token all-to-all from the shardings
alone.  Everything is static-shape: top-k routing, capacity dropping, and
combine are MXU-friendly dense ops — no ragged gathers.

The block's attention half, the embedding and the epilogue are the Llama
family's parts (models/llama.py); only the feed-forward is this file's own.

Two routed layers live here.  ``moe_mlp`` is the training model's: a
static capacity, tokens over it dropped, dispatch as einsums whose
shardings give XLA the all-to-all.  ``routed_mlp`` is the serving one
(models/sdar_moe.py through llm/model.py): dropless, assignments sorted by
expert into ``ops/grouped_matmul``, no (tokens, experts, capacity) tensor.
``dispatch_share`` is the same for ONE CHIP'S SHARE of an expert-parallel
layer (models/longcat_flash.py): a router over more columns than the chip
holds experts, some of them identity experts with no weights at all.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import (attention_block, batch_attend,
                                  decoder_logical_specs, embed, head,
                                  rms_norm)
from ray_tpu.models.llama import layer as decoder_layer
from ray_tpu.parallel.sharding import logical_spec as L


@dataclass(frozen=True)
class MoEConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 8
    d_ff: int = 14336
    n_experts: int = 8
    experts_per_token: int = 2
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    max_seq_len: int = 32768
    rope_theta: float = 1_000_000.0
    norm_eps: float = 1e-5
    dtype: str = "bfloat16"
    remat: bool = True

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @staticmethod
    def mixtral_8x7b() -> "MoEConfig":
        return MoEConfig()

    @staticmethod
    def tiny(vocab_size: int = 512) -> "MoEConfig":
        return MoEConfig(vocab_size=vocab_size, d_model=128, n_layers=2,
                         n_heads=4, n_kv_heads=2, d_ff=256, n_experts=4,
                         experts_per_token=2, max_seq_len=256, remat=False)


def param_logical_specs(cfg: MoEConfig):
    return decoder_logical_specs({
        "router": L("layers", "embed", None),
        "experts": {
            "w_gate": L("layers", "experts", "embed", "expert_mlp"),
            "w_up": L("layers", "experts", "embed", "expert_mlp"),
            "w_down": L("layers", "experts", "expert_mlp", "embed"),
        },
    })


def init(cfg: MoEConfig, key: jax.Array):
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    d, nl, ne = cfg.d_model, cfg.n_layers, cfg.n_experts
    hq = cfg.n_heads * cfg.head_dim
    hkv = cfg.n_kv_heads * cfg.head_dim

    def dense(key, shape, fan_in):
        return jax.random.normal(key, shape, jnp.float32) * (fan_in ** -0.5)

    ks = jax.random.split(k_layers, 8)
    layers = {
        "attn": {
            "wq": dense(ks[0], (nl, d, hq), d),
            "wk": dense(ks[1], (nl, d, hkv), d),
            "wv": dense(ks[2], (nl, d, hkv), d),
            "wo": dense(ks[3], (nl, hq, d), hq),
        },
        "router": dense(ks[4], (nl, d, ne), d),
        "experts": {
            "w_gate": dense(ks[5], (nl, ne, d, cfg.d_ff), d),
            "w_up": dense(ks[6], (nl, ne, d, cfg.d_ff), d),
            "w_down": dense(ks[7], (nl, ne, cfg.d_ff, d), cfg.d_ff),
        },
        "attn_norm": jnp.ones((nl, d), jnp.float32),
        "mlp_norm": jnp.ones((nl, d), jnp.float32),
    }
    return {
        "embed": dense(k_embed, (cfg.vocab_size, d), d) * (d ** 0.5) * 0.02,
        "layers": layers,
        "final_norm": jnp.ones((d,), jnp.float32),
        "lm_head": dense(k_head, (d, cfg.vocab_size), d),
    }


def expert_capacity(cfg: MoEConfig, n_tokens: int) -> int:
    """Static per-expert token capacity, rounded up to a multiple of 8."""
    c = int(n_tokens * cfg.experts_per_token * cfg.capacity_factor
            / cfg.n_experts)
    return max(8, -(-c // 8) * 8)


def moe_mlp(cfg: MoEConfig, x, router_w, experts):
    """Top-k routed expert MLP.  x: (B, S, D) -> (out (B, S, D), aux_loss).

    Dispatch/combine are dense einsums against a (tokens, experts, capacity)
    one-hot; with experts sharded over ``ep`` XLA turns these contractions
    into the EP all-to-all.  Tokens over an expert's capacity are dropped
    (their residual stream passes through unchanged), as in GShard/Switch.
    """
    b, s, d = x.shape
    n = b * s
    e, k = cfg.n_experts, cfg.experts_per_token
    cap = expert_capacity(cfg, n)
    xf = x.reshape(n, d)

    with jax.named_scope("moe/route"):
        logits = (xf.astype(jnp.float32)
                  @ router_w.astype(jnp.float32))  # (N, E)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_idx = jax.lax.top_k(probs, k)  # (N, k)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)  # Mixtral

    compute_dtype = x.dtype
    with jax.named_scope("moe/dispatch"):
        # Position of each (token, choice) in its expert's buffer.  Priority
        # is choice-major (all first choices before any second choice) so a
        # token's primary expert wins capacity contention.
        choice_onehot = jax.nn.one_hot(top_idx, e,
                                       dtype=jnp.float32)  # (N, k, E)
        flat = choice_onehot.transpose(1, 0, 2).reshape(k * n, e)
        pos_flat = jnp.cumsum(flat, axis=0) - flat  # (k*N, E) slot position
        pos = pos_flat.reshape(k, n, e).transpose(1, 0, 2)  # (N, k, E)
        pos_in_expert = jnp.sum(pos * choice_onehot, axis=-1)  # (N, k)
        keep = pos_in_expert < cap  # capacity drop mask

        # (N, k, E, C) collapsed over k -> dispatch (N, E, C)
        cap_onehot = jax.nn.one_hot(pos_in_expert.astype(jnp.int32), cap,
                                    dtype=jnp.float32)
        dispatch = jnp.einsum("nke,nkc,nk->nec", choice_onehot, cap_onehot,
                              keep.astype(jnp.float32))
        combine = jnp.einsum("nec,nke,nk->nec", dispatch, choice_onehot,
                             top_p)
        expert_in = jnp.einsum("nec,nd->ecd", dispatch.astype(compute_dtype),
                               xf)
    with jax.named_scope("moe/experts"):
        gate = jax.nn.silu(jnp.einsum(
            "ecd,edf->ecf", expert_in,
            experts["w_gate"].astype(compute_dtype)))
        up = jnp.einsum("ecd,edf->ecf", expert_in,
                        experts["w_up"].astype(compute_dtype))
        expert_out = jnp.einsum("ecf,efd->ecd", gate * up,
                                experts["w_down"].astype(compute_dtype))
    with jax.named_scope("moe/combine"):
        out = jnp.einsum("nec,ecd->nd", combine.astype(compute_dtype),
                         expert_out)

    # Switch-style load-balancing auxiliary loss: E * sum_e f_e * p_e where
    # f_e = fraction of tokens whose TOP choice is e, p_e = mean router prob.
    with jax.named_scope("moe/route"):
        top1 = jax.nn.one_hot(top_idx[:, 0], e, dtype=jnp.float32)
        f = jnp.mean(top1, axis=0)
        p = jnp.mean(probs, axis=0)
        aux = e * jnp.sum(f * p)
    return out.reshape(b, s, d), aux


def route(h, router_w, top_k: int, renormalise: bool = True, bias=None,
          scale=None, scoring: str | None = None):
    """The top_k experts of every row and their weights.  h: (N, D).
    Returns (weights (N, k) float32, experts (N, k) int32).

    Two things, apart.  HOW a column is scored (``scoring``): ``softmax``
    over the columns or ``sigmoid`` of each; left out, softmax without a
    bias and sigmoid with one, as the published models pair them
    (Qwen3-MoE, Mixtral; DeepSeek-V3's ``noaux_tc``, one group).  WHETHER a
    bias steers the choice (``bias`` (E,), a layer's
    ``e_score_correction_bias``): the experts are the top_k of score +
    bias, the bias for the CHOICE only; their weights are the scores of the
    chosen, without it (models/longcat_flash.py scores by softmax AND
    chooses with a bias).  Then, ``renormalise``, the weights divided by
    their sum, and times ``scale``.  The scores are a float32 product (on
    the TPU a float32 matmul runs in bf16 passes unless told otherwise)."""
    softmax = (bias is None) if scoring is None else scoring == "softmax"
    if scoring not in (None, "softmax", "sigmoid"):
        raise ValueError(f"a router scores by softmax or by sigmoid, not by "
                         f"{scoring!r}")
    with jax.named_scope("moe/route"):
        logits = jnp.dot(h.astype(jnp.float32), router_w.astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        s = (jax.nn.softmax(logits, axis=-1) if softmax
             else jax.nn.sigmoid(logits))
        if bias is None:
            top_p, top_idx = jax.lax.top_k(s, top_k)
        else:
            _, top_idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
            top_p = jnp.take_along_axis(s, top_idx, axis=-1)
        if renormalise:
            top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        if scale is not None:
            top_p = top_p * scale
        return top_p, top_idx.astype(jnp.int32)


def row_tile(assignments: int, n_experts: int) -> int:
    """Rows a tile of the grouped product: the rows one of the ``n_experts``
    experts HELD gets on average of the ``assignments`` that land on them
    (all of them where every expert is held), as a power of two between
    bf16's 16 sublanes and the MXU's 128 (a larger tile pads every
    expert's run further)."""
    per_expert = max(1, assignments // n_experts)
    return min(128, max(16, 1 << (per_expert.bit_length() - 1)))


def routed_mlp(h, router_w, experts, layer, *, top_k: int,
               renormalise: bool = True, bias=None, scale=None, shared=None):
    """Dropless top-k routed gated MLP.  h: (..., D) -> (..., D).

    ``experts``: w_gate / w_up [layers, E, D, F] and w_down [layers, E, F, D]
    (no ``w_gate``: experts of two matrices around a squared ReLU)
    stacked over layers, of which ``layer`` is read (ops/grouped_matmul says
    why they come whole); router_w: (D, E), this layer's; ``bias`` and
    ``scale`` as ``route`` takes them.  Every (token, expert) assignment is
    computed: no capacity, no dropped token.  ``shared``: this layer's
    shared expert (w_gate, w_up (D, Fs), w_down (Fs, D)), which every token
    passes through once, beside the routed ones and unweighted.
    Returns (out, experts_hit): how many experts some token reached, which
    is how many the grouped product read.
    """
    hf = h.reshape(-1, h.shape[-1])
    weights, chosen = route(hf, router_w, top_k, renormalise, bias, scale)
    out, experts_hit = dispatch(hf, weights, chosen, experts, layer)
    if shared is not None:
        out = out + shared_mlp(shared, hf)
    return out.reshape(h.shape), experts_hit


def shared_mlp(shared, hf):
    """The shared expert over every row, one part: a gated MLP, or without
    a ``w_gate`` the two-matrix form ``relu(x W_up)^2 W_down``
    (ops/grouped_matmul.py's two forms)."""
    with jax.named_scope("moe/shared"):
        if "w_gate" not in shared:
            up = jax.nn.relu(hf @ shared["w_up"].astype(hf.dtype))
            return (up * up) @ shared["w_down"].astype(hf.dtype)
        gate = jax.nn.silu(hf @ shared["w_gate"].astype(hf.dtype))
        up = hf @ shared["w_up"].astype(hf.dtype)
        return (gate * up) @ shared["w_down"].astype(hf.dtype)


def scan_routed_layers(cfg, layers, body, carry, first: int = 0):
    """``lax.scan`` of ``body(carry, layer_params, li, feed_forward)`` over
    the routed layers ``layers`` (leaves stacked on a leading axis), the
    experts held out of what the scan slices: they stay stacked over
    layers and are indexed where they are read.  ``first`` layers of
    another kind precede them in the model: ``li`` is the layer's place in
    the MODEL (its page pool's), the experts are indexed by its place among
    the routed.  ``feed_forward(p, h)`` is the layer's, for ``llama.layer``:
    ``routed_mlp`` as the parameters and ``cfg`` have it (a layer with a
    ``router_bias`` scores by sigmoid and chooses by score + bias; one with
    ``shared`` adds its shared expert).  Returns (carry, experts read,
    summed over the layers)."""
    stacked = dict(layers)
    experts = stacked.pop("experts")
    n_routed = experts["w_gate"].shape[0]

    def step(carry_hit, per_layer):
        carry, hit = carry_hit
        p, i = per_layer
        hits = []  # what this layer's feed-forward read, once it has run

        def routed(p, h):
            out, n = routed_mlp(
                h, p["router"], experts, i, top_k=cfg.experts_per_token,
                renormalise=cfg.norm_topk_prob, bias=p.get("router_bias"),
                scale=cfg.routed_scaling_factor,
                shared=p.get("shared"))
            hits.append(n)
            return out

        carry = body(carry, p, first + i if first else i, routed)
        return (carry, hit + sum(hits)), None

    with jax.named_scope("layers"):
        (carry, hit), _ = jax.lax.scan(
            step, (carry, jnp.int32(0)),
            (stacked, jnp.arange(n_routed, dtype=jnp.int32)))
    return carry, hit


def served_routed_walk(scan_layers, cfg, params, x, caches, positions, attend,
                       attention=attention_block):
    """``llama.served_walk`` for a family whose layers ``scan_layers(cfg,
    params, body, carry)`` walks with routed feed-forwards: the pools in
    the carry, ``attend`` and ``attention`` the family's own, and the
    experts its routed layers read counted as ``experts_read``."""
    cache_k, cache_v, state = caches

    def body(carry, p, li, feed_forward):
        x, ck, cv = carry
        x, (ck, cv) = decoder_layer(cfg, p, x, positions, attend,
                                    (ck, cv, li), feed_forward, attention)
        return x, ck, cv

    (x, cache_k, cache_v), hit = scan_layers(cfg, params, body,
                                             (x, cache_k, cache_v))
    return x, (cache_k, cache_v, state), {"experts_read": hit}, None


def _lay_out(flat, e: int, tile: int, n_tiles: int, top_k: int,
             spare: bool = False):
    """The m assignments ``flat`` (each the index of an expert held, 0 ..
    e - 1) sorted by expert into a padded layout of ``n_tiles`` tiles, an
    expert's run padded to whole tiles.  ``spare``: an index of ``e`` means
    NO expert held here; such assignments sort last, take no row and no
    tile, and their place in the layout is past its end.  Returns (order,
    sizes an index, row_sorted: the padded row of each sorted assignment,
    src: the token a padded row reads, tile_expert, run_end: where each
    held expert's padded run ends)."""
    m = flat.shape[0]
    order = jnp.argsort(flat, stable=True)  # by expert, then by token
    sizes = jnp.bincount(flat, length=e + spare)
    padded = -(-sizes // tile) * tile
    if spare:
        padded = padded.at[e].set(0)
    run_end = jnp.cumsum(padded)
    sorted_e = flat[order]
    rank = jnp.arange(m) - (jnp.cumsum(sizes) - sizes)[sorted_e]
    row_sorted = (run_end - padded)[sorted_e] + rank  # its padded row
    # padded rows with no assignment read token 0; nothing reads them
    # back
    src = jnp.zeros(n_tiles * tile, jnp.int32)
    if spare:  # the spare assignments' rows lie past the layout: dropped
        src = src.at[jnp.where(sorted_e < e, row_sorted,
                               n_tiles * tile)].set(
            (order // top_k).astype(jnp.int32), mode="drop")
        run_end = run_end[:e]
    else:
        src = src.at[row_sorted].set((order // top_k).astype(jnp.int32))
    tile_expert = jnp.searchsorted(
        run_end, jnp.arange(n_tiles) * tile, side="right")
    # the tiles past the last run keep its expert: no block is fetched
    tile_expert = jnp.minimum(
        tile_expert, jnp.max(jnp.where(sizes[:e] > 0, jnp.arange(e), 0))
        if spare else sorted_e[-1])
    return order, sizes, row_sorted, src, tile_expert, run_end


def dispatch(hf, weights, chosen, experts, layer):
    """sum_k weights[n, k] x expert chosen[n, k] of hf[n]: (N, D) -> ((N, D),
    experts with a row at all, int32).  Every index in ``chosen`` is an
    expert HELD in ``experts`` (``dispatch_share`` takes a router whose
    columns reach further).

    The N x k assignments are sorted by expert; each expert's run is padded
    to whole row tiles and the padded layout is gathered from hf,
    multiplied tile by tile with the tile's expert (``grouped_mlp``), and
    gathered back with the weights.  The padded layout is sized for the
    worst split (every held expert's run ending one row into a tile).
    """
    from ray_tpu.ops.grouped_matmul import grouped_mlp

    (n, d), top_k = hf.shape, chosen.shape[1]
    e = experts["w_down"].shape[1]
    m = n * top_k
    tile = row_tile(m, e)
    n_tiles = min(m, -(-(m + e * (tile - 1)) // tile))
    with jax.named_scope("moe/dispatch"):
        flat = chosen.reshape(m)  # assignment a = token * k + choice
        order, sizes, row_sorted, src, tile_expert, run_end = _lay_out(
            flat, e, tile, n_tiles, top_k)
        rows = hf[src]
    with jax.named_scope("moe/experts"):
        out = grouped_mlp(rows, experts.get("w_gate"), experts["w_up"],
                          experts["w_down"], tile_expert,
                          run_end[-1] // tile, layer, tile=tile)
    with jax.named_scope("moe/combine"):
        row = jnp.zeros(m, jnp.int32).at[order].set(
            row_sorted.astype(jnp.int32))
        out = out[row].reshape(n, top_k, d).astype(jnp.float32)
        return (jnp.einsum("nk,nkd->nd", weights, out).astype(hf.dtype),
                jnp.sum(sizes > 0).astype(jnp.int32))


# what ``dispatch_share`` counts on the device, in this order (one vector)
SHARE_COUNTED = ("experts_read", "moe_local_rows", "moe_zero_picks",
                 "moe_absent_picks")


def dispatch_share(hf, weights, chosen, experts, layer, *, first: int,
                   columns: int, identity: int):
    """``dispatch`` for ONE CHIP'S SHARE of a layer whose router has more
    columns than this chip holds experts: of the ``columns`` a row may be
    sent to, ``first`` .. ``first + E - 1`` are the E experts HELD in
    ``experts`` (index 0 there is column ``first``), the trailing
    ``identity`` columns are IDENTITY experts (no weights: the output is
    the pick's weight times the row itself, so it is computed where the
    row lives), and every other column is an expert held on another chip:
    such a pick adds nothing here, and nothing stands in for it.

    (N, D) -> ((N, D), counted [4] int32 under ``SHARE_COUNTED``: held
    experts with a row at all, rows the held experts computed, identity
    picks, picks on absent experts).  Dropless: the padded layout is sized
    for every pick landing on a held expert, the row tile for the share of
    them that does with a uniform router; picks that take no expert sort
    behind the held experts' runs and take no row."""
    from ray_tpu.ops.grouped_matmul import grouped_mlp

    (n, d), top_k = hf.shape, chosen.shape[1]
    e = experts["w_down"].shape[1]
    if not 0 <= first <= columns - identity - e:
        raise ValueError(
            f"experts {first}..{first + e - 1} are not among the "
            f"{columns - identity} columns that are experts with weights "
            f"({columns} columns, the last {identity} identity)")
    m = n * top_k
    tile = row_tile(m * e // columns, e)
    n_tiles = min(m, -(-(m + e * (tile - 1)) // tile))
    with jax.named_scope("moe/dispatch"):
        held = (chosen >= first) & (chosen < first + e)  # (N, k)
        flat = jnp.where(held, chosen - first, e).reshape(m)
        order, sizes, row_sorted, src, tile_expert, run_end = _lay_out(
            flat, e, tile, n_tiles, top_k, spare=True)
        rows = hf[src]
    with jax.named_scope("moe/experts"):
        out = grouped_mlp(rows, experts.get("w_gate"), experts["w_up"],
                          experts["w_down"], tile_expert,
                          run_end[-1] // tile, layer, tile=tile)
    with jax.named_scope("moe/combine"):
        # a pick with no held expert reads SOME row (its place is past the
        # layout, the gather clips it); rows no tile computed are
        # undefined, so what it read is cut off, not multiplied by 0
        row = jnp.zeros(m, jnp.int32).at[order].set(
            row_sorted.astype(jnp.int32))
        out = jnp.where(held[..., None], out[row].reshape(n, top_k, d), 0)
        out = jnp.einsum("nk,nkd->nd", weights, out.astype(jnp.float32))
    with jax.named_scope("moe/zero"):
        zero = chosen >= columns - identity
        out = (out + jnp.sum(jnp.where(zero, weights, 0.0), axis=-1,
                             keepdims=True) * hf.astype(jnp.float32))
        n_local = jnp.sum(sizes[:e])
        n_zero = jnp.sum(zero)
        counted = jnp.stack([jnp.sum(sizes[:e] > 0), n_local, n_zero,
                             m - n_local - n_zero]).astype(jnp.int32)
    return out.astype(hf.dtype), counted


def _layer(cfg: MoEConfig, carry, layer_params, positions, attn_impl, mesh,
           rules):
    x, aux_sum = carry
    p = layer_params
    x, _ = attention_block(cfg, p, x, positions,
                           batch_attend(attn_impl, mesh, rules))
    with jax.named_scope("mlp/norm"):
        h = rms_norm(x, p["mlp_norm"], cfg.norm_eps)
    moe_out, aux = moe_mlp(cfg, h, p["router"], p["experts"])
    return (x + moe_out, aux_sum + aux)


def apply(params, tokens, cfg: MoEConfig, attn_impl: str = "auto",
          mesh=None, rules=None, return_aux: bool = False):
    """Forward: tokens (B, S) -> logits (B, S, vocab) [, aux_loss]."""
    x = embed(params, tokens, cfg)
    positions = jnp.arange(tokens.shape[1])[None, :]

    step = partial(_layer, cfg, positions=positions, attn_impl=attn_impl,
                   mesh=mesh, rules=rules)
    if cfg.remat:
        step = jax.checkpoint(step)

    def scan_body(carry, layer_params):
        return step(carry, layer_params), None

    with jax.named_scope("layers"):
        (x, aux), _ = jax.lax.scan(
            scan_body, (x, jnp.zeros((), jnp.float32)), params["layers"])
    logits = head(params, x, cfg)
    aux = aux / cfg.n_layers
    return (logits, aux) if return_aux else logits


def loss_fn(params, tokens, cfg: MoEConfig, attn_impl: str = "auto",
            mesh=None, rules=None):
    """Next-token CE + load-balancing aux loss."""
    logits, aux = apply(params, tokens[:, :-1], cfg, attn_impl, mesh=mesh,
                        rules=rules, return_aux=True)
    targets = tokens[:, 1:]
    with jax.named_scope("loss"):
        logz = jax.scipy.special.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1)[..., 0]
        return jnp.mean(logz - gold) + cfg.aux_loss_weight * aux
