"""The served programs: bucketed prefill, suffix prefill through a prefix's
pages, batched paged decode, block diffusion's denoising pass.  ONE compiled
decode step for the whole engine (a static [max_slots] batch), one compiled
prefill a length bucket: nothing that depends on a sequence's length is a
Python branch or a program variant.  ``cfg`` is the model's configuration,
hashable (a static argument), of whichever family the parameters are.

Who owns which decision.  A PROGRAM here owns WHERE rows are written and
WHAT is visible: its write coordinates (page and slot a position, or the
slot's state row), its mask, and the closures that cache and attend its
way, which it hands the family's walk as one bundle ``via``:

- ``attend(q, k, v, (ck, cv, li))``: K/V rows into pool layer ``li``, then
  ``prefill`` a dense mask over this call's own k and v, the suffix prefill
  a gather through the page table, the decode step and ``block_step``
  ``ops/paged_attention`` (pages read where they lie, at KV-head width; a
  pool may hold more heads than the model, ``_pad_heads``);
  A model with WINDOW layers beside full ones (``cfg.window`` > 0,
  models/afmoe.py) has a pool a kind (paged_cache.py), so its pools, page
  ids and page tables come as dicts ``{"full": ..., "window": ...}`` and
  the program makes ``attend`` TWICE, ``via["attend_by_kind"][kind]``: the
  family's walk hands a layer its kind's closure, pool and index among its
  kind.  Which program sees which pages of which layer: ``prefill`` sees no
  page of either (this call's own k and v under a causal mask, for a
  window layer also ``qpos - kpos < window``); ``prefill_with_prefix``
  gathers a FULL layer's keys through the whole full table and a WINDOW
  layer's through the ``ceil((window + L) / page_size) + 1`` entries of the
  window table that the chunk's L positions and the window before them
  reach (the entries behind are null, paged_cache.py); the decode step
  walks a full layer's pages ``0 .. length`` and a window layer's from the
  page that holds ``length - window`` on;
- ``attend_latent(q_nope, q_rope, row, a, (pool, None, li))``: latent rows
  into the ONE pool, then the prefills REBUILT (K and V made from the rows
  this call wrote or the page table reaches), the decode step ABSORBED
  (``paged_latent_decode_attention``: K and V are never made).  ``li`` is
  a POOL layer, which need not be a scanned layer: models/longcat_flash.py's
  scanned layer has two attention sublayers and writes pool layers ``2 i``
  and ``2 i + 1`` (its ``cache_layout()`` declares twice its layers);
- ``recur(mix, qkv, b, a, (state, li))``: ``prefill`` runs the chunked
  recurrence from a ZERO state and writes the slot's rows ONCE after the
  scan, the decode step updates every live slot's row in place.
- ``recur_fixed(q, k, v, g, (S, li), conv=None)`` (ops/lightning.py's
  recurrence: models/minicpm_sala.py's linear layers, models/falcon_h1.py's
  and models/nemotron_h.py's Mamba-2 mixer, the latter's state rows PACKED
  two heads side by side, which the recurrence takes by the rows' shape):
  as ``recur``, and ``prefill_with_prefix`` takes the SLOT'S ROW as the
  initial state, so a prompt in chunks carries its state from chunk to
  chunk; only ``prefill`` (a prompt's first chunk, or all of it) begins
  from zeros.  ``g`` is the decay's log a head ([H], a constant
  of the head) or a token a head ([..., H]), told by its rank; keys and
  queries come a group of heads.  The part it runs under is the family's
  (``cfg.state_part``).  A family whose recurrence's inputs pass a SHORT
  CONVOLUTION first (it declares a ``conv`` state row) hands ``conv`` =
  (taps, bias, the rows to convolve, ``gates``) in place of q, k, v and g:
  ONE wrapper of the three closures (``_conv_first``) convolves from the
  rows that came BEFORE this call's (zeros; the slot's ``conv`` rows),
  makes ``(q, k, v, g, skip) = gates(convolved rows)`` (``skip`` what is
  added to the recurrence's output, Mamba-2's ``D x``), and the tail goes
  where the state goes: a prefill's into its slot's rows, a decode step's
  into the rows of the slots that take the step AND NO OTHER (a slot
  between two chunks of its prompt keeps its rows through other slots'
  steps).  What the seam lacked for that: it took q, k and v READY, and a
  convolution's output depends on rows that only the program can find.
- ``attend_sparse(q, k, v, (ck, cv, pooled, li))`` (its sparse layers,
  ops/block_sparse.py): K/V rows into pool layer ``li`` and the POOLED KEYS
  those rows complete into ``pooled``, a pair: a row a page beside the pool
  (``cache_layout()["page_rows"]``) and the same rows again in SLOT order
  (paged_cache.py ``CacheConfig``: row j of a slot is the row of the page
  at entry j of its table); both ride in the walk with the pools and are
  written where they lie, the same value at the same place.  The decode
  step and the suffix prefill READ a sequence's rows in slot order (no
  gather through the table); the page order is kept for whoever finds
  rows by page id.  Then the choice of blocks a query: both
  prefills attend through ONE Pallas kernel under the chosen blocks' mask
  (``block_sparse.selected_attention``: a tile of scores, a tile of queries
  with their KV head's 16 query heads against a tile of keys, lives in
  VMEM and nowhere else; a tile of keys over the diagonal, past the
  prompt's end or in which no query of the tile selected a block has no
  grid work), the decode step hands ``paged_decode_attention`` a LIST of
  pages a slot a KV head.  A third thing comes back beside the pools, what
  the call COUNTED on the device (``block_sparse.walked`` of the lists the
  decode kernel was handed, the key tiles the prefills' kernel was handed,
  the pooled rows completed): the walk sums it over the sparse layers into
  the program's ``counted``, one vector under the tuple of its names.

A FAMILY (its configuration class in ``models/``) owns WHAT a row is, how
its layers are walked and what it refuses, and says so once:
``cache_layout()``, ``serving_layout(params)``, ``served_walk(params, x,
caches, positions, via)`` (it takes of ``via`` what it uses and hands back
what it counted BY NAME and the rows to write once), ``refuses`` (feature
-> why), ``block_length`` (0: a token at a time) and ``mask_token_id``.
No program finds a family out from the tree's keys.

Every token program hands back ONE shape: (result, counted, cache_k,
cache_v, state); ``counted`` is a mapping (empty for a dense model) of a
counter's name to a scalar, or of a TUPLE of names to one vector of as
many (what the engine fetches is a transfer a key), ``state`` and a latent
model's ``cache_v`` None.  A greedy burst's step
(``decode_step_greedy_chained``) hands back its CARRY as the result (the
next step's tokens and positions, the row to write next and ``acc``, which
holds a step's tokens and counts a row) and an empty ``counted``: what it
counted is in ``acc``, in ``counted_layout``'s order.  Pools and state are
donated and ride in the layer scan's carry whole, scattered in place at
``[li, page, slot]``: nothing pool-sized is sliced, stacked or copied.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from ray_tpu.models import (afmoe, falcon_h1, glm_moe_lite, llama,
                            longcat_flash, nemotron_h, olmo_hybrid, sdar_moe)
from ray_tpu.models.llama import embed, head
from ray_tpu.ops import block_sparse, gated_delta, lightning
from ray_tpu.ops.paged_attention import (paged_decode_attention,
                                         paged_latent_decode_attention)


def cache_layout(cfg) -> dict:
    """What these programs cache for a model of configuration ``cfg``, as
    ``paged_cache.CacheConfig`` takes it: the family's own declaration."""
    return cfg.cache_layout()


def serving_layout(params):
    """The tree as these programs hold it, for a caller with a tree and no
    configuration (the engine asks ``cfg.serving_layout``).  The ONE place
    in ``ray_tpu/llm/`` that recognises a family by its tree's keys."""
    if isinstance(params["layers"], tuple):  # minicpm_sala's, laid out
        return params  # (its layout takes the configuration's mixer_types)
    if "first" in params["layers"]:  # a double layer's two sublayers
        return longcat_flash.serving_layout(params)
    if "M" in params["layers"]:  # layers stacked a KIND
        return nemotron_h.serving_layout(params)
    attn = params["layers"].get("attn", ())
    if "lin" in params["layers"]:
        return olmo_hybrid.serving_layout(params)
    if "wkv_b" in attn or "w_uk" in attn:
        return glm_moe_lite.serving_layout(params)
    if "wg" in attn or "each" in params["layers"]:
        return afmoe.serving_layout(params)
    return llama.serving_layout(params)


def refuse(cfg, feature: str, where: str) -> None:
    """Raise the family's own sentence if it declares (``cfg.refuses``) that
    it cannot be served with ``feature``; ``where`` names the call."""
    why = cfg.refuses.get(feature)
    if why is not None:
        raise ValueError(why.format(cfg=cfg, where=where))


def _pad_heads(x, n: int):
    """x [..., heads, d] with zero heads behind up to ``n``: a pool whose
    pages must be whole tiles may hold more heads than the model has."""
    short = n - x.shape[-2]
    if short == 0:
        return x
    return jnp.pad(x, ((0, 0),) * (x.ndim - 2) + ((0, short), (0, 0)))


def _masked_attention(cfg, q, keys, vals, mask):
    """Dense softmax attention of q [L, H, d] over keys/vals [T, Hkv, d]
    repeated to the query heads, where ``mask`` [L, T] allows."""
    rep = cfg.n_heads // cfg.n_kv_heads
    with jax.named_scope("attn/attend"):
        with jax.named_scope("repeat_kv"):
            keys = jnp.repeat(keys, rep, axis=1)  # [T, H, d]
            vals = jnp.repeat(vals, rep, axis=1)
        scores = jnp.einsum("qhd,khd->hqk", q, keys) / (cfg.head_dim ** 0.5)
        scores = jnp.where(mask[None], scores, -1e30)
        attn = jax.nn.softmax(scores.astype(jnp.float32), axis=-1)
        return jnp.einsum("hqk,khd->qhd", attn.astype(vals.dtype), vals)


def _write_rows(pool, li, pages, slots, row):
    """Latent rows [n, latent_dim] into pool layer ``li`` at (pages, slots),
    zeros behind them to the pool's width."""
    with jax.named_scope("attn/kv_write"):
        short = pool.shape[-1] - row.shape[-1]
        return pool.at[li, pages, slots].set(
            jnp.pad(row, ((0, 0), (0, short))).astype(pool.dtype))


def _rebuilt_attention(cfg, a, q_nope, q_rope, rows, mask):
    """Latent attention in the REBUILT form: per-head K and V made from
    the latent ``rows`` [T, >= latent_dim], then dense masked attention."""
    keys, vals = glm_moe_lite.rebuild_kv(cfg, a, rows)
    return _masked_attention(
        cfg, jnp.concatenate([q_nope, q_rope], axis=-1), keys, vals, mask)


def _conv_and_gates(cfg, mix, qkv, before, b, a):
    """A recurrent layer's rows through the short convolution and the
    norms and gates: (q, k, v, g, beta, the convolution's rows)."""
    y, rows = olmo_hybrid.short_conv(mix["conv"], qkv, before)
    return (*olmo_hybrid.delta_inputs(cfg, mix, y, b, a), rows)


def _rows_that_ride(cfg, state):
    """What of ``state`` a prefill's walk carries: the rows a PAGE holds
    (``cache_layout()["page_rows"]``) and their twins in slot order, written
    where they lie as the pools are, and None in the place of the rows of a
    slot's STATE (``state_rows``), which the prefill writes itself, once.
    None for a family that declares no page rows (its walk carries no state
    at all)."""
    layout = cfg.cache_layout() if state is not None else {}
    if not layout.get("page_rows"):
        return None
    return {name: None if name in layout["state_rows"] else rows
            for name, rows in state.items()}


def _conv_first(cfg, plain, before_of, keep, ready=lambda rows: rows):
    """``recur_fixed`` as a program hands it to a walk: ``plain(q, k, v, g,
    rows)`` on ready inputs, and with ``conv`` = (taps [W, C], bias, x,
    gates) the recurrence's inputs pass a short convolution first.  What a
    program says of that, once each: ``before_of(rows, W - 1, x)`` the W - 1
    rows that precede x's ([W - 1, ..., C]; x [L, C] a sequence's rows or
    [slots, C] ONE position of many sequences), ``keep(rows, kept, before,
    rows_in_order [W - 1 + L, ..., C])`` what ``plain`` kept (the rows the
    walk carries on, what the program writes after it) with the
    convolution's rows beside the state, and ``ready(rows)`` the rows as
    ``plain`` takes them.  ``(q, k, v, g, skip) =
    gates(convolved rows)``; ``skip`` (Mamba-2's ``D x``) is added to the
    recurrence's output under the state's part."""

    def recur_fixed(q, k, v, g, rows, conv=None):
        if conv is None:
            return plain(q, k, v, g, rows)
        taps, bias, x, gates = conv
        before = before_of(rows, taps.shape[0] - 1, x)
        y, in_order = olmo_hybrid.short_conv(
            taps, x.reshape(-1, *before.shape[1:]), before, bias,
            falcon_h1.CONV_PART)
        q, k, v, g, skip = gates(y.reshape(x.shape))
        o, kept = plain(q, k, v, g, ready(rows))
        with jax.named_scope(cfg.state_part):
            o = o + skip
        with jax.named_scope(falcon_h1.CONV_PART):
            return o, keep(rows, kept, before, in_order)

    return recur_fixed


def _last_inputs(true_len):
    """A prefill's ``keep``: the state beside the convolution's inputs at
    the last W - 1 REAL positions (a chunk shorter than that reaches back
    into ``before``)."""
    return lambda rows, kept, before, in_order: (kept[0], (
        kept[1], jax.lax.dynamic_slice_in_dim(in_order, true_len,
                                              before.shape[0], 0)))


def _write_slot_rows(cfg, state, rode, left, slot):
    """``state`` after a prefill's walk: the page rows as the walk left
    them (``rode``) and ``left`` (name -> [layers, ...]) in ``slot``'s row.
    Outside the scans, as ``prefill`` says below."""
    with jax.named_scope(cfg.state_part):
        out = {**state,
               **{k: v for k, v in (rode or {}).items() if v is not None}}
        for name, rows in left.items():
            out[name] = jax.lax.dynamic_update_slice(
                state[name], rows[:, None].astype(state[name].dtype),
                (0, slot) + (0,) * (rows.ndim - 1))
        return out


def _pooled_rows(cfg, k, page_size: int):
    """The pooled keys of k [T, G, d] (whole pages), a row a page, at the
    sizes the rows' cache takes (ops/block_sparse.py ``check_sizes``)."""
    block_sparse.check_sizes(cfg, page_size)
    if k.shape[0] % page_size:
        raise ValueError(
            f"a prefill of {k.shape[0]} positions is no whole number of "
            f"pages of {page_size}: the pooled keys are cached a row a page")
    return block_sparse.pool_keys(cfg, k)


def _in_slot_order(by_slot, P: int):
    """``by_slot`` (the pooled rows in slot order, [layers, slots, entries,
    G, d]) if it has an entry for each of the P entries of a page table."""
    if by_slot.shape[2] != P:
        raise ValueError(
            f"the pooled keys in slot order hold {by_slot.shape[2]} rows a "
            f"slot and a page table {P} entries: they are read a row an "
            f"entry (CacheConfig.max_pages_per_seq)")
    return by_slot


def _visible(cfg, qpos, kpos, window: int = 0):
    """[q, k] bool: may the query at ``qpos`` see the key at ``kpos``?
    Causal; for a block-diffusion configuration causal over blocks; in a
    window layer (``window`` > 0) the last ``window`` keys only, the
    query's own among them."""
    if cfg.block_length:
        return sdar_moe.block_causal(qpos, kpos, cfg.block_length)
    seen = kpos[None, :] <= qpos[:, None]
    if window:
        seen &= qpos[:, None] - kpos[None, :] < window
    return seen


def _by_kind(cfg, make):
    """``via``'s attending closures: ``make(kind, window)`` once, or for a
    model with window layers once a kind."""
    if not cfg.window:
        return {"attend": make(None, 0)}
    return {"attend_by_kind": {"full": make("full", 0),
                               "window": make("window", cfg.window)}}


def _of(kind, x):
    """``x``, or its ``kind``'s where a model has a pool a kind."""
    return x if kind is None else x[kind]


def _last_logits(params, x, cfg, true_len):
    """A prefill's result: the last token's logits, or None for a
    block-diffusion configuration (no token follows from a prompt, so the
    output head is not run; the engine waits for what was counted)."""
    return None if cfg.block_length else head(params, x, cfg, true_len)


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("cache_k", "cache_v", "state"))
def prefill(params, tokens, cache_k, cache_v, page_rows, true_len,
            slot_positions, cfg, state=None, slot=None):
    """Prefill ONE sequence padded to a length bucket.

    tokens: [L] int32 (padded); page_rows: [L] page id per token position;
    slot_positions: [L] slot inside the page; true_len: scalar.
    Writes K/V for positions < true_len into the paged cache and returns
    (logits_at_last_token [V], counted, cache_k, cache_v, state).

    A model with recurrent layers also takes ``state`` (its rows, donated)
    and ``slot`` (the row this sequence is admitted to): each such layer
    runs the chunked recurrence from a ZERO state over the true_len tokens
    and leaves the final state, and its convolution's last inputs, in the
    slot's row, whatever the row held.
    """
    x = embed(params, tokens, cfg)  # [L, D]
    positions = jnp.arange(tokens.shape[0])
    causal = _visible(cfg, positions, positions)  # [L, L]
    valid = positions[None, :] < true_len
    mask = causal & valid

    def attend_through(kind, window):
        rows = _of(kind, page_rows)
        seen = (mask & _visible(cfg, positions, positions, window)
                if window else mask)

        def attend(q, k, v, pools):
            ck, cv, li = pools
            # write k/v into this layer's pages (beyond true_len the rows
            # write into the sequence's own pages — masked out of attention)
            with jax.named_scope("attn/kv_write"):
                n_pool = ck.shape[3]
                ck = ck.at[li, rows, slot_positions].set(
                    _pad_heads(k, n_pool))
                cv = cv.at[li, rows, slot_positions].set(
                    _pad_heads(v, n_pool))
            # within the sequence: this call's own k and v, never the pool
            return _masked_attention(cfg, q, k, v, seen), (ck, cv)

        return attend

    def attend_latent(q_nope, q_rope, row, a, pools):
        pool, _, li = pools  # rebuilt from this call's own rows
        pool = _write_rows(pool, li, page_rows, slot_positions, row)
        return (_rebuilt_attention(cfg, a, q_nope, q_rope, row, mask),
                (pool, None))

    def recur(mix, qkv, b, a, rows):  # qkv: [L, channels]
        taps = mix["conv"].shape[0] - 1  # inputs the convolution keeps
        q, k, v, g, beta, conv = _conv_and_gates(
            cfg, mix, qkv, jnp.zeros((taps, qkv.shape[-1]), qkv.dtype), b, a)
        with jax.named_scope("lin_attn/state"):
            # a padded position changes nothing: no decay, no write
            real = (positions < true_len)[:, None]
            o, S = gated_delta.chunked(
                q, k, v, jnp.where(real, g, 0.0), jnp.where(real, beta, 0.0),
                jnp.zeros((q.shape[1], v.shape[2], q.shape[2]), jnp.float32))
            left = (gated_delta.pack_state(S, cfg.state_pack),
                    jax.lax.dynamic_slice_in_dim(conv, true_len, taps, 0))
        return o, (None, left)

    def attend_sparse(q, k, v, pools):
        ck, cv, (by_page, by_slot), li = pools
        L, G, d = k.shape
        ps, bs = ck.shape[2], cfg.block_size
        with jax.named_scope("attn/kv_write"):
            ck = ck.at[li, page_rows, slot_positions].set(k)
            cv = cv.at[li, page_rows, slot_positions].set(v)
        T = -(-L // bs) * bs  # whole blocks
        kp, vp = (jnp.pad(y, ((0, T - L), (0, 0), (0, 0))) for y in (k, v))
        with jax.named_scope("sparse_attn/index"):
            # a row a page, at the page of the row's first key (the last
            # rows are not complete: a later chunk or step writes them
            # again, and no query sees them before)
            rows = _pooled_rows(cfg, kp, ps).astype(by_page.dtype)
            by_page = by_page.at[li, page_rows[::ps]].set(rows[:L // ps])
            # and rows [0, L / ps) of the slot this call admits to
            by_slot = jax.lax.dynamic_update_slice(
                by_slot, rows[None, None, :min(L // ps, by_slot.shape[2])],
                (li, slot, 0, 0, 0))
        out, tiles = block_sparse.selected_attention(
            cfg, q, positions, rows,
            lambda at, n: (jax.lax.dynamic_slice_in_dim(kp, at, n),
                           jax.lax.dynamic_slice_in_dim(vp, at, n)),
            T, true_len)
        return out, (ck, cv, (by_page, by_slot)), {
            **tiles,
            "index_rows_written": block_sparse.rows_complete(cfg, true_len)}

    def recur_fixed(q, k, v, g, rows):
        # q, k: [L, G, d_k]; v: [L, H, d_v]; g: [H] or [L, H]
        with jax.named_scope(cfg.state_part):
            # a padded position changes nothing: no decay, no write
            real = (positions < true_len)[:, None]
            # (from zeros laid out as the slot's rows are: a state of
            # narrow heads is kept packed, ops/lightning.py)
            o, S = lightning.chunked(
                q, jnp.where(real[..., None], k, 0), v,
                jnp.where(real, g, 0.0),
                jnp.zeros(state["S"].shape[2:], jnp.float32))
        return o, (rows[0], S)

    # (a convolution from zeros: a prompt's first rows)
    recur_fixed = _conv_first(
        cfg, recur_fixed,
        lambda rows, taps, x: jnp.zeros((taps, x.shape[-1]), x.dtype),
        _last_inputs(true_len))

    # the scan carries no SLOT's state: a prefill begins its slot's rows
    # anew (the rows a page holds ride with the pools)
    x, (cache_k, cache_v, rode), counted, left = cfg.served_walk(
        params, x, (cache_k, cache_v, _rows_that_ride(cfg, state)), positions,
        {**_by_kind(cfg, attend_through), "attend_latent": attend_latent,
         "recur": recur, "attend_sparse": attend_sparse,
         "recur_fixed": recur_fixed})
    if isinstance(left, dict):
        state = _write_slot_rows(cfg, state, rode, left, slot)
    elif state is not None:
        # The slot's rows are written HERE, once, and not in the scan: a
        # row-sized update inside the loop lets XLA choose the carried
        # state's layout to suit the update, and copy all of the state
        # (0.85 GB) to that layout and back around the loop.
        with jax.named_scope("lin_attn/state"):
            S, tail = (jnp.stack(rows, axis=1) for rows in zip(*left))
            S = S.reshape(-1, 1, *S.shape[2:])  # [layers, 1, ...]
            tail = tail.reshape(-1, 1, tail.shape[-1])
            state = {"S": jax.lax.dynamic_update_slice(
                         state["S"], S, (0, slot, 0, 0, 0)),
                     "conv": jax.lax.dynamic_update_slice(
                         state["conv"], tail.astype(state["conv"].dtype),
                         (0, slot, 0))}
    return (_last_logits(params, x, cfg, true_len), counted, cache_k,
            cache_v, state)


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("cache_k", "cache_v", "state"))
def prefill_with_prefix(params, tokens, cache_k, cache_v, page_rows,
                        true_len, slot_positions, page_table, positions,
                        cfg, state=None, slot=None):
    """Prefill the SUFFIX of one sequence whose leading pages are already
    resident (prefix-cache hit).

    tokens: [L] int32 suffix padded to a bucket; positions: [L] absolute
    positions (prefix_len + 0..L-1); page_rows/slot_positions: [L] write
    coordinates for the suffix KV; page_table: [P] the sequence's FULL
    page table (prefix pages + suffix pages, 0-padded); true_len: scalar
    suffix length.  Attention gathers keys through the page table like the
    decode step — cached prefix columns come straight from the pool, suffix
    columns from this call's writes — masked at tpos <= position, so the
    null page, padded query rows, and future suffix columns all drop out.
    Returns (logits at the last suffix token [V], counted, cache_k,
    cache_v, state).

    A model whose recurrent layers take it (``recur_fixed``) hands
    ``state`` and ``slot`` as ``prefill`` does: the slot's rows are the
    recurrence's INITIAL state here, what the positions before this call's
    left, and its final state goes back into them.  Its sparse layers'
    chunk begins on a page.
    """
    refuse(cfg, "suffix_prefill", "prefill_with_prefix")
    P = jax.tree.leaves(page_table)[0].shape[0]
    page_size = jax.tree.leaves(cache_k)[0].shape[2]
    x = embed(params, tokens, cfg)  # [L, D]

    def attend_through(kind, window):
        rows, table = _of(kind, page_rows), _of(kind, page_table)
        reach, kpos = P, None  # pages gathered; the first one's position
        if window:
            # a window layer: the pages this call's positions and the
            # window before the first of them reach, not the whole table
            # (the entries behind them are null)
            reach = min(P, -(-(window + tokens.shape[0]) // page_size) + 1)
            first = jnp.clip((positions[0] - window + 1) // page_size, 0,
                             P - reach)
            table = jax.lax.dynamic_slice_in_dim(table, first, reach)
            kpos = first * page_size

        def attend(q, k, v, pools):
            ck, cv, li = pools
            # suffix writes go to the sequence's own fresh pages only:
            # matched prefix pages cover positions < prefix_len and are
            # never written
            with jax.named_scope("attn/kv_write"):
                ck = ck.at[li, rows, slot_positions].set(k)
                cv = cv.at[li, rows, slot_positions].set(v)
            with jax.named_scope("attn/attend"):  # the gather is attending
                keys = ck[li, table].reshape(
                    reach * page_size, cfg.n_kv_heads, cfg.head_dim)
                vals = cv[li, table].reshape(
                    reach * page_size, cfg.n_kv_heads, cfg.head_dim)
                # [L, T] causal over absolutes
                cols = jnp.arange(reach * page_size)
                mask = _visible(cfg, positions,
                                cols if kpos is None else kpos + cols, window)
            return _masked_attention(cfg, q, keys, vals, mask), (ck, cv)

        return attend

    def attend_latent(q_nope, q_rope, row, a, pools):
        pool, _, li = pools  # rebuilt from the rows the page table reaches
        pool = _write_rows(pool, li, page_rows, slot_positions, row)
        with jax.named_scope("attn/attend"):  # the gather is attending
            rows = pool[li, page_table].reshape(P * page_size, -1)
            mask = _visible(cfg, positions, jnp.arange(P * page_size))
        return (_rebuilt_attention(cfg, a, q_nope, q_rope, rows, mask),
                (pool, None))

    def attend_sparse(q, k, v, pools):
        ck, cv, (by_page, by_slot), li = pools
        L, G, d = k.shape
        with jax.named_scope("attn/kv_write"):
            ck = ck.at[li, page_rows, slot_positions].set(k)
            cv = cv.at[li, page_rows, slot_positions].set(v)

        def entries(first, n):  # n entries of the table from ``first`` on:
            at = first + jnp.arange(n)  # where they are, which are in it
            return at, (at >= 0) & (at < P)

        def through(first, n):  # their page ids, the null page outside it
            at, inside = entries(first, n)
            return jnp.where(inside, page_table[jnp.clip(at, 0, P - 1)], 0)

        with jax.named_scope("sparse_attn/index"):
            # this call's rows, and those that began in the pages before
            # it and end here: their first keys are the pool's
            w = cfg.kernel_size // page_size  # pages a row spans
            first = positions[0] // page_size - (w - 1)
            before = ck[li, through(first, w - 1)].reshape(-1, G, d)
            rows = _pooled_rows(cfg, jnp.concatenate([before, k]),
                                page_size).astype(by_page.dtype)
            by_page = by_page.at[li, through(first, rows.shape[0])].set(rows)
            # the same entries of the slot's rows, those outside dropped
            at, inside = entries(first, rows.shape[0])
            by_slot = _in_slot_order(by_slot, P).at[
                li, slot, jnp.where(inside, at, P)].set(rows, mode="drop")
            # the sequence's, [P, G, d], where they lie
            rows = jax.lax.dynamic_index_in_dim(by_slot[li], slot, 0, False)

        def keys_of(at, n):
            pages = jax.lax.dynamic_slice_in_dim(
                page_table, at // page_size, n // page_size)
            return (ck[li, pages].reshape(n, G, d),
                    cv[li, pages].reshape(n, G, d))

        if (P * page_size) % cfg.block_size:
            raise ValueError(
                f"a page table of {P * page_size} positions is no whole "
                f"number of blocks of {cfg.block_size}")
        ends = positions[0] + true_len
        out, tiles = block_sparse.selected_attention(
            cfg, q, positions, rows, keys_of, P * page_size, ends)
        return out, (ck, cv, (by_page, by_slot)), {
            **tiles,
            "index_rows_written": block_sparse.rows_complete(cfg, ends)
            - block_sparse.rows_complete(cfg, positions[0])}

    def recur_fixed(q, k, v, g, rows):  # from what the slot's row holds
        with jax.named_scope(cfg.state_part):
            real = (jnp.arange(tokens.shape[0]) < true_len)[:, None]
            S0 = jax.lax.dynamic_slice(
                state["S"], (rows[1], slot, 0, 0, 0),
                (1, 1, *state["S"].shape[2:]))[0, 0]
            o, S = lightning.chunked(q, jnp.where(real[..., None], k, 0), v,
                                     jnp.where(real, g, 0.0), S0)
        return o, (rows[0], S)

    def conv_before(rows, taps, x):
        return jax.lax.dynamic_slice_in_dim(conv_rows, rows[1] * taps, taps)

    if state is not None and "conv" in state:
        # the SLOT's convolution rows [layers x taps, C], read out once and
        # not in the walk: a loop that carries every slot's rows (11.8 MB at
        # Falcon-H1's widths) to slice a layer's three out of them lets XLA
        # stage all of them through VMEM around each layer
        with jax.named_scope(falcon_h1.CONV_PART):
            conv_rows = jax.lax.dynamic_index_in_dim(state["conv"], slot, 1,
                                                     keepdims=False)
    recur_fixed = _conv_first(cfg, recur_fixed, conv_before,
                              _last_inputs(true_len))

    x, (cache_k, cache_v, rode), counted, left = cfg.served_walk(
        params, x, (cache_k, cache_v, _rows_that_ride(cfg, state)), positions,
        {**_by_kind(cfg, attend_through), "attend_latent": attend_latent,
         "attend_sparse": attend_sparse, "recur_fixed": recur_fixed})
    if isinstance(left, dict):
        state = _write_slot_rows(cfg, state, rode, left, slot)
    return (_last_logits(params, x, cfg, true_len), counted, cache_k,
            cache_v, state)


def _decode_impl(params, tokens, cache_k, cache_v, page_tables, positions,
                 active, cfg, state=None):
    """One token for EVERY slot (the continuous-batching hot loop).

    tokens: [B] int32 current token per slot; positions: [B] its position;
    page_tables: [B, P] page ids (0 = null page); active: [B] bool.
    Returns (logits [B, V], counted, cache_k, cache_v, state).

    A layer writes its B new rows into the pool in place and the paged
    kernel reads that layer's pages out of the same buffer.  A recurrent
    layer updates the row of every ACTIVE slot in ``state`` in place
    (``ops/gated_delta.decode_update``: a live slot's state is read once
    and written once, the others' not at all).
    """
    P = jax.tree.leaves(page_tables)[0].shape[1]
    page_size = jax.tree.leaves(cache_k)[0].shape[2]
    x = embed(params, tokens, cfg)  # [B, D]

    def coordinates(page_tables):
        """(write_page, write_slot, lengths) through one kind's tables."""
        # where this step's k/v lands: slot b writes page_tables[b, pos//ps].
        # Inactive slots, and a burst's overshoot past the table's last page,
        # write into the null page (page 0) — harmless scratch
        in_table = active & (positions < P * page_size)
        write_page = jnp.take_along_axis(
            page_tables, jnp.minimum(positions // page_size, P - 1)[:, None],
            axis=1)[:, 0]
        write_page = jnp.where(in_table, write_page, 0)
        write_slot = positions % page_size
        # attend up to and including the current token; 0 skips the slot
        lengths = jnp.where(active, positions + 1, 0)
        return write_page, write_slot, lengths

    # one kind of page: every closure below writes and walks through these
    one = None if cfg.window else coordinates(page_tables)
    write_page, write_slot, lengths = one or (None,) * 3

    def attend_through(kind, window):
        # q: [B, H, d]; k, v: [B, Hkv, d]
        tables = _of(kind, page_tables)
        write_page, write_slot, lengths = one or coordinates(tables)
        bound = {"window": window} if window else {}

        def attend(q, k, v, pools):
            ck, cv, li = pools
            n_pool = ck.shape[3]
            with jax.named_scope("attn/kv_write"):
                ck = ck.at[li, write_page, write_slot].set(
                    _pad_heads(k, n_pool).astype(ck.dtype))
                cv = cv.at[li, write_page, write_slot].set(
                    _pad_heads(v, n_pool).astype(cv.dtype))
            with jax.named_scope("attn/attend"):
                if n_pool != k.shape[1]:  # a padded pool: one query head each
                    out = paged_decode_attention(
                        _pad_heads(q, n_pool), ck, cv, tables, lengths, li,
                        **bound)
                    return out[:, :q.shape[1]], (ck, cv)
                return (paged_decode_attention(q, ck, cv, tables, lengths,
                                               li, **bound), (ck, cv))

        return attend

    def attend_latent(q_nope, q_rope, row, a, pools):
        pool, _, li = pools  # absorbed: K and V are never made
        pool = _write_rows(pool, li, write_page, write_slot, row)
        q = glm_moe_lite.absorb(cfg, a, q_nope, q_rope, pool.shape[-1])
        with jax.named_scope("mla/attend"):
            out = paged_latent_decode_attention(
                q, pool, page_tables, lengths, li,
                value_dim=cfg.kv_lora_rank, sm_scale=cfg.head_dim ** -0.5)
        return glm_moe_lite.unabsorb(cfg, a, out), (pool, None)

    def recur(mix, qkv, b, a, rows):  # qkv: [B, channels]
        st, li = rows
        taps = mix["conv"].shape[0] - 1  # rows a layer, every slot's
        before = jax.lax.dynamic_slice_in_dim(st["conv"], li * taps, taps)
        q, k, v, g, beta, conv = _conv_and_gates(
            cfg, mix, qkv[None], before, b[None], a[None])
        with jax.named_scope("lin_attn/state"):
            o, S = gated_delta.decode_update(
                st["S"], li, q[0], k[0], v[0], g[0], beta[0], active,
                pack=cfg.state_pack)
            st = {"S": S, "conv": jax.lax.dynamic_update_slice_in_dim(
                st["conv"], conv[1:], li * taps, axis=0)}
        return o, (st, None)

    def attend_sparse(q, k, v, pools):  # q: [B, H, d]; k, v: [B, G, d]
        ck, cv, (by_page, by_slot), li = pools
        with jax.named_scope("attn/kv_write"):
            ck = ck.at[li, write_page, write_slot].set(k.astype(ck.dtype))
            cv = cv.at[li, write_page, write_slot].set(v.astype(cv.dtype))
        with jax.named_scope("sparse_attn/index"):
            block_sparse.check_sizes(cfg, page_size)
            # the row this step's key completes: the one that began w - 1
            # pages before the page this position fills
            w = cfg.kernel_size // page_size
            first = lengths // page_size - w
            done = (lengths > 0) & (lengths % page_size == 0) & (first >= 0)
            pages = jnp.take_along_axis(
                page_tables, jnp.clip(first[:, None] + jnp.arange(w), 0,
                                      P - 1), axis=1)  # [B, w]
            row = ck[li, pages].astype(jnp.float32).mean(
                axis=(1, 2)).astype(by_page.dtype)
            by_page = by_page.at[li, jnp.where(done, pages[:, 0], 0)].set(row)
            # entry ``first`` of its slot's rows; a slot that completes no
            # row writes nothing (no null row absorbs it here)
            by_slot = _in_slot_order(by_slot, P).at[
                li, jnp.arange(tokens.shape[0]),
                jnp.where(done, first, P)].set(row, mode="drop")
            # every slot's rows where they lie: no gather through the tables
            lists, held = block_sparse.page_lists(
                cfg, q, by_slot[li], page_tables, lengths,
                block_sparse.list_width(cfg, P))
            counted = {**block_sparse.walked(cfg, held, lengths),
                       "index_rows_written": done.sum().astype(jnp.int32)}
        with jax.named_scope("sparse_attn/attend"):
            return (paged_decode_attention(q, ck, cv, lists, held, li,
                                           heads_apart=True),
                    (ck, cv, (by_page, by_slot)), counted)

    def recur_fixed(q, k, v, g, rows):
        # q, k: [B, G, d_k]; v: [B, H, d_v]; g: [H] or [B, H]
        S, li = rows
        with jax.named_scope(cfg.state_part):
            o, S = lightning.decode_update(
                S, li, q, k, v, jnp.broadcast_to(g, v.shape[:2]), active)
        return o, (S, None)

    def slots_rows(rows, taps, x):  # layer li's, every slot's
        return jax.lax.dynamic_slice_in_dim(rows[0]["conv"], rows[1] * taps,
                                            taps)

    def keep(rows, kept, before, in_order):
        # A slot that takes no step keeps its rows as its state is kept
        # (``decode_update`` masks by ``active`` too): it may be between
        # two CHUNKS of its prompt, and the next chunk convolves from them.
        tail = jnp.where(active[None, :, None], in_order[1:], before)
        return {"S": kept[0], "conv": jax.lax.dynamic_update_slice_in_dim(
            rows[0]["conv"], tail, rows[1] * before.shape[0], axis=0)}, None

    # (a family with a convolution carries its rows through the walk by
    # name, ``{"S", "conv"}``)
    recur_fixed = _conv_first(cfg, recur_fixed, slots_rows, keep,
                              lambda rows: (rows[0]["S"], rows[1]))

    x, caches, counted, _ = cfg.served_walk(
        params, x, (cache_k, cache_v, state), positions,
        {**_by_kind(cfg, attend_through), "attend_latent": attend_latent,
         "recur": recur, "attend_sparse": attend_sparse,
         "recur_fixed": recur_fixed})
    return (head(params, x, cfg), counted, *caches)


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("cache_k", "cache_v", "state"))
def decode_step(params, tokens, cache_k, cache_v, page_tables, positions,
                active, cfg, state=None):
    return _decode_impl(params, tokens, cache_k, cache_v, page_tables,
                        positions, active, cfg, state)


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("cache_k", "cache_v", "state"))
def decode_step_greedy(params, tokens, cache_k, cache_v, page_tables,
                       positions, active, cfg, state=None):
    """Greedy decode: argmax ON DEVICE, so the host fetches [B] int32
    instead of [B, vocab] fp32 logits — the device-to-host round trip is the
    decode loop's fixed cost when every active request samples greedily."""
    logits, *rest = _decode_impl(
        params, tokens, cache_k, cache_v, page_tables, positions, active,
        cfg, state)
    with jax.named_scope("sample"):
        return (jnp.argmax(logits, axis=-1).astype(jnp.int32), *rest)


BURST_ROWS = 8  # the rows of ``acc``: the engine's longest greedy burst


def _layout(counted) -> tuple:
    """((key, entries), ...) of a step's ``counted`` in the order a row of
    ``acc`` holds it behind the tokens: a name's scalar, a tuple of names'
    vector."""
    return tuple((key, math.prod(n.shape))
                 for key, n in sorted(counted.items(),
                                     key=lambda kn: str(kn[0])))


def counted_layout(params, tokens, cache_k, cache_v, page_tables, positions,
                   active, cfg, state=None) -> tuple:
    """``_layout`` of what a decode step over these arguments counts (the
    engine sizes ``acc`` by it and names a burst's counts by it).  The step
    is traced in the abstract: nothing is compiled and nothing runs."""
    return _layout(jax.eval_shape(
        partial(_decode_impl, cfg=cfg), params, tokens, cache_k, cache_v,
        page_tables, positions, active, state=state)[1])


def acc_shape(slots: int, layout: tuple) -> tuple:
    """``acc``'s shape for ``slots`` slots and a step that counts what
    ``layout`` (``counted_layout``) says: a row a step of the longest
    burst, a slot's token a column, then a column an entry counted."""
    return BURST_ROWS, slots + sum(entries for _, entries in layout)


@partial(jax.jit, static_argnames=("cfg",),
         donate_argnames=("tokens", "cache_k", "cache_v", "positions", "acc",
                          "state"))
def decode_step_greedy_chained(params, tokens, cache_k, cache_v, page_tables,
                               positions, active, row, acc, cfg, state=None):
    """``decode_step_greedy`` for a burst whose carry STAYS on the device:
    the step takes the last step's tokens and positions (handed back ``+
    1``: no program of the host's advances them) and writes its argmax
    tokens, then what it counted (``_layout``'s order), into row ``row`` of
    ``acc`` ([BURST_ROWS, B + entries] int32, donated as tokens and
    positions are), so that a burst is as many launches and ONE fetch,
    tokens and counts together.  Returns ((tokens, positions, row + 1,
    acc), {}, cache_k, cache_v, state): the counts are in ``acc``."""
    logits, counted, *rest = _decode_impl(
        params, tokens, cache_k, cache_v, page_tables, positions, active,
        cfg, state)
    with jax.named_scope("sample"):
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        acc = jax.lax.dynamic_update_slice(acc, jnp.concatenate(
            [tokens, *(counted[key].reshape(-1).astype(jnp.int32)
                       for key, _ in _layout(counted))])[None], (row, 0))
    return ((tokens, positions + 1, row + 1, acc), {}, *rest)


def _fill(cfg, logits, masked, step):
    """Which of a block's masked positions this pass fills, and with what:
    ``generate.py``'s three strategies.  logits [S, B, V] float32; masked
    [S, B]; step [S] passes this block has had.  Returns (x0 [S, B] int32,
    fill [S, B] bool).  Only a masked position is ever filled (the
    published top-k can name an unmasked one in a block a prompt's tail
    opened; a filled position never changes here)."""
    with jax.named_scope("sample"):
        B, T = cfg.block_length, cfg.denoising_steps
        x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        n_t = jnp.asarray(sdar_moe.num_transfer_tokens(B, T), jnp.int32)[
            jnp.minimum(step, T - 1)][:, None]  # [S, 1]
        if cfg.remasking_strategy == "sequential":  # the leftmost masked
            return x0, masked & (jnp.cumsum(masked, axis=1) <= n_t)
        # softmax probability of x0, masked positions only
        conf = jnp.exp(jnp.max(logits, axis=-1)
                       - jax.scipy.special.logsumexp(logits, axis=-1))
        conf = jnp.where(masked, conf, -jnp.inf)
        # rank 0 is the most confident; ties go to the leftmost
        order = jnp.argsort(-conf, axis=1, stable=True)
        rank = jnp.argsort(order, axis=1, stable=True)
        static = masked & (rank < n_t)
        if cfg.remasking_strategy == "low_confidence_static":
            return x0, static
        high = conf > cfg.confidence_threshold
        enough = jnp.sum(high, axis=1, keepdims=True) >= n_t
        return x0, jnp.where(enough, high, static)


@partial(jax.jit, static_argnames=("cfg",), donate_argnums=(1, 2))
def block_step(params, cache_k, cache_v, page_tables, active, tokens,
               masked, starts, step, cfg):
    """One denoising pass over the open block of EVERY slot.

    tokens: [S, B] int32 the block as it stands, the mask token at masked
    positions; masked: [S, B] bool (the state, not ``tokens == mask``: a
    drawn id may be the mask token's); starts: [S] the block's first
    position, a multiple of B; step: [S] passes this block has had;
    page_tables: [S, P]; active: [S] bool.

    Every row attends to pages [0, start) and to its own block whole
    (bidirectional inside it): the S x B rows go through
    ``paged_decode_attention`` as S slots of B x H query heads, each KV
    group's B x H/Hkv rows together, with the block's END as the length, so
    a page is read once for the B rows.  The pass writes the block's K/V
    rows in place; a later pass over the same block overwrites them, and
    the rows are final when the pass's input held no mask.  Such a pass
    fills nothing and opens the next block (all masks); any other fills
    masks by ``cfg.remasking_strategy``.

    Returns (record [S, 2B + 2] int32: the block after the pass, its masks
    after the pass, whether the pass made it final, and in every row the
    experts the pass's routed layers read; then what the next pass takes:
    tokens, masked, starts, step; cache_k, cache_v).
    """
    S, B = tokens.shape
    P = page_tables.shape[1]
    page_size = cache_k.shape[2]
    n_kv, rep, d = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.head_dim
    x = embed(params, tokens.reshape(S * B), cfg)  # [S * B, D]
    offsets = jnp.arange(B, dtype=starts.dtype)
    positions = (starts[:, None] + offsets).reshape(S * B)
    # a block lies in one page (page_size is a multiple of B); inactive
    # slots and a burst's overshoot write into the null page, as decoding
    in_table = active & (starts < P * page_size)
    write_page = jnp.take_along_axis(
        page_tables, jnp.minimum(starts // page_size, P - 1)[:, None],
        axis=1)[:, 0]
    write_page = jnp.repeat(jnp.where(in_table, write_page, 0), B)
    write_slot = ((starts % page_size)[:, None] + offsets).reshape(S * B)
    lengths = jnp.where(active, starts + B, 0)

    def attend(q, k, v, pools):  # q: [S * B, H, d]; k, v: [S * B, Hkv, d]
        ck, cv, li = pools
        with jax.named_scope("attn/kv_write"):
            ck = ck.at[li, write_page, write_slot].set(k.astype(ck.dtype))
            cv = cv.at[li, write_page, write_slot].set(v.astype(cv.dtype))
        with jax.named_scope("attn/attend"):
            q = q.reshape(S, B, n_kv, rep, d).transpose(0, 2, 1, 3, 4)
            out = paged_decode_attention(q.reshape(S, n_kv * B * rep, d), ck,
                                         cv, page_tables, lengths, li)
            out = out.reshape(S, n_kv, B, rep, d).transpose(0, 2, 1, 3, 4)
            return out.reshape(S * B, n_kv * rep, d), (ck, cv)

    x, (cache_k, cache_v, _), counted, _ = cfg.served_walk(
        params, x, (cache_k, cache_v, None), positions, {"attend": attend})
    x0, fill = _fill(cfg, head(params, x, cfg).reshape(S, B, -1), masked,
                     step)
    final = active & ~jnp.any(masked, axis=1)
    tokens = jnp.where(fill, x0, tokens)
    masked = masked & ~fill
    record = jnp.concatenate(
        [tokens, masked.astype(jnp.int32), final[:, None].astype(jnp.int32),
         jnp.broadcast_to(counted["experts_read"], (S, 1))], axis=1)
    nxt = final[:, None]
    return (record, jnp.where(nxt, jnp.int32(cfg.mask_token_id), tokens),
            masked | nxt, jnp.where(final, starts + B, starts),
            jnp.where(final, 0, step + 1), cache_k, cache_v)


@partial(jax.jit, donate_argnums=(0, 1))
def copy_page(cache_k, cache_v, src, dst):
    """Copy-on-write boundary page: duplicate one KV page across all
    layers (a [n_layers, page_size, n_kv, head_dim] gather/scatter, not a
    whole-cache copy thanks to donation).  The whole page is copied even
    when only the first `cow_len` slots are valid — the suffix prefill /
    decode overwrites every slot past the divergence point before any
    attention reads it, the same invariant that makes null-page garbage
    safe."""
    return (cache_k.at[:, dst].set(cache_k[:, src]),
            None if cache_v is None  # a latent pool is the one pool
            else cache_v.at[:, dst].set(cache_v[:, src]))
