"""MiniCPM-SALA's family (``minicpm_sala``: block-sparse attention layers
and fixed-decay linear-attention layers in one stack): what the program is
given for a configuration of this family, and what the algorithm needs of
the chip.

Two halves, as ``afmoe``.  ``model_config``, ``make_params``,
``pinned_logits``, ``recurrence_outputs``, ``engine_rows``,
``served_attention``, ``engine_selection`` and ``engine_state`` turn a
configuration file (the published ``config.json`` keys and
``sparse_config``) into what the program takes; they import nothing of the
program above its model module and ``ops``.  Everything above them
is plain arithmetic on the published sizes, the benchmark's own count of
the operations and bytes a call requires; no change to the program moves
it.

Names the metric readers use: a decode step is a program
``jit_decode_step*`` in the device trace; the sparse layers' parts lie
under ``sparse_attn/`` (``index``: pooled keys and the choice of blocks,
``attend``: the paged kernel over the chosen pages, ``proj``: the
products) and the linear layers' under ``lightning/`` (``proj``, ``state``:
the recurrence, ``out``).
"""

from __future__ import annotations

BYTES = {"bfloat16": 2, "float32": 4}
DECODE_MODULE = "jit_decode_step"
PREFILL_MODULE = "jit_prefill"
SPARSE_PREFIX, LINEAR_PREFIX = "sparse_attn/", "lightning/"
INDEX_PART, ATTEND_PART = "sparse_attn/index", "sparse_attn/attend"
STATE_PART = "lightning/state"
SPARSE, LINEAR = "minicpm4", "lightning-attn"


# --------------------------------------------------------------------------
# sizes (plain arithmetic; ``c`` is the configuration file as a dict)

def layers_by_kind(c: dict) -> tuple:
    """(sparse layers, linear layers)."""
    n = sum(t == SPARSE for t in c["mixer_types"])
    return n, len(c["mixer_types"]) - n


def mlp_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def sparse_mixer_params(c: dict) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    hq, hkv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return 3 * d * hq + 2 * d * hkv  # W_q, W_g, W_o; W_k, W_v


def linear_mixer_params(c: dict) -> int:
    return 5 * c["hidden_size"] * c["lightning_nh"] * c["lightning_head_dim"]


def n_params(c: dict) -> int:
    d = c["hidden_size"]
    sparse, linear = layers_by_kind(c)
    return (sparse * (sparse_mixer_params(c) + 2 * c["head_dim"])
            + linear * (linear_mixer_params(c) + 3 * c["lightning_head_dim"])
            + (sparse + linear) * (mlp_params(c) + 2 * d)
            + 2 * c["vocab_size"] * d + d)


def weight_bytes(c: dict, dtype: str = "bfloat16") -> int:
    return n_params(c) * BYTES[dtype]


def kv_row_bytes(c: dict, dtype: str = "bfloat16") -> int:
    """K and V of one token in one sparse layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BYTES[dtype]


def kv_bytes_per_token(c: dict, dtype: str = "bfloat16") -> float:
    """Page bytes a token takes over the sparse layers: K and V, and its
    share of the page's row of pooled keys."""
    sparse, _ = layers_by_kind(c)
    pooled = (c["num_key_value_heads"] * c["head_dim"] * BYTES[dtype]
              / c["sparse_config"]["kernel_stride"])
    return sparse * (kv_row_bytes(c, dtype) + pooled)


def state_bytes_per_layer(c: dict) -> int:
    """A slot's float32 state in one linear layer."""
    return c["lightning_nh"] * c["lightning_head_dim"] ** 2 * 4


def state_bytes_per_slot(c: dict) -> int:
    return layers_by_kind(c)[1] * state_bytes_per_layer(c)


# --------------------------------------------------------------------------
# required operations and bytes

def state_update_bytes(c: dict, slots: float) -> float:
    """HBM bytes ONE decode step's recurrences have to move: every live
    slot's state of every linear layer read once and written once."""
    return 2.0 * slots * state_bytes_per_slot(c)


def keys_attended(c: dict, context: float) -> float:
    """Keys the query with ``context`` tokens of context attends to in a
    sparse layer: all of them at or under ``dense_len``, ``topk`` blocks'
    past it (the last as far as the query's own position: half a block on
    average)."""
    sp = c["sparse_config"]
    if context <= sp["dense_len"]:
        return context
    return (sp["topk"] - 0.5) * sp["block_size"]


def sparse_attend_bytes(c: dict, pages_read: float, page_size: int,
                        dtype: str = "bfloat16") -> float:
    """HBM bytes the sparse layers' decode attention has to move for
    ``pages_read`` pages (the engine's count of what the kernel's lists
    held, a list a KV head a sparse layer a live slot): the list's own
    head's K and V rows of those pages."""
    return pages_read * page_size * 2 * c["head_dim"] * BYTES[dtype]


def prefill_flops(c: dict, new_tokens: int, cached_tokens: int = 0) -> float:
    """Multiply-adds x 2 that computing ``new_tokens`` positions behind
    ``cached_tokens`` requires: every matrix once a token, the sparse
    layers' scores and values over the keys the rule selects (and the
    pooled keys' scores past ``dense_len``), the linear layers' state
    update and read."""
    sparse, linear = layers_by_kind(c)
    H, hd = c["num_attention_heads"], c["head_dim"]
    lin = c["lightning_nh"] * c["lightning_head_dim"] ** 2
    sp = c["sparse_config"]
    matmul = (sparse * sparse_mixer_params(c) + linear * linear_mixer_params(c)
              + (sparse + linear) * mlp_params(c)
              + c["hidden_size"] * c["vocab_size"] / max(1, new_tokens))
    attended = pooled = 0.0
    # (by steps of a block: the sum over queries of the keys each sees)
    at = cached_tokens
    while at < cached_tokens + new_tokens:
        n = min(sp["block_size"], cached_tokens + new_tokens - at)
        mid = at + (n + 1) / 2.0
        attended += n * keys_attended(c, mid)
        if mid > sp["dense_len"]:
            pooled += n * mid / sp["kernel_stride"]
        at += n
    return 2.0 * (new_tokens * matmul
                  + sparse * H * hd * (2 * attended + pooled)
                  + linear * new_tokens * 2 * lin)


# --------------------------------------------------------------------------
# the program's side

def model_module():
    from ray_tpu.models import minicpm_sala

    return minicpm_sala


def model_config(c: dict, **overrides):
    sp = c["sparse_config"]
    return model_module().MiniCPMSALAConfig(**{**dict(
        vocab_size=c["vocab_size"], d_model=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        d_ff=c["intermediate_size"], lightning_heads=c["lightning_nh"],
        lightning_head_dim=c["lightning_head_dim"],
        mixer_types=tuple(c["mixer_types"]), scale_emb=float(c["scale_emb"]),
        scale_depth=float(c["scale_depth"]),
        dim_model_base=c["dim_model_base"],
        depth_base=c["published"]["num_hidden_layers"],
        kernel_size=sp["kernel_size"], kernel_stride=sp["kernel_stride"],
        block_size=sp["block_size"], init_blocks=sp["init_blocks"],
        window_size=sp["window_size"], topk=sp["topk"],
        dense_len=sp["dense_len"],
        max_seq_len=c["max_position_embeddings"],
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=c.get("dtype", "bfloat16")), **overrides})


def make_params(c: dict, seed: int, dtype: str):
    """Seeded weights in the type they are served in, made on the device in
    one jitted call (``rbg`` keys, as ``llama_dense.make_params``)."""
    import jax
    import jax.numpy as jnp

    ms, cfg = model_module(), model_config(c)
    return jax.jit(lambda k: ms.init(cfg, k, jnp.dtype(dtype)))(
        jax.random.key(seed, impl="rbg"))


def round_state(S):
    """A float32 state kept to bf16's 8 bits of mantissa (the planted fault
    ``state_bf16``; ``reduce_precision``: XLA would remove a pair of
    casts)."""
    import jax

    return jax.lax.reduce_precision(S, 8, 7)


def plant_state_bf16(piece: int = 256) -> None:
    """The fault ``state_bf16`` in THIS process: the program's recurrence,
    both forms, keeps its state rounded to bf16: every step of the decode
    form, every ``piece`` tokens of the chunked one (a state stored in bf16
    is rounded wherever it is stored).  A control's, never a run's."""
    import jax.numpy as jnp

    from ray_tpu.ops import lightning

    chunked, update = lightning.chunked, lightning.decode_update

    def chunked_rounded(q, k, v, g, S0, *a, **kw):
        S, outs = round_state(S0), []
        for at in range(0, q.shape[0], piece):
            o, S = chunked(*(x[at:at + piece] for x in (q, k, v, g)), S,
                           *a, **kw)
            S = round_state(S)
            outs.append(o)
        return jnp.concatenate(outs), S

    def update_rounded(state, *a, **kw):
        o, state = update(state, *a, **kw)
        return o, round_state(state)

    lightning.chunked, lightning.decode_update = chunked_rounded, update_rounded


def plant_list_page_shifted() -> None:
    """The fault ``list_page_shifted`` in THIS process: every list a decode
    step's kernel walks begins a page late (entry i holds what entry i + 1
    should), its length as it was.  A control's, never a run's."""
    import jax.numpy as jnp

    from ray_tpu.ops import block_sparse

    lists_from = block_sparse.lists_from

    def shifted(*a, **kw):
        lists, lengths = lists_from(*a, **kw)
        return jnp.roll(lists, -1, axis=-1), lengths

    block_sparse.lists_from = shifted


def plant_other_heads_columns() -> None:
    """The fault ``other_heads_columns`` in THIS process: under
    ``heads_apart`` a KV head's query heads walk their own list and read
    the OTHER KV head's rows of its pages.  A control's, never a run's."""
    from ray_tpu.llm import model as lm
    from ray_tpu.ops import paged_attention

    attention = paged_attention.paged_decode_attention

    def crossed(q, k_pool, v_pool, lists, lengths, layer, **kw):
        if not kw.get("heads_apart"):
            return attention(q, k_pool, v_pool, lists, lengths, layer, **kw)
        B, H, d = q.shape
        G = lists.shape[1]
        # a KV head of a slot is a slot of the kernel's and reads the
        # columns of its place: the groups change places, lists with them
        out = attention(q.reshape(B, G, H // G, d)[:, ::-1].reshape(B, H, d),
                        k_pool, v_pool, lists[:, ::-1], lengths[:, ::-1],
                        layer, **kw)
        return out.reshape(B, G, H // G, d)[:, ::-1].reshape(B, H, d)

    paged_attention.paged_decode_attention = crossed
    lm.paged_decode_attention = crossed


def _walk(c: dict, params, tokens, attend, **overrides):
    """The PROGRAM's layers over tokens [s], cacheless: its products, norms,
    gates, rotary embedding, chunked recurrence from a zero state, stream
    and head; ``attend(i, q, k, v) -> out`` is the sparse layers' core."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.ops import lightning

    ms, cfg = model_module(), model_config(c, **overrides)
    params = ms.serving_layout(cfg, params)
    positions = jnp.arange(tokens.shape[0])
    x = llama.embed(params, tokens, cfg)

    def recur(q, k, v, g, cache):
        zero = jnp.zeros((q.shape[1], k.shape[2], v.shape[2]), jnp.float32)
        return lightning.chunked(
            q, k, v, jnp.broadcast_to(g, q.shape[:2]), zero)[0], cache

    for p, (kind, first, n) in zip(params["layers"], cfg.runs()):
        for i in range(n):
            one = jax.tree.map(lambda w: w[i], p)
            if kind == LINEAR:
                x, _ = ms.lightning_layer(cfg, one, x, positions, recur, None)
            else:
                x, _ = ms.sparse_layer(
                    cfg, one, x,
                    lambda q, k, v, _, i=first + i: (attend(i, q, k, v), None),
                    None)
    return x, params, cfg


def pinned_logits(c: dict, params, tokens, rows, selection, **overrides):
    """The PROGRAM's layers over tokens [s] with the sparse layers' choice
    of blocks HANDED IN (``selection`` [sparse layers, s, G, M] bool, the
    reference's), and the program's OWN choice at every query beside it.
    Returns (logits [r, vocab] float32 at ``rows`` [r], own [sparse layers,
    s, G, M] bool).

    Why the choice is pinned: a bf16 stream moves a pooled score a little,
    the 64th and 65th of some hundred blocks lie close, so the served model
    takes another last block than float32 at a share of queries, each swap
    moving the logits by more than a misread weight would.  That is no
    fault, and it buries what IS one unless both sides attend to the same
    blocks; how often the choices differ is its own comparison.  Attention
    is the PREFILLS' own (``block_sparse.attend_under``: keys a block of
    512 at a time under the blocks' mask with a running softmax, what
    ``selected_attention`` runs behind the choice) and the program's own
    choice ``block_sparse.masks``, the other half of it; the decode step's
    lists and kernel are ``served_attention``'s, the pages, the chunks and
    the engine are checked on the rows they leave and on tokens
    (runners/serve_sparse_linear).  ``overrides``: a control's, fields of
    the program's configuration other than the file's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama
    from ray_tpu.ops import block_sparse

    own = []
    s = tokens.shape[0]
    pos = jnp.arange(s)
    cfg = model_config(c, **overrides)

    def attend(i, q, k, v):
        pooled = block_sparse.pool_keys(cfg, k).astype(k.dtype)
        own.append(block_sparse.masks(cfg, q, pos, pooled, s))
        return block_sparse.attend_under(
            cfg, q, pos, selection[i],
            lambda at, n: (jax.lax.dynamic_slice_in_dim(k, at, n),
                           jax.lax.dynamic_slice_in_dim(v, at, n)), s, s)

    x, params, _ = _walk(c, params, tokens, attend, **overrides)
    return llama.head(params, x[rows], cfg), jnp.stack(own)


def recurrence_outputs(c: dict, q, k, v, prefill_tokens: int, chunk: int):
    """The PROGRAM's recurrence as the engine runs it, on inputs handed in
    (float32 [T, H, d] each): ``chunked`` over the first ``prefill_tokens``
    in calls of ``chunk`` tokens, each from the state the one before left
    (a prompt in chunks), then ``decode_update`` a token at a time.
    Returns (o [T, H, d], the final state [H, d, d])."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import lightning

    T, H, d = q.shape
    g = jnp.broadcast_to(lightning.log_decays(H), (T, H))
    S = jnp.zeros((H, d, d), jnp.float32)
    outs = []
    for at in range(0, prefill_tokens, chunk):
        to = min(at + chunk, prefill_tokens)
        o, S = lightning.chunked(q[at:to], k[at:to], v[at:to], g[at:to], S)
        outs.append(o)
    state = S[None, None]  # [layers, slots, ...]
    live = jnp.ones((1,), bool)

    def step(state, x):
        q, k, v, g = x
        o, state = lightning.decode_update(
            state, 0, q[None], k[None], v[None], g[None], live)
        return state, o[0]

    state, o = jax.lax.scan(step, state, tuple(
        x[prefill_tokens:] for x in (q, k, v, g)))
    return jnp.concatenate(outs + [o]), state[0, 0]


def engine_rows(engine, pages: list):
    """What the engine's pools hold for one sequence through ``pages``:
    ``k``, ``v`` [sparse layers, len(pages) x page_size, G, d] and
    ``pooled`` [sparse layers, len(pages), G, d]."""
    import jax.numpy as jnp

    idx = jnp.asarray(pages, jnp.int32)
    k, v = (pool[:, idx].reshape(pool.shape[0], -1, *pool.shape[3:])
            for pool in (engine.cache_k, engine.cache_v))
    return {"k": k, "v": v, "pooled": engine.state["pooled_k"][:, idx]}


def _table(engine, pages: list):
    """A sequence's page table as a decode step is handed it."""
    import numpy as np

    table = np.zeros(engine.max_pages_per_seq, np.int32)
    table[:len(pages)] = pages
    return table


def engine_selection(engine, pages: list, q, n: int, li: int) -> list:
    """The pages the ENGINE's decode step lists in sparse layer ``li`` for
    a query q [H, d] with n tokens of context, by the program's own
    ``page_lists`` under the engine's configuration over the rows of pooled
    keys its programs left, found through the table of the sequence that
    holds ``pages`` as the step finds them: a set of page ids a KV head,
    what each list holds as far as its length."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import block_sparse

    cfg = engine.model_cfg
    table = jnp.asarray(_table(engine, pages))[None]
    pooled = engine.state["pooled_k"]
    lists, held = block_sparse.page_lists(
        cfg, q[None].astype(pooled.dtype), pooled[li, table], table,
        jnp.asarray([n], jnp.int32),
        block_sparse.list_width(cfg, table.shape[1]))
    lists, held = np.asarray(lists[0]), np.asarray(held[0])
    return [set(lists[g, :-(-held[g] // cfg.kernel_stride)].tolist())
            for g in range(lists.shape[0])]


def selected_pages(engine, pages: list, selection, n: int) -> list:
    """The pages of the blocks ``selection`` [G, M] bool (the reference's,
    for the query with n tokens of context) as far as the query's own
    position: a set of page ids a KV head (this file's arithmetic)."""
    import numpy as np

    cfg = engine.model_cfg
    ps = cfg.kernel_stride
    at = np.arange(-(-n // ps))  # the pages the context reaches
    return [set(np.asarray(pages)[at[np.asarray(selection[g])[
        at * ps // cfg.block_size]]].tolist())
        for g in range(selection.shape[0])]


def served_attention(engine, pages: list, q, n: int, selection):
    """What the ENGINE's decode step computes in its sparse layers for the
    query with n tokens of context, by the step's own pieces in its order
    over the engine's own pools: ``block_sparse.lists_from`` (the lists of
    pages, from the selection HANDED IN, the reference's, so that no near
    tie flips) and ``paged_decode_attention(heads_apart=True)``, the kernel
    that walks them.  q [sparse layers, H, d] (the reference's queries),
    selection [sparse layers, G, M] bool; the sequence holds ``pages``.
    Returns [sparse layers, H, d] float32."""
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import block_sparse, paged_attention

    cfg = engine.model_cfg
    table = jnp.asarray(_table(engine, pages))[None]
    M = table.shape[1] * cfg.kernel_stride // cfg.block_size
    ctx = jnp.asarray([n], jnp.int32)
    out = []
    for li in range(q.shape[0]):
        # the selection as ``block_sparse.select`` lists it: the blocks in
        # ascending order, M past the last, and their number
        sel = np.asarray(selection[li])
        blocks = np.full((sel.shape[0], cfg.topk), M, np.int32)
        for g, row in enumerate(sel):
            chosen = np.nonzero(row)[0][:cfg.topk]
            blocks[g, :len(chosen)] = chosen
        count = np.minimum(sel.sum(axis=-1), cfg.topk).astype(np.int32)
        lists, held = block_sparse.lists_from(
            cfg, jnp.asarray(blocks)[None], jnp.asarray(count)[None], table,
            ctx, block_sparse.list_width(cfg, table.shape[1]))
        out.append(paged_attention.paged_decode_attention(
            q[li][None].astype(engine.cache_k.dtype), engine.cache_k,
            engine.cache_v, lists, held, li, heads_apart=True)[0])
    return jnp.stack(out).astype(jnp.float32)


def engine_state(engine, slot: int):
    """The linear layers' state rows of ``slot``: [linear layers, H, d, d]
    float32."""
    return engine.state["S"][:, slot]
