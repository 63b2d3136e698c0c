"""Which cached blocks a query attends to: InfLLM-v2's selection (MiniCPM4,
arXiv:2506.07900) in plain ``jax.numpy``, for the prefills and the decode
step alike.

Sizes (``sp``: anything with these attributes; models/minicpm_sala.py's
configuration): a POOLED KEY is the mean of ``kernel_size`` keys and there
is one every ``kernel_stride`` positions, row j over positions
``[stride j, stride j + kernel_size)``, complete once the context holds its
last key; a BLOCK is ``block_size`` positions.  For the query at position
t, n = t + 1 tokens of context, a KV head at a time (its query heads choose
together):

- n <= ``dense_len``: every block (dense causal attention);
- else p_j = sum over the group's heads of softmax_j(q . c_j / sqrt(d)) over
  the complete rows; a block scores the max of p over the rows that overlap
  it (-1 where none is complete); selected are the first ``init_blocks``
  blocks, every block that overlaps the last ``window_size`` positions,
  and the highest scores among the rest until ``topk`` in all, the LOWER
  index first among equals (neighbouring blocks share a row, so equals are
  common).

``pool_keys`` makes the rows, ``block_scores`` and ``select`` the choice as
a mask over blocks (a prefill's block mask) and ``page_lists`` as the pages
a decode step's kernel walks (``paged_decode_attention`` with
``heads_apart``): the selected blocks' pages in order with a count, so that
a slot under ``dense_len`` (all its pages) and one past it (``topk``
blocks') go through one list.  ``selected_attention`` is the prefills'
attention under the chosen blocks' mask (``masks`` the choice,
``attend_under`` the attention, keys a block at a time); ``walked`` counts
what a step's lists held, ``rows_complete`` the pooled rows a context
holds: the engine's counters are these, made on the device from what the
kernel is handed, and nobody re-derives the rule to count by.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

# positions a key block of ``attend_under`` holds at most
KEY_BLOCK = 512

_FORCED, _OUT = 1e4, -1e4  # a score is in [-1, heads a group]


def check_sizes(sp, page_size: int) -> None:
    """The sizes this module and the pooled rows' cache take."""
    if (sp.kernel_stride != page_size or sp.kernel_size != 2 * page_size
            or sp.block_size % sp.kernel_stride):
        raise ValueError(
            f"the pooled keys are cached a row a page: kernel_stride "
            f"({sp.kernel_stride}) must be page_size ({page_size}), "
            f"kernel_size ({sp.kernel_size}) two pages and block_size "
            f"({sp.block_size}) whole pages")
    forced = sp.init_blocks + -(-sp.window_size // sp.block_size) + 1
    if forced > sp.topk or sp.dense_len < sp.topk * sp.block_size:
        raise ValueError(
            f"topk ({sp.topk}) must hold the {forced} blocks that are "
            f"always selected, and dense_len ({sp.dense_len}) topk blocks "
            f"of {sp.block_size}")


def pool_keys(sp, k):
    """k [T, G, d], T whole strides -> rows [T / stride, G, d] float32: row
    j the mean of k[stride j : stride j + kernel_size] (zeros past T, so
    the last ``kernel_size / stride - 1`` rows are not complete)."""
    T = k.shape[0]
    s, w = sp.kernel_stride, sp.kernel_size // sp.kernel_stride
    part = k.astype(jnp.float32).reshape(T // s, s, *k.shape[1:]).sum(axis=1)
    part = jnp.pad(part, ((0, w - 1),) + ((0, 0),) * (part.ndim - 1))
    return sum(part[i:i + T // s] for i in range(w)) / sp.kernel_size


def block_scores(sp, q, rows, n):
    """q [Q, H, d]; rows [J, G, d] pooled keys (J whole blocks' worth);
    n [Q] each query's context.  Returns b [Q, G, M] float32, M = J
    strides / block: a block's score, -1 where no row over it is
    complete."""
    Q, H, d = q.shape
    J, G, _ = rows.shape
    r, w = (sp.block_size // sp.kernel_stride,
            sp.kernel_size // sp.kernel_stride)
    done = (jnp.arange(J) * sp.kernel_stride + sp.kernel_size
            <= n[:, None])  # [Q, J]
    s = jnp.einsum("qghd,jgd->qghj", q.reshape(Q, G, H // G, d).astype(
        jnp.float32), rows.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST) * d ** -0.5
    s = jnp.where(done[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).sum(axis=2)  # [Q, G, J]
    p = jnp.where(done[:, None, :], p, -1.0).reshape(Q, G, J // r, r)
    # the rows that overlap block m: its own r, and the w - 1 before them
    own = p.max(axis=-1)
    if w == 1:
        return own
    before = jnp.pad(p[:, :, :-1, r - w + 1:].max(axis=-1),
                     ((0, 0), (0, 0), (1, 0)), constant_values=-1.0)
    return jnp.maximum(own, before)


def _ordered(score):
    """float32 -> uint32 in the same order (-0.0 beside 0.0)."""
    u = jax.lax.bitcast_convert_type(jnp.where(score == 0, 0.0, score),
                                     jnp.uint32)
    return jnp.where(u >> 31 == 1, ~u, u | jnp.uint32(1 << 31))


@partial(jax.jit, static_argnames="k")
def _kth_largest(u, k: int):
    """u [..., M] uint32 -> [...]: the largest t with at least k of u at or
    over it (0 where M < k), two bits a round from the top: a counting pass
    over M for each of a round's three candidates, which fewer of u reach
    the higher they are, so the candidates that k reach are the round's
    digit.  (A prefill's masks, a layer a chunk of 2,048 over 400 blocks:
    every block ranked against every other 1.87 ms; one bit a round in a
    ``fori_loop`` 2.83, unrolled 0.88; two bits 0.76; four 0.81: my chip
    run, PR 50.)  A program of its own only for a caller outside any (a
    test, the benchmark's check): one dispatch, not sixteen rounds of
    operations; inside a program it is inlined."""
    t = jnp.zeros(u.shape[:-1], jnp.uint32)
    for shift in range(30, -1, -2):
        higher = t[..., None] | (jnp.arange(1, 4, dtype=jnp.uint32) << shift)
        reached = (u[..., None, :] >= higher[..., None]).sum(axis=-1) >= k
        t = t | (reached.sum(axis=-1).astype(jnp.uint32) << shift)
    return t


def chosen(sp, b, n):
    """b [Q, G, M] block scores, n [Q] contexts -> [Q, G, M] bool: the
    blocks the rule selects (forced ones, then the highest scores, the
    lower index first among equals, until ``topk``).  Under ``dense_len``
    the choice is not made here (every block is selected): the callers
    branch on n.

    The choice is made by a THRESHOLD: the ``topk``-th largest score is
    found by counting (``_kth_largest``: 48 passes over M), every block
    over it is selected, and of the blocks AT it the lowest indices until
    ``topk`` in all (a running count).  The work grows with M, whatever M
    is.  What was measured (PERF.md section 6, PRs 49 and 50), part
    ``sparse_attn/index`` of two sparse layers, 400 blocks: by
    ``lax.top_k``, which the TPU lowers to a full sort, two sorts a layer,
    2.65 ms a decode step at 32 slots; every block ranked against every
    other, M^2 comparisons, 2.19 ms a step and 3.51 ms a chunk of 2,048;
    this form 2.15 ms a step and 1.43 ms a chunk.  A decode step's part
    was never the choice (25 us of a layer's 951 were the ranking) but two
    gathers, each paid by the INDEX: a slot's pooled rows through its table
    (51,200 rows, 686 us a layer) and a list's page ids one by one (16,384,
    166 us).  Since PR 52 the step reads the rows where they lie, in slot
    order (llm/paged_cache.py ``CacheConfig``), and ``lists_from`` gathers
    a block's entries an index: 912 -> 138 us a layer timed apart."""
    M = b.shape[-1]
    m = jnp.arange(M)
    n = n[:, None, None]
    forced = (m < sp.init_blocks) | ((m + 1) * sp.block_size
                                     > n - sp.window_size)
    score = jnp.where(forced, _FORCED, b)
    score = jnp.where(m * sp.block_size < n, score, _OUT)
    u = _ordered(score)
    least = _kth_largest(u, sp.topk)[..., None]
    over, at = u > least, u == least
    room = sp.topk - over.sum(axis=-1, keepdims=True)
    return ((over | (at & (jnp.cumsum(at, axis=-1) <= room)))
            & (score > _OUT / 2))


def block_mask(sp, q, rows, n):
    """[Q, G, M] bool: the blocks each query's KV heads attend to, every
    block at or under ``dense_len`` (causality is the caller's)."""
    return (chosen(sp, block_scores(sp, q, rows, n), n)
            | (n <= sp.dense_len)[:, None, None])


def select(sp, b, n):
    """``chosen`` as a LIST: (blocks [Q, G, topk] int32 in ascending
    order, M where fewer are selected; count [Q, G])."""
    M = b.shape[-1]
    picked = chosen(sp, b, n)
    place = jnp.cumsum(picked, axis=-1) - 1  # a chosen block's place
    hit = picked[..., None, :] & (
        place[..., None, :] == jnp.arange(min(sp.topk, M))[:, None])
    blocks = jnp.where(hit.any(axis=-1),
                       (hit * jnp.arange(M)).sum(axis=-1), M)
    return blocks.astype(jnp.int32), picked.sum(axis=-1).astype(jnp.int32)


def lists_from(sp, blocks, count, tables, n, width: int):
    """The lists of a decode step from the selection as ``select`` gives it
    (blocks [B, G, K] ascending, count [B, G]); ``page_lists`` says what
    comes back."""
    B, P = tables.shape
    G = blocks.shape[1]
    ppb = sp.block_size // sp.kernel_stride  # pages a block
    if P % ppb:
        raise ValueError(
            f"a page table of {P} entries is no whole number of blocks of "
            f"{ppb} pages: a list is gathered a block's entries at a time")
    # a block's pages are adjacent entries of the table: ONE index a block
    # (an index at a time is what a gather costs, 10 ns each on the chip:
    # K x ppb of them a list were 166 us a layer a step, PERF.md section
    # 6, PR 52); the sentinel M and whatever lies past the table give 0
    sparse = jax.vmap(partial(jnp.take, axis=0, mode="clip"))(
        tables.reshape(B, P // ppb, ppb), blocks)  # [B, G, K, ppb]
    sparse = jnp.where((blocks < P // ppb)[..., None], sparse,
                       0).reshape(B, G, -1)
    short = width - sparse.shape[-1]
    sparse = (jnp.pad(sparse, ((0, 0), (0, 0), (0, short))) if short >= 0
              else sparse[..., :width])
    dense = jnp.pad(tables, ((0, 0), (0, max(0, width - P))))[:, None, :width]
    under = (n <= sp.dense_len)[:, None]
    # the last block selected holds the query's own position
    held = (count - 1) * sp.block_size + ((n - 1) % sp.block_size + 1)[:, None]
    lengths = jnp.where(under, n[:, None], held)
    return (jnp.where(under[..., None], dense, sparse).astype(jnp.int32),
            jnp.where((n > 0)[:, None], lengths, 0).astype(jnp.int32))


def page_lists(sp, q, rows, tables, n, width: int):
    """A decode step's lists.  q [B, H, d] one query a slot; rows [B, J, G,
    d] each slot's pooled keys through its table; tables [B, P] page ids;
    n [B] contexts (0: the slot is not live).  Returns (lists [B, G, width]
    page ids, the selected blocks' pages in order, the table's own first
    ``width`` at or under ``dense_len``; lengths [B, G]: the positions the
    list holds, 0 for a slot that is not live)."""
    score = jax.vmap(lambda q, rows, n: block_scores(
        sp, q[None], rows, n[None])[0])(q, rows, n)  # [B, G, M]
    blocks, count = jax.vmap(lambda b, n: tuple(
        x[0] for x in select(sp, b[None], n[None])))(score, n)
    return lists_from(sp, blocks, count, tables, n, width)


def list_width(sp, P: int) -> int:
    """Entries a step's list has for a table of P pages: the pages of
    ``dense_len`` or of ``topk`` blocks, whichever is more."""
    return min(P, max(sp.dense_len, sp.topk * sp.block_size)
               // sp.kernel_stride)


def walked(sp, lengths, n):
    """What a decode step's lists held, from what the kernel is handed
    (lengths [B, G] of ``page_lists``, n [B] the contexts): int32 scalars
    by the engine's counter names, each a sum over live slots and KV heads
    of ONE sparse layer.  A list holds its slot's whole context exactly
    when the dense rule made it (``check_sizes``: ``topk`` blocks are fewer
    positions than ``dense_len``)."""
    ps, G = sp.kernel_stride, lengths.shape[1]
    return {"sparse_blocks_selected": (-(-lengths // sp.block_size)).sum(),
            "sparse_pages_read": (-(-lengths // ps)).sum(),
            "sparse_pages_resident": G * (-(-n // ps)).sum(),
            "dense_rule_slot_steps":
                ((n > 0) & (lengths[:, 0] == n)).sum().astype(jnp.int32)}


def rows_complete(sp, n):
    """Rows of pooled keys a context of n positions completes."""
    return jnp.maximum(
        n // sp.kernel_stride - (sp.kernel_size // sp.kernel_stride - 1), 0)


def masks(sp, q, positions, rows, T: int):
    """``block_mask`` of q [L, H, d] at ``positions`` [L] over rows [T /
    stride, G, d], 256 queries at a time ([queries, H, rows] scores): [L,
    G, T / block] bool."""
    L, H, d = q.shape
    qb = math.gcd(L, 256)
    return jax.lax.map(
        lambda a: block_mask(sp, a[0], rows, a[1]),
        (q.reshape(L // qb, qb, H, d),
         (positions + 1).reshape(L // qb, qb))).reshape(
             L, rows.shape[1], T // sp.block_size)


def attend_under(sp, q, positions, picked, keys_of, T: int, ends):
    """Attention of q [L, H, d] at ``positions`` [L] over the blocks
    ``picked`` [L, G, T / block] allows each query and KV head, causal
    inside them.  ``keys_of(at, n) -> (k, v)`` [n, G, d]: the keys at
    positions [at, at + n); T (static): positions the keys span, whole
    blocks; ``ends``: one past the last position any query sees.  The keys
    come a block of ``KEY_BLOCK`` positions at a time under a running
    softmax, as far as ``ends``: scores are [H, L, KEY_BLOCK] at any
    instant."""
    L, H, d = q.shape
    G, bs = picked.shape[1], sp.block_size
    rep, f32 = H // G, jnp.float32
    kb = math.gcd(T, KEY_BLOCK)
    kb = kb if kb % bs == 0 else T
    qg = q.reshape(L, G, rep, d)

    def block(i, carry):
        m, l, acc = carry
        k, v = keys_of(i * kb, kb)
        s = jnp.einsum("qgrd,kgd->grqk", qg, k.astype(q.dtype),
                       preferred_element_type=f32) * d ** -0.5
        seen = jnp.repeat(jax.lax.dynamic_slice_in_dim(
            picked, i * (kb // bs), kb // bs, axis=2), bs, axis=2)
        seen &= (i * kb + jnp.arange(kb))[None, None, :] \
            <= positions[:, None, None]
        seen = seen.transpose(1, 0, 2)[:, None]  # [G, 1, L, kb]
        s = jnp.where(seen, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + p.sum(axis=-1, keepdims=True),
                acc * alpha + jnp.einsum(
                    "grqk,kgd->grqd", p.astype(v.dtype), v,
                    preferred_element_type=f32))

    # block 0 holds position 0, which every query attends to (the first
    # block is always selected), so l > 0 from the first on
    _, l, acc = jax.lax.fori_loop(
        0, jnp.clip((ends + kb - 1) // kb, 1, T // kb), block,
        (jnp.full((G, rep, L, 1), -1e30, f32),
         jnp.zeros((G, rep, L, 1), f32), jnp.zeros((G, rep, L, d), f32)))
    return (acc / l).transpose(2, 0, 1, 3).reshape(L, H, d).astype(q.dtype)


def selected_attention(sp, q, positions, rows, keys_of, T: int, ends):
    """A prefill's attention over the blocks the rule selects for each
    query and KV head: ``masks`` (part ``sparse_attn/index``) then
    ``attend_under`` (``sparse_attn/attend``); rows [T / stride, G, d] the
    sequence's pooled keys."""
    with jax.named_scope("sparse_attn/index"):
        picked = masks(sp, q, positions, rows, T)
    with jax.named_scope("sparse_attn/attend"):
        return attend_under(sp, q, positions, picked, keys_of, T, ends)
