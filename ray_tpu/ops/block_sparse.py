"""Which cached blocks a query attends to: InfLLM-v2's selection (MiniCPM4,
arXiv:2506.07900) in plain ``jax.numpy``, for the prefills and the decode
step alike, and the prefills' attention under it, a Pallas kernel.

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
attention under the chosen blocks' mask: ``masks`` the choice,
``attend_under`` the attention, a kernel gridded over (KV head, tile of
queries, tile of keys) whose score tile, the KV head's query heads as rows
of ONE product, lives in VMEM and nowhere else (in ``jax.numpy`` a turn's
[G, rep, L, 512] float32 scores went to HBM and back three times: 0.56 ms
a turn of 512 keys at a seventh of the MXU's rate, my chip run, PR 55).
The mask of a tile is made inside it from ``picked`` and ``positions``;
``tile_table`` lists, from the same two, the key tiles a query tile works
on, and the others (over the diagonal, past ``ends``, or with no block
that a query of the tile both sees and selected) get no copy and no
product.  ``walked`` counts what a step's lists held, ``rows_complete``
the pooled rows a context holds, ``selected_attention``'s second return
the tiles of that table: the engine's counters are these, made on the
device from what the kernel is handed, and nobody re-derives the rule to
count by.
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import _LANES, NEG_INF, _dot

# positions a tile of keys of ``attend_under`` holds at most, and rows (a
# tile's queries x the query heads of a KV head) a tile of its scores.  At
# 2,048 x 1,024 the float32 scores are 8 MB, and with ``p``, the operands'
# double buffers and the accumulators the kernel needs 20 MB, more than the
# 16 MiB it gets by default (a v5e core has 128 MiB of VMEM).  A grid step
# costs ~3 us whatever its keys (the accumulators' rescaling, the row
# statistics) and 5.3 us a 1,024 keys, the MXU's own rate: a layer a chunk
# of 2,048 behind 6,144 took 6.06 / 3.47 / 2.46 ms at 256 / 512 / 1,024
# keys, 3.99 / 3.47 / 3.12 at 1,024 / 2,048 / 4,096 rows of 512 (my chip
# run, PR 55; PERF.md section 6)
KEY_BLOCK = 1024
_TILE_ROWS = 2048
VMEM_LIMIT_BYTES = 32 * 1024 * 1024
_M_FLOOR = -1e29  # where a row's running max begins: over a masked score

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


def _tile_sizes(L: int, T: int, rep: int, bs: int) -> tuple[int, int]:
    """(queries, keys) of a score tile for L queries over T key positions:
    powers of two that divide them, as many queries as keep the group's
    ``rep`` heads within ``_TILE_ROWS`` rows of one product, as many whole
    blocks of ``bs`` keys as ``KEY_BLOCK`` holds (and one lane tile of
    ``picked`` has: a tile's blocks lie in one)."""
    def power(n):  # the largest power of two at or under n
        return 1 << max(n, 1).bit_length() - 1

    return (math.gcd(L, power(_TILE_ROWS // rep)),
            math.gcd(T // bs, power(min(KEY_BLOCK // bs, _LANES))) * bs)


def tile_table(sp, positions, picked, ends, tq: int, tk: int):
    """What the kernel is handed: for each KV head and tile of ``tq``
    queries, the tiles of ``tk`` keys it works on.  A key tile is REACHED
    where a query of the tile sees one of its positions (at or under its
    own, under ``ends``), VISITED where one also selected the block it lies
    in.  Returns (tiles [G, L / tq, T / tk] int32: the visited tiles in
    ascending order, then the last of them again; count [G, L / tq]: how
    many; reached [L / tq])."""
    L, G, M = picked.shape
    nb = tk // sp.block_size
    n_q, n_k = L // tq, M // nb
    first = jnp.arange(M) * sp.block_size  # a block's first position
    seen = (first <= positions[:, None]) & (first < ends)  # [L, M]
    reached = seen.reshape(n_q, tq, n_k, nb).any(axis=(1, 3)).sum(axis=-1)
    visited = (picked & seen[:, None]).reshape(
        n_q, tq, G, n_k, nb).any(axis=(1, 4)).transpose(1, 0, 2)
    before = jnp.cumsum(visited, axis=-1)  # visited tiles up to each
    count = before[..., -1]
    step = jnp.minimum(jnp.arange(n_k), jnp.maximum(count[..., None] - 1, 0))
    # the tile of step s: as many tiles as have at most s visited up to them
    tiles = (before[..., None, :] <= step[..., None]).sum(axis=-1)
    return jnp.minimum(tiles, n_k - 1), count, reached


def _attend_kernel(tiles_ref, count_ref, ends_ref, q_ref, pos_ref, pick_ref,
                   k_ref, v_ref, o_ref, qs_ref, acc_ref, m_ref, l_ref, *,
                   bs: int, sm_scale: float):
    """One (KV head, query tile, step) of the running softmax.  The group's
    heads are ROWS of the one product, head-major (row r tq + i is query i
    of head r), so the mask of a tile is made once, [tq, tk], and every
    head's scores take it as they are."""
    g, i, s = (pl.program_id(a) for a in range(3))
    n_q, n_k = pl.num_programs(1), pl.num_programs(2)
    rep, tq, d = acc_ref.shape
    tk = k_ref.shape[0]
    at = g * n_q + i

    @pl.when(s == 0)
    def _init():
        for r in range(rep):
            qs_ref[r * tq:(r + 1) * tq] = q_ref[:, r * d:(r + 1) * d]
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _M_FLOOR)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(s < count_ref[at])
    def _fold():
        j = tiles_ref[at * n_k + s]
        # a query's choice of the tile's blocks, spread over their keys by
        # a product: pick_ref holds the lane tile of blocks this key tile's
        # lie in, one number a query and block
        lane, col = (jax.lax.broadcasted_iota(jnp.int32, (_LANES, tk), a)
                     for a in (0, 1))
        spread = (lane == (j * (tk // bs)) % _LANES + col // bs)
        picked = _dot(pick_ref[...], spread.astype(pick_ref.dtype), 0)
        key = j * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        keep = (picked > 0.5) & (key <= pos_ref[...]) & (key < ends_ref[0])
        v = v_ref[...]
        scores = (_dot(qs_ref[...], k_ref[...], 1) * sm_scale).reshape(
            rep, tq, tk) + jnp.where(keep, 0.0, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, scores.max(axis=-1, keepdims=True))
        p = jnp.exp(scores - m_new)  # 0 where masked: m_new >= _M_FLOOR
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(
            p.astype(v.dtype).reshape(rep * tq, tk), v, 0).reshape(
                rep, tq, d)
        m_ref[...] = m_new

    @pl.when(s == n_k - 1)
    def _finish():
        l = l_ref[...]
        out = acc_ref[...] / jnp.where(l == 0.0, 1.0, l)
        for r in range(rep):
            o_ref[:, r * d:(r + 1) * d] = out[r].astype(o_ref.dtype)


@partial(jax.jit, static_argnames=("sp", "tile", "interpret"))
def _attend(sp, q, positions, picked, k, v, ends, *, tile: tuple[int, int],
            interpret: bool):
    """``attend_under`` over the keys whole (k, v [T, G, d]) in score
    tiles of ``tile`` (queries, keys) -> (out, the tiles it worked on by
    counter name)."""
    L, H, d = q.shape
    T, G, _ = k.shape
    rep, bs = H // G, sp.block_size
    tq, tk = tile
    n_q, n_k = L // tq, T // tk
    tiles, count, reached = tile_table(sp, positions, picked, ends, tq, tk)
    # a query's choice a KV head as numbers, whole lane tiles of blocks
    pick = jnp.pad(picked.transpose(1, 0, 2).astype(jnp.bfloat16),
                   ((0, 0), (0, 0), (0, -picked.shape[2] % _LANES)))

    def key_tile(g, i, s, tiles_ref, *_):
        return tiles_ref[(g * n_q + i) * n_k + s]

    def queries(g, i, s, *_):
        return i, g

    def keys(g, i, s, *refs):
        return key_tile(g, i, s, *refs), g

    out = pl.pallas_call(
        partial(_attend_kernel, bs=bs, sm_scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(G, n_q, n_k),
            in_specs=[
                pl.BlockSpec((tq, rep * d), queries),
                pl.BlockSpec((tq, 1), lambda g, i, s, *_: (i, 0)),
                pl.BlockSpec((None, tq, _LANES), lambda g, i, s, *refs: (
                    g, i, key_tile(g, i, s, *refs) * (tk // bs) // _LANES)),
                pl.BlockSpec((tk, d), keys),
                pl.BlockSpec((tk, d), keys),
            ],
            out_specs=pl.BlockSpec((tq, rep * d), queries),
            scratch_shapes=[pltpu.VMEM((rep * tq, d), q.dtype),
                            pltpu.VMEM((rep, tq, d), jnp.float32),
                            pltpu.VMEM((rep, tq, 1), jnp.float32),
                            pltpu.VMEM((rep, tq, 1), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((L, H * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="sparse_prefill_attention",
    )(tiles.reshape(-1), count.reshape(-1),
      jnp.asarray(ends, jnp.int32).reshape(1), q.reshape(L, H * d),
      positions.astype(jnp.int32)[:, None], pick,
      k.astype(q.dtype).reshape(T, G * d), v.reshape(T, G * d))
    return out.reshape(L, H, d), {
        "sparse_prefill_tiles_causal": G * reached.sum(),
        "sparse_prefill_tiles_visited": count.sum()}


def _attend_over(sp, q, positions, picked, keys_of, T: int, ends):
    """``attend_under`` and what its kernel's table counted."""
    k, v = keys_of(0, T)
    return _attend(
        sp, q, positions, picked, k, v, ends,
        tile=_tile_sizes(q.shape[0], T, q.shape[1] // k.shape[1],
                         sp.block_size),
        interpret=jax.default_backend() != "tpu")


def attend_under(sp, q, positions, picked, keys_of, T: int, ends):
    """Attention of q [L, H, d] at ``positions`` [L] over the blocks
    ``picked`` [L, G, T / block] allows each query and KV head, causal
    inside them and under ``ends``, one past the last position any query
    sees.  ``keys_of(at, n) -> (k, v)`` [n, G, d]: the keys at positions
    [at, at + n), taken once, whole (T, static: the positions they span,
    whole blocks).  A Pallas kernel (``_attend_kernel``) under a running
    softmax: a tile of scores lives in fast memory and nowhere else, and a
    tile of keys that no query of a tile of queries both sees and selected
    is neither copied nor multiplied (``tile_table``)."""
    return _attend_over(sp, q, positions, picked, keys_of, T, ends)[0]


def selected_attention(sp, q, positions, rows, keys_of, T: int, ends):
    """A prefill's attention over the blocks the rule selects for each
    query and KV head: ``masks`` (part ``sparse_attn/index``) then
    ``attend_under`` (``sparse_attn/attend``); rows [T / stride, G, d] the
    sequence's pooled keys.  Returns (out, counted): beside the attention,
    int32 sums over the kernel's own table of tiles by the engine's counter
    names, ``sparse_prefill_tiles_causal`` (key tiles a query tile's causal
    bound and ``ends`` reach, a KV head) and
    ``sparse_prefill_tiles_visited`` (those of them in which a query
    selected a block it sees: the ones the kernel works on)."""
    with jax.named_scope("sparse_attn/index"):
        picked = masks(sp, q, positions, rows, T)
    with jax.named_scope("sparse_attn/attend"):
        return _attend_over(sp, q, positions, picked, keys_of, T, ends)
