"""Paged decode attention for TPU: one query token a slot, read from the
page pool where it lies.  Two kernels: ``paged_decode_attention`` over K and
V pools (the first part of this file and of this text) and, at the end,
``paged_latent_decode_attention`` over ONE pool of latent rows that are key
and value both (models/glm_moe_lite.py), the same walk with one copy a page.

The decode step's attention used to gather every slot's whole page table
into a dense ``[B, P * page_size, n_kv_heads, d]`` K and V, repeat both to
the query heads' width and mask what was not there.  This kernel walks each
slot's page table only as far as the slot's length, copies those pages
straight out of the pool (``[n_layers, num_pages, page_size, n_kv_heads,
head_dim]``, the layer indexed here, so no per-layer slice of the pool is
ever made), and keeps a running float32 softmax over blocks of pages.

One kernel invocation serves the whole batch.  Lengths, page tables and the
layer ride in SMEM (scalar prefetch); q and the output sit whole in VMEM
(``B * H * d`` elements each); the pools stay in HBM.  A compute block is
``pages_per_block`` pages: a page is one contiguous ``page_size * n_kv_heads
* head_dim`` run of the pool (32 KB at 16 x 8 x 128 bf16), too small to
hide a copy's latency behind its own compute, so a block's pages are copied
together into one of two VMEM buffers while the other is being computed on,
and the prefetch runs across slot boundaries (the last block of a slot
starts the first block of the next active one).

WHAT A COPY COSTS.  A page copy costs the kernel's scalar core the same
whatever it moves: two predicates ~15 ns, the issue of its descriptor ~12 ns,
its wait ~3 ns (a v5e, ``time_paged_walk.py``; PERF.md section 6, PR 60),
and that time is not hidden behind the block's products but ADDED to them
(the walk with its products taken out and with its copies taken out sum to
the whole walk; only the bytes' flight is hidden).  ~33 ns is what the
memory needs for ~24-32 KB: a pool of 32 KB pages pays about its bytes, a
pool of smaller ones (4 or 2 KV heads, latent rows) pays for the COUNT of
its copies and predicates.  So a pool whose page is under ``COPY_BYTES``
walks the same pages in fewer copies, predicates and waits
(``_run_copies``): a block that is reached whole issues its copies in a
straight line and waits ONCE a pool, and where every aligned group of
``RUN_PAGES`` table entries in it is page ids in a row (one contiguous
stretch of the pool) a group moves in one copy (``block_kinds`` tests the
table in the program, before the kernel, and a number a block rides in as
one more prefetched array).  A prompt's pages are ids in a row because the
allocator goes round its free list in page order (llm/paged_cache.py).  A
pool of larger pages keeps the page-by-page program text for text.

Pages past a slot's length
are neither copied nor computed; a slot of length 0 is skipped and returns
zeros.  With a ``window`` (a layer that sees the last ``window`` positions
only, models/afmoe.py) the walk also has a LOWER bound: it begins at the
block that holds position ``max(0, length - window)``, the pages before that
position's are neither copied nor scored, and the rows before it inside its
page are masked.  Without one the program is the one it was.

Grouped-query attention without a repeat: a block's K lies in VMEM as
``[tokens * n_kv_heads, d]`` rows (the pool's own order), all H query heads
multiply all of it in one MXU call, and the columns of another KV head are
masked out with the columns past the length.  ``n_kv_heads`` times the
useful multiply-adds, on a kernel that is bound by its copies: one K/V page
read serves every query head of every group.

Off the TPU the same kernel runs through the Pallas interpreter, as
``flash_attention`` does.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.attention import NEG_INF

# tokens a compute block holds unless the caller says otherwise: on a v5e,
# 16-token pages of 8 KV heads x 128 read at 419 / 574 / 666 / 643 GB/s in
# blocks of 4 / 8 / 16 / 32 pages (PERF.md, PR 25), i.e. 0.63 / 0.91 / 1.57 /
# 3.26 us a block of 8 / 16 / 32 / 64 copies: ~0.3 us a block and ~40 ns a
# copy until, at 2 MB, the bytes take over
BLOCK_TOKENS = 256

# The crossover: the page whose bytes take the memory as long as a copy takes
# the kernel's scalar core (two predicates, the issue and the wait a page: 33
# ns, (1.958 - 0.911) us over the 32 copies of a Trinity block with and
# without its copies; 32 KB take 39-47 ns at the 819-685 GB/s the memory
# gives; Mistral's 1 MB block, page by page, reads 1.63 us where its bytes
# alone take 1.41: ``time_paged_walk.py``, PERF.md section 6, PR 60).
# A pool whose page is UNDER it is bound by the count of its copies and moves
# RUN_PAGES adjacent pages in one wherever its table names them in a row; a
# page at or over it costs its bytes already and keeps the page-by-page
# program.  Derived from the timing, read from the pool's shape: no argument,
# no environment variable, no field of a configuration.
COPY_BYTES = 32 * 1024
RUN_PAGES = 4


def walk_blocks(pool_shape, itemsize: int, table_width: int,
                pages_per_block: int | None = None) -> tuple:
    """(pages a block, pages a copy may move) of a walk, read from the
    pool's shape (``[layers, pages, page_size, ...]``, K and V or latent
    rows).  The second is 1, the page-by-page program, for a page at or
    over ``COPY_BYTES`` and where groups of ``RUN_PAGES`` do not tile a
    block; else ``RUN_PAGES``."""
    ps = pool_shape[2]
    if pages_per_block is None:
        tokens = LATENT_BLOCK_TOKENS if len(pool_shape) == 4 else BLOCK_TOKENS
        pages_per_block = max(1, tokens // ps)
    ppb = min(pages_per_block, table_width)
    small = math.prod(pool_shape[2:]) * itemsize < COPY_BYTES
    return ppb, RUN_PAGES if small and ppb % RUN_PAGES == 0 else 1


def blocks_in_runs(page_tables, ppb: int, run: int):
    """[B, P // ppb] bool: whether every aligned group of ``run`` entries in
    a whole block of a table [B, P] is page ids in a row (``p, p + 1, ..``:
    one contiguous stretch of the pool), so that the block moves in ``ppb
    // run`` copies.  Operators only: the host's numpy tables (llm/engine.py
    counts what a burst will find) and the program's traced ones go through
    the same test."""
    B, P = page_tables.shape
    n = P // ppb
    t = page_tables[:, :n * ppb].reshape(B, n, ppb // run, run)
    return (t[..., 1:] - t[..., :-1] == 1).all(axis=(-1, -2))


def block_kinds(page_tables, lengths, starts, page_size: int, ppb: int,
                run: int):
    """[B, P // ppb] int: how a walk copies each whole block of a table: 0
    page by page, each page under its predicate (an EDGE: the slot's length
    ends inside the block, or its start under a window lies inside it or
    past it); 1 reached whole: its ``ppb`` copies in a straight line and
    one wait; 2 reached whole and every group a run (``blocks_in_runs``):
    ``ppb // run`` copies.  Operators only, as ``blocks_in_runs``."""
    first = ppb * np.arange(page_tables.shape[1] // ppb)
    whole = first + ppb <= ((lengths + page_size - 1) // page_size)[:, None]
    if starts is not None:
        whole &= first >= (starts // page_size)[:, None]
    return whole * (1 + blocks_in_runs(page_tables, ppb, run))


def _page_copies(lengths_ref, starts_ref, tables_ref, layer, moves, P: int,
                 ppb: int, ps: int):
    """``block_copies(b, blk, buf, wait)``: start (or wait for) the copies
    of block ``blk`` of slot ``b`` into buffer ``buf``, a predicate, a copy
    and a wait a page: only the pages the slot's length reaches (and, with
    ``starts_ref``, none that lies wholly before its start).  ``moves``:
    (pool, buffers, semaphore of a buffer) a pool."""
    def block_copies(b, blk, buf, wait: bool):
        n_pages = (lengths_ref[b] + ps - 1) // ps
        for i in range(ppb):
            pg = blk * ppb + i
            reached = pg < n_pages
            if starts_ref is not None:
                reached &= pg >= starts_ref[b] // ps

            @pl.when(reached)
            def _():
                # a wait needs the copy's shape and semaphore, not its source
                page = 0 if wait else tables_ref[b * P + pg]
                for pool, dst, sem in moves:
                    copy = pltpu.make_async_copy(
                        pool.at[layer, page], dst.at[buf, i], sem(buf))
                    copy.wait() if wait else copy.start()

    return block_copies


def _run_copies(lengths_ref, starts_ref, tables_ref, kinds_ref, layer, moves,
                P: int, ppb: int, ps: int, run: int):
    """``_page_copies`` for a pool of small pages (``walk_blocks``): the
    same pages in fewer copies, predicates and waits.  ``kinds_ref`` says
    of each block (``block_kinds``) whether it is reached whole (all of a
    walk but its first block under a window and its last): such a block
    starts its copies in a straight line, ``run`` pages a copy where every
    group of it is a run, and waits ONCE a pool for the buffer's bytes; an
    edge block is ``_page_copies``'s.  Few and flat branches: a predicate
    costs the kernel about what a copy does (PERF.md, PR 60)."""
    edge = _page_copies(lengths_ref, starts_ref, tables_ref, layer, moves, P,
                        ppb, ps)
    n_whole = P // ppb  # blocks ``kinds_ref`` knows; a later one is an edge

    def copy(move, buf, page, i, n):
        pool, dst, sem = move
        return pltpu.make_async_copy(pool.at[layer, pl.ds(page, n)],
                                     dst.at[buf, pl.ds(i, n)], sem(buf))

    def block_copies(b, blk, buf, wait: bool):
        kind = jnp.where(
            blk < n_whole,
            kinds_ref[b * n_whole + jnp.minimum(blk, n_whole - 1)], 0)

        def straight(n):  # the block's copies, ``n`` pages each
            for i in range(0, ppb, n):
                page = tables_ref[b * P + blk * ppb + i]
                for move in moves:
                    copy(move, buf, page, i, n).start()

        def once():  # the semaphore counts bytes: every copy's at once
            for move in moves:
                copy(move, buf, 0, 0, ppb).wait()

        # one branch for a block in runs, two for the others
        if wait:
            jax.lax.cond(kind != 0, once, lambda: edge(b, blk, buf, True))
        else:
            jax.lax.cond(
                kind == 2, lambda: straight(run),
                lambda: jax.lax.cond(kind == 1, lambda: straight(1),
                                     lambda: edge(b, blk, buf, False)))

    return block_copies


def _paged_decode_kernel(lengths_ref, tables_ref, layer_ref, *refs,
                         sm_scale: float, bounded: bool = False,
                         heads_apart: bool = False, run: int = 1):
    # ``bounded``: a fourth prefetched scalar a slot, the first position
    # the slot's query sees (0 <= start < length)
    # ``heads_apart``: a "slot" is one KV head of a slot (slot b's is
    # b % n_kv, its rows that head's query heads) with a page list of its
    # own, so only that head's columns of a page are its keys
    # ``run`` > 1: a last prefetched scalar a block of a slot's table, how
    # the block is copied (``block_kinds``, ``_run_copies``)
    starts_ref, refs = (refs[0], refs[1:]) if bounded else (None, refs)
    kinds_ref, refs = (refs[0], refs[1:]) if run > 1 else (None, refs)
    q_ref, k_pool, v_pool, o_ref, k_buf, v_buf, sems = refs
    B, H, d = q_ref.shape
    _, ppb, ps, n_kv, _ = k_buf.shape
    P = tables_ref.shape[0] // B
    block_tokens = ppb * ps
    cols = block_tokens * n_kv
    layer = layer_ref[0]

    def first_block(b):
        """The block a slot's walk begins at."""
        return starts_ref[b] // block_tokens if bounded else 0

    moves = [(pool, dst, lambda buf, s=s: sems.at[s, buf])
             for s, (pool, dst) in enumerate(((k_pool, k_buf),
                                              (v_pool, v_buf)))]
    if run > 1:
        block_copies = _run_copies(lengths_ref, starts_ref, tables_ref,
                                   kinds_ref, layer, moves, P, ppb, ps, run)
    else:
        block_copies = _page_copies(lengths_ref, starts_ref, tables_ref,
                                    layer, moves, P, ppb, ps)

    def next_active(b):
        """First slot after ``b`` with something to attend to, or B."""
        return jax.lax.while_loop(
            lambda n: (n < B) & (lengths_ref[jnp.minimum(n, B - 1)] == 0),
            lambda n: n + 1, b + 1)

    # A column of a block's scores is (token, kv head), in the pool's order;
    # a query head keeps the columns of its own group's KV head.
    row = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (H, cols), 1)
    own_head = (col % n_kv) == (row // (H // n_kv))

    o_ref[...] = jnp.zeros_like(o_ref)
    # A block's last pages may not be copied; what lies there (an earlier
    # block's rows, or at first whatever VMEM held) is masked out of the
    # scores but still multiplied by p == 0, so it has to be finite.
    v_buf[...] = jnp.zeros_like(v_buf)
    first = next_active(-1)

    @pl.when(first < B)
    def _():
        block_copies(first, first_block(first), 0, wait=False)

    def slot(carry):
        b, buf = carry
        length = lengths_ref[b]
        n_blocks = (length + block_tokens - 1) // block_tokens
        nxt = next_active(b)
        q = q_ref[b]
        own = (col % n_kv) == (b % n_kv) if heads_apart else own_head

        def block(i, carry):
            m, l, acc, buf = carry
            more = i + 1 < n_blocks

            @pl.when(more | (nxt < B))
            def _():
                after = jnp.minimum(nxt, B - 1)
                block_copies(jnp.where(more, b, after),
                             jnp.where(more, i + 1, first_block(after)),
                             1 - buf, wait=False)

            block_copies(b, i, buf, wait=True)
            k = k_buf[buf].reshape(cols, d)
            v = v_buf[buf].reshape(cols, d)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            keep = own & (col < (length - i * block_tokens) * n_kv)
            if bounded:
                keep &= col >= (starts_ref[b] - i * block_tokens) * n_kv
            s = jnp.where(keep, s * sm_scale, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)  # masked columns: exactly 0
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc, 1 - buf

        # the first block walked holds position 0 (or the slot's start),
        # which every query head attends to, so the running max is finite
        # from the first block on
        _, l, acc, buf = jax.lax.fori_loop(
            first_block(b), n_blocks, block,
            (jnp.full((H, 1), NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, d), jnp.float32), buf))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return nxt, buf

    jax.lax.while_loop(lambda c: c[0] < B, slot, (first, 0))


@functools.partial(jax.jit, static_argnames=("pages_per_block", "interpret",
                                             "heads_apart"))
def _paged_decode(q, k_pool, v_pool, page_tables, lengths, layer,
                  starts=None, *, pages_per_block: int, interpret: bool,
                  heads_apart: bool = False):
    B, H, d = q.shape
    _, _, ps, n_kv, _ = k_pool.shape
    P = page_tables.shape[1]
    ppb = min(pages_per_block, P)
    any_space = pl.BlockSpec(memory_space=pl.ANY)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    lengths = jnp.minimum(lengths, P * ps).astype(jnp.int32)
    kernel = functools.partial(_paged_decode_kernel,
                               sm_scale=1.0 / math.sqrt(d))
    bound = ()
    if starts is not None:  # a start a slot, under its length
        kernel = functools.partial(kernel, bounded=True)
        bound = (jnp.clip(starts, 0, jnp.maximum(lengths - 1, 0)).astype(
            jnp.int32),)
    if heads_apart:
        kernel = functools.partial(kernel, heads_apart=True)
    _, run = walk_blocks(k_pool.shape, k_pool.dtype.itemsize, P, ppb)
    if run > 1:  # small pages: how each block is copied
        kernel = functools.partial(kernel, run=run)
        bound += (block_kinds(page_tables, lengths,
                              bound[0] if bound else None, ps, ppb,
                              run).reshape(-1).astype(jnp.int32),)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(bound),
            grid=(),
            in_specs=[vmem, any_space, any_space],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, ppb, ps, n_kv, d), k_pool.dtype),
                pltpu.VMEM((2, ppb, ps, n_kv, d), v_pool.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),  # (k | v, buffer)
            ]),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name="paged_decode_attention",
    )(lengths, page_tables.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *bound,
      q, k_pool, v_pool)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, page_tables: jax.Array,
                           lengths: jax.Array, layer, *, window=None,
                           pages_per_block: int | None = None,
                           heads_apart: bool = False) -> jax.Array:
    """Attention of one query token a slot over that slot's cached tokens.

    q: [B, H, d], the new token's (rotated) query heads.  k_pool / v_pool:
    [n_layers, num_pages, page_size, n_kv_heads, d], the whole pool; only
    ``layer`` (an int32 scalar, traced or not) is read.  page_tables:
    [B, P] page ids.  lengths: [B] tokens to attend to in each slot,
    positions ``0 .. lengths[b] - 1`` through the table; 0 marks an inactive
    slot, whose output row is zeros.  The new token's own K and V must
    already be in the pool.  Returns [B, H, d] in q's dtype; operands go to
    the MXU in the pool's dtype, scores and the softmax state are float32.
    ``pages_per_block`` defaults to ``BLOCK_TOKENS`` worth of pages.
    ``window`` (None or 0: no bound, and the program it was): positions a
    slot's query sees, its own among them, a whole number or an int32
    scalar (traced or not); the slot then attends to positions ``max(0,
    lengths[b] - window) .. lengths[b] - 1`` and the table's entries for
    the pages wholly before them are never read (they may be null).
    ``heads_apart`` (a layer whose KV heads each attend to pages of their
    own choice, ops/block_sparse.py): page_tables [B, n_kv_heads, W] is a
    LIST of pages a slot a KV head, in the order of their positions, and
    lengths [B, n_kv_heads] the positions a list holds (its last page as
    far as the query's own position); a KV head's query heads attend to
    their list's pages alone, of which they read their own head's rows.
    """
    shape = q.shape
    if heads_apart:  # a KV head of a slot is a slot of the kernel's
        G = k_pool.shape[3]
        if (q.ndim != 3 or page_tables.shape[:2] != (shape[0], G)
                or lengths.shape != (shape[0], G) or shape[1] % G):
            raise ValueError(
                f"with heads_apart the lists {page_tables.shape} and their "
                f"lengths {lengths.shape} lead with q's batch and the "
                f"pool's KV heads ({shape[0]}, {G})")
        q = q.reshape(shape[0] * G, shape[1] // G, shape[2])
        page_tables = page_tables.reshape(shape[0] * G, -1)
        lengths = lengths.reshape(-1)
    if q.ndim != 3 or k_pool.ndim != 5 or k_pool.shape != v_pool.shape:
        raise ValueError(
            f"paged decode attention takes q [B, H, d] and two pools "
            f"[layers, pages, page_size, kv_heads, d] of one shape; got "
            f"{q.shape}, {k_pool.shape}, {v_pool.shape}")
    B, H, d = q.shape
    n_kv = k_pool.shape[3]
    if k_pool.shape[4] != d or H % n_kv != 0:
        raise ValueError(
            f"query heads ({H} of {d}) must be a multiple of the pool's KV "
            f"heads ({n_kv} of {k_pool.shape[4]}) at the same head_dim")
    if page_tables.shape[0] != B or lengths.shape != (B,):
        raise ValueError(
            f"page_tables {page_tables.shape} and lengths {lengths.shape} "
            f"must lead with q's batch ({B})")
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and (d % 128 != 0 or (n_kv % 8 != 0 and n_kv not in (2, 4))):
        raise ValueError(
            f"on the TPU the paged decode kernel copies whole pages, and a "
            f"[page_size, {n_kv}, {d}] page is not made of whole tiles: "
            f"head_dim must be a multiple of 128 and the KV heads 2, 4 or a "
            f"multiple of 8")
    if pages_per_block is None:
        pages_per_block = max(1, BLOCK_TOKENS // k_pool.shape[2])
    starts = None
    if window is not None and not (isinstance(window, int) and window == 0):
        if isinstance(window, int) and window < 0:
            raise ValueError(f"a window holds 1 or more positions, the "
                             f"query's own among them; got {window}")
        starts = jnp.maximum(lengths - window, 0)
    out = _paged_decode(q.astype(k_pool.dtype), k_pool, v_pool, page_tables,
                        lengths, layer, starts,
                        pages_per_block=pages_per_block, interpret=not on_tpu,
                        **({"heads_apart": True} if heads_apart else {})
                        ).astype(q.dtype)
    return out.reshape(shape) if heads_apart else out


# ---------------------------------------------------------------------------
# Latent pages (models/glm_moe_lite.py): one pool of rows, each read twice.

# tokens a compute block of the latent kernel holds: a latent page is one
# contiguous page_size x width run (20 KB at 16 x 640 bf16), a fifth of a
# K/V block's bytes a token, so a block takes twice the tokens: 32 copies
# of 20 KB, under COPY_BYTES, so the walk is ``_run_copies``'s (apart, GLM's
# pool: 16 / 32 / 64 pages a block 119.6 / 94.7 / 109.2 us, PERF.md, PR 60)
LATENT_BLOCK_TOKENS = 512


def _paged_latent_kernel(lengths_ref, tables_ref, layer_ref, *refs,
                         sm_scale: float, run: int = 1):
    """``_paged_decode_kernel`` over ONE pool whose row is key and value
    both: every query head scores against the whole row (its zero tail
    meets the query's), and the weighted sum is taken over the row's first
    ``o_ref.shape[-1]`` values.  No KV heads, so no column of a block
    belongs to another head and nothing but the length is masked."""
    kinds_ref, refs = (refs[0], refs[1:]) if run > 1 else (None, refs)
    q_ref, pool, o_ref, buf, sems = refs
    B, H, _ = q_ref.shape
    _, ppb, ps, W = buf.shape
    V = o_ref.shape[-1]
    P = tables_ref.shape[0] // B
    block_tokens = ppb * ps
    layer = layer_ref[0]
    moves = [(pool, buf, lambda which: sems.at[which])]
    if run > 1:
        block_copies = _run_copies(lengths_ref, None, tables_ref, kinds_ref,
                                   layer, moves, P, ppb, ps, run)
    else:
        block_copies = _page_copies(lengths_ref, None, tables_ref, layer,
                                    moves, P, ppb, ps)

    def next_active(b):
        return jax.lax.while_loop(
            lambda n: (n < B) & (lengths_ref[jnp.minimum(n, B - 1)] == 0),
            lambda n: n + 1, b + 1)

    col = jax.lax.broadcasted_iota(jnp.int32, (H, block_tokens), 1)
    o_ref[...] = jnp.zeros_like(o_ref)
    # rows past a block's last copied page are masked out of the scores but
    # still multiplied by p == 0: they have to be finite
    buf[...] = jnp.zeros_like(buf)
    first = next_active(-1)

    @pl.when(first < B)
    def _():
        block_copies(first, 0, 0, wait=False)

    def slot(carry):
        b, which = carry
        length = lengths_ref[b]
        n_blocks = (length + block_tokens - 1) // block_tokens
        nxt = next_active(b)
        q = q_ref[b]

        def block(i, carry):
            m, l, acc, which = carry
            more = i + 1 < n_blocks

            @pl.when(more | (nxt < B))
            def _():
                block_copies(jnp.where(more, b, jnp.minimum(nxt, B - 1)),
                             jnp.where(more, i + 1, 0), 1 - which,
                             wait=False)

            block_copies(b, i, which, wait=True)
            rows = buf[which].reshape(block_tokens, W)
            s = jax.lax.dot_general(q, rows, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(col < length - i * block_tokens, s * sm_scale,
                          NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)  # masked columns: exactly 0
            alpha = jnp.exp(m - m_new)
            l = l * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc = acc * alpha + jax.lax.dot_general(
                p.astype(rows.dtype), rows[:, :V], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, l, acc, 1 - which

        _, l, acc, which = jax.lax.fori_loop(
            0, n_blocks, block,
            (jnp.full((H, 1), NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, V), jnp.float32), which))
        o_ref[b] = (acc / l).astype(o_ref.dtype)
        return nxt, which

    jax.lax.while_loop(lambda c: c[0] < B, slot, (first, 0))


@functools.partial(jax.jit, static_argnames=(
    "value_dim", "sm_scale", "pages_per_block", "interpret"))
def _paged_latent(q, pool, page_tables, lengths, layer, *, value_dim: int,
                  sm_scale: float, pages_per_block: int, interpret: bool):
    B, H, _ = q.shape
    _, _, ps, W = pool.shape
    P = page_tables.shape[1]
    ppb = min(pages_per_block, P)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    lengths = jnp.minimum(lengths, P * ps).astype(jnp.int32)
    kernel = functools.partial(_paged_latent_kernel, sm_scale=sm_scale)
    kinds = ()
    _, run = walk_blocks(pool.shape, pool.dtype.itemsize, P, ppb)
    if run > 1:  # small pages: how each block is copied
        kernel = functools.partial(kernel, run=run)
        kinds = (block_kinds(page_tables, lengths, None, ps, ppb,
                             run).reshape(-1).astype(jnp.int32),)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3 + len(kinds),
            grid=(),
            in_specs=[vmem, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=vmem,
            scratch_shapes=[
                pltpu.VMEM((2, ppb, ps, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),  # a buffer each
            ]),
        out_shape=jax.ShapeDtypeStruct((B, H, value_dim), q.dtype),
        interpret=interpret,
        name="paged_latent_decode_attention",
    )(lengths, page_tables.reshape(-1).astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *kinds, q, pool)


def paged_latent_decode_attention(
        q: jax.Array, pool: jax.Array, page_tables: jax.Array,
        lengths: jax.Array, layer, *, value_dim: int, sm_scale: float,
        pages_per_block: int | None = None) -> jax.Array:
    """Absorbed latent attention of one query token a slot over that slot's
    cached LATENT rows: each row is walked once and used twice.

    q: [B, H, W], the new token's absorbed query heads (``[q_nope W_uk |
    q_rope]``, zeros to the pool's width).  pool: [n_layers, num_pages,
    page_size, W], the whole latent pool (rows ``[c_kv | k_rope | 0]``);
    only ``layer`` is read.  page_tables [B, P], lengths [B] and ``layer``
    as ``paged_decode_attention`` takes them; a slot of length 0 is skipped
    and its output row is zeros.  The new token's own row must already be
    in the pool.  Scores are ``sm_scale * q . row`` over the whole width;
    the value of a row is its first ``value_dim`` entries.  Returns [B, H,
    value_dim] in q's dtype (attention's weighted sum of ``c_kv``, still to
    go through ``W_uv``); operands go to the MXU in the pool's dtype, scores
    and the softmax state are float32.  The heads are padded here to whole
    sublane tiles of the pool's dtype (20 -> 32 in bf16).
    """
    if q.ndim != 3 or pool.ndim != 4 or q.shape[2] != pool.shape[3]:
        raise ValueError(
            f"paged latent decode attention takes q [B, H, W] and one pool "
            f"[layers, pages, page_size, W] of the same width; got "
            f"{q.shape}, {pool.shape}")
    B, H, W = q.shape
    ps = pool.shape[2]
    if not 0 < value_dim <= W:
        raise ValueError(
            f"a row's value is its first value_dim entries: {value_dim} is "
            f"not in 1..{W}")
    if page_tables.shape[0] != B or lengths.shape != (B,):
        raise ValueError(
            f"page_tables {page_tables.shape} and lengths {lengths.shape} "
            f"must lead with q's batch ({B})")
    on_tpu = jax.default_backend() == "tpu"
    sublanes = 32 // jnp.dtype(pool.dtype).itemsize
    if on_tpu and (W % 128 or value_dim % 128 or ps % sublanes):
        raise ValueError(
            f"on the TPU the paged latent kernel copies whole pages and "
            f"slices a row's value on a lane tile: a [{ps}, {W}] "
            f"{jnp.dtype(pool.dtype).name} page whose value is {value_dim} "
            f"wide is not made of whole tiles (width and value_dim "
            f"multiples of 128, page_size of {sublanes})")
    if pages_per_block is None:
        pages_per_block = max(1, LATENT_BLOCK_TOKENS // ps)
    padded = jnp.pad(q.astype(pool.dtype),
                     ((0, 0), (0, -H % sublanes), (0, 0)))
    out = _paged_latent(padded, pool, page_tables, lengths, layer,
                        value_dim=value_dim, sm_scale=float(sm_scale),
                        pages_per_block=pages_per_block,
                        interpret=not on_tpu)
    return out[:, :H].astype(q.dtype)
