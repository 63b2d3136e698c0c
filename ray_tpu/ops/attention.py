"""Fused attention for TPU: Pallas flash-attention forward AND backward.

Net-new relative to the reference, which delegates attention math to
torch/vLLM (SURVEY.md §2.4): here it is a first-class op.  Forward is a
Pallas kernel — online-softmax over KV blocks, O(seq) memory — and saves
the per-row logsumexp.  The backward is the FlashAttention-2 split, also in
Pallas: a dK/dV kernel gridded over KV blocks and a dQ kernel gridded over
Q blocks, each recomputing p = exp(s - lse) blockwise from the saved
statistics, so activation memory stays O(seq) end to end.

A kernel does a tile's work and no more:

- Every operand is blocked, and the grid is (batch*heads, tiles): the
  (query block, key block) tiles a call VISITS, listed once from its
  lengths (``tile_schedule``) and handed to the index maps as prefetched
  tables, with a tile's accumulators in VMEM scratch across the tiles of
  its outer block.  A causal call has no grid step for a tile over the
  diagonal.  What a program holds in fast memory is a few (block,
  head_dim) tiles whatever the sequence length.
- A tile that lies under the diagonal and inside the key length runs a
  body with no mask in it; only the tiles the diagonal or the padded tail
  crosses build one, by sub-blocks, and the square ON the diagonal is
  computed in stairs that stop at each step's last row.
- The products take q, k, v and dO in the dtype they arrive in (bf16 in
  training: what the MXU multiplies; ``p`` and ``ds`` are cast to it for
  their second product), summed in float32.  Scores, the running max and
  sum, ``exp``, ``lse``, ``delta`` and every accumulator are float32, and
  ``sm_scale`` is applied in float32, to a block once before its one cast.
- GQA by index map: K and V stay packed at their own heads,
  (batch*kv_heads, seq, head_dim), and query head ``b`` reads KV head
  ``b // reps``; the dK/dV kernel sums a KV head's gradient over its
  ``reps`` query heads in its scratch, so nothing is repeated before the
  kernels or summed after them.
- ``lse`` and ``delta`` ride with positions in the lanes, float32
  (batch*heads, 1, seq): a (.., seq, 1) array is tiled to 128 times its
  numbers in HBM.  The dK/dV kernel computes its tiles transposed (keys
  down the sublanes), so a row of statistics broadcasts as it arrives.

Sequences are padded to a whole number of blocks (padded keys are masked
where a kept row could see one, padded query rows sliced off), because
Mosaic refuses a block that is not aligned to the (8, 128) tiling.

Layout: (batch*heads, seq, head_dim) inside the kernels; the public API
takes (batch, seq, heads, head_dim).  On a mesh of more than one device the
kernels run under ``shard_map`` over the axes that shard batch and heads:
Mosaic kernels cannot be partitioned by GSPMD.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from ray_tpu.parallel.mesh import mesh_axis_size
from ray_tpu.parallel.sharding import to_partition_spec

DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30
_LANES = 128  # blocks are whole lane tiles, which also satisfies sublanes

# (bh, tile): a tile's accumulators live across the tiles of its outer block
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"))

_STAIR = 256  # rows a step of the stairs a diagonal square is computed in
_FIRST, _LAST = 1, 2  # a tile's place among the tiles of its outer block


def _vmem_block(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def repeat_kv_heads(k, v, num_heads):
    """Expand GQA K/V (..., kv_heads, d) to num_heads along axis 2: for the
    plain XLA path and the sequence-parallel forms.  The kernels below read
    K and V at their own heads."""
    kv_heads = k.shape[2]
    if kv_heads != num_heads:
        reps = num_heads // kv_heads
        # inside the caller's ``attn/attend`` (models/llama.py PARTS)
        with jax.named_scope("repeat_kv"):
            k = jnp.repeat(k, reps, axis=2)
            v = jnp.repeat(v, reps, axis=2)
    return k, v


def _pack(x):
    """(batch, seq, heads, d) -> (batch*heads, seq, d)."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unpack(x, batch: int):
    """(batch*heads, seq, d) -> (batch, seq, heads, d)."""
    bh, s, d = x.shape
    return x.reshape(batch, bh // batch, s, d).transpose(0, 2, 1, 3)


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _block_and_padded(seq: int, block: int) -> tuple[int, int]:
    """Block size (a whole number of lane tiles, no longer than the padded
    sequence) and the sequence length padded to whole blocks."""
    block = min(_round_up(block, _LANES), _round_up(seq, _LANES))
    return block, _round_up(seq, block)


def _pad_seq(x, padded: int):
    pad = padded - x.shape[1]
    return x if pad == 0 else jnp.pad(x, ((0, 0), (0, pad), (0, 0)))


# --------------------------------------------------------------------------
# The tile schedule.  Which (query block, key block) tiles a call computes,
# and which of them a mask can change, follows from its lengths and blocks
# alone.  The predicates take Python ints (the tables and counts below) and
# traced scalars (inside the kernels) alike, so the two cannot disagree.

def _visible(q0, block_q, k0, causal):
    """Rows [q0, q0 + block_q) keep some column from k0 on."""
    return k0 <= q0 + block_q - 1 if causal else True


def _crossed(q0, k0, width, causal, tail):
    """A mask can change an entry of rows [q0, ...) x columns
    [k0, k0 + width): the diagonal passes through, or the padded tail
    (columns from ``tail`` on; None when nothing kept can see one) starts
    inside."""
    crossed = k0 + width - 1 > q0 if causal else False
    if tail is not None:
        crossed = crossed | (k0 + width > tail)
    return crossed


def _tail(causal, q_len, kv_len, padded_k):
    """First padded key column a kept query row could see, or None.  Under
    ``causal`` a row sees no column past its own position, so with no more
    rows than keys the diagonal already hides the padding (a padded query
    row is sliced off forward and has zero ``d_out`` backward)."""
    if kv_len == padded_k or (causal and q_len <= kv_len):
        return None
    return kv_len


def _sub_width(block_q, block_k):
    """A tile that a mask crosses is computed in column sub-blocks this
    wide, and those wholly over the diagonal are left out: with square
    sub-blocks a diagonal tile computes the squares under and on the
    diagonal and no others."""
    return block_q if block_k % block_q == 0 else block_k


def _steps(block_q, width, causal, tail, by_key=False):
    """The rectangles (query rows, key columns; slices from the sub-block's
    corner) a crossed sub-block is computed in.  A square one that only the
    diagonal crosses sits ON the diagonal (its first column is its first
    row), so a step of ``_STAIR`` rows needs the columns up to its own last
    row and no others (``by_key``: a step of columns, the rows from its
    first on): stairs.  Any other crossed sub-block is one rectangle."""
    step = _STAIR if block_q % _STAIR == 0 else _LANES
    if not (causal and tail is None and width == block_q and step < width):
        return [(slice(0, block_q), slice(0, width))]
    if by_key:
        return [(slice(r, block_q), slice(r, r + step))
                for r in range(0, width, step)]
    return [(slice(r, r + step), slice(0, r + step))
            for r in range(0, block_q, step)]


def _visited(n_q, n_k, block_q, block_k, causal):
    return [(i, j) for i in range(n_q) for j in range(n_k)
            if _visible(i * block_q, block_q, j * block_k, causal)]


def _tables(groups):
    """int32 tables, one entry a grid step, of the tiles in ``groups`` (a
    list of lists of index tuples, one list an outer block): the tuples'
    columns, then each tile's ``_FIRST`` / ``_LAST`` place in its list."""
    rows = [t + ((_FIRST if n == 0 else 0)
                 | (_LAST if n == len(g) - 1 else 0),)
            for g in groups for n, t in enumerate(g)]
    return tuple(np.asarray(col, np.int32) for col in zip(*rows))


def _by_query_block(tiles):
    """(q block, k block, place): a query block's key blocks in a row."""
    outer = sorted({i for i, _ in tiles})
    return _tables([[t for t in tiles if t[0] == i] for i in outer])


def _by_key_block(tiles, reps):
    """(k block, query head of the KV head, q block, place): the query
    blocks of every query head that reads a key block, in a row."""
    outer = sorted({j for _, j in tiles})
    return _tables([[(j, r, i) for r in range(reps) for i, jj in tiles
                     if jj == j] for j in outer])


def tile_schedule(seq_q: int, seq_k: int, block_q: int = DEFAULT_BLOCK_Q,
                  block_k: int = DEFAULT_BLOCK_K, causal: bool = True,
                  kv_len: Optional[int] = None) -> dict:
    """What one (batch, head) of a ``flash_attention`` call of these lengths
    visits, in each of its three kernels: the tiles it computes, those of
    them that run the masked body, and the score entries it computes over
    the entries the result needs.  Static, so this is the kernels' counter:
    they are gridded over the same list and branch on the same predicates."""
    kv_len = seq_k if kv_len is None else kv_len
    block_q, padded_q = _block_and_padded(seq_q, block_q)
    block_k, padded_k = _block_and_padded(seq_k, block_k)
    tail = _tail(causal, seq_q, kv_len, padded_k)
    width = _sub_width(block_q, block_k)
    tiles = _visited(padded_q // block_q, padded_k // block_k, block_q,
                     block_k, causal)
    masked = computed = 0
    for i, j in tiles:
        q0, k0 = i * block_q, j * block_k
        if not _crossed(q0, k0, block_k, causal, tail):
            computed += block_q * block_k
            continue
        masked += 1
        for lo in range(0, block_k, width):
            if not _visible(q0, block_q, k0 + lo, causal):
                continue
            if _crossed(q0, k0 + lo, width, causal, tail):
                computed += sum(
                    (rows.stop - rows.start) * (cols.stop - cols.start)
                    for rows, cols in _steps(block_q, width, causal, tail))
            else:
                computed += block_q * width
    required = (sum(min(r + 1, kv_len) for r in range(seq_q)) if causal
                else seq_q * kv_len)
    return {"block_q": block_q, "block_k": block_k,
            "tiles_visited": len(tiles), "tiles_masked": masked,
            "entries_computed": computed,
            "entries_required": required,
            "computed_over_required": computed / required}


def _visit(fold, q0, k0, block_q, block_k, *, causal, tail, by_key=False):
    """Run ``fold(lo, steps, masked)`` over the columns from ``lo`` of one
    visited tile: once over the whole tile, unmasked, where no mask can
    change it;
    else by the sub-blocks the rows can see, masked and in the rectangles
    of ``_steps`` where the diagonal or the tail crosses the sub-block.
    ``fold``'s last argument says whether its steps are masked."""
    def plain(width):
        return [(slice(0, block_q), slice(0, width))]

    whole = _crossed(q0, k0, block_k, causal, tail)
    if whole is False:  # neither causal nor padded: every tile is plain
        fold(0, plain(block_k), False)
        return
    pl.when(jnp.logical_not(whole))(lambda: fold(0, plain(block_k), False))
    width = _sub_width(block_q, block_k)
    steps = _steps(block_q, width, causal, tail, by_key)
    if width == block_k:  # one sub-block: crossed as the tile is
        pl.when(whole)(lambda: fold(0, steps, True))
        return
    for lo in range(0, block_k, width):
        seen = whole & _visible(q0, block_q, k0 + lo, causal)
        crossed = _crossed(q0, k0 + lo, width, causal, tail)
        pl.when(seen & crossed)(functools.partial(fold, lo, steps, True))
        pl.when(seen & jnp.logical_not(crossed))(
            functools.partial(fold, lo, plain(width), False))


def _keep(shape, q_axis, q0, k0, causal, tail):
    """Which entries of a score tile (queries along ``q_axis``, rows from
    q0, columns from k0) the masks keep."""
    col = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    keep = None
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
        keep = row - col >= k0 - q0
    if tail is not None:
        inside = col < tail - k0
        keep = inside if keep is None else keep & inside
    return keep


def _dot(a, b, b_axis):
    """a (m, c) times b, contracting b's ``b_axis``: the operands as they
    are (the MXU takes bf16 as it arrives), the sum in float32."""
    return jax.lax.dot_general(a, b, (((1,), (b_axis,)), ((), ())),
                               preferred_element_type=jnp.float32)


def _scaled(ref, sm_scale):
    """A block times ``sm_scale`` in float32, cast once to its own dtype."""
    return (ref[0].astype(jnp.float32) * sm_scale).astype(ref.dtype)


def _eye(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _store_as_row(row_ref, col):
    """Write a (n, 1) column of row statistics into ``row_ref`` (1, 1, n),
    positions in the lanes: a lane tile at a time, the column spread over
    the diagonal of a square and summed down the sublanes (exact: one
    non-zero a lane)."""
    eye = _eye(_LANES)
    for lo in range(0, col.shape[0], _LANES):
        square = jnp.where(eye, col[lo:lo + _LANES], 0.0)
        row_ref[0, :, lo:lo + _LANES] = jnp.sum(square, axis=0, keepdims=True)


def _store_as_column(col_ref, row_ref):
    """The inverse: ``row_ref`` (1, 1, n) into ``col_ref`` (n, 1)."""
    eye = _eye(_LANES)
    for lo in range(0, col_ref.shape[0], _LANES):
        square = jnp.where(eye, row_ref[0, :, lo:lo + _LANES], 0.0)
        col_ref[lo:lo + _LANES] = jnp.sum(square, axis=1, keepdims=True)


def _flash_kernel(qi_ref, kj_ref, place_ref, q_ref, k_ref, v_ref, o_ref,
                  lse_ref, qs_ref, acc_ref, m_ref, l_ref, *, causal: bool,
                  sm_scale: float, tail):
    """One tile of the online softmax.  A query block's first tile holds
    column 0, which every row keeps, so the running max is finite before
    any masked entry is folded in."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    t = pl.program_id(1)
    q0, k0, place = qi_ref[t] * block_q, kj_ref[t] * block_k, place_ref[t]

    @pl.when(place & _FIRST != 0)
    def _init():
        qs_ref[...] = _scaled(q_ref, sm_scale)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def fold(lo, steps, masked):
        for rows, cols in steps:
            keys = slice(lo + cols.start, lo + cols.stop)
            v = v_ref[0, keys]
            s = _dot(qs_ref[rows], k_ref[0, keys], 1)
            if masked:
                s = jnp.where(_keep(s.shape, 0, q0 + rows.start,
                                    k0 + keys.start, causal, tail), s, NEG_INF)
            m_prev = m_ref[rows]  # (rows, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[rows] = l_ref[rows] * alpha + jnp.sum(p, axis=1,
                                                        keepdims=True)
            acc_ref[rows] = acc_ref[rows] * alpha + _dot(p.astype(v.dtype),
                                                         v, 0)
            m_ref[rows] = m_new

    _visit(fold, q0, k0, block_q, block_k, causal=causal, tail=tail)

    @pl.when(place & _LAST != 0)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # Per-row logsumexp, saved for the backward: p = exp(s - lse)
        # reconstructs softmax blockwise without the O(seq^2) score matrix.
        # Rows with no unmasked column get +inf-ish so backward p == 0.
        # It leaves with positions in the lanes, (bh, 1, seq): a float32
        # (.., seq, 1) would be tiled to 128 times its numbers in HBM.
        _store_as_row(lse_ref, jnp.where(l == 0.0, -NEG_INF,
                                         m_ref[...] + jnp.log(l_safe)))


def _call(kernel, name, tables, grid, in_specs, out_specs, out_shape,
          scratch_shapes, interpret):
    """A kernel gridded over (bh, its tiles); the index maps read a tile's
    blocks from the prefetched ``tables``."""
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables), grid=grid, in_specs=in_specs,
            out_specs=out_specs, scratch_shapes=scratch_shapes),
        out_shape=out_shape, compiler_params=_GRID_SEMANTICS,
        interpret=interpret, name=name)


def _row_pass(q, k, block_q, block_k, causal):
    """Of a kernel gridded by query block (forward, dQ): its tables, its
    grid, and the block specs of a (bh, seq, d) operand, of K or V at their
    own heads (query head ``b`` reads KV head ``b // reps``) and of a row
    of statistics."""
    bh, seq_q, head_dim = q.shape
    reps = bh // k.shape[0]
    tables = _by_query_block(_visited(
        seq_q // block_q, k.shape[1] // block_k, block_q, block_k, causal))
    return tables, (bh, len(tables[0])), _vmem_block(
        (1, block_q, head_dim), lambda b, t, qi, kj, _: (b, qi[t], 0)
    ), _vmem_block(
        (1, block_k, head_dim), lambda b, t, qi, kj, _: (b // reps, kj[t], 0)
    ), _vmem_block((1, 1, block_q), lambda b, t, qi, kj, _: (b, 0, qi[t]))


_STATIC = ("causal", "sm_scale", "block_q", "block_k", "lens", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_forward(q, k, v, *, causal: bool, sm_scale: float,
                   block_q: int, block_k: int, lens: tuple[int, int],
                   interpret: bool):
    """q: (bh, seq, head_dim), k, v: (b * kv_heads, seq, head_dim), seq a
    whole number of blocks; ``lens`` the unpadded (query, key) lengths.
    Returns (out, lse), lse float32 (bh, 1, seq)."""
    bh, seq_q, head_dim = q.shape
    tables, grid, q_spec, kv_spec, stat_spec = _row_pass(
        q, k, block_q, block_k, causal)
    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale,
        tail=_tail(causal, *lens, k.shape[1]))
    return _call(
        kernel, "flash_attention_fwd", tables, grid,
        [q_spec, kv_spec, kv_spec], [q_spec, stat_spec],
        [jax.ShapeDtypeStruct(q.shape, q.dtype),
         jax.ShapeDtypeStruct((bh, 1, seq_q), jnp.float32)],
        [pltpu.VMEM((block_q, head_dim), q.dtype),
         pltpu.VMEM((block_q, head_dim), jnp.float32),
         pltpu.VMEM((block_q, 1), jnp.float32),
         pltpu.VMEM((block_q, 1), jnp.float32)],
        interpret)(*tables, q, k, v)


def _reference_attention(q, k, v, causal: bool, sm_scale: float):
    """Plain XLA attention: the reference the kernels are tested against,
    and the ``impl="xla"`` path."""
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * sm_scale
    if causal:
        seq_q, seq_k = s.shape[-2], s.shape[-1]
        row = jnp.arange(seq_q)[:, None]
        col = jnp.arange(seq_k)[None, :]
        s = jnp.where(row >= col, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _flash_bwd_dkv_kernel(ki_ref, rep_ref, qj_ref, place_ref, q_ref, k_ref,
                          v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                          ks_ref, dk_acc, dv_acc, *, causal: bool,
                          sm_scale: float, tail):
    """One tile of the column pass (FlashAttention-2 backward): a KV
    block's dK and dV, summed over the query blocks that attend to it and
    over the query heads that read this KV head.  The tile is computed
    TRANSPOSED, keys down the sublanes and queries along the lanes, so a
    row of ``lse`` or ``delta`` broadcasts down it as it arrives and all
    four products are plain (no operand is turned)."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    t = pl.program_id(1)
    q0, k0, place = qj_ref[t] * block_q, ki_ref[t] * block_k, place_ref[t]

    @pl.when(place & _FIRST != 0)
    def _init():
        ks_ref[...] = _scaled(k_ref, sm_scale)
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def fold(lo, steps, masked):
        for rows, cols in steps:
            keys = slice(lo + cols.start, lo + cols.stop)
            q, do = q_ref[0, rows], do_ref[0, rows]
            st = _dot(ks_ref[keys], q, 1)  # (keys, queries)
            if masked:
                st = jnp.where(_keep(st.shape, 1, q0 + rows.start,
                                     k0 + keys.start, causal, tail), st,
                               NEG_INF)
            pt = jnp.exp(st - lse_ref[0, :, rows])  # masked entries -> 0
            dst = pt * (_dot(v_ref[0, keys], do, 1) - delta_ref[0, :, rows])
            dv_acc[keys] += _dot(pt.astype(do.dtype), do, 0)
            dk_acc[keys] += _dot(dst.astype(q.dtype), q, 0)

    _visit(fold, q0, k0, block_q, block_k, causal=causal, tail=tail,
           by_key=True)

    @pl.when(place & _LAST != 0)
    def _finish():
        # ds = p * (dp - delta) * sm_scale: the factor once, on the sum
        dk_ref[0] = (dk_acc[...] * sm_scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(qi_ref, kj_ref, place_ref, q_ref, k_ref, v_ref,
                         do_ref, lse_ref, delta_ref, dq_ref, qs_ref, lse_col,
                         delta_col, dq_acc, *, causal: bool, sm_scale: float,
                         tail):
    """One tile of the row pass: a query block's dQ over its KV range.
    The rows of ``lse`` and ``delta`` are stood up once a query block."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    t = pl.program_id(1)
    q0, k0, place = qi_ref[t] * block_q, kj_ref[t] * block_k, place_ref[t]

    @pl.when(place & _FIRST != 0)
    def _init():
        qs_ref[...] = _scaled(q_ref, sm_scale)
        _store_as_column(lse_col, lse_ref)
        _store_as_column(delta_col, delta_ref)
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def fold(lo, steps, masked):
        for rows, cols in steps:
            keys = slice(lo + cols.start, lo + cols.stop)
            k, v = k_ref[0, keys], v_ref[0, keys]
            s = _dot(qs_ref[rows], k, 1)
            if masked:
                s = jnp.where(_keep(s.shape, 0, q0 + rows.start,
                                    k0 + keys.start, causal, tail), s, NEG_INF)
            p = jnp.exp(s - lse_col[rows])  # masked entries -> 0
            ds = p * (_dot(do_ref[0, rows], v, 1) - delta_col[rows])
            dq_acc[rows] += _dot(ds.astype(k.dtype), k, 0)

    _visit(fold, q0, k0, block_q, block_k, causal=causal, tail=tail)

    @pl.when(place & _LAST != 0)
    def _finish():
        dq_ref[0] = (dq_acc[...] * sm_scale).astype(dq_ref.dtype)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _flash_backward(q, k, v, out, lse, d_out, *, causal: bool,
                    sm_scale: float, block_q: int, block_k: int,
                    lens: tuple[int, int], interpret: bool):
    bh, seq_q, head_dim = q.shape
    bkv, seq_k, _ = k.shape
    reps = bh // bkv
    static = dict(causal=causal, sm_scale=sm_scale,
                  tail=_tail(causal, *lens, seq_k))
    # delta = rowsum(do * o): one fused elementwise+reduce, O(seq) memory,
    # positions in the lanes like lse
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (bh, 1, seq_q)

    # A KV head's tiles, its query heads one after the other: dK and dV
    # leave at KV-head width with nothing left to sum.
    tables = _by_key_block(_visited(seq_q // block_q, seq_k // block_k,
                                    block_q, block_k, causal), reps)
    q_block = _vmem_block(
        (1, block_q, head_dim),
        lambda b, t, ki, rep, qj, _: (b * reps + rep[t], qj[t], 0))
    kv_block = _vmem_block((1, block_k, head_dim),
                           lambda b, t, ki, rep, qj, _: (b, ki[t], 0))
    stat_block = _vmem_block(
        (1, 1, block_q),
        lambda b, t, ki, rep, qj, _: (b * reps + rep[t], 0, qj[t]))
    dk, dv = _call(
        functools.partial(_flash_bwd_dkv_kernel, **static),
        "flash_attention_bwd_dkv", tables, (bkv, len(tables[0])),
        [q_block, kv_block, kv_block, q_block, stat_block, stat_block],
        [kv_block, kv_block],
        [jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((block_k, head_dim), k.dtype),
         pltpu.VMEM((block_k, head_dim), jnp.float32),
         pltpu.VMEM((block_k, head_dim), jnp.float32)],
        interpret)(*tables, q, k, v, d_out, lse, delta)

    tables, grid, q_spec, kv_spec, stat_spec = _row_pass(
        q, k, block_q, block_k, causal)
    dq = _call(
        functools.partial(_flash_bwd_dq_kernel, **static),
        "flash_attention_bwd_dq", tables, grid,
        [q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec], q_spec,
        jax.ShapeDtypeStruct(q.shape, q.dtype),
        [pltpu.VMEM((block_q, head_dim), q.dtype),
         pltpu.VMEM((block_q, 1), jnp.float32),
         pltpu.VMEM((block_q, 1), jnp.float32),
         pltpu.VMEM((block_q, head_dim), jnp.float32)],
        interpret)(*tables, q, k, v, d_out, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(q, k, v, causal, sm_scale, block_q, block_k, lens,
                     interpret):
    out, _ = _flash_forward(q, k, v, causal=causal, sm_scale=sm_scale,
                            block_q=block_q, block_k=block_k, lens=lens,
                            interpret=interpret)
    return out


# What the forward kernel alone can make, by name (``checkpoint_name``:
# nothing in the lowered program).  A ``jax.checkpoint`` whose policy saves
# these never runs the kernel a second time; q, k and v, packed and padded,
# are cheap to make again from what the caller keeps (models/llama.py
# ``REMAT_KEEPS``).
FLASH_RESIDUALS = ("flash/out", "flash/lse")


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, lens, interpret):
    out, lse = _flash_forward(q, k, v, causal=causal, sm_scale=sm_scale,
                              block_q=block_q, block_k=block_k,
                              lens=lens, interpret=interpret)
    # named HERE, so that the primal output and the residual are the one
    # value: a name on ``attention``'s result would keep a copy and still
    # run the kernel again for ``lse``
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _bwd(causal, sm_scale, block_q, block_k, lens, interpret, res, d_out):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, d_out, causal=causal,
                           sm_scale=sm_scale, block_q=block_q,
                           block_k=block_k, lens=lens,
                           interpret=interpret)


_flash_attention.defvjp(_fwd, _bwd)


def _pallas_attention(q, k, v, *, causal: bool, sm_scale: float,
                      block_q: int, block_k: int, interpret: bool):
    """The kernel path on one device's (batch, seq, heads, head_dim)
    arrays: pack to (b*h, s, d), K and V at their own heads, pad to whole
    blocks."""
    seq_q, seq_k = q.shape[1], k.shape[1]
    block_q, padded_q = _block_and_padded(seq_q, block_q)
    block_k, padded_k = _block_and_padded(seq_k, block_k)
    out = _flash_attention(
        _pad_seq(_pack(q), padded_q), _pad_seq(_pack(k), padded_k),
        _pad_seq(_pack(v), padded_k), causal, sm_scale, block_q, block_k,
        (seq_q, seq_k), interpret)
    return _unpack(out[:, :seq_q], q.shape[0])


def _sharded(local, q, k, v, mesh: Mesh, rules: Optional[dict]):
    """Run ``local`` per device under shard_map, batch and heads split as
    the logical rules say; the sequence stays whole on every device."""
    q_spec = to_partition_spec(("batch", None, "heads", None), rules)
    kv_spec = to_partition_spec(("batch", None, "kv_heads", None), rules)

    def axes(entry):
        if entry is None:
            return ()
        return entry if isinstance(entry, tuple) else (entry,)

    for what, size, entry in (("batch", q.shape[0], q_spec[0]),
                              ("heads", q.shape[2], q_spec[2]),
                              ("kv_heads", k.shape[2], kv_spec[2])):
        ways = mesh_axis_size(mesh, *axes(entry))
        if size % ways != 0:
            raise ValueError(
                f"flash attention on a mesh needs {what} ({size}) to be a "
                f"multiple of the mesh axes {axes(entry)} that shard it "
                f"({ways} ways); change the batch, the mesh or the rules")
    return jax.shard_map(local, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: float | None = None,
    block_q: int = DEFAULT_BLOCK_Q,
    block_k: int = DEFAULT_BLOCK_K,
    impl: str = "auto",  # auto | pallas | xla
    mesh: Optional[Mesh] = None,
    rules: Optional[dict] = None,
) -> jax.Array:
    """Multi-head attention with GQA support.

    Shapes: q (batch, seq, heads, head_dim); k/v (batch, seq, kv_heads,
    head_dim) with heads % kv_heads == 0.  Returns (batch, seq, heads,
    head_dim) in q's dtype.  Pass the ``mesh`` (and the logical ``rules``)
    the surrounding jit shards its arrays over: with more than one device
    the kernel path runs per shard.  Inside a ``shard_map`` pass none.
    """
    num_heads, head_dim = q.shape[2], q.shape[3]
    if num_heads % k.shape[2] != 0:
        raise ValueError(
            f"heads ({num_heads}) must be a multiple of kv_heads "
            f"({k.shape[2]})")
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(head_dim)
    # Backend query, not array query: works under tracing.  Off the TPU
    # (the CPU tests) "auto" is the plain XLA path and an explicit kernel
    # request runs the same kernels through the Pallas interpreter.
    on_tpu = jax.default_backend() == "tpu"
    if impl == "auto":
        impl = "pallas" if on_tpu else "xla"
    if impl == "xla":
        k, v = repeat_kv_heads(k, v, num_heads)
        out = _reference_attention(_pack(q), _pack(k), _pack(v), causal,
                                   sm_scale)
        return _unpack(out, q.shape[0])
    local = functools.partial(
        _pallas_attention, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, interpret=not on_tpu)
    if mesh is not None and mesh.size > 1:
        return _sharded(local, q, k, v, mesh, rules)
    return local(q, k, v)
