"""The recurrence of linear attention with a decay and no delta term, three
ways: Lightning Attention's (Qin et al., arXiv:2401.04658) and Mamba-2's
selective scan (Dao and Gu, arXiv:2405.21060) are two cases of it.

A head keeps a matrix S [d_k, d_v] (float32) that a token updates:

    S_t = exp(g_t) S_{t-1} + k_t^T v_t,        o_t = q_t S_t

with g_t <= 0 the LOG of the head's decay at that token.  A FIXED decay is
the case ``g`` constant (models/minicpm_sala.py: ``log_decays``, the ALiBi
slopes); Mamba-2's is the case ``g = dt A``, ``v = dt x``, ``k = B``,
``q = C`` (models/falcon_h1.py), with d_k (the state's size N) unequal to
d_v (a head's width) and B and C shared by the heads of a GROUP.  No write
strength and no correction by what the state already holds
(ops/gated_delta.py's delta rule), so no triangular system: inside a chunk
the outputs are one masked product, and only the state goes from chunk to
chunk.

Every form takes the decay as its log, a number a token a head (``g`` [L,
H]), so that a caller can let a padded position change nothing (g = 0 and
a zero key) with the same program, and so that a decay between two tokens
of a chunk is ``exp(G_t - G_i)``, the exponential of a difference of
running sums, never a quotient of two running products (at a decay of
exp(-1.6) a token a chunk's product underflows float32; the difference does
not).  Keys and queries come a GROUP, [L, G, d_k] with H a multiple of G
(G = H: a head its own); head h reads group h // (H / G), and no form
repeats them to H heads: a group's scores are made once, and what is a
head's own is what its decays make of them.  The state is held as it is
written, [H, d_k, d_v], where d_v fills the lanes: at d_v = 128 and d_k a
multiple of 8 a head's state is whole (8, 128) tiles already.  A NARROWER
head (models/nemotron_h.py: Mamba-2 heads of 64) would be half-lane tiles,
padded to twice its bytes wherever it is stored or moved, so such a state is
held PACKED (``pack_state``): ``pack`` heads that read the same key side by
side in the lanes, [H / pack, d_k, pack x d_v], as ``gated_delta.pack_state``
lays heads of 192.  The update is the same arithmetic on it (a key's column
against a row of ``pack`` heads' values, each lane under its own head's
decay), so ``decode_update`` takes either by the rows' shape and ``chunked``
hands its state back as S0 came.

``recurrent``: the definition, a token at a time (the tests' yardstick).
``chunked``: a whole (padded) sequence from an initial state S0, for
prefill and for a later chunk of a prompt, ONE Pallas kernel: a grid over
(block of state rows, chunk of the sequence), the chunks in order; the
block's state stays in VMEM from the first chunk to the last and a chunk's
scores, decays and products exist nowhere else; every product at
``highest`` precision on float32 operands (Mosaic makes six passes of it),
the state float32.  One kernel for a head its own key, keys a group and
packed rows, told apart by the shapes it is handed.
``chunked_plain``: the same by chunks in plain ``jax.numpy`` (what
``chunked`` was, and its second yardstick): every chunk's scores and writes
for the whole sequence as float32 arrays in HBM, then a ``lax.scan``.
``decode_update``: one token a slot, a Pallas kernel on the pattern of
``gated_delta.decode_update``: a live slot's state is read once and written
once where it lies (aliased to the output), another slot's not at all.  A
grid step moves as much of a slot's state as its buffers may take of VMEM,
the whole slot at the served shapes (``_heads_a_block``): a step costs
0.3-0.4 us whatever it moves, and past that the time is the stream's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 128
LANES = 128  # a tile's
_HI = jax.lax.Precision.HIGHEST


def log_decays(n_heads: int):
    """log lambda_h = -2^(-8 (h + 1) / n_heads), h = 0 .. n_heads - 1: the
    ALiBi slopes, as Lightning Attention sets its decay (float32 [H])."""
    h = jnp.arange(1, n_heads + 1, dtype=jnp.float32)
    return -jnp.exp2(-8.0 * h / n_heads)


def _by_group(q, v, g, S0):
    """(v, g, S0) with their heads split [G, H / G], G the groups q has."""
    G, H = q.shape[1], v.shape[1]
    if H % G:
        raise ValueError(
            f"{H} heads are no whole number of heads for each of the {G} "
            f"groups the keys and queries come in")
    f32 = jnp.float32
    return (v.astype(f32).reshape(v.shape[0], G, H // G, v.shape[-1]),
            g.astype(f32).reshape(g.shape[0], G, H // G),
            S0.astype(f32).reshape(G, H // G, *S0.shape[1:]))


def pack_state(S, pack: int):
    """[..., H, d_k, d_v] -> [..., H / pack, d_k, pack * d_v]: heads ``pack
    i .. pack i + pack - 1`` side by side in the lanes (they must read one
    key: ``decode_update`` checks it)."""
    if pack == 1:
        return S
    *lead, H, dk, dv = S.shape
    S = S.reshape(*lead, H // pack, pack, dk, dv)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, H // pack, dk, pack * dv)


def unpack_state(S, pack: int):
    """The inverse of ``pack_state``."""
    if pack == 1:
        return S
    *lead, Hp, dk, lanes = S.shape
    S = S.reshape(*lead, Hp, dk, pack, lanes // pack)
    return jnp.swapaxes(S, -3, -2).reshape(*lead, Hp * pack, dk,
                                           lanes // pack)


def _pack_of(rows_shape, H: int, dk: int, dv: int) -> int:
    """How many heads lie side by side in state rows [..., H / pack, d_k,
    pack * d_v] that hold H heads [d_k, d_v]."""
    pack = rows_shape[-1] // dv if dv else 0
    if not pack or tuple(rows_shape[-3:]) != (H // pack, dk, pack * dv) \
            or H % pack:
        raise ValueError(
            f"state rows {tuple(rows_shape[-3:])} hold no {H} heads "
            f"[{dk}, {dv}], as they are or side by side in the lanes")
    return pack


def recurrent(q, k, v, g, S0):
    """Token by token.  q, k: [L, G, d_k]; v: [L, H, d_v]; g (log decay):
    [L, H]; S0: [H, d_k, d_v].  Returns (o [L, H, d_v], S_L), float32."""
    f32 = jnp.float32
    L, H, dv = v.shape
    v, g, S0 = _by_group(q, v, g, S0)

    def step(S, x):  # S: [G, R, d_k, d_v]
        q, k, v, g = x
        S = (S * jnp.exp(g)[..., None, None]
             + k[:, None, :, None] * v[:, :, None, :])
        return S, jnp.einsum("gk,grkv->grv", q, S, precision=_HI)

    S, o = jax.lax.scan(step, S0, (q.astype(f32), k.astype(f32), v, g))
    return o.reshape(L, H, dv), S.reshape(H, *S.shape[2:])


def chunked_plain(q, k, v, g, S0, chunk: int = CHUNK):
    """``chunked`` in plain ``jax.numpy`` (the kernel's yardstick beside
    ``recurrent``; what ``chunked`` was before it was a kernel): a chunk's
    scores, ``inside`` and ``wrote`` made for the WHOLE sequence at once,
    float32 [n, H, ...] arrays in HBM, and a ``lax.scan`` over them.  Heads
    that share a key run the one scan side by side over the group's key and
    query."""
    L, H, dv = v.shape
    G = q.shape[1]
    pack = _pack_of(S0.shape, H, q.shape[2], dv)
    S0 = unpack_state(S0, pack)
    if G == H:
        o, S = _chunked(q, k, v, g, S0, chunk)
        return o, pack_state(S, pack)
    v, g, S0 = _by_group(q, v, g, S0)
    o, S = jax.vmap(lambda v, g, S0: _chunked(q, k, v, g, S0, chunk),
                    in_axes=(2, 2, 1), out_axes=(2, 1))(v, g, S0)
    return o.reshape(L, H, dv), pack_state(S.reshape(H, *S.shape[2:]), pack)


def _chunked(q, k, v, g, S0, chunk: int):
    """``chunked_plain`` with a key and a query for every head of v."""
    f32 = jnp.float32
    L, H, _ = q.shape
    n = -(-L // chunk)
    pad = n * chunk - L

    def chunks(x):  # [L, H, ...] -> [n, H, C, ...]
        x = jnp.pad(x.astype(f32), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape(n, chunk, *x.shape[1:]), 1, 2)

    q, k, v, g = (chunks(x) for x in (q, k, v, g))
    gam = jnp.cumsum(g, axis=-1)  # [n, H, C] log of the running decay
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    # ratio[t, i] = G_t / G_i where i <= t, as exp of a difference <= 0
    diff = gam[..., :, None] - gam[..., None, :]
    ratio = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    qk = jnp.einsum("nhtd,nhid->nhti", q, k, precision=_HI) * ratio
    inside = jnp.einsum("nhti,nhiv->nhtv", qk, v, precision=_HI)
    q_in = q * jnp.exp(gam)[..., None]  # what of S_0 a query still sees
    # what of a write is left at the chunk's end
    k_out = k * jnp.exp(gam[..., -1:] - gam)[..., None]
    wrote = jnp.einsum("nhtk,nhtv->nhkv", k_out, v, precision=_HI)
    decay = jnp.exp(gam[..., -1])  # [n, H]

    def step(S, x):
        q_in, inside, wrote, decay = x
        o = inside + jnp.einsum("htk,hkv->htv", q_in, S, precision=_HI)
        return S * decay[:, None, None] + wrote, o

    S, o = jax.lax.scan(step, S0.astype(f32), (q_in, inside, wrote, decay))
    return jnp.moveaxis(o, 1, 2).reshape(n * chunk, H, -1)[:L], S


# ---------------------------------------------------------------------------
# prefill: a sequence's chunks in ONE kernel, the state in VMEM between them


# What sets a block of the scan.  A grid step holds ``rows`` state rows
# [d_k, lanes] (a head's, or ``pack`` narrow heads' side by side) through
# every chunk of the sequence: S0's block in and S_L's block out, each
# double-buffered, are the VMEM that grows with it, beside a chunk's q, k, v,
# o and decays.  More rows a step are fewer steps (0.3-0.4 us each whatever
# they hold, PR 58) and a longer unrolled body; the budget keeps the kernel
# under the 16 MiB a kernel gets unasked, so that it takes nothing from what
# XLA keeps in VMEM around it.
SCAN_BLOCKS_BYTES = 8 * 1024 * 1024


def _whole_keys(n: int, keys: int) -> list:
    """The divisors of ``n`` heads (or state rows) that are heads of ONE key
    or every head of several (n / keys read a key)."""
    share = n // keys
    return [d for d in range(1, n + 1)
            if n % d == 0 and (share % d == 0 or d % share == 0)]


def _rows_a_block(rows: int, keys: int, dk: int, lanes: int, chunk: int,
                  ) -> int:
    """State rows [d_k, lanes] float32 the scan holds a grid step: a divisor
    of ``rows`` that is rows of ONE key or every row of several (rows /
    keys read a key) and whole sublane tiles of heads (8, or all there
    are), the most whose buffers fit ``SCAN_BLOCKS_BYTES``."""
    share = rows // keys

    def buffers(d):  # the state in and out, v and o, the keys and queries
        return 4 * (_STATE_BUFFERS * d * dk * lanes + 4 * d * chunk * lanes
                    + 4 * max(d // share, 1) * chunk * dk)

    allowed = [d for d in _whole_keys(rows, keys)
               if d % 8 == 0 or d == rows]
    return max([d for d in allowed if buffers(d) <= SCAN_BLOCKS_BYTES],
               default=allowed[0])


def _scan_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, ge_ref, s0_ref, o_ref,
                 s_ref, *, pack: int):
    f32 = jnp.float32
    C = q_ref.shape[0]
    hb, dk, lanes = s_ref.shape
    kb = q_ref.shape[1] // dk  # the block's keys: one a row, or one for all
    dv = lanes // pack

    def dot(a, b, dims):
        return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HI,
                                   preferred_element_type=f32)

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    # a row's ``pack`` heads lie side by side: in the lanes of its values
    # (d_v each) and, for what goes on inside the chunk, in the columns of
    # its scores (C each)
    def runs(shape, axis, width):  # where ``axis`` is in run u of ``width``
        at = jax.lax.broadcasted_iota(jnp.int32, shape, axis)
        return [(at >= u * width) & (at < (u + 1) * width)
                for u in range(pack)]

    of_lane = runs((C, lanes), 1, dv)  # the head a value's lane is of
    of_col = runs((C, pack * C), 1, C)  # the head a score's column is of
    t, i = (jax.lax.broadcasted_iota(jnp.int32, (C, pack * C), axis)
            for axis in range(2))
    lower = functools.reduce(jnp.logical_or, (
        of_col[u] & (i - u * C <= t) for u in range(pack)))
    # each head's values against its own columns alone
    own = functools.reduce(jnp.logical_or, (
        rows & cols for rows, cols in zip(runs((pack * C, lanes), 0, C),
                                          runs((pack * C, lanes), 1, dv))))

    def by_head(cols, of):  # [C, pack] a head a column -> a head's a lane
        out = jnp.broadcast_to(cols[:, :1], of[0].shape)
        for u in range(1, pack):
            out = jnp.where(of[u], cols[:, u:u + 1], out)
        return out

    gam = gc_ref[0, 0]  # [C, heads] log of the running decay, a column a head
    seen = jnp.exp(gam)  # what of S_0 a query still sees
    left = jnp.exp(gam[C - 1:C] - gam)  # what of a write is left at the end
    scored = None  # the key whose scores ``scores`` holds
    for p in range(hb):
        c = p * kb // hb
        q = q_ref[:, c * dk:(c + 1) * dk].astype(f32)
        k = k_ref[:, c * dk:(c + 1) * dk].astype(f32)
        if c != scored:  # a key's scores are made once, [C, pack * C]
            scored, scores = c, dot(q, jnp.concatenate([k] * pack, axis=0),
                                    ((1,), (1,)))
        at = slice(p * pack, (p + 1) * pack)
        # ratio[t, i] = G_t / G_i where i <= t, as exp of a difference <= 0
        diff = by_head(gam[:, at], of_col) - gr_ref[0, 0, p:p + 1, :]
        ratio = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
        # the row's heads' values side by side, as its lanes hold them
        if v_ref.ndim == 3:  # [C, heads, d_v]
            v = jnp.concatenate([v_ref[:, h, :].astype(f32)
                                 for h in range(p * pack, (p + 1) * pack)],
                                axis=1)
        else:  # [heads x d_v, C]: the tokens in the lanes
            v = v_ref[p * lanes:(p + 1) * lanes, :].astype(f32).T
        mine = jnp.concatenate([v] * pack, axis=0)
        if pack > 1:
            mine = jnp.where(own, mine, 0.0)
        S = s_ref[p]
        o = (dot(scores * ratio, mine, ((1,), (0,)))
             + dot(q, S, ((1,), (0,))) * by_head(seen[:, at], of_lane))
        if o_ref.ndim == 3:
            for u in range(pack):
                o_ref[:, p * pack + u, :] = o[:, u * dv:(u + 1) * dv]
        else:
            o_ref[p * lanes:(p + 1) * lanes, :] = o.T
        s_ref[p] = (S * jnp.exp(ge_ref[0, :, p * lanes:(p + 1) * lanes])
                    + dot(k, v * by_head(left[:, at], of_lane),
                          ((0,), (0,))))


@functools.partial(jax.jit, static_argnames=("chunk", "rows", "interpret"))
def _scan(q, k, v, g, S0, *, chunk: int, rows: int, interpret: bool):
    f32 = jnp.float32
    L, G, dk = q.shape
    H, dv = v.shape[1:]
    n_rows, _, lanes = S0.shape  # H heads of d_v lanes, or H / pack of pack
    pack = lanes // dv
    C = chunk
    n = -(-L // C)
    n_blocks = n_rows // rows
    # keys a block reads: a row's own (G = H), or the one its rows share
    kb = rows * G // n_rows or 1
    per = n_blocks // (G // kb)  # row blocks that read each block of keys

    def padded(x):  # with tokens that change nothing: g = 0, a zero key
        return jnp.pad(x, ((0, n * C - L),) + ((0, 0),) * (x.ndim - 1))

    q, k, v = (padded(x) for x in (q, k, v))
    q, k = (x.reshape(n * C, -1) for x in (q, k))
    # v and o are moved as XLA holds them, so that no pass over them stands
    # beside the kernel: heads as wide as the lanes a block of [L, H, d_v];
    # narrower ones (d_v 64) with the TOKENS in the lanes, [H x d_v, L],
    # which is how XLA lays such an array out wherever a program makes or
    # reads one (compiled for a v5e: f32[2048,128,64]{0,2,1}; laid out again
    # as [L, H x d_v] they cost a copy of 64 MB a layer each way)
    wide = dv % LANES == 0
    if wide:
        values = pl.BlockSpec((C, rows * pack, dv), lambda j, c: (c, j, 0))
        o_shape = (n * C, H, dv)
    else:
        v = jnp.moveaxis(v, 0, 2).reshape(H * dv, n * C)
        values = pl.BlockSpec((rows * lanes, C), lambda j, c: (j, c))
        o_shape = (H * dv, n * C)
    # the log of the running decay INSIDE a chunk (H stays in the lanes for
    # the sum: an array whose last axis is ``pack`` is padded 64-fold), then
    # a column a head for what goes down the chunk, a row a state row for
    # what goes along it
    gam = jnp.cumsum(padded(g.astype(f32)).reshape(n, C, H), axis=1)
    gc = jnp.swapaxes(gam.reshape(n, C, n_blocks, rows * pack), 1, 2)
    gr = jnp.swapaxes(gam, 1, 2).reshape(n, n_blocks, rows, pack * C)
    # and of a whole chunk, a lane (a [1, 1] decay would have to be spread
    # over sublanes and lanes at once, which Mosaic does not do)
    ge = jnp.repeat(gam[:, -1].reshape(n, 1, H), dv, axis=-1)
    width = rows * lanes
    o, S = pl.pallas_call(
        functools.partial(_scan_kernel, pack=pack),
        grid=(n_blocks, n),
        in_specs=[
            pl.BlockSpec((C, kb * dk), lambda j, c: (c, j // per)),
            pl.BlockSpec((C, kb * dk), lambda j, c: (c, j // per)),
            values,
            pl.BlockSpec((1, 1, C, rows * pack), lambda j, c: (c, j, 0, 0)),
            pl.BlockSpec((1, 1, rows, pack * C), lambda j, c: (c, j, 0, 0)),
            pl.BlockSpec((1, 1, width), lambda j, c: (c, 0, j)),
            pl.BlockSpec((rows, dk, lanes), lambda j, c: (j, 0, 0)),
        ],
        out_specs=[
            values,
            # the same block through a sequence's chunks: the state stays
            # in VMEM between them and is written back once
            pl.BlockSpec((rows, dk, lanes), lambda j, c: (j, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct(o_shape, f32),
                   jax.ShapeDtypeStruct(S0.shape, f32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lightning_scan",
    )(q, k, v, gc, gr, ge, S0.astype(f32))
    if not wide:
        o = jnp.moveaxis(o.reshape(H, dv, n * C), 2, 0)
    return o[:L], S


def chunked(q, k, v, g, S0, chunk: int = CHUNK):
    """The same as ``recurrent`` by chunks of ``chunk`` tokens (L is padded
    to a multiple of it with tokens that change nothing), in ONE Pallas
    kernel: a grid over (block of state rows, chunk), the chunk axis in
    order; the block's state stays in VMEM from the first chunk to the last
    (read from S0 once, written to S_L once), and a chunk's scores, decays
    and products exist nowhere else.  Returns (o [L, H, d_v] float32, S_L
    float32 laid out as S0 came: [H, d_k, d_v], or PACKED, ``pack_state``).
    A key's scores are made once and each head's decays laid over them;
    heads packed side by side are one product over the row's lanes, each
    lane under its own head's decay."""
    L, H, dv = v.shape
    G, dk = q.shape[1:]
    if H % G:
        raise ValueError(
            f"{H} heads are no whole number of heads for each of the {G} "
            f"groups the keys and queries come in")
    pack = _pack_of(S0.shape, H, dk, dv)
    if (H // G) % pack:
        raise ValueError(
            f"state rows {tuple(S0.shape)} hold heads of different keys "
            f"side by side ({H // G} heads read a key)")
    lanes = pack * dv
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and (lanes % LANES or dk % LANES
                   or chunk % (16 if dv % LANES == 0 else LANES)):
        raise ValueError(
            f"on the TPU the chunked scan moves whole tiles, and chunks of "
            f"{chunk} keys [{dk}] over rows [{dk}, {lanes}] are not made "
            f"of them: d_k and the lanes a head's rows take (d_v, or the "
            f"d_v of the heads packed side by side) must be multiples of "
            f"{LANES}, the chunk of 16 (of {LANES} where the heads are "
            f"narrower than the lanes: their tokens lie in them)")
    return _scan(q, k, v, g, S0, chunk=chunk,
                 rows=_rows_a_block(H // pack, G, dk, lanes, chunk),
                 interpret=not on_tpu)


# ---------------------------------------------------------------------------
# decode: one token a slot, the state updated where it lies


# What sets a block of the update (PERF.md section 6, PR 58: my chip runs,
# the kernel apart).  Falcon-H1's [64 slots, 32, 256, 128] with 50 live, 419
# MB read and written, 512 us at a v5e's 819 GB/s: 704 us in blocks of 4 heads
# (0.5 MB, 64 x 8 = 512 grid steps), 684 and 680 us at 1 and 2 MB, 660 us
# with a slot's whole 4.19 MB a step (64 steps); MiniCPM-SALA's [32, 32, 128,
# 128] with 24 live 178.5 -> 160.2 us (128 -> 32 steps), with 7 live 64.7 ->
# 50.4.  Two costs lie over the bytes.  A grid step costs 0.3-0.4 us whatever
# it moves, a step past the live slots too: that is what small blocks lose.
# And the stream itself: a body that only copies its block takes the same time
# at every size (659.3 against 659.8 us), reads alone move at 750 GB/s and
# reads beside writes at 636, so at a slot a step the kernel is at what this
# pipeline gets from the memory (78 % of the peak) and its arithmetic is free.
# So a block is the most heads of a slot whose buffers (the state in and out,
# each double-buffered) fit ``STATE_BLOCKS_BYTES`` of VMEM, the whole slot
# where it fits.  Where those buffers and ``_VMEM_BESIDE_BYTES`` (the keys',
# vectors' and outputs' blocks: 0.04-0.23 MiB as compiled for a v5e) pass the
# 16 MiB a kernel gets unasked, the kernel asks for them and no more (Falcon-
# H1: 17 MiB): a core has 128 MiB, but what a kernel holds XLA cannot use
# around it.
STATE_BLOCKS_BYTES = 20 * 1024 * 1024
_STATE_BUFFERS = 4
_VMEM_BESIDE_BYTES = 1024 * 1024
_VMEM_DEFAULT_BYTES = 16 * 1024 * 1024  # what a kernel gets unasked


def _heads_a_block(H: int, G: int, dk: int, dv: int) -> int:
    """Heads of [d_k, d_v] float32 the update moves a grid step: a divisor
    of H that is heads of ONE key or every head of several (H / G heads
    read a key), the most whose buffers fit ``STATE_BLOCKS_BYTES``."""
    return max(d for d in _whole_keys(H, G)
               if _STATE_BUFFERS * d * dk * dv * 4 <= STATE_BLOCKS_BYTES
               or d == 1)


def _decode_kernel(layer_ref, order_ref, live_ref, kq_ref, vec_ref, s_ref,
                   o_ref, s_out):
    del layer_ref, order_ref  # the block indices read them
    hb, _, dv = s_ref.shape[2:]
    kb = kq_ref.shape[-1]  # the block's keys: one a head, or one for all

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        for p in range(hb):
            c = p * kb // hb
            kx = kq_ref[0, 0, 0][:, c:c + 1]  # [d_k, 1]
            qx = kq_ref[0, 0, 1][:, c:c + 1]
            at = slice(p * dv, (p + 1) * dv)
            v, a = (vec_ref[0, 0, r:r + 1, at] for r in range(2))  # [1, d_v]
            st = s_ref[0, 0, p] * a + kx * v
            s_out[0, 0, p] = st
            o_ref[0, 0, :, at] = jnp.sum(st * qx, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("group", "interpret"))
def _decode_update(state, layer, q, k, v, g, active, *, group: int,
                   interpret: bool):
    f32 = jnp.float32
    B, G, dk = q.shape
    H, dv = v.shape[1:]
    # the rows as they lie: H heads of d_v lanes, or H / pack of pack * d_v
    # (a packed head's lanes are its heads' in order, as ``by_lane`` has
    # them; the kernel sees wider heads and no difference)
    rows, lanes = state.shape[2], state.shape[4]
    n_groups = rows // group  # blocks of ``group`` heads
    width = group * lanes
    # keys a block reads: a head's own (G = H), or the one its heads share
    kb = group * G // rows or 1
    n_keys = G // kb  # key blocks; ``per`` head blocks read each
    per = n_groups // n_keys

    def by_group(x):  # [B, G, d_k] -> [B, key blocks, d_k, keys a block]
        return jnp.swapaxes(x.astype(f32).reshape(B, n_keys, kb, dk), 2, 3)

    def by_lane(x):  # [B, H, d_v] -> [B, groups, lanes]
        return x.astype(f32).reshape(B, n_groups, width)

    kq = jnp.stack([by_group(k), by_group(q)], axis=2)
    vec = jnp.stack([by_lane(v), by_lane(jnp.broadcast_to(
        jnp.exp(g.astype(f32))[..., None], (B, H, dv)))], axis=2)
    # live slots first; the steps past them stay on the last live block
    # (same index: no copy in, and it is written back once, whole)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    live = jnp.sum(active).astype(jnp.int32).reshape(1)

    vmem = _STATE_BUFFERS * group * dk * lanes * 4 + _VMEM_BESIDE_BYTES

    def at(i, j, layer_ref, order_ref, live_ref):
        last = jnp.maximum(live_ref[0] - 1, 0)
        on = i < live_ref[0]
        return (order_ref[jnp.minimum(i, last)],
                jnp.where(on, j, n_groups - 1))

    def small(i, j, *refs):
        return (*at(i, j, *refs), 0, 0)

    def columns(i, j, *refs):
        slot, grp = at(i, j, *refs)
        return (slot, grp if per == 1 else grp // per, 0, 0, 0)

    def rows_of(i, j, layer_ref, *refs):
        slot, grp = at(i, j, layer_ref, *refs)
        return (layer_ref[0], slot, grp, 0, 0)

    o, state = pl.pallas_call(
        _decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, n_groups),
            in_specs=[
                pl.BlockSpec((1, 1, 2, dk, kb), columns),
                pl.BlockSpec((1, 1, 2, width), small),
                pl.BlockSpec((1, 1, group, dk, lanes), rows_of),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, width), small),
                pl.BlockSpec((1, 1, group, dk, lanes), rows_of),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, n_groups, 1, width), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalars: the state is the sixth
        input_output_aliases={5: 1},
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=vmem if vmem > _VMEM_DEFAULT_BYTES else None),
        interpret=interpret,
        name="lightning_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, live, kq, vec,
      state)
    o = jnp.where(active[:, None, None], o.reshape(B, H, dv), 0.0)
    return o, state


def decode_update(state, layer, q, k, v, g, active):
    """One token for every live slot, in place.

    state: [layers, slots, H, d_k, d_v] float32, every layer's rows, or
    PACKED [layers, slots, H / pack, d_k, pack * d_v] (``pack_state``: the
    heads side by side read one key, so ``pack`` divides H / G); only
    ``layer`` (an int32 scalar, traced or not) is read and written, and of
    it only the slots where ``active`` [B] holds.  q, k: [B, G, d_k] (H a
    multiple of G); v: [B, H, d_v]; g (log decay): [B, H].  Returns
    (o [B, H, d_v] float32, zeros where not active; the state)."""
    if state.ndim != 5 or state.dtype != jnp.float32:
        raise ValueError(
            f"the lightning update takes the float32 state [layers, slots, "
            f"H, d_k, d_v]; got {state.dtype}{list(state.shape)}")
    B, G, dk = q.shape
    H, dv = v.shape[1:]
    pack = _pack_of(state.shape, H, dk, dv)
    if state.shape[1] != B or H % G or (H // G) % pack:
        raise ValueError(
            f"state rows {state.shape[1:]} do not hold {B} slots of {H} "
            f"heads [{dk}, {dv}], {H // G} to each of {G} keys (heads side "
            f"by side in the lanes read one key)")
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and (pack * dv % 128 or dk % 8):
        raise ValueError(
            f"on the TPU the lightning update moves whole tiles, and rows "
            f"[{dk}, {pack * dv}] are not made of them: d_k must be a "
            f"multiple of 8 and the lanes a head's rows take (d_v, or the "
            f"d_v of the heads packed side by side) of 128")
    return _decode_update(state, layer, q, k, v, g, active,
                          group=_heads_a_block(H // pack, G, dk, pack * dv),
                          interpret=not on_tpu)
