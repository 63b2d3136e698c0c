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
prefill and for a later chunk of a prompt, in plain ``jax.numpy``; state
products at ``highest`` precision, the state float32.
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

CHUNK = 64
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


def chunked(q, k, v, g, S0, chunk: int = CHUNK):
    """The same as ``recurrent`` by chunks of ``chunk`` tokens (L is padded
    to a multiple of it with tokens that change nothing).  Returns
    (o [L, H, d_v] float32, S_L [H, d_k, d_v] float32).  Heads that share a
    key run the one scan side by side over the group's key and query: its
    scores are made once a group, each head's decays laid over them.  S0
    may come PACKED (``pack_state``); S_L then goes back packed the same."""
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
    """``chunked`` with a key and a query for every head of v."""
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
    share = H // G
    return max(d for d in range(1, H + 1)
               if H % d == 0 and (share % d == 0 or d % share == 0)
               and (_STATE_BUFFERS * d * dk * dv * 4 <= STATE_BLOCKS_BYTES
                    or d == 1))


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
