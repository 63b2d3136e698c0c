"""The gated delta rule (Gated DeltaNet linear attention), two ways.

A head keeps a matrix S [d_v, d_k] (float32) that a token updates:

    S_t = a_t S_{t-1} (I - b_t k_t k_t^T) + b_t v_t k_t^T
        = a_t S_{t-1} + b_t (v_t - a_t S_{t-1} k_t) k_t^T,    o_t = S_t q_t

with a decay a_t = exp(g_t) in (0, 1) and a write strength b_t in (0, 2).
Everywhere below the state is held TRANSPOSED and PACKED,
``[head packs, d_k, pack * d_v]``: ``pack`` heads side by side along the
last axis, so that at d_v 192 (two heads: 384 lanes) and d_k 96 a state is
whole (8, 128) tiles and no byte of it is padding in HBM; as [d_v, d_k]
its rows of 96 would be stored as rows of 128.

``decode_update``: one token a slot (a Pallas kernel).  It is bound by the
state's bytes, 2.2 MB a slot a layer at 30 heads: the kernel reads each
live slot's state once and writes it once, in place (the state array is
aliased to the output; the layer is a scalar the block index reads), and
does not touch a slot that is not live.  Plain ``jax.numpy`` under XLA
reads the state three times (S k, the update, S q): that is why this one is
a kernel.  A grid step moves as many head packs of a slot as its buffers may
take of VMEM (``_packs_a_block``: 5 of the 15 at the served shape, and why
not all).  Off the TPU it runs through the Pallas interpreter.

``chunked``: a whole (padded) sequence from an initial state, for prefill,
in plain ``jax.numpy``: the WY / UT-transform of the delta rule with the
decays folded in.  Inside a chunk of C tokens the writes u_t = b_t (v_t -
a_t S_{t-1} k_t) obey a unit lower-triangular system, (I + diag(b) A) U =
diag(b) (V - diag(G) K S_0) with A[t, i] = (G_t / G_i) k_t . k_i for i < t
and G the running product of the decays, so all chunks solve theirs at once
and only the state goes from chunk to chunk, in a ``lax.scan`` of a few
matrix products.  The systems are solved by their inverses
(``_unit_lower_inverse``: the 16 x 16 blocks on the diagonal by forward
substitution, then blocks merged two by two, every step a batched matrix
product), because XLA's own triangular solve took three quarters of the
whole scan on the chip (2.6 of 3.4 ms a layer at 1,024 tokens; PERF.md
section 6, PR 38).  The state's products run at ``highest`` precision: the
state is float32 and is kept so.  A decay is taken as 1 + expm1(g)
wherever it multiplies the state a token at a time: the TPU's exp is good
to 1.5e-6 of its result, which 300 such products compound to 7e-5, where
expm1's error is that share of g.

``recurrent`` is the definition, a token at a time: the tests' yardstick.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def head_pack(n_heads: int, d_v: int) -> int:
    """Heads laid side by side in a packed state: the fewest that make its
    last axis whole 128-lane tiles, if the head count allows; else 1."""
    pack = 128 // math.gcd(d_v, 128)
    return pack if n_heads % pack == 0 else 1


def pack_state(S, pack: int):
    """[..., H, d_v, d_k] -> [..., H / pack, d_k, pack * d_v]."""
    *lead, H, dv, dk = S.shape
    S = S.reshape(*lead, H // pack, pack, dv, dk)
    S = jnp.moveaxis(S, -1, -3)  # [..., H/pack, d_k, pack, d_v]
    return S.reshape(*lead, H // pack, dk, pack * dv)


def unpack_state(S, pack: int):
    """The inverse of ``pack_state``."""
    *lead, G, dk, pdv = S.shape
    S = S.reshape(*lead, G, dk, pack, pdv // pack)
    S = jnp.moveaxis(S, -3, -1)  # [..., G, pack, d_v, d_k]
    return S.reshape(*lead, G * pack, pdv // pack, dk)


def decay(g):
    """exp(g) for a log decay g <= 0, as 1 + expm1(g) (see above)."""
    return 1.0 + jnp.expm1(g)


# ---------------------------------------------------------------------------
# the definition


def recurrent(q, k, v, g, beta, S0):
    """Token by token.  q, k: [L, H, d_k]; v: [L, H, d_v]; g (log decay),
    beta: [L, H]; S0: [H, d_v, d_k].  Returns (o [L, H, d_v], S_L), all
    float32."""
    f32 = jnp.float32

    def step(S, x):
        q, k, v, g, b = x
        S = S * decay(g)[:, None, None]
        u = b[:, None] * (v - jnp.einsum("hvk,hk->hv", S, k, precision=_HI))
        S = S + u[:, :, None] * k[:, None, :]
        return S, jnp.einsum("hvk,hk->hv", S, q, precision=_HI)

    S, o = jax.lax.scan(step, S0.astype(f32), tuple(
        x.astype(f32) for x in (q, k, v, g, beta)))
    return o, S


# ---------------------------------------------------------------------------
# prefill: chunks


def _unit_lower_inverse(system, base: int = 16):
    """The inverses of unit lower-triangular matrices [..., C, C].  The
    ``base`` x ``base`` blocks on the diagonal by forward substitution, a
    row at a time (row i of (I + D)^-1 is e_i - D[i, :i] times the rows
    before it), then neighbouring blocks merged, [[A, 0], [X, B]]^-1 =
    [[A^-1, 0], [-B^-1 X A^-1, B^-1]], until one is left.  C is ``base``
    times a power of two, or the whole matrix is one block."""
    C = system.shape[-1]
    if C % base or (C // base) & (C // base - 1):
        base = C
    n = C // base

    def block(row, col, size):
        return system[..., row * size:(row + 1) * size,
                      col * size:(col + 1) * size]

    D = jnp.stack([block(i, i, base) for i in range(n)], axis=-3)
    eye = jnp.eye(base, dtype=system.dtype)
    rows = [jnp.broadcast_to(eye[0], D.shape[:-2] + (base,))]
    for i in range(1, base):
        rows.append(eye[i] - jnp.einsum(
            "...j,...jk->...k", D[..., i, :i], jnp.stack(rows, axis=-2),
            precision=_HI))
    inv = jnp.stack(rows, axis=-2)  # [..., n, base, base]
    size = base
    while n > 1:
        A, B = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        X = jnp.stack([block(i + 1, i, size) for i in range(0, n, 2)],
                      axis=-3)  # the block under A, left of B
        low = -jnp.einsum("...ij,...jk,...kl->...il", B, X, A, precision=_HI)
        inv = jnp.concatenate(
            [jnp.concatenate([A, jnp.zeros_like(A)], axis=-1),
             jnp.concatenate([low, B], axis=-1)], axis=-2)
        size, n = 2 * size, n // 2
    return inv[..., 0, :, :]


def chunked(q, k, v, g, beta, S0, chunk: int = CHUNK):
    """The same as ``recurrent`` by chunks of ``chunk`` tokens (L is padded
    to a multiple of it with tokens that change nothing: g = 0, beta = 0).
    Returns (o [L, H, d_v] float32, S_L [H, d_v, d_k] float32)."""
    f32 = jnp.float32
    L, H, dk = q.shape
    n = -(-L // chunk)
    pad = n * chunk - L

    def chunks(x):  # [L, H, ...] -> [n, H, C, ...]
        x = jnp.pad(x.astype(f32), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape(n, chunk, *x.shape[1:]), 1, 2)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gam = jnp.cumsum(g, axis=-1)  # [n, H, C] log of the running decay
    # ratio[t, i] = G_t / G_i where i <= t, as exp of a difference <= 0
    diff = gam[..., :, None] - gam[..., None, :]
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    ratio = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    kk = jnp.einsum("nhtd,nhid->nhti", k, k, precision=_HI)
    strict = jnp.tril(jnp.ones((chunk, chunk), f32), -1)
    system = (jnp.eye(chunk, dtype=f32)
              + beta[..., :, None] * ratio * kk * strict)
    rhs = jnp.concatenate(
        [beta[..., None] * v,
         (beta * jnp.exp(gam))[..., None] * k], axis=-1)
    # U = W_v - W_k S_0^T: the part of a chunk that needs no state
    w = jnp.einsum("nhti,nhid->nhtd", _unit_lower_inverse(system), rhs,
                   precision=_HI)
    w_v, w_k = w[..., :v.shape[-1]], w[..., v.shape[-1]:]
    qk = jnp.einsum("nhtd,nhid->nhti", q, k, precision=_HI) * ratio
    q_in = q * jnp.exp(gam)[..., None]  # what of S_0 a query still sees
    # what of a write is left at the chunk's end
    k_out = k * jnp.exp(gam[..., -1:] - gam)[..., None]
    decay = jnp.exp(gam[..., -1])  # [n, H]

    def step(St, x):  # St = S^T: [H, d_k, d_v]
        w_v, w_k, qk, q_in, k_out, decay = x
        u = w_v - jnp.einsum("htk,hkv->htv", w_k, St, precision=_HI)
        o = (jnp.einsum("htk,hkv->htv", q_in, St, precision=_HI)
             + jnp.einsum("hti,hiv->htv", qk, u, precision=_HI))
        St = (St * decay[:, None, None]
              + jnp.einsum("htk,htv->hkv", k_out, u, precision=_HI))
        return St, o

    St, o = jax.lax.scan(step, jnp.swapaxes(S0.astype(f32), -1, -2),
                         (w_v, w_k, qk, q_in, k_out, decay))
    o = jnp.moveaxis(o, 1, 2).reshape(n * chunk, H, -1)[:L]
    return o, jnp.swapaxes(St, -1, -2)


# ---------------------------------------------------------------------------
# decode: one token a slot, the state updated where it lies


# What sets a block of the update: the form of ``ops/lightning.py``'s rule, the
# twin of this kernel (its comment has the account), with a budget of its own.
# A grid step costs 0.3-0.4 us whatever it moves, a step past the live slots
# too, and past that the time is the stream's (80 % of a v5e's 819 GB/s, reads
# beside writes): the kernel apart at [12, 32 slots, 15, 96, 384] (my chip
# runs, PR 58) takes 152.6 us at 19 live slots with 3 packs a step, 133.1 with
# 5 (0.74 MB, 96 steps; 191.0 at 28 live) and 129.1 with the slot's 15 (2.21
# MB, 32 steps; 189.1).  The last 3 % are not taken: with the whole slot a
# block XLA moves the served step's ``conv`` rows (26.5 MB) into VMEM and back
# around the layers (compiled for a v5e at any limit given the kernel;
# ``tests/test_tpu_compile.py`` holds the step to no such copy), so a block
# is the most head packs whose four buffers (the state in and out, each
# double-buffered) fit 4 MiB, 5 packs at that shape: inside the 16 MiB of
# VMEM a kernel gets unasked, so this one asks for none.
STATE_BLOCKS_BYTES = 4 * 1024 * 1024


def _packs_a_block(packs: int, dk: int, width: int) -> int:
    """Head packs of [d_k, width] float32 the update moves a grid step: the
    divisor of ``packs`` that is the most whose buffers fit
    ``STATE_BLOCKS_BYTES``."""
    return max(d for d in range(1, packs + 1)
               if packs % d == 0
               and (4 * d * dk * width * 4 <= STATE_BLOCKS_BYTES or d == 1))


def _decode_kernel(layer_ref, order_ref, live_ref, kq_ref, vec_ref, s_ref,
                   o_ref, s_out, *, pack: int):
    del layer_ref, order_ref  # the block indices read them
    hb, dk, width = s_ref.shape[2:]
    dv = width // pack

    @pl.when(pl.program_id(0) < live_ref[0])
    def _():
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, width), 1)
        for p in range(hb):
            def spread(cols):  # [d_k, heads] -> a pack's heads by lanes
                out = cols[:, p * pack:p * pack + 1]
                for j in range(1, pack):
                    out = jnp.where(lane < j * dv, out,
                                    cols[:, p * pack + j:p * pack + j + 1])
                return jnp.broadcast_to(out, (dk, width))

            kx, qx = spread(kq_ref[0, 0, 0]), spread(kq_ref[0, 0, 1])
            at = slice(p * width, (p + 1) * width)
            v, a, b = (vec_ref[0, 0, r:r + 1, at] for r in range(3))
            st = s_ref[0, 0, p] * a
            u = b * (v - jnp.sum(st * kx, axis=0, keepdims=True))
            st = st + kx * u
            s_out[0, 0, p] = st
            o_ref[0, 0, :, at] = jnp.sum(st * qx, axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("pack", "group", "interpret"))
def _decode_update(state, layer, q, k, v, g, beta, active, *, pack: int,
                   group: int, interpret: bool):
    f32 = jnp.float32
    B, H, dk = q.shape
    dv = v.shape[-1]
    n_groups = H // (pack * group)
    width = group * pack * dv

    def by_group(x):  # [B, H, d_k] -> [B, groups, d_k, heads a group]
        return jnp.swapaxes(x.astype(f32).reshape(B, n_groups, -1, dk), 2, 3)

    def by_lane(x):  # [B, H, d_v] -> [B, groups, lanes]
        return x.astype(f32).reshape(B, n_groups, width)

    def spread(x):  # a head's number over its d_v lanes
        return by_lane(jnp.broadcast_to(x[..., None], (B, H, dv)))

    kq = jnp.stack([by_group(k), by_group(q)], axis=2)
    vec = jnp.stack([by_lane(v), spread(decay(g)), spread(beta)], axis=2)
    # live slots first; the steps past them stay on the last live block
    # (same index: no copy in, and it is written back once, whole)
    order = jnp.argsort(~active, stable=True).astype(jnp.int32)
    live = jnp.sum(active).astype(jnp.int32).reshape(1)

    def at(i, j, layer_ref, order_ref, live_ref):
        last = jnp.maximum(live_ref[0] - 1, 0)
        on = i < live_ref[0]
        return (order_ref[jnp.minimum(i, last)],
                jnp.where(on, j, n_groups - 1))

    def small(i, j, *refs):
        return (*at(i, j, *refs), 0, 0)

    def columns(i, j, *refs):
        return (*at(i, j, *refs), 0, 0, 0)

    def rows(i, j, layer_ref, *refs):
        slot, grp = at(i, j, layer_ref, *refs)
        return (layer_ref[0], slot, grp, 0, 0)

    o, state = pl.pallas_call(
        functools.partial(_decode_kernel, pack=pack),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, n_groups),
            in_specs=[
                pl.BlockSpec((1, 1, 2, dk, group * pack), columns),
                pl.BlockSpec((1, 1, 3, width), small),
                pl.BlockSpec((1, 1, group, dk, pack * dv), rows),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, 1, width), small),
                pl.BlockSpec((1, 1, group, dk, pack * dv), rows),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, n_groups, 1, width), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operands count the scalars: the state is the sixth
        input_output_aliases={5: 1},
        interpret=interpret,
        name="gated_delta_update",
    )(jnp.asarray(layer, jnp.int32).reshape(1), order, live, kq, vec,
      state)
    o = jnp.where(active[:, None, None], o.reshape(B, H, dv), 0.0)
    return o, state


def decode_update(state, layer, q, k, v, g, beta, active, *, pack: int):
    """One token for every live slot, in place.

    state: [layers, slots, H / pack, d_k, pack * d_v] float32, every
    layer's rows; only ``layer`` (an int32 scalar, traced or not) is read
    and written, and of it only the slots where ``active`` [B] holds.
    q, k: [B, H, d_k]; v: [B, H, d_v]; g (log decay), beta: [B, H].
    Returns (o [B, H, d_v] float32, zeros where not active; the state)."""
    if state.ndim != 5 or state.dtype != jnp.float32:
        raise ValueError(
            f"the gated delta update takes the packed float32 state "
            f"[layers, slots, head packs, d_k, pack * d_v]; got "
            f"{state.dtype}{list(state.shape)}")
    B, H, dk = q.shape
    dv = v.shape[-1]
    if state.shape[1:] != (B, H // pack, dk, pack * dv):
        raise ValueError(
            f"state rows {state.shape[1:]} do not hold {B} slots of {H} "
            f"heads [{dv}, {dk}] packed by {pack}")
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and ((pack * dv) % 128 or dk % 8):
        raise ValueError(
            f"on the TPU the gated delta update moves whole tiles, and a "
            f"[{dk}, {pack} x {dv}] state is not made of them: d_k must be "
            f"a multiple of 8 and pack * d_v of 128")
    return _decode_update(state, layer, q, k, v, g, beta, active, pack=pack,
                          group=_packs_a_block(H // pack, dk, pack * dv),
                          interpret=not on_tpu)
