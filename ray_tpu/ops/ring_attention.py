"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

Net-new relative to the reference, which has NO sequence parallelism anywhere
(SURVEY.md §2.4: `grep -ri 'ring_attention|context_parallel|ulysses'` over
/root/reference/python returns nothing — long context is delegated to vLLM
engine kwargs).  Here it is a first-class mesh axis (``sp``):

* **Ring attention** (`ring_attention`): each device holds a sequence shard
  of Q/K/V.  KV shards rotate around the ``sp`` ring via ``lax.ppermute``
  (nearest-neighbour ICI hops) while each device accumulates online-softmax
  partial attention for its local Q shard — full-sequence attention with
  O(seq/sp) activation memory per chip and no all-gather.  Causal masking is
  computed against *global* positions, so cross-ring-step causality is exact.

* **Ulysses** (`ulysses_attention`): ``lax.all_to_all`` swaps the sharded
  axis from sequence to heads (each device gets the full sequence for
  heads/sp heads), runs dense local flash attention, and swaps back.  One
  all-to-all each way; preferable when heads % sp == 0 and seq is moderate.

Both run *inside* ``jax.shard_map`` over the mesh; `sequence_parallel_attention`
is the public wrapper that binds mesh + partition specs.  Differentiation is
plain JAX AD through the scan/ppermute (the transpose of a ppermute is the
reverse ppermute, so the backward pass is also a ring).
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.ops.attention import NEG_INF, flash_attention, repeat_kv_heads
from ray_tpu.parallel.sharding import to_partition_spec


def _shard_positions(idx, s_loc: int, sp: int, layout: str):
    """Global sequence positions held by ring shard ``idx``.

    contiguous: shard i holds [i*s_loc, (i+1)*s_loc).
    zigzag: shard i holds the PAIR of chunks (i, 2*sp-1-i), each of size
    s_loc/2 — the standard fix for causal ring imbalance: every shard owns
    one early chunk and one late chunk, so the unmasked area each shard
    computes per ring step is near-uniform (spread <= 1 block instead of
    sp-1; see tests/test_ring_attention.py balance test).
    """
    if layout == "zigzag":
        c = s_loc // 2
        lo = idx * c + jnp.arange(c)
        hi = (2 * sp - 1 - idx) * c + jnp.arange(c)
        return jnp.concatenate([lo, hi])
    return idx * s_loc + jnp.arange(s_loc)


def zigzag_permutation(seq: int, sp: int):
    """Index arrays mapping contiguous -> zigzag layout and back.

    zigzag layout order: shard 0's chunks (0, 2sp-1), shard 1's (1, 2sp-2),
    ...  ``perm`` gathers a contiguous-layout sequence axis into zigzag
    order (``x_zig = x[:, perm]``); ``inv`` undoes it.
    """
    import numpy as np

    c = seq // (2 * sp)
    order = []
    for i in range(sp):
        order.append(np.arange(i * c, (i + 1) * c))
        order.append(np.arange((2 * sp - 1 - i) * c, (2 * sp - i) * c))
    perm = np.concatenate(order)
    inv = np.empty_like(perm)
    inv[perm] = np.arange(seq)
    return perm, inv


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    layout: str = "contiguous",  # contiguous | zigzag
) -> jax.Array:
    """Ring attention over the ``axis_name`` device ring.

    Must be called inside ``shard_map``.  Local shapes: q/k/v
    (batch, seq_local, heads, head_dim) — k/v may have fewer (GQA) heads.
    Global sequence = seq_local * ring size.  ``layout`` names how global
    positions map onto shards (see _shard_positions): "zigzag" balances
    causal work across the ring and is what sequence_parallel_attention's
    ``impl="zigzag"`` uses; correctness is exact for both layouts (masks
    compare true global positions).
    """
    sp = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(d)

    qf = q.astype(jnp.float32) * sm_scale
    rows = _shard_positions(idx, s_loc, sp, layout)  # global q positions

    # KV rotates "upward": device i sends to i+1, so after t steps device i
    # holds the shard originally at (i - t) mod sp.  GQA K/V rotate in their
    # raw (kv_heads) form — heads are repeated locally per block so each hop
    # moves only the necessary bytes.
    perm = [(i, (i + 1) % sp) for i in range(sp)]

    def block(k_cur, v_cur, src, acc, m_prev, l_prev):
        """Fold one KV shard (originally at ring position src) into the
        online-softmax accumulator."""
        k_rep, v_rep = repeat_kv_heads(k_cur, v_cur, h)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_rep.astype(jnp.float32))
        if causal:
            cols = _shard_positions(src, s_loc, sp, layout)
            mask = rows[:, None] >= cols[None, :]
            s = jnp.where(mask[None, None], s, NEG_INF)
        m_cur = jnp.max(s, axis=-1)  # (b, h, q)
        m_new = jnp.maximum(m_prev, m_cur)
        # Fully-masked blocks keep m == NEG_INF; exp(s - m) would be 1 for
        # every masked entry, so zero them explicitly.
        p = jnp.where(s <= NEG_INF / 2, 0.0, jnp.exp(s - m_new[..., None]))
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        acc = acc * alpha[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p, v_rep.astype(jnp.float32))
        return acc, m_new, l_new

    def body(carry, t):
        k_cur, v_cur, acc, m_prev, l_prev = carry
        acc, m_new, l_new = block(k_cur, v_cur, (idx - t) % sp,
                                  acc, m_prev, l_prev)
        k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
        v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
        return (k_nxt, v_nxt, acc, m_new, l_new), None

    acc0 = jnp.zeros((b, h, s_loc, d), jnp.float32)
    m0 = jnp.full((b, h, s_loc), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_loc), jnp.float32)
    # Scan covers the first sp-1 steps (each ends with a rotation); the last
    # shard is folded outside the scan so no rotation result is discarded.
    (k_last, v_last, acc, m, l), _ = jax.lax.scan(
        body, (k, v, acc0, m0, l0), jnp.arange(sp - 1))
    acc, m, l = block(k_last, v_last, (idx - (sp - 1)) % sp, acc, m, l)

    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe[..., None]  # (b, h, q, d)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    attn_impl: str = "auto",
) -> jax.Array:
    """Ulysses sequence parallelism: all-to-all heads<->sequence swap.

    Must be called inside ``shard_map``.  Local q: (batch, seq_local, heads,
    head_dim); requires heads % ring_size == 0.  After the swap each device
    holds the FULL sequence for heads/sp heads and runs dense (flash)
    attention locally; a reverse all-to-all restores sequence sharding.
    """
    sp = jax.lax.axis_size(axis_name)
    h = q.shape[2]
    if h % sp != 0:
        raise ValueError(f"ulysses needs heads ({h}) % sp ({sp}) == 0")

    def fwd(x):  # (b, s/sp, h, d) -> (b, s, h/sp, d)
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    def rev(x):  # (b, s, h/sp, d) -> (b, s/sp, h, d)
        return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                  tiled=True)

    # When the kv_heads axis itself splits over sp, swap the raw GQA K/V
    # (fewer bytes over ICI) and expand to full heads locally afterwards.
    if k.shape[2] % sp == 0:
        kg, vg = fwd(k), fwd(v)
        kg, vg = repeat_kv_heads(kg, vg, h // sp)
    else:
        k, v = repeat_kv_heads(k, v, h)
        kg, vg = fwd(k), fwd(v)

    out = flash_attention(fwd(q), kg, vg, causal=causal,
                          sm_scale=sm_scale, impl=attn_impl)
    return rev(out)


def sequence_parallel_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    mesh: Mesh,
    *,
    impl: str = "ring",  # ring | ulysses
    causal: bool = True,
    sm_scale: Optional[float] = None,
    rules: Optional[dict] = None,
    sp_axis: str = "sp",
) -> jax.Array:
    """Sequence-parallel attention bound to a mesh (callable inside jit).

    Global shapes: q (batch, seq, heads, head_dim), k/v (batch, seq,
    kv_heads, head_dim).  Batch/heads follow the logical sharding rules
    (batch over dp+fsdp, heads over tp); sequence is sharded over ``sp``.
    Falls back to plain flash attention when the sp axis has size 1.

    impl="zigzag": causal-balanced ring.  Inputs arrive in natural
    (contiguous) sequence order; a global zigzag gather re-shards them so
    every ring shard holds one early + one late chunk, the balanced ring
    runs, and the inverse gather restores natural order.  Trainers that
    keep activations in zigzag layout end-to-end (permute once at the
    embedding, with zigzag position ids for RoPE) can call ring_attention
    with layout="zigzag" directly and skip both gathers.
    """
    if mesh.shape.get(sp_axis, 1) == 1:
        return flash_attention(q, k, v, causal=causal, sm_scale=sm_scale,
                               mesh=mesh, rules=rules)

    q_spec = to_partition_spec(("batch", "seq", "heads", "head_dim"), rules)
    kv_spec = to_partition_spec(("batch", "seq", "kv_heads", "head_dim"),
                                rules)

    if impl == "zigzag":
        sp = mesh.shape[sp_axis]
        seq = q.shape[1]
        if seq % (2 * sp) != 0:
            raise ValueError(
                f"zigzag needs seq ({seq}) % 2*sp ({2 * sp}) == 0")
        perm, inv = zigzag_permutation(seq, sp)
        q, k, v = (jnp.take(x, perm, axis=1) for x in (q, k, v))

    def local(ql, kl, vl):
        if impl == "ulysses":
            return ulysses_attention(ql, kl, vl, sp_axis, causal=causal,
                                     sm_scale=sm_scale)
        return ring_attention(
            ql, kl, vl, sp_axis, causal=causal, sm_scale=sm_scale,
            layout="zigzag" if impl == "zigzag" else "contiguous")

    out = jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec),
        out_specs=q_spec,
        check_vma=False,
    )(q, k, v)
    if impl == "zigzag":
        out = jnp.take(out, inv, axis=1)
    return out
