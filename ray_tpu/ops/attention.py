"""Fused attention for TPU: Pallas flash-attention forward AND backward.

Net-new relative to the reference, which delegates attention math to
torch/vLLM (SURVEY.md §2.4): here it is a first-class op.  Forward is a
Pallas kernel — online-softmax over KV blocks, O(seq) memory, bf16 inputs
with f32 accumulation on the MXU — and saves the per-row logsumexp.  The
backward is the FlashAttention-2 split, also in Pallas: a dK/dV kernel
gridded over KV blocks and a dQ kernel gridded over Q blocks, each
recomputing p = exp(s - lse) blockwise from the saved statistics, so
activation memory stays O(seq) end to end.

Every operand is blocked: the grid is (batch*heads, outer blocks, inner
blocks) with the inner (reduction) axis innermost and the running
accumulators in VMEM scratch, so what a program holds in fast memory is a
few (block, head_dim) tiles whatever the sequence length.  Sequences are
padded to a whole number of blocks (padded keys are masked, padded query
rows sliced off), because Mosaic refuses a block that is not aligned to
the (8, 128) tiling.

Layout: (batch*heads, seq, head_dim) inside the kernels; the public API
takes (batch, seq, heads, head_dim) and handles GQA by repeating KV heads.
On a mesh of more than one device the kernels run under ``shard_map`` over
the axes that shard batch and heads: Mosaic kernels cannot be partitioned
by GSPMD.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh

from ray_tpu.parallel.mesh import mesh_axis_size
from ray_tpu.parallel.sharding import to_partition_spec

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
NEG_INF = -1e30
_LANES = 128  # blocks are whole lane tiles, which also satisfies sublanes

# (bh, outer, inner): only the inner axis carries the accumulators
_GRID_SEMANTICS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _vmem_block(shape, index_map):
    return pl.BlockSpec(shape, index_map, memory_space=pltpu.VMEM)


def repeat_kv_heads(k, v, num_heads):
    """Expand GQA K/V (..., kv_heads, d) to num_heads along axis 2."""
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


def _scores(q, k, q_offset, k_offset, *, causal: bool, kv_len: int,
            kv_padded: bool):
    """(block_q, block_k) masked logits of one tile; q is pre-scaled."""
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    if causal or kv_padded:
        col = k_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = col < kv_len if kv_padded else None
        if causal:
            row = q_offset + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            keep = (row >= col) if keep is None else keep & (row >= col)
        s = jnp.where(keep, s, NEG_INF)
    return s


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                  *, causal: bool, sm_scale: float, kv_len: int,
                  kv_padded: bool):
    """One (bh, q_block, k_block) step of the online softmax.  Key block 0
    always holds an unmasked column for every row, so the running max is
    finite before any fully-masked tile is folded in."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    qi, kj = pl.program_id(1), pl.program_id(2)
    q_offset, k_offset = qi * block_q, kj * block_k

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _fold():
        q = q_ref[0].astype(jnp.float32) * sm_scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = _scores(q, k, q_offset, k_offset, causal=causal, kv_len=kv_len,
                    kv_padded=kv_padded)
        m_prev = m_ref[...]  # (block_q, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    if causal:
        # tiles wholly above the diagonal contribute nothing
        pl.when(k_offset <= q_offset + block_q - 1)(_fold)
    else:
        _fold()

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        l = l_ref[...]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        # Per-row logsumexp, saved for the backward: p = exp(s - lse)
        # reconstructs softmax blockwise without the O(seq^2) score matrix.
        # Rows with no unmasked column get +inf-ish so backward p == 0.
        # lse rides as (bh, seq, 1): TPU block-shape rules want the
        # trailing dim equal to the array's.
        lse_ref[0] = jnp.where(l == 0.0, -NEG_INF,
                               m_ref[...] + jnp.log(l_safe))


def _causal_last_k(qi, block_q: int, block_k: int):
    """Last key block a causal query block attends to."""
    return (qi * block_q + block_q - 1) // block_k


def _causal_first_q(ki, block_q: int, block_k: int):
    """First query block that attends to a causal key block."""
    return (ki * block_k) // block_q


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block_q", "block_k",
                              "kv_len", "interpret"))
def _flash_forward(q, k, v, *, causal: bool, sm_scale: float,
                   block_q: int, block_k: int, kv_len: int, interpret: bool):
    """q,k,v: (bh, seq, head_dim), seq a whole number of blocks; kv_len is
    the unpadded key length.  Returns (out, lse)."""
    bh, seq_q, head_dim = q.shape
    seq_k = k.shape[1]
    kernel = functools.partial(
        _flash_kernel, causal=causal, sm_scale=sm_scale, kv_len=kv_len,
        kv_padded=kv_len != seq_k)
    if causal:
        # A skipped tile re-names the last useful key block, so Pallas
        # sees an unchanged block index and issues no copy for it.
        def kv_index(b, i, j):
            return b, jnp.minimum(j, _causal_last_k(i, block_q, block_k)), 0
    else:
        def kv_index(b, i, j):
            return b, j, 0
    q_spec = _vmem_block((1, block_q, head_dim), lambda b, i, j: (b, i, 0))
    kv_spec = _vmem_block((1, block_k, head_dim), kv_index)
    out, lse = pl.pallas_call(
        kernel,
        grid=(bh, seq_q // block_q, seq_k // block_k),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            _vmem_block((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype),
            jax.ShapeDtypeStruct((bh, seq_q, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, head_dim), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="flash_attention_fwd",
    )(q, k, v)
    return out, lse


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


def _bwd_tile(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_offset,
              k_offset, *, causal, sm_scale, kv_len, kv_padded):
    """Recompute one tile's p and ds from the saved row statistics.
    Returns (q, k, do, p, ds) in float32."""
    q = q_ref[0].astype(jnp.float32)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    s = _scores(q * sm_scale, k, q_offset, k_offset, causal=causal,
                kv_len=kv_len, kv_padded=kv_padded)
    p = jnp.exp(s - lse_ref[0])  # masked entries -> 0
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_ref[0]) * sm_scale
    return q, k, do, p, ds


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                          dk_ref, dv_ref, dk_acc, dv_acc, *, causal: bool,
                          sm_scale: float, kv_len: int, kv_padded: bool):
    """One (bh, k_block, q_block) step: accumulate dK/dV over the Q blocks
    that attend to this KV block (FlashAttention-2 backward, column pass)."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    ki, qj = pl.program_id(1), pl.program_id(2)
    q_offset, k_offset = qj * block_q, ki * block_k

    @pl.when(qj == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def _fold():
        q, _, do, p, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_offset,
            k_offset, causal=causal, sm_scale=sm_scale, kv_len=kv_len,
            kv_padded=kv_padded)
        dv_acc[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # p^T @ do
        dk_acc[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)  # ds^T @ q

    if causal:
        # rows before this KV block's first column never attend to it
        pl.when(q_offset + block_q - 1 >= k_offset)(_fold)
    else:
        _fold()

    @pl.when(qj == pl.num_programs(2) - 1)
    def _finish():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         dq_ref, dq_acc, *, causal: bool, sm_scale: float,
                         kv_len: int, kv_padded: bool):
    """One (bh, q_block, k_block) step: accumulate dQ over this block's KV
    range (FlashAttention-2 backward, row pass)."""
    block_q, block_k = q_ref.shape[1], k_ref.shape[1]
    qi, kj = pl.program_id(1), pl.program_id(2)
    q_offset, k_offset = qi * block_q, kj * block_k

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def _fold():
        _, k, _, _, ds = _bwd_tile(
            q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, q_offset,
            k_offset, causal=causal, sm_scale=sm_scale, kv_len=kv_len,
            kv_padded=kv_padded)
        dq_acc[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    if causal:
        pl.when(k_offset <= q_offset + block_q - 1)(_fold)
    else:
        _fold()

    @pl.when(kj == pl.num_programs(2) - 1)
    def _finish():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block_q", "block_k",
                              "kv_len", "interpret"))
def _flash_backward(q, k, v, out, lse, d_out, *, causal: bool,
                    sm_scale: float, block_q: int, block_k: int,
                    kv_len: int, interpret: bool):
    bh, seq_q, head_dim = q.shape
    seq_k = k.shape[1]
    static = dict(causal=causal, sm_scale=sm_scale, kv_len=kv_len,
                  kv_padded=kv_len != seq_k)
    # delta = rowsum(do * o): one fused elementwise+reduce, O(seq) memory
    delta = jnp.sum(d_out.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[..., None]  # (bh, seq_q, 1)

    # As in the forward, a skipped causal tile re-names a block already
    # resident so that no copy is issued for it.
    last_q = seq_q // block_q - 1
    if causal:
        def q_of_dkv(b, i, j):
            first = _causal_first_q(i, block_q, block_k)
            return b, jnp.minimum(jnp.maximum(j, first), last_q), 0

        def k_of_dq(b, i, j):
            return b, jnp.minimum(j, _causal_last_k(i, block_q, block_k)), 0
    else:
        def q_of_dkv(b, i, j):
            return b, j, 0

        def k_of_dq(b, i, j):
            return b, j, 0

    def outer(b, i, j):
        return b, i, 0

    dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_dkv_kernel, **static),
        grid=(bh, seq_k // block_k, seq_q // block_q),
        in_specs=[
            _vmem_block((1, block_q, head_dim), q_of_dkv),
            _vmem_block((1, block_k, head_dim), outer),
            _vmem_block((1, block_k, head_dim), outer),
            _vmem_block((1, block_q, head_dim), q_of_dkv),
            _vmem_block((1, block_q, 1), q_of_dkv),
            _vmem_block((1, block_q, 1), q_of_dkv),
        ],
        out_specs=[
            _vmem_block((1, block_k, head_dim), outer),
            _vmem_block((1, block_k, head_dim), outer),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, seq_k, head_dim), k.dtype),
            jax.ShapeDtypeStruct((bh, seq_k, head_dim), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, head_dim), jnp.float32),
            pltpu.VMEM((block_k, head_dim), jnp.float32),
        ],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(q, k, v, d_out, lse, delta)

    dq = pl.pallas_call(
        functools.partial(_flash_bwd_dq_kernel, **static),
        grid=(bh, seq_q // block_q, seq_k // block_k),
        in_specs=[
            _vmem_block((1, block_q, head_dim), outer),
            _vmem_block((1, block_k, head_dim), k_of_dq),
            _vmem_block((1, block_k, head_dim), k_of_dq),
            _vmem_block((1, block_q, head_dim), outer),
            _vmem_block((1, block_q, 1), outer),
            _vmem_block((1, block_q, 1), outer),
        ],
        out_specs=_vmem_block((1, block_q, head_dim), outer),
        out_shape=jax.ShapeDtypeStruct((bh, seq_q, head_dim), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, head_dim), jnp.float32)],
        compiler_params=_GRID_SEMANTICS,
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(q, k, v, d_out, lse, delta)
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_attention(q, k, v, causal, sm_scale, block_q, block_k, kv_len,
                     interpret):
    out, _ = _flash_forward(q, k, v, causal=causal, sm_scale=sm_scale,
                            block_q=block_q, block_k=block_k, kv_len=kv_len,
                            interpret=interpret)
    return out


# What the forward kernel alone can make, by name (``checkpoint_name``:
# nothing in the lowered program).  A ``jax.checkpoint`` whose policy saves
# these never runs the kernel a second time; q, k and v, packed, padded and
# repeated to the query heads, are cheap to make again from what the caller
# keeps (models/llama.py ``REMAT_KEEPS``).
FLASH_RESIDUALS = ("flash/out", "flash/lse")


def _fwd(q, k, v, causal, sm_scale, block_q, block_k, kv_len, interpret):
    out, lse = _flash_forward(q, k, v, causal=causal, sm_scale=sm_scale,
                              block_q=block_q, block_k=block_k,
                              kv_len=kv_len, interpret=interpret)
    # named HERE, so that the primal output and the residual are the one
    # value: a name on ``attention``'s result would keep a copy and still
    # run the kernel again for ``lse``
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return out, (q, k, v, out, lse)


def _bwd(causal, sm_scale, block_q, block_k, kv_len, interpret, res, d_out):
    q, k, v, out, lse = res
    return _flash_backward(q, k, v, out, lse, d_out, causal=causal,
                           sm_scale=sm_scale, block_q=block_q,
                           block_k=block_k, kv_len=kv_len,
                           interpret=interpret)


_flash_attention.defvjp(_fwd, _bwd)


def _pallas_attention(q, k, v, *, causal: bool, sm_scale: float,
                      block_q: int, block_k: int, interpret: bool):
    """The kernel path on one device's (batch, seq, heads, head_dim)
    arrays: GQA repeat, pack to (b*h, s, d), pad to whole blocks."""
    seq_q, seq_k = q.shape[1], k.shape[1]
    k, v = repeat_kv_heads(k, v, q.shape[2])
    block_q, padded_q = _block_and_padded(seq_q, block_q)
    block_k, padded_k = _block_and_padded(seq_k, block_k)
    out = _flash_attention(
        _pad_seq(_pack(q), padded_q), _pad_seq(_pack(k), padded_k),
        _pad_seq(_pack(v), padded_k), causal, sm_scale, block_q, block_k,
        seq_k, interpret)
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
