"""The language-model loss head: chunked softmax cross-entropy.

The naive loss materializes float32 logits of shape (batch, seq, vocab).
``chunked_softmax_xent`` scans over sequence chunks so that only (batch,
chunk, vocab) logits ever exist, with bf16 operands and float32
accumulation in every product and the softmax in float32.

What the chip showed (PERF.md section 6, PR 40): on ``fsdp=2 x tp=2`` the
loss of a 7-layer Mistral-7B slice was 4 % of the step's FLOPs and 15.7 %
of its time, its products at 26 % of the MXU beside the MLP's at 90 %.
Not the products: where the head lay.  A scan that says nothing about
layout leaves the head ``[embed over fsdp, vocab over tp]`` to the
partitioner, which gathered it inside every chunk of both scans, shipped
the logits' gradient over the batch and reduce-scattered a float32 head
gradient a chunk (171 ms of a 1,090 ms step, ~118 of them links; 53 ms
since).  So under a mesh the function now promises:

- the head is cast to the compute dtype and gathered over the axes that
  shard ``embed`` ONCE, outside the scan; its columns stay split over the
  axes that shard ``vocab``;
- a chunk's rows stay on the device that holds them and are contracted over
  the whole model width there: logits exist on one device only and are
  never communicated.  What crosses the vocabulary's axes a chunk is the
  rows' maxima, sums and gold logits (one small gather) and the sum of the
  rows' gradient;
- the chunk's gradients are made in the pass that makes its loss (a
  ``jax.custom_vjp``: the logits are computed once, three products a chunk
  and no recompute); the head's gradient is accumulated in float32 on each
  device from its own rows and reduced ONCE after the scan, into the
  parameter's layout.

With no mesh it is the same pass with no collective.

No reference counterpart: the reference delegates loss math to
torch/vLLM (SURVEY §2.4); this is TPU-native net-new.
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.sharding import to_partition_spec


def chunked_softmax_xent(x: jax.Array, head: jax.Array, targets: jax.Array,
                         chunk: int = 256, mesh: Optional[Mesh] = None,
                         rules: Optional[dict] = None) -> jax.Array:
    """Mean next-token cross-entropy without materializing full logits.

    x:       (batch, seq, d_model) activations (any float dtype; bf16 keeps
             the matmul on the MXU fast path), logical ("batch", "seq", None)
    head:    (d_model, vocab) output projection, logical ("embed", "vocab")
             (tied embeddings: pass ``wte.T``)
    targets: (batch, seq) int32 gold next tokens
    chunk:   positions a pass; <= 0 means one pass over the whole sequence
    mesh, rules: what the surrounding jit shards its arrays over
    """
    with jax.named_scope("loss"):  # models/llama.py PARTS
        return _mean_xent(x, head, targets,
                          _passes(x.shape, chunk, mesh, rules))


def _mean_xent(x, head, targets, passes):
    @jax.custom_vjp
    def loss(x, head, targets):
        return passes(False)(x, head, targets)

    def fwd(x, head, targets):
        out, dx, dw = passes(True)(x, head, targets)
        return out, (dx, dw.astype(head.dtype))

    def bwd(grads, g):
        # the pass made the gradients of the MEAN: g is 1 unless the
        # caller scales the loss
        return (*((g * t).astype(t.dtype) for t in grads), None)

    loss.defvjp(fwd, bwd)
    return loss(x, head, targets)


def _passes(shape, chunk, mesh, rules):
    """``with_grads -> f(x, head, targets)``: the mean loss, and with
    ``with_grads`` its gradients in x and in the head beside it, each laid
    out as its operand."""
    def axes(name):
        if mesh is None:
            return ()
        entry = to_partition_spec((name,), rules)[0]
        entry = entry if isinstance(entry, tuple) else (entry,)
        return tuple(a for a in entry if mesh.shape.get(a, 1) > 1)

    batch, seq = axes("batch"), axes("seq")
    rows = batch + seq  # devices along these hold different rows
    gathered = tuple(a for a in axes("embed") if a in rows)
    split = tuple(a for a in axes("vocab") if a not in rows)
    row_spec = P(batch or None, seq or None)
    x_spec, head_spec = P(*row_spec, None), P(gathered or None, split or None)

    def one(with_grads):
        local = partial(_local, chunk=chunk, inv_n=1.0 / (shape[0] * shape[1]),
                        with_grads=with_grads, rows=rows, gathered=gathered,
                        split=split)
        if mesh is None:
            return local
        return jax.shard_map(
            local, mesh=mesh, in_specs=(x_spec, head_spec, row_spec),
            out_specs=(P(), x_spec, head_spec) if with_grads else P(),
            check_vma=False)
    return one


def _local(x, head, targets, *, chunk, inv_n, with_grads, rows, gathered,
           split):
    """One device's rows against its columns of the head.  ``gathered``:
    the mesh axes the head's rows are gathered over (once, here) and its
    gradient scattered over (once, at the end); ``split``: the axes its
    columns stay split over; ``rows``: all the axes along which devices
    hold different rows."""
    b, s, d = x.shape
    w = head.astype(x.dtype)
    if gathered:
        w = jax.lax.all_gather(w, gathered, axis=0, tiled=True)
    v = w.shape[1]
    first = jax.lax.axis_index(split) * v if split else 0

    if chunk <= 0 or chunk >= s:
        chunk = s  # one pass; fine whenever (b, s, vocab) fits HBM
    # pad the sequence up to a chunk multiple (LM losses see seq-1 tokens,
    # which is odd for every even seq — a divisibility requirement would
    # make the chunked path dead code); pads weigh nothing in the sum
    pad = (-s) % chunk
    n = (s + pad) // chunk
    weight = jnp.pad(jnp.full((b, s), inv_n, jnp.float32),
                     ((0, 0), (0, pad)))
    xc = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    xc = xc.reshape(b, n, chunk, d).swapaxes(0, 1)
    tc = jnp.pad(targets, ((0, 0), (0, pad))).reshape(b, n, chunk)
    wc = weight.reshape(b, n, chunk)
    columns = jax.lax.broadcasted_iota(jnp.int32, (1, 1, v), 2)

    def body(carry, xs):
        xch, tch, wch = xs
        logits = jnp.einsum("bcd,dv->bcv", xch, w,
                            preferred_element_type=jnp.float32)
        # a masked sum over the local columns: take_along_axis over a split
        # axis is a gather
        is_gold = columns == (tch - first)[..., None]
        top = jnp.max(logits, axis=-1)
        e = jnp.exp(logits - top[..., None])
        stats = jnp.stack([top, jnp.sum(e, axis=-1),
                           jnp.sum(jnp.where(is_gold, logits, 0.0), axis=-1)])
        if split:  # every device's three numbers a row, in one round
            stats = jax.lax.all_gather(stats, split, axis=0)
            tops, sums, golds = stats[:, 0], stats[:, 1], stats[:, 2]
            row_top = jnp.max(tops, axis=0)
            row_sum = jnp.sum(sums * jnp.exp(tops - row_top), axis=0)
            gold = jnp.sum(golds, axis=0)
        else:
            row_top, row_sum, gold = stats
        nll = jnp.sum((jnp.log(row_sum) + row_top - gold) * wch)
        if not with_grads:
            return carry + nll, None
        total, dw = carry
        share = jnp.exp(top - row_top) / row_sum * wch
        dlogits = (e * share[..., None]
                   - jnp.where(is_gold, wch[..., None], 0.0)).astype(x.dtype)
        dx = jnp.einsum("bcv,dv->bcd", dlogits, w,
                        preferred_element_type=jnp.float32)
        if split:
            dx = jax.lax.psum(dx, split)
        dw = dw + jnp.einsum("bcd,bcv->dv", xch, dlogits,
                             preferred_element_type=jnp.float32)
        return (total + nll, dw), dx.astype(x.dtype)

    zero = jnp.zeros((), jnp.float32)
    xs = (xc, tc.swapaxes(0, 1), wc.swapaxes(0, 1))
    if not with_grads:
        total, _ = jax.lax.scan(body, zero, xs)
        return jax.lax.psum(total, rows) if rows else total
    (total, dw), dx = jax.lax.scan(
        body, (zero, jnp.zeros((d, v), jnp.float32)), xs)
    dx = dx.swapaxes(0, 1).reshape(b, s + pad, d)[:, :s]
    if gathered:
        dw = jax.lax.psum_scatter(dw, gathered, scatter_dimension=0,
                                  tiled=True)
    rest = tuple(a for a in rows if a not in gathered)
    if rest:
        dw = jax.lax.psum(dw, rest)
    if rows:
        total = jax.lax.psum(total, rows)
    return total, dx, dw
