"""The residual stream split over the tensor-parallel axis between a
layer's products.

Tensor parallelism splits a layer's weights by column (``heads``,
``kv_heads``, ``mlp`` -> ``tp``).  Left to propagation the stream ``x``
[batch, seq, d_model] then comes out whole on every device of a ``tp``
group, and each product that contracts a split axis (``attn/out``,
``mlp/down``, and the backward of ``attn/qkv`` and ``mlp/gate_up``) ends in
an all-reduce of the whole stream that nothing can run beside: its result
is the next operation's operand.  On ``fsdp=2 x tp=2`` v5e chips those were
five all-reduces of 134 MB a layer a step, 12 % of the step, wholly exposed
(PERF.md section 6, PR 43).

Here the stream lives split between the products: a device holds a
``tp``-th of its group's batch rows, and the norms, the residual adds and
the carry that remat saves are a ``tp``-th of what they were.  A layer runs
under ONE ``jax.shard_map`` (``Split.layer``), so every array in it is a
device's own and every link is written out:

- ``Ring.into(h, *weights)``, before ``attn/qkv`` and ``mlp/gate_up``: the
  group's rows are gathered by passing them round the ring, and the rows at
  hand are multiplied while the next are on the link;
- ``Ring.back(a, w)``, after ``attn/out`` and ``mlp/down``: each device's
  partial products are summed round the ring towards the device that keeps
  those rows, each step's product made while the sum before it travels;
- a weight's other split (``embed`` -> ``fsdp``) is gathered in the compute
  dtype where the layer starts, as the partitioner did.

Each of the two products brings its own backward (a ``jax.custom_vjp``):
the backward of ``into`` sums the rows' gradient round the ring as ``back``
does, the backward of ``back`` gathers it as ``into`` does, and a weight's
gradient is ONE product over the group's rows, as it was before the split;
``jax.checkpoint`` recomputes the forward's links but the last scatter,
whose sum no gradient needs.  The same sums over the same terms as the
all-reduce made, in bf16 where it was bf16: only the place differs.  Rows,
not positions: a next-token loss runs an odd number of positions (4,095 of
4,096), which no ``tp`` divides, and the batch rows a group holds (4 at
``tp`` = 2 in the benchmark's cell) divide with no padding.  Within the
shard_map a group's rows stand in ring order, the device's own first;
attention is a row's own business, so no device ever puts them back in the
batch's order.

``stream_split`` says whether a mesh and its rules allow this, and hands
back ``None`` where they do not (no ``tp``, a sequence-parallel axis, rows
that do not divide): the caller then keeps the propagated layout.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.sharding import to_partition_spec


@dataclass(frozen=True)
class Ring:
    """The products of a layer whose stream is split over mesh axis
    ``axis`` (``size`` devices), for use inside a ``shard_map``.  A gathered
    array holds the group's row blocks in ring order: block ``k`` came from
    the device ``k`` places behind this one."""

    axis: str
    size: int

    def _pass(self, x, by: int, part: str):
        """x of the device ``by`` places behind; ``part`` names the link
        (models/llama.py PARTS)."""
        with jax.named_scope(part):
            return jax.lax.ppermute(
                x, self.axis,
                [(i, (i + by) % self.size) for i in range(self.size)])

    def gathered(self, x):
        """The group's blocks of x, this device's first, each handed on as
        it arrives: what is done with block k runs beside the pass of
        block k + 1."""
        blocks = [x]
        for _ in range(1, self.size):
            blocks.append(self._pass(blocks[-1], 1, "tp/gather"))
        return blocks

    def scattered(self, block):
        """This device's block of the sum over the group: ``block(k)`` is
        this device's term for the device k places behind.  Block k here is
        block k - 1 of the device ahead, so the sum starts with the block
        furthest from home and is handed on, each device adding the
        nearer: ``block(k)`` is made while the sum before it travels."""
        total = block(self.size - 1)
        for k in range(self.size - 2, -1, -1):
            # the barrier keeps the add out of the product's fusion (XLA's
            # choice, left alone), where the product would wait for the link
            arrived, here = jax.lax.optimization_barrier(
                (self._pass(total, -1, "tp/scatter"), block(k)))
            total = arrived + here
        return total

    def into(self, h, *weights):
        """``[rows(h) @ w for w in weights]``: h is this device's rows,
        ``rows(h)`` the whole group's, each w this device's columns."""
        return _into(self, h, tuple(w.astype(h.dtype) for w in weights))

    def back(self, a, w):
        """This device's rows of ``sum over the group of a @ w``: a holds
        the group's rows (in ring order) at this device's columns, w the
        matching rows of the weight."""
        return _back(self, a, w.astype(a.dtype))


def _rows(x, k: int, n: int):
    rows = x.shape[0] // n
    return x[k * rows:(k + 1) * rows]


# Each product is a ``jax.custom_vjp``: differentiation would transpose the
# passes well enough, but it makes a weight's gradient one product a BLOCK
# and adds them; here it is one product over the group's rows, made once
# they are all there, as it was before the stream was split.

@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _into(ring, h, weights):
    return _into_fwd(ring, h, weights)[0]


def _into_fwd(ring, h, weights):
    blocks = ring.gathered(h)
    outs = tuple(jnp.concatenate([b @ w for b in blocks], axis=0)
                 for w in weights)
    return outs, (jnp.concatenate(blocks, axis=0), weights)


def _into_bwd(ring, residuals, d_outs):
    rows, weights = residuals
    d_weights = tuple(jnp.einsum("...d,...c->dc", rows, d) for d in d_outs)

    def block(k):
        first, *rest = (jnp.einsum("...c,dc->...d", _rows(d, k, ring.size), w)
                        for d, w in zip(d_outs, weights))
        return sum(rest, first)
    return ring.scattered(block), d_weights


_into.defvjp(_into_fwd, _into_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _back(ring, a, w):
    return ring.scattered(lambda k: _rows(a, k, ring.size) @ w)


def _back_fwd(ring, a, w):
    return _back(ring, a, w), (a, w)


def _back_bwd(ring, residuals, d_total):
    a, w = residuals
    blocks = ring.gathered(d_total)
    d_a = jnp.concatenate([jnp.einsum("...d,cd->...c", b, w) for b in blocks],
                          axis=0)
    return d_a, jnp.einsum("...c,...d->cd", a,
                           jnp.concatenate(blocks, axis=0))


_back.defvjp(_back_fwd, _back_bwd)


@dataclass(frozen=True)
class Split:
    """How ``stream_split`` lays a trunk out on a mesh."""

    mesh: Mesh
    ring: Ring
    rows: P   # the stream between products: batch rows over batch axes + tp
    whole: P  # the stream as the trunk's consumer wants it
    weights: Any  # a layer's parameters: PartitionSpec tree
    gathers: Any  # per leaf, per dimension: the mesh axes gathered over

    def split_rows(self, x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.rows))

    def whole_rows(self, x):
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, self.whole))

    def layer(self, fn: Callable, dtype, *replicated) -> Callable:
        """``step(x, layer_params) -> x``: ``fn(ring, x, p, *replicated)``
        on each device's rows of x, p the layer's weights whole but for
        their ``tp`` columns, matrices in ``dtype``; ``replicated``: what
        every device holds whole (the positions)."""
        def whole_but_tp(w, gathers):
            if any(gathers):
                w = w.astype(dtype)  # the bytes that travel are compute's
            for dim, axes in enumerate(gathers):
                if axes:
                    w = jax.lax.all_gather(w, axes, axis=dim, tiled=True)
            return w

        def local(x, p, *replicated):
            p = jax.tree.map(whole_but_tp, p, self.gathers)
            return fn(self.ring, x, p, *replicated)

        def step(x, p):
            return jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(self.rows, self.weights, *(P() for _ in replicated)),
                out_specs=self.rows, check_vma=False)(x, p, *replicated)
        return step


def stream_split(mesh: Mesh, rules: Optional[dict], layer_specs,
                 sizes: dict) -> Optional[Split]:
    """The split layout of a trunk on ``mesh``, or None where it does not
    apply.  ``layer_specs``: logical spec tree of ONE layer's parameters;
    ``sizes``: the extent of each logical axis that must divide (``batch``,
    and every axis the ring's mesh axis shards)."""
    def axes(name):
        entry = to_partition_spec((name,), rules)[0]
        entry = entry if isinstance(entry, tuple) else (entry,)
        return tuple(a for a in entry if a and mesh.shape.get(a, 1) > 1)

    split = {axes(name) for name in sizes if name != "batch"}
    batch = axes("batch")
    if len(split) != 1 or axes("seq"):
        return None  # columns split differently, or positions split
    (ring_axes,) = split
    if len(ring_axes) != 1 or ring_axes[0] in batch:
        return None
    tp = ring_axes[0]
    ways = {name: math.prod(mesh.shape[a] for a in axes(name))
            for name in sizes}
    ways["batch"] *= mesh.shape[tp]
    if any(sizes[name] % ways[name] for name in sizes):
        return None

    is_spec = lambda s: isinstance(s, tuple)  # noqa: E731
    if any(tp in axes(name) and axes(name) != (tp,)
           for spec in jax.tree.leaves(layer_specs, is_leaf=is_spec)
           for name in spec):
        return None  # a dimension split over tp AND another axis
    gathers = jax.tree.map(
        lambda spec: tuple(tuple(a for a in axes(name) if a != tp)
                           for name in spec),
        layer_specs, is_leaf=is_spec)
    return Split(
        mesh=mesh, ring=Ring(tp, mesh.shape[tp]),
        rows=P(batch + (tp,), None, None), whole=P(batch or None, None, None),
        weights=jax.tree.map(
            lambda spec: P(*(axes(name) or None for name in spec)),
            layer_specs, is_leaf=is_spec),
        gathers=gathers)
