"""Pipeline parallelism over the ``pp`` mesh axis (GPipe schedule).

The reference expresses GPU pipelines as compiled DAGs of actors connected by
NCCL channels (/root/reference/python/ray/dag/, experimental/channel/); vLLM
owns the in-engine PP. On TPU the idiomatic design is one SPMD program: layer
stacks are sharded over the ``pp`` axis inside ``shard_map``, microbatches
flow stage-to-stage via ``lax.ppermute`` (nearest-neighbour ICI hops), and
the whole schedule is a ``lax.scan`` over M + P - 1 ticks — XLA sees a
static loop it can pipeline, and autodiff through scan/ppermute gives the
backward schedule for free.

This is the plain GPipe fill/drain schedule (bubble fraction (P-1)/(M+P-1));
a circular/interleaved schedule is a future refinement.
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from ray_tpu.parallel.sharding import to_partition_spec


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x: jax.Array,
    mesh: Mesh,
    *,
    n_microbatches: int,
    pp_axis: str = "pp",
    params_specs=None,
    x_spec: Optional[P] = None,
    rules: Optional[dict] = None,
):
    """Run ``stage_fn`` as a P-stage GPipe pipeline over the pp mesh axis.

    stage_fn(local_params, activations) -> activations: one pipeline stage
    (typically a scan over this stage's layer slice).  ``stage_params`` must
    be ``split_stages`` output: every leaf has leading dim == pp size (the
    stage axis); each rank gets its slice with that dim dropped.  ``x``:
    (batch, ...) activations; the per-device batch must divide by
    n_microbatches, and n_microbatches should be >= pp size to keep the
    bubble small.

    Returns activations after all stages, with x's sharding.
    """
    pp = mesh.shape.get(pp_axis, 1)
    if pp == 1:
        return stage_fn(jax.tree.map(lambda l: l[0], stage_params), x)
    batch = x.shape[0]
    if batch % n_microbatches:
        raise ValueError(f"batch {batch} % n_microbatches {n_microbatches}")

    if params_specs is None:
        params_specs = jax.tree.map(lambda _: P(pp_axis), stage_params)
    else:
        params_specs = jax.tree.map(
            lambda spec: to_partition_spec(spec, rules), params_specs,
            is_leaf=lambda s: isinstance(s, tuple))
    if x_spec is None:
        x_spec = to_partition_spec(("batch", "seq", None), rules)

    m = n_microbatches
    mb = batch // m
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def local(params_local, x_local):
        # Each rank sees its (1, L/pp, ...) slice of the staged params;
        # drop the stage dim so stage_fn scans over its local layers.
        params_local = jax.tree.map(lambda l: l[0], params_local)
        p_idx = jax.lax.axis_index(pp_axis)
        b_local = x_local.shape[0]
        if b_local % m:
            raise ValueError(
                f"per-device batch {b_local} (global {batch} over the data "
                f"axes) must divide by n_microbatches {m}")
        mb_local = b_local // m
        x_mb = x_local.reshape(m, mb_local, *x_local.shape[1:])

        state = jnp.zeros_like(x_mb[0])
        outputs = jnp.zeros_like(x_mb)

        def tick(carry, t):
            state, outputs = carry
            # Stage 0 injects microbatch t (garbage after the fill phase —
            # masked out by the output-index guard below).
            inj = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, m - 1), keepdims=False)
            state = jnp.where(p_idx == 0, inj, state)
            out = stage_fn(params_local, state)
            # Last stage emits microbatch t - (P-1) once it is real.
            out_t = t - (pp - 1)
            emit = jnp.logical_and(p_idx == pp - 1,
                                   jnp.logical_and(out_t >= 0, out_t < m))
            outputs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, out, jnp.clip(out_t, 0, m - 1), axis=0),
                lambda o: o,
                outputs)
            state = jax.lax.ppermute(out, pp_axis, perm)
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(
            tick, (state, outputs), jnp.arange(m + pp - 1))
        # Outputs are only real on the last stage; broadcast over the pp
        # axis so every rank returns the same activations.
        mask = (p_idx == pp - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * mask, pp_axis)
        return outputs.reshape(b_local, *x_local.shape[1:])

    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(params_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x)


def split_stages(stacked_params, pp: int):
    """Reshape (L, ...) stacked layer params to (pp, L/pp, ...) per leaf —
    the layout pipeline_apply shards over the pp axis."""

    def reshape(leaf):
        nl = leaf.shape[0]
        if nl % pp:
            raise ValueError(f"n_layers {nl} % pp {pp} != 0")
        return leaf.reshape(pp, nl // pp, *leaf.shape[1:])

    return jax.tree.map(reshape, stacked_params)
