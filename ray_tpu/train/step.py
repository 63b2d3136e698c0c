"""Sharded training step construction.

The TPU-native replacement for the reference's per-strategy training setup
(DDP/FSDP in /root/reference/python/ray/train/torch/train_loop_utils.py:153):
here a model module (init/apply/loss_fn/param_logical_specs) plus a Mesh and
logical-axis rules produce a jitted SPMD train step.  XLA inserts the
collectives (psum over dp/fsdp for grads, all-gathers for fsdp params) from
the shardings — there is no gradient-bucketing/NCCL code to write.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.parallel.sharding import named_shardings, to_partition_spec


def data_sharding(mesh: Mesh, rules: Optional[dict] = None) -> NamedSharding:
    """Batch goes over (dp, fsdp); sequence over sp."""
    return NamedSharding(mesh, to_partition_spec(("batch", "seq"), rules))


def train_state_shardings(
    model: Any,  # module with init/param_logical_specs
    cfg: Any,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    rules: Optional[dict] = None,
):
    """Layout of the train state on the mesh, as a tree of shardings.

    Every copy of the parameter tree inside the optimizer state (adam's
    moments) is laid out like the parameters, and the counters are
    replicated.  It has to be said: nothing in ``optimizer.init`` depends
    on the parameters' values, so left to propagation the whole optimizer
    state lands on the first device.
    """
    params = named_shardings(model.param_logical_specs(cfg), mesh, rules)
    replicated = NamedSharding(mesh, P())
    abstract_params = jax.eval_shape(
        lambda k: model.init(cfg, k), jax.random.PRNGKey(0))
    opt_state = optax.tree_utils.tree_map_params(
        optimizer, lambda _, sharding: sharding,
        jax.eval_shape(optimizer.init, abstract_params), params,
        transform_non_params=lambda _: replicated)
    return {"params": params, "opt_state": opt_state, "step": replicated}


def create_train_state(
    model: Any,
    cfg: Any,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    key: jax.Array,
    rules: Optional[dict] = None,
):
    """Initialize sharded params + optimizer state on the mesh.

    Everything is materialized directly into its shards (init runs under
    jit with output shardings, so no host-side full copy exists).
    """
    layout = train_state_shardings(model, cfg, mesh, optimizer, rules)
    params = jax.jit(
        lambda k: model.init(cfg, k), out_shardings=layout["params"])(key)
    opt_state = jax.jit(
        optimizer.init, out_shardings=layout["opt_state"])(params)
    step = jax.device_put(jnp.zeros((), jnp.int32), layout["step"])
    return {"params": params, "opt_state": opt_state, "step": step}


def make_train_step(
    model: Any,
    cfg: Any,
    mesh: Mesh,
    optimizer: optax.GradientTransformation,
    rules: Optional[dict] = None,
    loss_fn: Optional[Callable] = None,
    donate: bool = True,
    attn_impl: Optional[str] = None,
    out_shardings: Any = None,
) -> Callable:
    """Build the jitted SPMD train step: (state, batch) -> (state, metrics).

    attn_impl "ring"/"ulysses" enables sequence-parallel attention over the
    mesh's sp axis.  The model's loss_fn takes attn_impl/mesh/rules: the
    attention kernel needs the mesh to run per shard (ops/attention.py).

    ``out_shardings`` (a pytree prefix for ``(new_state, metrics)``) pins
    the output layout.  Required when the step is AOT-compiled and called
    in a loop: without it GSPMD may reshard small params in the output,
    and the fixed executable then rejects its own output as input.
    """
    if loss_fn is None:
        loss_kwargs = {"mesh": mesh, "rules": rules}
        if attn_impl is not None:
            loss_kwargs["attn_impl"] = attn_impl
        loss = lambda p, b: model.loss_fn(p, b, cfg, **loss_kwargs)  # noqa: E731
    else:
        loss = loss_fn
    batch_sharding = data_sharding(mesh, rules)

    def step_fn(state, batch):
        batch = jax.lax.with_sharding_constraint(batch, batch_sharding)
        loss_val, grads = jax.value_and_grad(loss)(state["params"], batch)
        with jax.named_scope("optim"):  # models/llama.py PARTS
            updates, new_opt_state = optimizer.update(
                grads, state["opt_state"], state["params"])
            new_params = optax.apply_updates(state["params"], updates)
            grad_norm = optax.global_norm(grads)
        new_state = {
            "params": new_params,
            "opt_state": new_opt_state,
            "step": state["step"] + 1,
        }
        return new_state, {"loss": loss_val, "grad_norm": grad_norm}

    donate_argnums = (0,) if donate else ()
    jit_kwargs = {}
    if out_shardings is not None:
        jit_kwargs["out_shardings"] = out_shardings
    return jax.jit(step_fn, donate_argnums=donate_argnums, **jit_kwargs)


def default_optimizer(
    learning_rate: float = 3e-4,
    weight_decay: float = 0.1,
    b1: float = 0.9,
    b2: float = 0.95,
    grad_clip: float = 1.0,
    warmup_steps: int = 100,
    total_steps: int = 10_000,
) -> optax.GradientTransformation:
    schedule = optax.warmup_cosine_decay_schedule(
        0.0, learning_rate, warmup_steps, max(total_steps, warmup_steps + 1))
    return optax.chain(
        optax.clip_by_global_norm(grad_clip),
        optax.adamw(schedule, b1=b1, b2=b2, weight_decay=weight_decay),
    )
