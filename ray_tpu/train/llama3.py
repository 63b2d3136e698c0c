"""Llama-3-8B pretraining recipe: the BASELINE.json north-star config
("Llama-3 8B Ray Train FSDP → XLA SPMD on v5e-16").

Where the reference's 8B recipe is TorchTrainer + FSDP + NCCL
(/root/reference/python/ray/train/torch/config.py:115 backend setup),
this is the TPU-native shape: ONE JaxTrainer worker per host drives
every local chip through a single jitted train step over an
fsdp×tp mesh; XLA emits the ICI collectives the NCCL process group
provided there.  Checkpoints are sharded orbax saves — each host
writes only its addressable shards (train/checkpoint.py save_pytree).

Run on a v5e-16 (4 hosts x 4 chips) unchanged:

    from ray_tpu.train.llama3 import train_llama3_8b
    result = train_llama3_8b(num_workers=4, steps=100,
                             storage_path="gs://.../llama3-8b")

Dry run (CI / laptop): ``train_llama3_8b(dry_run=True)`` uses the
8B-SHAPED tiny geometry (LlamaConfig.llama3_8b_dry — same GQA ratio,
FFN multiple, and sharding structure) over however many local devices
exist; the multichip sharding itself is validated by
``__graft_entry__.dryrun_multichip``'s 8B-shaped section.
"""

from __future__ import annotations

import os
from typing import Optional

from ray_tpu.train.trainer import JaxTrainer

# v5e-16 mesh recipe: fsdp outermost over hosts+chips, tp=2 innermost so
# tensor-parallel collectives ride nearest-neighbour ICI links.  8B in
# bf16 + fp32 adam = ~10 bytes/param -> ~80GB, / 16 chips = 5GB/chip of
# state — fits v5e's 16GB HBM with activations remat'd per layer.
V5E16_MESH = {"fsdp": 8, "tp": 2}


def llama3_train_loop(config: dict):
    """Per-worker loop: mesh -> sharded state -> jitted step -> orbax.

    Instrumented with the goodput/step-anatomy tracker (util/goodput.py):
    the step is AOT-compiled under an explicit compile bracket (so the
    compiled program's cost_analysis feeds the MFU gauge), each step is
    split into data / h2d / compute / checkpoint phases, and the reported
    ``tokens_per_sec`` is STEADY-STATE — post-warmup steps only, never
    diluted by step-0 compile (``compile_s`` is reported separately).
    """
    import jax
    import numpy as np

    from ray_tpu import train
    from ray_tpu.models import llama
    from ray_tpu.parallel import mesh as mesh_mod
    from ray_tpu.train.checkpoint import Checkpoint, save_pytree
    from ray_tpu.train.step import (
        create_train_state,
        default_optimizer,
        make_train_step,
    )
    from ray_tpu.util import goodput as goodput_mod

    dry = config.get("dry_run", False)
    cfg = (llama.LlamaConfig.llama3_8b_dry() if dry
           else llama.LlamaConfig.llama3_8b())
    n_dev = len(jax.devices())
    if dry:
        # fit whatever devices exist, keeping the fsdp×tp structure
        tp = 2 if n_dev % 2 == 0 else 1
        axes = {"fsdp": n_dev // tp, "tp": tp}
    else:
        axes = dict(config.get("mesh", V5E16_MESH))
    mesh = mesh_mod.create_mesh(mesh_mod.MeshConfig(**axes))
    mesh_mod.set_active_mesh_context(mesh_mod.MeshContext(mesh=mesh))

    steps = int(config.get("steps", 10))
    seq_len = int(config.get("seq_len", 128 if dry else 8192))
    batch = int(config.get("batch",
                           max(1, axes.get("fsdp", 1)) * (1 if dry else 2)))
    ckpt_every = int(config.get("ckpt_every", max(1, steps)))

    opt = default_optimizer(learning_rate=config.get("lr", 3e-4))
    with mesh:
        state = create_train_state(llama, cfg, mesh, opt,
                                   jax.random.PRNGKey(config.get("seed", 0)))
        # Pin the output state to the input layout: the step is AOT-compiled
        # below and iterated, so it must be a sharding fixed point.
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        state_sh = jax.tree_util.tree_map(lambda x: x.sharding, state)
        step = make_train_step(llama, cfg, mesh, opt,
                               attn_impl=config.get("attn_impl", "flash"),
                               out_shardings=(state_sh, rep))
        tok_per_step = batch * seq_len
        run_name = config.get("run_name") or (
            "llama3-8b-dry" if dry else "llama3-8b")
        gp = goodput_mod.GoodputTracker(run=run_name,
                                        tokens_per_step=tok_per_step)
        np_rng = np.random.default_rng(config.get("seed", 0) + 1234)

        def host_batch():
            return np_rng.integers(0, cfg.vocab_size,
                                   size=(batch, seq_len + 1),
                                   dtype=np.int32)

        # AOT-compile so compile time is bracketed apart from the steps
        # and cost_analysis() prices the step for the MFU gauge.
        first = jax.device_put(host_batch())
        with gp.compile_bracket():
            compiled = step.lower(state, first).compile()
        params = state["params"] if isinstance(state, dict) \
            and "params" in state else state
        n_params = sum(int(x.size)
                       for x in jax.tree_util.tree_leaves(params))
        gp.set_flops_per_step(*goodput_mod.step_flops(
            compiled, n_params=n_params, tokens=tok_per_step))

        tokens = first
        for i in range(steps):
            with gp.step() as st:
                if i > 0:
                    with st.phase("data"):
                        batch_np = host_batch()
                    with st.phase("h2d"):
                        tokens = jax.device_put(batch_np)
                with st.phase("compute"):
                    state, metrics = compiled(state, tokens)
                    jax.block_until_ready(metrics["loss"])
                if (i + 1) % ckpt_every == 0 or i + 1 == steps:
                    loss = float(metrics["loss"])
                    ctx = train.get_context()
                    ckpt_dir = os.path.join(
                        ctx.experiment_dir, f"ckpt-{i + 1:06d}",
                        f"worker-{ctx.get_world_rank()}")
                    with st.phase("checkpoint"):
                        os.makedirs(ckpt_dir, exist_ok=True)
                        # sharded orbax save: each process persists its
                        # addressable shards; restore reshards onto any
                        # mesh
                        save_pytree(ckpt_dir, state)
                    ckpt = Checkpoint.from_directory(ckpt_dir)
                    rep = gp.report()
                    train.report(
                        {"loss": loss, "step": i + 1,
                         "tokens_per_sec":
                             rep["tokens_per_sec_steady"] or 0.0,
                         "compile_s": rep["compile_s"],
                         "mfu": rep["mfu"],
                         "model_tflops_per_s": rep["model_tflops_per_s"],
                         "flops_source": rep["flops_source"],
                         "goodput_fraction": rep["fractions"]["goodput"]},
                        checkpoint=ckpt)
        gp.close()


def train_llama3_8b(num_workers: int = 1, dry_run: bool = False,
                    storage_path: Optional[str] = None, **config):
    """The north-star entry point: JaxTrainer over the 8B recipe."""
    from ray_tpu.train.config import RunConfig, ScalingConfig

    config = dict(config, dry_run=dry_run)
    trainer = JaxTrainer(
        llama3_train_loop,
        train_loop_config=config,
        scaling_config=ScalingConfig(
            num_workers=num_workers,
            resources_per_worker=(
                None if dry_run else {"TPU": 4.0})),  # one host = 4 chips
        run_config=(RunConfig(storage_path=storage_path)
                    if storage_path else None),
    )
    return trainer.fit()
