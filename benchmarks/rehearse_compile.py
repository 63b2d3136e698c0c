#!/usr/bin/env python3
"""Rehearsal 3 of the on-chip-measurement guide: compile the programs of a
configuration at their real sizes for a DESCRIBED ``v5e:2x2`` (no chip
attached) and print what each plans to hold on a device.  Nothing runs, so
nothing here is a time or a rate.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_compile.py <config> [layers]
"""

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental import topologies  # noqa: E402
from jax.sharding import NamedSharding, SingleDeviceSharding  # noqa: E402

from benchmarks import common  # noqa: E402

V5E_BYTES_LIMIT = 16.91e9  # memory_stats()["bytes_limit"] (my chip run, PR 21)


def footprint(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def report(name, compiled):
    m = compiled.memory_analysis()
    total = footprint(compiled)
    print(f"{name}: plans {total / 1e9:.2f} GB a device "
          f"({total / V5E_BYTES_LIMIT:.2f} of bytes_limit): arguments "
          f"{m.argument_size_in_bytes / 1e9:.2f}, temporaries "
          f"{m.temp_size_in_bytes / 1e9:.2f}, outputs "
          f"{m.output_size_in_bytes / 1e9:.2f}, aliased "
          f"{m.alias_size_in_bytes / 1e9:.2f}", flush=True)
    return total


def serve(c, topo):
    from ray_tpu.llm import model as lm

    family = common.module("families", c["family"])
    cfg, eng = family.model_config(c), c["engine"]
    one = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    params = jax.tree.map(
        lambda x: sds(x.shape, jnp.bfloat16),
        jax.eval_shape(lambda k: family.model_module().init(cfg, k),
                       jax.random.PRNGKey(0)))
    pool = (cfg.n_layers, eng["num_pages"], eng["page_size"],
            cfg.n_kv_heads, cfg.head_dim)
    ck, cv = sds(pool, jnp.bfloat16), sds(pool, jnp.bfloat16)
    B, P = eng["max_slots"], -(-eng["max_seq_len"] // eng["page_size"])
    i32 = jnp.int32
    report("decode_step_greedy", lm.decode_step_greedy.lower(
        params, sds((B,), i32), ck, cv, sds((B, P), i32), sds((B,), i32),
        sds((B,), jnp.bool_), cfg).compile())
    for L in eng["prefill_buckets"][-2:]:
        report(f"prefill[{L}]", lm.prefill.lower(
            params, sds((L,), i32), ck, cv, sds((L,), i32), sds((), i32),
            sds((L,), i32), cfg).compile())
    L = 512
    report(f"prefill_with_prefix[{L}]", lm.prefill_with_prefix.lower(
        params, sds((L,), i32), ck, cv, sds((L,), i32), sds((), i32),
        sds((L,), i32), sds((P,), i32), sds((L,), i32), cfg).compile())


def train(c, topo, mix):
    from ray_tpu.train.step import (data_sharding, default_optimizer,
                                    make_train_step, train_state_shardings)

    family = common.module("families", c["family"])
    model, cfg = family.model_module(), family.model_config(c)
    axes = c["train"]["mesh"]
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(**axes), devices=topo.devices)
    opt = default_optimizer()
    with mesh:
        layout = train_state_shardings(model, cfg, mesh, opt)
        shapes = jax.eval_shape(lambda k: (lambda p: {
            "params": p, "opt_state": opt.init(p),
            "step": jnp.zeros((), jnp.int32)})(model.init(cfg, k)),
            jax.random.PRNGKey(0))
        state = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, layout)
        rows = mix["global_batch_tokens"] // mix["seq_len"]
        batch = jax.ShapeDtypeStruct((rows, mix["seq_len"] + 1), jnp.int32,
                                     sharding=data_sharding(mesh))
        step = make_train_step(
            model, cfg, mesh, opt, attn_impl=c["train"]["attn_impl"],
            out_shardings=(layout, NamedSharding(
                mesh, jax.sharding.PartitionSpec())))
        compiled = step.lower(state, batch).compile()
    total = report(f"train step, {cfg.n_layers} layers", compiled)
    text = compiled.as_text()
    print("  kernel:", "tpu_custom_call" in text, " collectives:",
          sorted(k for k in ("all-gather", "all-reduce", "reduce-scatter",
                             "all-to-all", "collective-permute")
                 if k in text))
    return total


def main():
    c = common.load_json("configs", sys.argv[1] + ".json")
    if len(sys.argv) > 2:
        c["num_hidden_layers"] = int(sys.argv[2])
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # the program asks jax.default_backend() whether to run its kernels
    # compiled or interpreted; here it is steered to the chip's branch, in
    # this script and not through an option of the program
    jax.default_backend = lambda: "tpu"
    if "engine" in c:
        serve(c, topo)
    else:
        train(c, topo, common.load_json("traffic", "packed_4k.json"))


if __name__ == "__main__":
    main()
