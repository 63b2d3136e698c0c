"""Mesh + logical sharding tests on the virtual 8-device CPU platform."""

import jax
import pytest
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.mesh import (
    AXIS_ORDER,
    MeshConfig,
    create_mesh,
    mesh_axis_size,
)
from ray_tpu.parallel.sharding import (
    DEFAULT_RULES,
    logical_spec,
    to_partition_spec,
)


def test_mesh_axes_all_present():
    mesh = create_mesh(MeshConfig(fsdp=-1))
    assert mesh.axis_names == AXIS_ORDER
    assert mesh.size == len(jax.devices())


def test_mesh_fill_axis():
    mesh = create_mesh(MeshConfig(dp=2, fsdp=-1, tp=2))
    assert mesh.shape["dp"] == 2
    assert mesh.shape["tp"] == 2
    assert mesh.shape["fsdp"] == len(jax.devices()) // 4


def test_mesh_invalid_product():
    with pytest.raises(ValueError):
        create_mesh(MeshConfig(dp=3, fsdp=1))  # 3 doesn't divide 8


def test_mesh_two_fill_axes_rejected():
    with pytest.raises(ValueError):
        MeshConfig(dp=-1, fsdp=-1).resolved(8)


def test_logical_to_partition_spec():
    spec = to_partition_spec(logical_spec("batch", "seq", "embed"))
    assert spec == P(("dcn", "dp", "fsdp"), "sp", "fsdp")
    assert to_partition_spec(logical_spec(None, "heads")) == P(None, "tp")


def test_unknown_logical_name_raises():
    """A typo'd logical axis must fail loudly: silently replicating it
    (the old rules.get behavior) costs memory without any error."""
    with pytest.raises(ValueError, match="nonexistent"):
        to_partition_spec(logical_spec("nonexistent"))


def test_intentional_replication_spellings():
    assert to_partition_spec(logical_spec(None, "replicated")) == P(None,
                                                                    None)
    # a `name: None` rule is the third spelling (e.g. "layers")
    assert to_partition_spec(logical_spec("layers")) == P(None)


def test_custom_rules_override():
    rules = dict(DEFAULT_RULES, embed=None)
    assert to_partition_spec(logical_spec("embed"), rules) == P(None)


def test_mesh_axis_size():
    mesh = create_mesh(MeshConfig(dp=2, fsdp=2, tp=2))
    assert mesh_axis_size(mesh, "dp", "fsdp") == 4
    assert mesh_axis_size(mesh, "tp") == 2


def test_dcn_multi_slice_mesh():
    """dcn is the outermost axis: two virtual 4-device 'slices' with dp
    across slices over DCN and fsdp/tp inside each slice over ICI
    (SURVEY §2.5 multi-slice mapping)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel.mesh import MeshConfig, create_mesh

    mesh = create_mesh(MeshConfig(dcn=2, fsdp=-1, tp=2))
    assert mesh.axis_names[0] == "dcn"
    assert mesh.shape["dcn"] == 2 and mesh.shape["tp"] == 2
    assert mesh.shape["fsdp"] == len(jax.devices()) // 4
    # a batch-sharded array spreads across slices; psum over dcn crosses
    # the slice boundary (DCN allreduce in a real pod)
    x = jnp.arange(16.0).reshape(8, 2)
    xs = jax.device_put(x, NamedSharding(mesh, P(("dcn", "dp", "fsdp"))))

    def summed(v):
        return jax.lax.psum(v, ("dcn", "fsdp"))

    out = jax.jit(
        jax.shard_map(summed, mesh=mesh,
                      in_specs=P(("dcn", "dp", "fsdp")),
                      out_specs=P(("dcn", "dp", "fsdp"))))(xs)
    assert out.shape == x.shape


def test_dcn_train_step_dp_across_slices():
    """Full sharded train step on a dcn=2 mesh: gradients all-reduce over
    the dcn axis (the cross-slice DCN collective) and fsdp inside."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.train.step import (
        create_train_state, default_optimizer, make_train_step)

    mesh = create_mesh(MeshConfig(dcn=2, dp=2, fsdp=2, tp=1))
    cfg = llama.LlamaConfig.tiny()
    opt = default_optimizer()
    with mesh:
        state = create_train_state(llama, cfg, mesh, opt,
                                   jax.random.PRNGKey(0))
        step = make_train_step(llama, cfg, mesh, opt)
        tokens = jax.random.randint(
            jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size, jnp.int32)
        tokens = jax.device_put(
            tokens, NamedSharding(mesh, P(("dcn", "dp", "fsdp"), None)))
        state, metrics = step(state, tokens)
        loss = float(metrics["loss"])
    assert jnp.isfinite(loss)


def test_optimizer_state_is_laid_out_like_the_parameters():
    """Nothing in ``optimizer.init`` depends on the parameters' values, so
    left to propagation adam's moments all land on the first device (64 GB
    of them for an 8B model).  They must follow the parameters instead, and
    no device may hold more than its share."""
    import jax

    from ray_tpu.models import llama
    from ray_tpu.parallel.mesh import MeshConfig, create_mesh
    from ray_tpu.train.step import create_train_state, default_optimizer

    devices = jax.devices()[:4]
    mesh = create_mesh(MeshConfig(fsdp=2, tp=2), devices=devices)
    cfg = llama.LlamaConfig.tiny()
    with mesh:
        state = create_train_state(llama, cfg, mesh, default_optimizer(),
                                   jax.random.PRNGKey(0))
    adam = state["opt_state"][1][0]
    params = jax.tree.leaves(state["params"])
    for moments in (adam.mu, adam.nu):
        for moment, param in zip(jax.tree.leaves(moments), params,
                                 strict=True):
            assert moment.sharding == param.sharding
    held = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(state):
        assert leaf.sharding.device_set == set(devices)
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    # the norms and counters are replicated; everything else is quartered
    assert max(held.values()) < 0.3 * total, held
