"""Sequence-parallel attention vs dense reference, on the 8-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.ring_attention import sequence_parallel_attention
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.train.step import data_sharding


def _make_qkv(key, batch=2, seq=64, heads=4, kv_heads=4, d=16):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (batch, seq, heads, d), jnp.float32)
    k = jax.random.normal(kk, (batch, seq, kv_heads, d), jnp.float32)
    v = jax.random.normal(kv, (batch, seq, kv_heads, d), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("impl", ["ring", "ulysses"])
@pytest.mark.parametrize("causal", [True, False])
def test_matches_dense(impl, causal):
    mesh = create_mesh(MeshConfig(fsdp=2, sp=4, tp=1))
    q, k, v = _make_qkv(jax.random.PRNGKey(0))
    ref = flash_attention(q, k, v, causal=causal, impl="xla")
    out = jax.jit(lambda q, k, v: sequence_parallel_attention(
        q, k, v, mesh, impl=impl, causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_gqa_and_tp():
    mesh = create_mesh(MeshConfig(fsdp=2, sp=2, tp=2))
    q, k, v = _make_qkv(jax.random.PRNGKey(1), heads=4, kv_heads=2)
    ref = flash_attention(q, k, v, causal=True, impl="xla")
    out = jax.jit(lambda q, k, v: sequence_parallel_attention(
        q, k, v, mesh, impl="ring"))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_ring_gradients_match_dense():
    mesh = create_mesh(MeshConfig(fsdp=1, dp=2, sp=4, tp=1))
    q, k, v = _make_qkv(jax.random.PRNGKey(2), seq=32, d=8)

    def loss_ring(q, k, v):
        out = sequence_parallel_attention(q, k, v, mesh, impl="ring")
        return jnp.sum(jnp.sin(out))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(flash_attention(q, k, v, impl="xla")))

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-3, atol=1e-3)


def test_sp1_falls_back_to_flash():
    mesh = create_mesh(MeshConfig(fsdp=-1, sp=1))
    q, k, v = _make_qkv(jax.random.PRNGKey(3))
    ref = flash_attention(q, k, v, causal=True)
    out = sequence_parallel_attention(q, k, v, mesh, impl="ring")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_llama_ring_attention_end_to_end():
    """Llama forward with ring attention == single-device forward."""
    from ray_tpu.models import llama

    mesh = create_mesh(MeshConfig(fsdp=2, sp=2, tp=2))
    cfg = llama.LlamaConfig.tiny()
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                cfg.vocab_size, dtype=jnp.int32)
    ref = llama.apply(params, tokens, cfg, attn_impl="xla")
    with mesh:
        tokens_sharded = jax.device_put(tokens, data_sharding(mesh))
        out = jax.jit(lambda p, t: llama.apply(
            p, t, cfg, attn_impl="ring", mesh=mesh))(params, tokens_sharded)
    # bf16 compute: ring vs dense differ in reduction order, so compare
    # loosely elementwise.
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=5e-2, atol=1e-1)


@pytest.mark.parametrize("causal", [True, False])
def test_zigzag_matches_dense(causal):
    mesh = create_mesh(MeshConfig(fsdp=2, sp=4, tp=1))
    q, k, v = _make_qkv(jax.random.PRNGKey(3))
    ref = flash_attention(q, k, v, causal=causal, impl="xla")
    out = jax.jit(lambda q, k, v: sequence_parallel_attention(
        q, k, v, mesh, impl="zigzag", causal=causal))(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_zigzag_gradients_match_dense():
    mesh = create_mesh(MeshConfig(fsdp=1, dp=2, sp=4, tp=1))
    q, k, v = _make_qkv(jax.random.PRNGKey(4))

    def loss_sp(q, k, v):
        out = sequence_parallel_attention(q, k, v, mesh, impl="zigzag")
        return jnp.sum(out * out)

    def loss_dense(q, k, v):
        out = flash_attention(q, k, v, causal=True, impl="xla")
        return jnp.sum(out * out)

    g_sp = jax.jit(jax.grad(loss_sp, argnums=(0, 1, 2)))(q, k, v)
    g_dn = jax.jit(jax.grad(loss_dense, argnums=(0, 1, 2)))(q, k, v)
    for a, b in zip(g_sp, g_dn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_zigzag_balances_causal_work():
    """The point of zigzag: with contiguous sharding the per-shard
    unmasked area ranges ~sp-fold across the ring;
    zigzag pins every shard's total work to within one block of uniform.
    Computed analytically from the layout (multi-device wall-clock cannot
    be observed on a host-emulated mesh)."""
    from ray_tpu.ops.ring_attention import _shard_positions, zigzag_permutation

    sp, s_loc = 8, 16
    seq = sp * s_loc

    def shard_work(layout):
        work = []
        for i in range(sp):
            rows = np.asarray(_shard_positions(jnp.asarray(i), s_loc, sp,
                                               layout))
            unmasked = 0
            for src in range(sp):
                cols = np.asarray(_shard_positions(jnp.asarray(src), s_loc,
                                                   sp, layout))
                unmasked += int((rows[:, None] >= cols[None, :]).sum())
            work.append(unmasked)
        return work

    contiguous, zigzag = shard_work("contiguous"), shard_work("zigzag")
    # identical total area (same global causal mask)...
    assert sum(contiguous) == sum(zigzag) == seq * (seq + 1) // 2
    # ...but contiguous spreads ~sp-fold while zigzag is near-uniform
    assert max(contiguous) / min(contiguous) > 4.0
    assert max(zigzag) / min(zigzag) < 1.1

    # the permutation round-trips
    perm, inv = zigzag_permutation(seq, sp)
    x = np.arange(seq)
    assert (x[perm][inv] == x).all()
