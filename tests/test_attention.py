"""Flash-attention kernel tests (interpret mode on CPU) vs XLA reference."""

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import flash_attention

B, S, H, D = 2, 256, 4, 64


@pytest.fixture(scope="module")
def qkv():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, H, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, H, D), jnp.float32)
    return q, k, v


def _pallas(q, k, v, **kw):
    return flash_attention(q, k, v, impl="pallas", block_q=128, block_k=128,
                           **kw)


def _xla(q, k, v, **kw):
    return flash_attention(q, k, v, impl="xla", **kw)


def test_forward_causal_matches_reference(qkv):
    q, k, v = qkv
    err = jnp.abs(_pallas(q, k, v, causal=True) - _xla(q, k, v, causal=True))
    assert float(err.max()) < 1e-5


def test_forward_noncausal_matches_reference(qkv):
    q, k, v = qkv
    err = jnp.abs(_pallas(q, k, v, causal=False) - _xla(q, k, v, causal=False))
    assert float(err.max()) < 1e-5


def _f64_grads(q, k, v, causal=True):
    """Ground-truth gradients of sum(attn^2) in float64 numpy.  K and V may
    have fewer heads (GQA: a KV head's gradient is summed over the query
    heads that read it) and another length than q."""
    import numpy as np

    qf, kf, vf = (np.asarray(x, np.float64) for x in (q, k, v))
    b, s, h, d = qf.shape
    sk, kvh = kf.shape[1], kf.shape[2]
    reps = h // kvh

    def pack(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, x.shape[1], d)

    qf = pack(qf)
    kf, vf = (pack(np.repeat(x, reps, axis=2)) for x in (kf, vf))
    sc = np.einsum("bqd,bkd->bqk", qf, kf) / np.sqrt(d)
    if causal:
        sc = np.where(np.arange(s)[:, None] >= np.arange(sk)[None, :],
                      sc, -np.inf)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    o = np.einsum("bqk,bkd->bqd", p, vf)
    do = 2 * o
    dv = np.einsum("bqk,bqd->bkd", p, do)
    dp = np.einsum("bqd,bkd->bqk", do, vf)
    delta = np.sum(do * o, -1, keepdims=True)
    ds = p * (dp - delta) / np.sqrt(d)
    dq = np.einsum("bqk,bkd->bqd", ds, kf)
    dk = np.einsum("bqk,bqd->bkd", ds, qf)

    def unpack(x):
        return x.reshape(b, h, x.shape[1], d).transpose(0, 2, 1, 3)

    def per_kv_head(x):
        x = unpack(x)
        return x.reshape(b, sk, kvh, reps, d).sum(3)

    return unpack(dq), per_kv_head(dk), per_kv_head(dv)


def _inputs(seq_q, seq_k, kv_heads, seed=3, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(
        jax.random.normal(key, shape, jnp.float32).astype(dtype)
        for key, shape in zip(ks, ((B, seq_q, H, D), (B, seq_k, kv_heads, D),
                                   (B, seq_k, kv_heads, D))))


def _grads(attn_fn, q, k, v, causal):
    return jax.grad(lambda q, k, v: jnp.sum(
        attn_fn(q, k, v, causal=causal).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("kv_heads", [H, 2, 1])
def test_gradients_match_float64_truth(qkv, kv_heads):
    """The Pallas backward (FlashAttention-2 dq/dkv kernels) must be as
    accurate as the dense f32 backward against float64 ground truth.  The
    two f32 backwards CANNOT be compared to each other tightly — different
    summation orders diverge by ~1e-2 at seq 256 while both sit the same
    distance from the true gradient.  With fewer KV heads (``reps`` 2 and
    4) the dK/dV kernel sums a KV head's gradient over its query heads."""
    import numpy as np

    q, k, v = qkv if kv_heads == H else _inputs(S, S, kv_heads)

    def loss(attn_fn):
        return lambda q, k, v: jnp.sum(attn_fn(q, k, v, causal=True) ** 2)

    gp = jax.grad(loss(_pallas), argnums=(0, 1, 2))(q, k, v)
    gx = jax.grad(loss(_xla), argnums=(0, 1, 2))(q, k, v)
    truth = _f64_grads(q, k, v)
    for name, a, b, t in zip(("dq", "dk", "dv"), gp, gx, truth):
        err_pallas = float(np.abs(np.asarray(a, np.float64) - t).max())
        err_dense = float(np.abs(np.asarray(b, np.float64) - t).max())
        assert err_pallas < 2.0 * err_dense + 1e-4, (
            f"{name}: pallas {err_pallas} vs dense {err_dense}")


def test_gradients_noncausal_match_truth(qkv):
    import numpy as np

    q, k, v = qkv

    def loss_fn(q, k, v):
        return jnp.sum(_pallas(q, k, v, causal=False) ** 2)

    gp = jax.grad(loss_fn, argnums=(0, 1, 2))(q, k, v)
    truth = _f64_grads(q, k, v, causal=False)
    for a, t in zip(gp, truth):
        scale = float(np.abs(t).max())
        assert float(np.abs(np.asarray(a, np.float64) - t).max()) \
            < 3e-3 * max(scale, 1.0)


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("seq_q,seq_k", [(200, 200), (136, 200), (200, 136)])
def test_ragged_lengths_match_truth(seq_q, seq_k, causal):
    """Lengths that are no multiple of the block (128 here), equal and not:
    the operands are padded to whole blocks, and no kept row may see a
    padded column, forward or backward, whether the diagonal hides it
    (causal, no more rows than keys: no tail test runs) or the tail mask
    does."""
    import numpy as np

    q, k, v = _inputs(seq_q, seq_k, 2)
    err = jnp.abs(_pallas(q, k, v, causal=causal)
                  - _xla(q, k, v, causal=causal))
    assert float(err.max()) < 1e-5
    got = _grads(_pallas, q, k, v, causal)
    for name, a, t in zip(("dq", "dk", "dv"), got,
                          _f64_grads(q, k, v, causal=causal)):
        scale = max(float(np.abs(t).max()), 1.0)
        assert float(np.abs(np.asarray(a, np.float64) - t).max()) \
            < 3e-3 * scale, name


@pytest.mark.parametrize("block_q,block_k", [(384, 768), (512, 512)])
def test_stairs_and_sub_blocks_match_truth(block_q, block_k):
    """Blocks wide enough for what the small ones above never reach: a
    tile of two sub-blocks (the one over the diagonal left out), and the
    square on the diagonal computed in stairs of 128 or 256 rows, in the
    forward and, transposed, in the dK/dV kernel."""
    import numpy as np

    sched = attention.tile_schedule(700, 700, block_q, block_k)
    assert sched["entries_computed"] < (
        sched["tiles_visited"] * block_q * block_k)
    q, k, v = _inputs(700, 700, 2, seed=5)

    def blocked(q, k, v, **kw):
        return flash_attention(q, k, v, impl="pallas", block_q=block_q,
                               block_k=block_k, **kw)

    err = jnp.abs(blocked(q, k, v, causal=True) - _xla(q, k, v, causal=True))
    assert float(err.max()) < 1e-5
    for name, a, t in zip(("dq", "dk", "dv"), _grads(blocked, q, k, v, True),
                          _f64_grads(q, k, v, causal=True)):
        scale = max(float(np.abs(t).max()), 1.0)
        assert float(np.abs(np.asarray(a, np.float64) - t).max()) \
            < 3e-3 * scale, name


def test_bf16_operands_match_float32_reference():
    """The products take bf16 operands as they arrive (and ``p``, ``ds``
    in bf16 for their second product) with float32 sums and statistics:
    against the float32 reference of the SAME bf16 values the result is
    off by bf16's rounding and no more."""
    import numpy as np

    q, k, v = _inputs(S, S, 2, dtype=jnp.bfloat16)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    out = _pallas(q, k, v, causal=True)
    assert out.dtype == jnp.bfloat16
    want = _xla(*f32, causal=True)
    assert float(jnp.abs(out.astype(jnp.float32) - want).max()) \
        < 2e-2 * float(jnp.abs(want).max())
    for a, t in zip(_grads(_pallas, q, k, v, True),
                    _f64_grads(*f32, causal=True)):
        assert a.dtype == jnp.bfloat16
        assert float(np.abs(np.asarray(a, np.float64) - t).max()) \
            < 3e-2 * float(np.abs(t).max())


def test_masked_and_plain_bodies_agree_off_the_diagonal(monkeypatch):
    """Every kernel has two bodies, and a tile that no mask can change
    runs the plain one.  Sent through the masked body instead (every tile
    declared crossed; blocks of 128 are one step) the same tiles give the same
    numbers: the mask keeps all of a tile that lies under the diagonal."""
    q, k, v = _inputs(384, 384, 2, seed=11)

    def run():
        jax.clear_caches()
        return (_pallas(q, k, v, causal=True),
                *_grads(_pallas, q, k, v, True))

    plain = run()
    monkeypatch.setattr(attention, "_crossed", lambda *a: jnp.bool_(True))
    masked = run()
    monkeypatch.undo()
    jax.clear_caches()
    for a, b in zip(plain, masked):
        assert float(jnp.abs(a - b).max()) < 1e-6


def test_tile_schedule_of_the_train_cell():
    """What a call at the train cell's per-device shape (4,095 positions,
    default blocks) visits, a (batch, head): the counts the kernels' grids
    are built from.  Before PR 47: 512 x 1,024 tiles, 20 visited, all 20
    masked, 10.49 M entries for 8.39 M required (1.25)."""
    got = attention.tile_schedule(4095, 4095)
    assert got == {
        "block_q": 1024, "block_k": 1024, "tiles_visited": 10,
        "tiles_masked": 4, "entries_computed": 8_912_896,
        "entries_required": 4095 * 4096 // 2,
        "computed_over_required": 8_912_896 / (4095 * 4096 // 2)}
    assert round(got["computed_over_required"], 4) == 1.0628
    # the parent's blocks: sub-blocks of 512 leave out the halves over the
    # diagonal, the squares on it are computed in stairs
    old = attention.tile_schedule(4095, 4095, 512, 1024)
    assert (old["tiles_visited"], old["tiles_masked"]) == (20, 8)
    assert old["entries_computed"] == got["entries_computed"]
    # not causal: every tile, and only the padded tail's are masked
    full = attention.tile_schedule(4095, 4095, causal=False)
    assert (full["tiles_visited"], full["tiles_masked"]) == (16, 4)
    # the kernels are gridded over the same list
    tiles = attention._visited(4, 4, 1024, 1024, True)
    assert len(attention._by_query_block(tiles)[0]) == 10
    assert len(attention._by_key_block(tiles, 4)[0]) == 40


def test_gqa(qkv):
    q, _, _ = qkv
    ks = jax.random.split(jax.random.PRNGKey(7), 2)
    k = jax.random.normal(ks[0], (B, S, 2, D), jnp.float32)
    v = jax.random.normal(ks[1], (B, S, 2, D), jnp.float32)
    err = jnp.abs(_pallas(q, k, v, causal=True) - _xla(q, k, v, causal=True))
    assert float(err.max()) < 1e-5


def test_causal_masking_is_real(qkv):
    """Perturbing future keys must not change earlier outputs."""
    q, k, v = qkv
    out1 = _pallas(q, k, v, causal=True)
    k2 = k.at[:, S // 2:].set(jax.random.normal(
        jax.random.PRNGKey(9), (B, S // 2, H, D)))
    out2 = _pallas(q, k2, v, causal=True)
    err = jnp.abs(out1[:, : S // 2] - out2[:, : S // 2])
    assert float(err.max()) < 1e-6


def test_uneven_seq_blocks():
    # seq not divisible by typical block sizes still must work (block
    # clamps to seq when seq < block).
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 64, 2, 32))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 64, 2, 32))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 64, 2, 32))
    err = jnp.abs(
        flash_attention(q, k, v, impl="pallas") - _xla(q, k, v, causal=True))
    assert float(err.max()) < 1e-5
