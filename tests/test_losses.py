"""``chunked_softmax_xent`` against the unchunked float32 loss.

Value, gradient in the activations and gradient in the head, on no mesh
and on three meshes of the virtual CPU devices, through the chunked path,
the pad-and-mask path (an odd length) and the single pass (``chunk=0``),
for a head that is ``[embed, vocab]`` and for GPT-2's ``wte.T``.  The pass
makes its gradients beside the loss (a ``jax.custom_vjp``), so the
cotangent that comes from upstream has a test of its own.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding

from ray_tpu.models.losses import chunked_softmax_xent
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.parallel.sharding import to_partition_spec

BATCH, D_MODEL, VOCAB, CHUNK = 4, 32, 64, 16
MESHES = {"no_mesh": None, "fsdp2_tp2": {"fsdp": 2, "tp": 2},
          "fsdp4": {"fsdp": 4}, "tp4": {"fsdp": 1, "tp": 4}}
# (sequence, chunk): a multiple of the chunk; an odd length, as every
# next-token loss has (tokens[:, :-1]); one pass
LENGTHS = {"whole_chunks": (64, CHUNK), "odd_length": (63, CHUNK),
           "one_pass": (64, 0)}
# float32 holds the reference to rounding; bf16 operands carry 8 bits, and
# the reference is given the same rounded operands
TOLERANCE = {"float32": 1e-5, "bfloat16": 2e-2}


def _mesh(name):
    if MESHES[name] is None:
        return None
    return create_mesh(MeshConfig(**MESHES[name]), devices=jax.devices()[:4])


def _reference(x, head, targets):
    logits = jnp.einsum("bsd,dv->bsv", x.astype(jnp.float32),
                        head.astype(jnp.float32), precision="highest")
    gold = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(jax.scipy.special.logsumexp(logits, axis=-1) - gold)


def _inputs(seq, dtype, tied, mesh):
    kx, kw, kt = jax.random.split(jax.random.PRNGKey(seq), 3)
    x = jax.random.normal(kx, (BATCH, seq, D_MODEL)).astype(dtype)
    shape = (VOCAB, D_MODEL) if tied else (D_MODEL, VOCAB)
    weight = (jax.random.normal(kw, shape) * 0.3).astype(dtype)
    targets = jax.random.randint(kt, (BATCH, seq), 0, VOCAB)
    if mesh is not None:
        put = lambda a, *names: jax.device_put(a, NamedSharding(  # noqa: E731
            mesh, to_partition_spec(names)))
        x = put(x, "batch", "seq", None)
        targets = put(targets, "batch", "seq")
        weight = put(weight, *(("vocab", "embed") if tied
                               else ("embed", "vocab")))
    return x, weight, targets


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * max(np.abs(want).max(), 1e-6)


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("tied", [False, True], ids=["embed_vocab", "wte_T"])
@pytest.mark.parametrize("length", sorted(LENGTHS))
@pytest.mark.parametrize("layout", sorted(MESHES))
def test_value_and_gradients_match_the_unchunked_float32_loss(
        layout, length, tied, dtype):
    mesh = _mesh(layout)
    (seq, chunk), tol = LENGTHS[length], TOLERANCE[dtype]
    x, weight, targets = _inputs(seq, dtype, tied, mesh)
    as_head = (lambda w: w.T) if tied else (lambda w: w)

    def ours(x, w):
        return chunked_softmax_xent(x, as_head(w), targets, chunk=chunk,
                                    mesh=mesh)

    def theirs(x, w):
        return _reference(x, as_head(w), targets)

    got = jax.jit(jax.value_and_grad(ours, argnums=(0, 1)))(x, weight)
    want = jax.jit(jax.value_and_grad(theirs, argnums=(0, 1)))(x, weight)
    _close(got[0], want[0], tol)
    for g, w, like in zip(got[1], want[1], (x, weight)):
        assert g.dtype == like.dtype
        assert g.sharding.is_equivalent_to(like.sharding, g.ndim)
        _close(g, w, tol)
    if dtype == "float32":  # and from the pass that makes no gradients
        _close(jax.jit(ours)(x, weight), want[0], tol)


def test_the_upstream_cotangent_scales_both_gradients():
    """A pass that makes its gradients beside the loss must still apply
    what comes from upstream: the gradient of 3 x loss is 3 x."""
    mesh = _mesh("fsdp2_tp2")
    x, head, targets = _inputs(63, "float32", False, mesh)

    def loss(x, w, scale):
        return scale * chunked_softmax_xent(x, w, targets, chunk=CHUNK,
                                            mesh=mesh)

    grad = jax.jit(jax.grad(loss, argnums=(0, 1)), static_argnums=2)
    for one, three in zip(grad(x, head, 1.0), grad(x, head, 3.0)):
        assert np.abs(np.asarray(one)).max() > 0
        np.testing.assert_allclose(np.asarray(three), 3.0 * np.asarray(one),
                                   rtol=1e-6)
