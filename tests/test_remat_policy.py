"""What the layers' ``remat`` keeps (``models/llama.py`` ``REMAT_KEEPS``).

``remat=True`` keeps a layer's input and the flash kernel's output and row
statistics, and makes the rest again in the backward pass.  At tiny sizes
on the CPU, with the kernels interpreted (``attn_impl="pallas"``): loss and
every gradient equal those of ``remat=False`` on no mesh and with the
stream split over ``tp`` on four devices; and the COMPILED gradient's
recompute holds no run of the forward kernel and no product under
``attn/attend``, while ``mlp/gate_up`` and ``attn/qkv`` are still made
again there.  With the kept set emptied the kernel is back in the
recompute, so the reading can see what it says is gone.
"""

import contextlib
import dataclasses
import re

import jax
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from benchmarks.trace.device_parts import split_path
from ray_tpu.models import llama
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.parallel.sharding import named_shardings

# float32 holds remat to rounding; in bf16 the two programs fuse, and so
# round, on their own (tests/test_tp_stream.py's tolerances)
TOLERANCE = {"float32": 1e-5, "bfloat16": 5e-2}
MESHES = {"no_mesh": None, "fsdp2_tp2": {"fsdp": 2, "tp": 2}}
BATCH, SEQ = 8, 34  # 33 positions: the kernel pads them to its block


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """Metadata is not in the persistent cache's key (tests/
    test_model_parts.py): the paths read below must be this tree's."""
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _mesh(name):
    sizes = MESHES[name]
    return sizes and create_mesh(MeshConfig(**sizes),
                                 devices=jax.devices()[:4])


def _cfg(dtype, remat):
    return dataclasses.replace(llama.LlamaConfig.tiny(), n_kv_heads=2,
                               dtype=dtype, remat=remat, loss_chunk=16)


def _inputs(cfg, mesh):
    params = llama.init(cfg, jax.random.PRNGKey(0))
    if mesh is not None:
        params = jax.device_put(
            params, named_shardings(llama.param_logical_specs(cfg), mesh))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (BATCH, SEQ), 0,
                                cfg.vocab_size)
    return params, tokens


def _value_and_grad(cfg, mesh):
    def loss(p, tokens):
        return llama.loss_fn(p, tokens, cfg, attn_impl="pallas", mesh=mesh)
    return jax.jit(jax.value_and_grad(loss))


def _on(mesh):
    return contextlib.nullcontext() if mesh is None else mesh


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("layout", sorted(MESHES))
def test_loss_and_gradients_are_those_without_remat(layout, dtype):
    mesh = _mesh(layout)
    if mesh is not None:
        assert llama._tp_split(_cfg(dtype, True), BATCH, "pallas", mesh,
                               None) is not None
    got, want = [], []
    for out, remat in ((got, True), (want, False)):
        cfg = _cfg(dtype, remat)
        with _on(mesh):
            out.extend(_value_and_grad(cfg, mesh)(*_inputs(cfg, mesh)))
    tol = TOLERANCE[dtype]
    assert abs(float(got[0]) - float(want[0])) <= tol * float(want[0])
    assert jax.tree.structure(got[1]) == jax.tree.structure(want[1])
    for (path, w), g in zip(jax.tree.leaves_with_path(want[1]),
                            jax.tree.leaves(got[1])):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), path


def _op_names(mesh):
    """The ``op_name`` of every operation of the compiled bf16 gradient
    under ``remat``."""
    cfg = _cfg("bfloat16", True)
    with _on(mesh):
        text = _value_and_grad(cfg, mesh).lower(
            *_inputs(cfg, mesh)).compile().as_text()
    return sorted(n for n in set(re.findall(r'op_name="([^"]*)"', text))
                  if n.startswith("jit("))


def _recomputed(names):
    """(the parts whose PRODUCTS the recompute holds, whether it runs the
    forward kernel)."""
    products, kernel = set(), False
    for n in names:
        part, phase = split_path(n, llama.PARTS)
        if phase != "recompute":
            continue
        kernel |= "flash_attention_fwd" in re.split(r"[/()]", n)
        if "dot_general" in n:
            products.add(part)
    return products, kernel


@pytest.mark.parametrize("layout", sorted(MESHES))
def test_recompute_runs_no_forward_kernel(layout, monkeypatch):
    mesh = _mesh(layout)
    names = _op_names(mesh)
    # the kernel runs, forward and backward, outside the recompute
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dkv",
                   "flash_attention_bwd_dq"):
        assert any(kernel in re.split(r"[/()]", n) for n in names), kernel
    products, kernel = _recomputed(names)
    assert not kernel
    assert "attn/attend" not in products, products
    # what is still made again from the layer's input: q, k, v for the
    # backward kernels, the attention output for the MLP's input, and the
    # MLP's first two products; never the last product, whose sum nothing
    # needs
    assert {"attn/qkv", "attn/out", "mlp/gate_up"} <= products, products
    assert "mlp/down" not in products

    # the same reading with nothing kept beside the carry: the forward
    # kernel (interpreted here: its products) is back in the recompute
    monkeypatch.setattr(llama, "REMAT_KEEPS", ())
    products, kernel = _recomputed(_op_names(mesh))
    assert kernel and "attn/attend" in products
