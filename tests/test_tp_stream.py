"""The training trunk with its stream split over ``tp``
(``parallel/tp_stream.py``) against the same trunk on no mesh.

Loss and every gradient on meshes of the virtual CPU devices, with and
without ``remat``, at an odd position count (a next-token loss runs
``tokens[:, :-1]``); what the compiled step ships between devices inside
the layer scans; the ring's two products alone against ``all_gather`` and
``psum_scatter``; and the meshes on which the split must NOT engage, whose
programs stay what they were.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from benchmarks.trace.device_parts import split_path
from ray_tpu.models import llama
from ray_tpu.parallel import tp_stream
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.parallel.sharding import DEFAULT_RULES, named_shardings
from ray_tpu.train.step import (create_train_state, default_optimizer,
                                make_train_step)

# float32 holds the no-mesh trunk to rounding (tests/test_losses.py's
# tolerance); in bf16 both sides round on their own, and the propagated
# layout this replaces reads 0.022-0.024 against no mesh on the same inputs
TOLERANCE = {"float32": 1e-5, "bfloat16": 5e-2}
MESHES = {"fsdp2_tp2": {"fsdp": 2, "tp": 2},
          "dp2_fsdp2_tp2": {"dp": 2, "fsdp": 2, "tp": 2},
          "tp4": {"fsdp": 1, "tp": 4}, "fsdp2_tp4": {"fsdp": 2, "tp": 4}}
BATCH, SEQ = 8, 34  # 33 positions


def _mesh(name):
    sizes = MESHES[name]
    return create_mesh(MeshConfig(**sizes),
                       devices=jax.devices()[:int(np.prod(list(
                           sizes.values())))])


def _cfg(dtype="float32", remat=False, **kw):
    # four KV heads, so that four ``tp`` devices divide them
    return dataclasses.replace(llama.LlamaConfig.tiny(), n_kv_heads=4,
                               dtype=dtype, remat=remat, loss_chunk=16, **kw)


def _tokens(cfg, batch=BATCH):
    return jax.random.randint(jax.random.PRNGKey(1), (batch, SEQ), 0,
                              cfg.vocab_size)


def _value_and_grad(cfg, mesh, params, tokens):
    def loss(p):
        return llama.loss_fn(p, tokens, cfg, attn_impl="xla", mesh=mesh)
    return jax.jit(jax.value_and_grad(loss))(params)


@pytest.mark.parametrize("dtype", sorted(TOLERANCE))
@pytest.mark.parametrize("remat", [False, True], ids=["no_remat", "remat"])
@pytest.mark.parametrize("layout", sorted(MESHES))
def test_split_loss_and_gradients_match_no_mesh(layout, remat, dtype):
    cfg, mesh = _cfg(dtype, remat), _mesh(layout)
    assert llama._tp_split(cfg, BATCH, "xla", mesh, None) is not None
    params = llama.init(cfg, jax.random.PRNGKey(0))
    tokens = _tokens(cfg)
    want = _value_and_grad(cfg, None, params, tokens)
    with mesh:
        got = _value_and_grad(cfg, mesh, jax.device_put(
            params, named_shardings(llama.param_logical_specs(cfg), mesh)),
            tokens)
    tol = TOLERANCE[dtype]
    assert abs(float(got[0]) - float(want[0])) <= tol * float(want[0])
    flat_got, tree = jax.tree.flatten(got[1])
    assert tree == jax.tree.structure(want[1])
    for path, g, w in zip(jax.tree.leaves_with_path(want[1]), flat_got,
                          jax.tree.leaves(want[1])):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert g.shape == w.shape
        assert np.abs(g - w).max() <= tol * np.abs(w).max(), path[0]


def _step_text(remat):
    cfg, mesh = _cfg("bfloat16", remat), _mesh("fsdp2_tp2")
    opt = default_optimizer()
    with mesh:
        state = create_train_state(llama, cfg, mesh, opt,
                                   jax.random.PRNGKey(0))
        step = make_train_step(llama, cfg, mesh, opt, attn_impl="xla",
                               donate=False)
        return step.lower(state, _tokens(cfg)).compile().as_text(), cfg


_COLLECTIVE = re.compile(
    r" = (.*?) (all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")


def _links_in_the_layer_scans(text):
    """[(collective, shapes, part, phase)] of the collectives that lie in a
    loop under ``layers``."""
    found = []
    for line in text.splitlines():
        hit = _COLLECTIVE.search(line)
        name = re.search(r'op_name="([^"]*)"', line)
        if not (hit and name and "layers" in name.group(1)
                and "while/body" in name.group(1)):
            continue
        if re.search(r"replica_groups=\{(\{\d+\},?)+\}", line):
            continue  # a sum over no axis: groups of one device, no link
        shapes = [tuple(int(n) for n in dims.split(",") if n)
                  for dims in re.findall(r"\b(?:bf16|f32)\[([\d,]*)\]",
                                         hit.group(1))]
        found.append((hit.group(2), shapes,
                      *split_path(name.group(1), llama.PARTS)))
    return found


@pytest.mark.parametrize("remat", [False, True], ids=["no_remat", "remat"])
def test_compiled_step_passes_the_stream_round_the_ring(remat):
    """Inside the layer scans of the compiled step no all-reduce carries
    anything the stream's size (a device's rows or its group's), and a
    layer makes the links the ring writes: forward two gathers and two
    scatters; backward the same four transposed; under ``remat`` the
    forward's again but the last scatter, whose sum no gradient needs."""
    text, cfg = _step_text(remat)
    links = _links_in_the_layer_scans(text)
    assert links
    stream = {(rows, SEQ - 1, cfg.d_model)
              for rows in (BATCH // 4, BATCH // 2)}
    for kind, shapes, part, phase in links:
        if kind == "all-reduce":
            assert not stream & set(shapes), (kind, shapes, part, phase)
    passes = {}
    for kind, shapes, part, phase in links:
        if kind == "collective-permute":
            assert part in ("tp/gather", "tp/scatter"), (part, phase)
            assert set(shapes) == {(BATCH // 4, SEQ - 1, cfg.d_model)}
            passes[part, phase] = passes.get((part, phase), 0) + 1
    want = {("tp/gather", "fwd"): 2, ("tp/scatter", "fwd"): 2,
            ("tp/gather", "bwd"): 2, ("tp/scatter", "bwd"): 2}
    if remat:
        want.update({("tp/gather", "recompute"): 2,
                     ("tp/scatter", "recompute"): 1})
    assert passes == want


@pytest.mark.parametrize("tp", [2, 4])
def test_ring_products_are_a_gather_and_a_scatter(tp):
    """``Ring.into`` holds the group's rows in RING order (the device's own
    first), ``Ring.back`` takes them in that order: composed they are what
    ``all_gather`` and ``psum_scatter`` compose to, values and gradients."""
    mesh = create_mesh(MeshConfig(fsdp=1, tp=tp), devices=jax.devices()[:tp])
    ring = tp_stream.Ring("tp", tp)
    ks = jax.random.split(jax.random.PRNGKey(tp), 4)
    h = jax.random.normal(ks[0], (2 * tp, 5, 16))
    w1, w2 = (jax.random.normal(k, (16, 8 * tp)) for k in ks[1:3])
    w3 = jax.random.normal(ks[3], (8 * tp, 16))

    def through(products):
        def local(h, w1, w2, w3):
            return products(h, w1, w2, w3)
        f = jax.shard_map(local, mesh=mesh,
                          in_specs=(P("tp"), P(None, "tp"), P(None, "tp"),
                                    P("tp")),
                          out_specs=P("tp"), check_vma=False)
        return lambda *args: jnp.sum(jnp.sin(f(*args)))

    def ours(h, w1, w2, w3):
        g, u = ring.into(h, w1, w2)
        return ring.back(jnp.tanh(g) * u, w3)

    def theirs(h, w1, w2, w3):
        rows = jax.lax.all_gather(h, "tp", axis=0, tiled=True)
        return jax.lax.psum_scatter((jnp.tanh(rows @ w1) * (rows @ w2)) @ w3,
                                    "tp", scatter_dimension=0, tiled=True)

    args = (h, w1, w2, w3)
    got = jax.jit(jax.value_and_grad(through(ours), argnums=(0, 1, 2, 3)))(
        *args)
    want = jax.jit(jax.value_and_grad(through(theirs), argnums=(0, 1, 2, 3)))(
        *args)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=2e-4,
                                   atol=2e-4)


SIZES = {"batch": 8, "heads": 4, "kv_heads": 4, "mlp": 256}
LAYER = jax.tree.map(
    lambda spec: spec[1:],
    llama.param_logical_specs(llama.LlamaConfig.tiny())["layers"],
    is_leaf=lambda s: isinstance(s, tuple))


def test_split_lays_rows_over_batch_axes_then_tp():
    split = tp_stream.stream_split(_mesh("dp2_fsdp2_tp2"), None, LAYER, SIZES)
    assert split.ring == tp_stream.Ring("tp", 2)
    assert split.rows == P(("dp", "fsdp", "tp"), None, None)
    assert split.whole == P(("dp", "fsdp"), None, None)
    assert split.weights["attn"]["wq"] == P(("fsdp",), ("tp",))
    assert split.weights["mlp"]["w_down"] == P(("tp",), ("fsdp",))
    # what is gathered inside the layer: the fsdp half of each matrix
    assert split.gathers["attn"]["wo"] == ((), ("fsdp",))
    assert split.gathers["attn_norm"] == ((),)


@pytest.mark.parametrize("why, mesh, rules, sizes", [
    ("no tp axis", {"fsdp": 4}, None, SIZES),
    ("positions are split too", {"fsdp": 2, "sp": 2, "tp": 2}, None, SIZES),
    ("rows do not divide", {"fsdp": 2, "tp": 2}, None, {**SIZES, "batch": 2}),
    ("kv heads do not divide", {"fsdp": 1, "tp": 4}, None,
     {**SIZES, "kv_heads": 2}),
    ("mlp split over another axis", {"fsdp": 2, "tp": 2},
     {**DEFAULT_RULES, "mlp": "fsdp"}, SIZES),
    ("columns over two axes", {"fsdp": 2, "tp": 2},
     {**DEFAULT_RULES, **dict.fromkeys(("heads", "kv_heads", "mlp"),
                                       ("fsdp", "tp")), "embed": None},
     SIZES),
    ("tp shards the batch as well", {"fsdp": 2, "tp": 2},
     {**DEFAULT_RULES, "batch": ("fsdp", "tp")}, SIZES),
])
def test_split_does_not_engage(why, mesh, rules, sizes):
    n = int(np.prod(list(mesh.values())))
    mesh = create_mesh(MeshConfig(**{"fsdp": 1, **mesh}),
                       devices=jax.devices()[:n])
    assert tp_stream.stream_split(mesh, rules, LAYER, sizes) is None, why


def test_sequence_parallel_attention_keeps_the_propagated_layout():
    assert llama._tp_split(_cfg(), BATCH, "ring", _mesh("fsdp2_tp2"),
                           None) is None


@pytest.mark.parametrize("layout, batch", [
    ("no_mesh", 8), ("fsdp4", 8), ("fsdp2_tp2", 2)])
def test_without_a_tp_axis_that_divides_the_program_is_the_old_one(
        layout, batch, monkeypatch):
    """``mesh=None`` (every served program), a mesh with no ``tp``, rows
    that do not divide: the trunk asks for no layout and passes nothing
    round a ring; its lowered text is the one it had (hash for hash against
    the parent commit: CHANGES.md, PR 43)."""
    attn_impl = "xla"
    def refuse(*a, **k):
        raise AssertionError("the ring was used")
    monkeypatch.setattr(tp_stream.Ring, "into", refuse)
    monkeypatch.setattr(tp_stream.Ring, "back", refuse)
    meshes = {"no_mesh": None, "fsdp4": {"fsdp": 4},
              "fsdp2_tp2": {"fsdp": 2, "tp": 2}}
    mesh = meshes[layout] and create_mesh(MeshConfig(**meshes[layout]),
                                          devices=jax.devices()[:4])
    cfg = _cfg(remat=True)
    assert llama._tp_split(cfg, batch, attn_impl, mesh, None) is None
    params = llama.init(cfg, jax.random.PRNGKey(0))

    def grad(p, tokens):
        return jax.grad(lambda p: llama.loss_fn(
            p, tokens, cfg, attn_impl=attn_impl, mesh=mesh))(p)
    text = jax.jit(grad).lower(params, _tokens(cfg, batch)).as_text()
    assert "collective_permute" not in text
    assert "sharding_constraint" not in text
    if mesh is None:
        assert not re.search(r"all_gather|all_reduce|shard_map|manual", text)
