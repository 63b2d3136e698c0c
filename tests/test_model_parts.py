"""The programs name their parts (``models/llama.py`` ``PARTS``).

At tiny sizes on the CPU, each program's COMPILED text is parsed for
``op_name``: every product (``dot_general``) and every kernel lies under
exactly one part, every part the program is made of occurs in it, a
gradient under ``remat`` recomputes under ``attn/*`` and ``mlp/*`` (all of a
layer but the flash kernel's forward run, whose results are kept) and one
without recomputes nothing, and a program's outputs equal, bit for bit,
those of the same function traced with ``jax.named_scope`` a no-op.  The
paths are split by the benchmark's own reader
(``benchmarks/trace/device_parts.py``), so what is checked here is what a
traced run on the chip is read with.
"""

import contextlib
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache

from benchmarks.trace.device_parts import part_runs, split_path
from ray_tpu.llm import model as lm
from ray_tpu.llm.paged_cache import CacheConfig, init_cache, init_state
from ray_tpu.models import (afmoe, falcon_h1, glm_moe_lite, llama,
                            nemotron_h,
                            longcat_flash, minicpm_sala, moe, olmo_hybrid,
                            sdar_moe)
from ray_tpu.parallel.mesh import MeshConfig, create_mesh
from ray_tpu.train.step import (create_train_state, default_optimizer,
                                make_train_step)

PARTS = frozenset(llama.PARTS)
FLASH = ("flash_attention_fwd", "flash_attention_bwd_dkv",
         "flash_attention_bwd_dq")
KERNELS = {**dict.fromkeys(FLASH, "attn/attend"),
           # (over a list of pages a KV head in MiniCPM-SALA's sparse layers)
           "paged_decode_attention": ("attn/attend", "sparse_attn/attend"),
           "paged_latent_decode_attention": "mla/attend",
           "moe_grouped_mlp": "moe/experts",
           "gated_delta_update": "lin_attn/state",
           # (the one recurrence kernel, under the family's own part)
           "lightning_update": ("lightning/state", "ssm/state")}
BLOCK = ("embed", "layers", "attn/norm", "attn/qkv", "attn/rope",
         "attn/attend", "attn/out", "mlp/norm")
DENSE = BLOCK + ("mlp/gate_up", "mlp/down")
ROUTED = BLOCK + ("moe/route", "moe/dispatch", "moe/experts", "moe/combine")
HYBRID = DENSE + ("lin_attn/proj", "lin_attn/conv", "lin_attn/gates",
                  "lin_attn/state", "lin_attn/out")
# latent attention: a leading dense layer, then routed ones with a shared
# expert; no ``attn/qkv`` (the projections are ``mla/*``); both prefills
# rebuild K and V (``mla/kv_up``) and attend densely, the decode step absorbs
LATENT = (("embed", "layers", "attn/norm", "attn/rope", "attn/kv_write",
           "attn/out", "mlp/norm", "mlp/gate_up", "mlp/down", "mla/kv_down",
           "mla/q_proj", "head") + ROUTED[-4:] + ("moe/shared",))
LATENT_PREFILL = LATENT + ("mla/kv_up", "attn/attend")  # a head a head:
# the repeat of K and V to the query heads is by one and leaves no operation
# shortcut-connected double layers over latent rows, a chip's share of the
# experts: no shared expert, the identity picks' add a part of its own
SHORTCUT = LATENT[:-1] + ("moe/zero",)
# window and full layers in one stack: gated attention with a QK norm of
# its own part, sandwich norms, a leading dense layer then routed ones
# beside a shared expert
WINDOWED = (DENSE + ROUTED[-4:] + (
    "moe/shared", "attn/qk_norm", "attn/gate", "norm/post", "attn/kv_write",
    "head"))
# block-sparse and fixed-decay linear layers in one stack: no ``attn/qkv``,
# ``attn/attend`` or ``attn/out`` (the sparse layers' products, choice of
# blocks and attention and the linear layers' products, recurrence and
# output have parts of their own)
SPARSE_LINEAR = ("embed", "layers", "attn/norm", "attn/rope", "attn/kv_write",
                 "mlp/norm", "mlp/gate_up", "mlp/down", "head",
                 "sparse_attn/proj", "sparse_attn/index",
                 "sparse_attn/attend", "lightning/proj", "lightning/state",
                 "lightning/out")
# a state-space mixer BESIDE attention in every block: the dense block's
# parts and the mixer's, state rows and pages in one layer
PARALLEL_SSM = DENSE + ("attn/kv_write", "head", "ssm/proj", "ssm/conv",
                        "ssm/gates", "ssm/state", "ssm/out")
# layers that are ONE thing each (a mixer, attention, a routed feed-forward):
# no ``attn/rope`` (its attention carries no position), no dense MLP; the
# experts in a latent with a product into it and one back, a share of them
# held (``dispatch_share`` with no identity column: its ``moe/zero`` adds 0)
ONE_KIND = (("embed", "layers", "attn/norm", "attn/qkv", "attn/kv_write",
             "attn/attend", "attn/out", "mlp/norm", "head", "ssm/proj",
             "ssm/conv", "ssm/gates", "ssm/state", "ssm/out") + ROUTED[-4:]
            + ("moe/latent_in", "moe/latent_out", "moe/shared", "moe/zero"))
PS, PAGES = 8, 16  # page size, pages in the pool


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """Metadata is not in the persistent cache's key: an entry written by
    a tree without scopes would hand back an executable without them."""
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


def _pools(cfg):
    shape = (cfg.n_layers, PAGES, PS, cfg.n_kv_heads, cfg.head_dim)
    return jnp.zeros(shape, cfg.dtype), jnp.zeros(shape, cfg.dtype)


def _dense():
    """The engine's programs run over the serving layout (one ``wqkv``);
    the gradients below keep training's three weights."""
    cfg = llama.LlamaConfig.tiny()
    return cfg, llama.serving_layout(llama.init(cfg, jax.random.PRNGKey(0)))


def _prefill():
    cfg, params = _dense()
    L = 16
    ck, cv = _pools(cfg)
    pos = np.arange(L)
    return lm.prefill, (params, jnp.arange(L, dtype=jnp.int32) % 7, ck, cv,
                        jnp.asarray(pos // PS + 1, jnp.int32), jnp.int32(13),
                        jnp.asarray(pos % PS, jnp.int32)), cfg


def _prefill_with_prefix():
    cfg, params = _dense()
    L, P = 16, 4
    ck, cv = _pools(cfg)
    pos = np.arange(L) + PS  # one resident page before the suffix
    return lm.prefill_with_prefix, (
        params, jnp.arange(L, dtype=jnp.int32) % 7, ck, cv,
        jnp.asarray(pos // PS + 1, jnp.int32), jnp.int32(13),
        jnp.asarray(pos % PS, jnp.int32), jnp.arange(1, P + 1, dtype=jnp.int32),
        jnp.asarray(pos, jnp.int32)), cfg


def _decode():
    cfg, params = _dense()
    B, P = 4, 4
    ck, cv = _pools(cfg)
    tables = jnp.asarray(np.arange(1, 1 + B * 2).reshape(B, 2).repeat(2, 1),
                         jnp.int32)[:, :P]
    return lm.decode_step_greedy, (
        params, jnp.arange(B, dtype=jnp.int32), ck, cv, tables,
        jnp.full((B,), 5, jnp.int32), jnp.ones((B,), bool)), cfg


def _block_step():
    cfg = sdar_moe.SDARMoEConfig.tiny()
    params = llama.serving_layout(sdar_moe.init(cfg, jax.random.PRNGKey(0)))
    S, P, B = 4, 4, cfg.block_length
    ck, cv = _pools(cfg)
    tables = jnp.asarray(np.arange(1, 1 + S * 2).reshape(S, 2).repeat(2, 1),
                         jnp.int32)[:, :P]
    return lm.block_step, (
        params, ck, cv, tables, jnp.ones((S,), bool),
        jnp.full((S, B), 7, jnp.int32), jnp.ones((S, B), bool),
        jnp.full((S,), 8, jnp.int32), jnp.zeros((S,), jnp.int32)), cfg


def _hybrid():
    """(configuration, serving tree, pools over the full layers, state
    rows of 4 slots)."""
    cfg = olmo_hybrid.OlmoHybridConfig.tiny()
    params = lm.serving_layout(olmo_hybrid.init(cfg, jax.random.PRNGKey(0)))
    cc = CacheConfig(**lm.cache_layout(cfg), num_pages=PAGES, page_size=PS,
                     dtype="float32", max_slots=4)
    return cfg, params, init_cache(cc), init_state(cc)


def _hybrid_prefill():
    cfg, params, (ck, cv), state = _hybrid()
    fn, (_, tokens, _, _, *rest), _ = _prefill()
    return fn, (params, tokens, ck, cv, *rest), cfg, {
        "state": state, "slot": jnp.int32(1)}


def _hybrid_decode():
    cfg, params, (ck, cv), state = _hybrid()
    fn, (_, tokens, _, _, *rest), _ = _decode()
    return fn, (params, tokens, ck, cv, *rest), cfg, {"state": state}


def _latent(program):
    """``program`` of the dense tree over a latent pool and no V pool."""
    cfg = glm_moe_lite.GLMMoELiteConfig.tiny()
    params = lm.serving_layout(glm_moe_lite.init(cfg, jax.random.PRNGKey(0)))
    pool, none = init_cache(CacheConfig(
        **lm.cache_layout(cfg), num_pages=PAGES, page_size=PS,
        dtype="float32"))
    assert none is None
    fn, (_, tokens, _, _, *rest), _ = program()
    return fn, (params, tokens, pool, None, *rest), cfg


def _shortcut(program):
    """``program`` of the dense tree over a latent pool of TWO layers a
    scanned layer, a share of the experts held."""
    cfg = longcat_flash.LongCatFlashConfig.tiny(
        n_experts_held=4, first_expert_held=8)
    params = lm.serving_layout(longcat_flash.init(cfg, jax.random.PRNGKey(0)))
    pool, none = init_cache(CacheConfig(
        **lm.cache_layout(cfg), num_pages=PAGES, page_size=PS,
        dtype="float32"))
    assert none is None and pool.shape[0] == 2 * cfg.n_layers
    fn, (_, tokens, _, _, *rest), _ = program()
    return fn, (params, tokens, pool, None, *rest), cfg


def _windowed(program):
    """``program`` of the dense tree over a pool a kind of layer: the page
    ids and the page tables come a kind too."""
    cfg = afmoe.AfmoeConfig.tiny()
    params = lm.serving_layout(afmoe.init(cfg, jax.random.PRNGKey(0)))
    ck, cv = init_cache(CacheConfig(
        **lm.cache_layout(cfg), num_pages=PAGES, page_size=PS,
        dtype="float32", window_pages=PAGES))
    fn, (_, tokens, _, _, *rest), _ = program()
    both = lambda x: {"full": x, "window": x}  # noqa: E731
    rest[0] = both(rest[0])  # page ids by position, or the page tables
    if len(rest) == 5:  # prefill_with_prefix: and its page table
        rest[3] = both(rest[3])
    return fn, (params, tokens, ck, cv, *rest), cfg


def _sparse_linear(program):
    """``program`` of the dense tree over MiniCPM-SALA's caches: pools over
    the sparse layers, the rows of pooled keys beside them (and again in
    slot order, a row an entry of the programs' 4-entry tables) and the
    linear layers' state rows (pages of 8: a pooled row a page)."""
    cfg = minicpm_sala.MiniCPMSALAConfig.tiny()
    params = cfg.serving_layout(minicpm_sala.init(cfg, jax.random.PRNGKey(0)))
    cc = CacheConfig(**lm.cache_layout(cfg), num_pages=PAGES, page_size=PS,
                     dtype="float32", max_slots=4, max_pages_per_seq=4)
    ck, cv = init_cache(cc)
    fn, (_, tokens, _, _, *rest), _ = program()
    rows = {"state": init_state(cc)}
    if program is not _decode:
        rows["slot"] = jnp.int32(1)
    return fn, (params, tokens, ck, cv, *rest), cfg, rows


def _parallel_ssm(program):
    """``program`` of the dense tree over Falcon-H1's caches: a pool layer
    AND a state layer (the state and the convolution's tail) for every
    scanned layer."""
    cfg = falcon_h1.FalconH1Config.tiny()
    params = cfg.serving_layout(falcon_h1.init(cfg, jax.random.PRNGKey(0)))
    cc = CacheConfig(**lm.cache_layout(cfg), num_pages=PAGES, page_size=PS,
                     dtype="float32", max_slots=4)
    ck, cv = init_cache(cc)
    fn, (_, tokens, _, _, *rest), _ = program()
    rows = {"state": init_state(cc)}
    if program is not _decode:
        rows["slot"] = jnp.int32(1)
    return fn, (params, tokens, ck, cv, *rest), cfg, rows


def _one_kind(program):
    """``program`` over Nemotron-H's caches: a pool layer for each ``*`` of
    the pattern and a state layer (packed rows, the convolution's tail) for
    each ``M``, the held experts stacked over the ``E`` layers."""
    cfg = nemotron_h.NemotronHConfig.tiny()
    params = cfg.serving_layout(nemotron_h.init(cfg, jax.random.PRNGKey(0)))
    cc = CacheConfig(**lm.cache_layout(cfg), num_pages=PAGES, page_size=PS,
                     dtype="float32", max_slots=4)
    ck, cv = init_cache(cc)
    fn, (_, tokens, _, _, *rest), _ = program()
    rows = {"state": init_state(cc)}
    if program is not _decode:
        rows["slot"] = jnp.int32(1)
    return fn, (params, tokens, ck, cv, *rest), cfg, rows


def _grad(model, cfg, **kw):
    """(function of (params, tokens), its arguments)."""
    params = model.init(cfg, jax.random.PRNGKey(0))
    tokens = jnp.arange(2 * 33, dtype=jnp.int32).reshape(2, 33) % 11

    def grad(params, tokens):
        return jax.grad(lambda p: model.loss_fn(p, tokens, cfg, **kw))(params)
    return grad, (params, tokens)


def _llama_grad(remat):
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), remat=remat,
                              loss_chunk=16)
    return _grad(llama, cfg, attn_impl="pallas")


def _moe_grad():
    return _grad(moe, moe.MoEConfig.tiny())


def _train_step(batch=2, **axes):
    """The step on one device, or on the mesh ``axes`` name: with a ``tp``
    axis that divides the rows the stream is split over it between the
    products (parallel/tp_stream.py) and the links have names."""
    cfg = dataclasses.replace(llama.LlamaConfig.tiny(), remat=True,
                              loss_chunk=16)
    mesh = create_mesh(MeshConfig(**{"fsdp": 1, **axes}),
                       devices=jax.devices()[:int(np.prod([*axes.values(),
                                                           1]))])
    opt = default_optimizer()
    with mesh:
        state = create_train_state(llama, cfg, mesh, opt,
                                   jax.random.PRNGKey(0))
        step = make_train_step(llama, cfg, mesh, opt, attn_impl="pallas",
                               donate=False)
    return step, (state, jnp.zeros((batch, 33), jnp.int32)), mesh


def _chained(decode):
    """A family's decode step as a burst's chained program: the same
    arguments, then the row to write and ``acc`` at the family's width."""
    _, args, cfg, *rows = decode()
    layout = lm.counted_layout(*args, cfg, **dict(*rows))
    acc = jnp.full(lm.acc_shape(args[1].shape[0], layout), -1, jnp.int32)
    return (lm.decode_step_greedy_chained, (*args, jnp.int32(0), acc), cfg,
            *rows)


def _compiled_text(name):
    """The compiled text of one of the programs below."""
    if name in ENGINE:
        fn, args, cfg, *rows = ENGINE[name]()  # rows: a hybrid's state
        return fn.lower(*args, cfg=cfg, **dict(*rows)).compile().as_text()
    if name in TRAIN:
        step, args, mesh = _train_step(**TRAIN[name])
        with mesh:
            return step.lower(*args).compile().as_text()
    fn, args = GRADS[name]()
    return jax.jit(fn).lower(*args).compile().as_text()


ENGINE = {"prefill": _prefill, "prefill_with_prefix": _prefill_with_prefix,
          "decode_step_greedy": _decode, "block_step": _block_step,
          "hybrid_prefill": _hybrid_prefill,
          "hybrid_decode_step_greedy": _hybrid_decode,
          "latent_prefill": lambda: _latent(_prefill),
          "latent_prefill_with_prefix": lambda: _latent(_prefill_with_prefix),
          "latent_decode_step_greedy": lambda: _latent(_decode),
          "shortcut_prefill": lambda: _shortcut(_prefill),
          "shortcut_prefill_with_prefix":
              lambda: _shortcut(_prefill_with_prefix),
          "shortcut_decode_step_greedy": lambda: _shortcut(_decode),
          "windowed_prefill": lambda: _windowed(_prefill),
          "windowed_prefill_with_prefix":
              lambda: _windowed(_prefill_with_prefix),
          "windowed_decode_step_greedy": lambda: _windowed(_decode),
          "sparse_linear_prefill": lambda: _sparse_linear(_prefill),
          "sparse_linear_prefill_with_prefix":
              lambda: _sparse_linear(_prefill_with_prefix),
          "sparse_linear_decode_step_greedy":
              lambda: _sparse_linear(_decode),
          "parallel_ssm_prefill": lambda: _parallel_ssm(_prefill),
          "parallel_ssm_prefill_with_prefix":
              lambda: _parallel_ssm(_prefill_with_prefix),
          "parallel_ssm_decode_step_greedy":
              lambda: _parallel_ssm(_decode),
          "one_kind_prefill": lambda: _one_kind(_prefill),
          "one_kind_decode_step_greedy": lambda: _one_kind(_decode)}
# a family's greedy decode step, and the burst's chained program over it
DECODE = {name: build for name, build in ENGINE.items()
          if name.endswith("decode_step_greedy")}
ENGINE.update({name + "_chained": (lambda build=build: _chained(build))
               for name, build in DECODE.items()})
TRAIN = {"train_step": {}, "train_step_fsdp2_tp2": {
    "batch": 4, "fsdp": 2, "tp": 2}}
GRADS = {"llama_grad_remat": lambda: _llama_grad(True),
         "llama_grad": lambda: _llama_grad(False),
         "moe_grad": _moe_grad}
# the parts each program is made of
EXPECTED = {
    "prefill": DENSE + ("attn/kv_write", "attn/attend/repeat_kv", "head"),
    "prefill_with_prefix": DENSE + ("attn/kv_write", "attn/attend/repeat_kv",
                                    "head"),
    "decode_step_greedy": DENSE + ("attn/kv_write", "head", "sample"),
    "block_step": ROUTED + ("attn/kv_write", "head", "sample"),
    "hybrid_prefill": HYBRID + ("attn/kv_write", "attn/attend/repeat_kv",
                                "head"),
    "hybrid_decode_step_greedy": HYBRID + ("attn/kv_write", "head", "sample"),
    "latent_prefill": LATENT_PREFILL,
    "latent_prefill_with_prefix": LATENT_PREFILL,
    "latent_decode_step_greedy": LATENT + ("mla/absorb", "mla/attend",
                                           "mla/unabsorb", "sample"),
    "shortcut_prefill": SHORTCUT + ("mla/kv_up", "attn/attend"),
    "shortcut_prefill_with_prefix": SHORTCUT + ("mla/kv_up", "attn/attend"),
    "shortcut_decode_step_greedy": SHORTCUT + ("mla/absorb", "mla/attend",
                                               "mla/unabsorb", "sample"),
    "windowed_prefill": WINDOWED + ("attn/attend/repeat_kv",),
    "windowed_prefill_with_prefix": WINDOWED + ("attn/attend/repeat_kv",),
    "windowed_decode_step_greedy": WINDOWED + ("sample",),
    "sparse_linear_prefill": SPARSE_LINEAR,
    "sparse_linear_prefill_with_prefix": SPARSE_LINEAR,
    "sparse_linear_decode_step_greedy": SPARSE_LINEAR + ("sample",),
    "parallel_ssm_prefill": PARALLEL_SSM + ("attn/attend/repeat_kv",),
    "parallel_ssm_prefill_with_prefix":
        PARALLEL_SSM + ("attn/attend/repeat_kv",),
    "parallel_ssm_decode_step_greedy": PARALLEL_SSM + ("sample",),
    "one_kind_prefill": ONE_KIND + ("attn/attend/repeat_kv",),
    "one_kind_decode_step_greedy": ONE_KIND + ("sample",),
    # the flash kernels read K and V at their own heads (PR 47): a program
    # that attends through them repeats nothing; the plain XLA path does
    "llama_grad_remat": DENSE + ("head", "loss"),
    "llama_grad": DENSE + ("head", "loss"),
    "moe_grad": ROUTED + ("attn/attend/repeat_kv", "head", "loss"),
    "train_step": DENSE + ("head", "loss", "optim"),
    "train_step_fsdp2_tp2": DENSE + ("head", "loss", "optim", "tp/gather",
                                     "tp/scatter"),
}
# (the chained program is its step's parts: the write into ``acc`` lies
# under ``sample``)
EXPECTED.update({name + "_chained": EXPECTED[name] for name in DECODE})
_TEXTS = {}


def _own_path(n):
    """Where XLA folds a conditional away (an interpreted kernel's
    ``pl.when`` on a grid of one tile, whose tables it reads as constants)
    its inliner prefixes the conditional's path to the whole path of every
    operation it lifts out: keep the operation's own."""
    root = "/" + n.split("/", 1)[0] + "/"
    return n[n.rfind(root) + 1:] if root in n else n


def _op_names(name):
    if name not in _TEXTS:
        # whole paths only: the body of a reduction or a scatter carries
        # the tail of its caller's
        _TEXTS[name] = sorted({_own_path(n) for n in set(re.findall(
            r'op_name="([^"]*)"', _compiled_text(name)))
            if n.startswith("jit(")})
    return _TEXTS[name]


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_products_and_kernels_lie_under_exactly_one_part(name):
    names = _op_names(name)
    products = [n for n in names if n.rstrip(":").endswith("dot_general")]
    assert products, "no product in the compiled text"
    for n in products:
        runs = [r for r in part_runs(n, PARTS) if r != "layers"]
        assert len(runs) == 1, f"{n}: parts {runs}"
    seen = set()
    for n in names:
        for kernel, part in KERNELS.items():
            if kernel in re.split(r"[/()]", n):
                seen.add(kernel)
                assert split_path(n, PARTS)[0] in (
                    part if isinstance(part, tuple) else (part,)), n
    wanted = {"decode_step_greedy": {"paged_decode_attention"},
              "block_step": {"paged_decode_attention", "moe_grouped_mlp"},
              "hybrid_decode_step_greedy": {"paged_decode_attention",
                                            "gated_delta_update"},
              "latent_prefill": {"moe_grouped_mlp"},
              "latent_decode_step_greedy": {"paged_latent_decode_attention",
                                            "moe_grouped_mlp"},
              "shortcut_prefill": {"moe_grouped_mlp"},
              "shortcut_decode_step_greedy": {
                  "paged_latent_decode_attention", "moe_grouped_mlp"},
              "windowed_decode_step_greedy": {"paged_decode_attention",
                                              "moe_grouped_mlp"},
              "sparse_linear_decode_step_greedy": {"paged_decode_attention",
                                                   "lightning_update"},
              "parallel_ssm_decode_step_greedy": {"paged_decode_attention",
                                                  "lightning_update"},
              "one_kind_prefill": {"moe_grouped_mlp"},
              "one_kind_decode_step_greedy": {
                  "paged_decode_attention", "lightning_update",
                  "moe_grouped_mlp"},
              "llama_grad": set(FLASH),
              **dict.fromkeys(TRAIN, set(FLASH))}.get(
                  name.removesuffix("_chained"), set())
    assert wanted <= seen


@pytest.mark.parametrize("name", sorted(EXPECTED))
def test_every_part_of_a_program_occurs_in_it(name):
    found = {split_path(n, PARTS)[0] for n in _op_names(name)}
    assert set(EXPECTED[name]) <= found, set(EXPECTED[name]) - found
    # and nothing the table does not give it (None: outside every part)
    assert found - {None} <= set(EXPECTED[name]), found - set(EXPECTED[name])


def test_every_part_is_some_programs():
    assert set().union(*EXPECTED.values()) == PARTS


@pytest.mark.parametrize("name, recomputes", [
    ("llama_grad_remat", True), ("llama_grad", False), ("train_step", True),
    ("train_step_fsdp2_tp2", True)])
def test_remat_recomputes_under_the_layers_parts(name, recomputes):
    phases = {}
    for n in _op_names(name):
        part, phase = split_path(n, PARTS)
        phases.setdefault(phase, set()).add(part)
    assert {"fwd", "bwd"} <= set(phases)
    again = phases.get("recompute", set())
    if not recomputes:
        # the chunked loss checkpoints its own chunk: that is not the
        # layers' remat
        assert again <= {"loss"}, again
        return
    # what is still made again from a layer's input: the norms, q, k, v and
    # their rotation, K and V repeated, packed and padded for the backward
    # kernels (``attn/attend``: no product and no kernel of it), the
    # attention output and the MLP's first two products
    assert {"attn/norm", "attn/qkv", "attn/rope", "attn/attend", "attn/out",
            "mlp/norm", "mlp/gate_up"} <= again, again
    assert "optim" not in again and "embed" not in again
    # the forward kernel's output and row statistics are KEPT
    # (``llama.REMAT_KEEPS``), so it runs once a layer, in the forward scan
    kernel_runs = {split_path(n, PARTS)[1] for n in _op_names(name)
                   if "flash_attention_fwd" in re.split(r"[/()]", n)}
    assert kernel_runs == {"fwd"}, kernel_runs


@pytest.mark.parametrize("name", sorted(ENGINE) + ["llama_grad_remat"])
def test_outputs_are_bit_for_bit_those_without_scopes(name, monkeypatch):
    def build():  # a new function each time: a trace is cached by it
        if name in ENGINE:
            fn, args, cfg, *rows = ENGINE[name]()
            return (lambda *a: fn.__wrapped__(*a, cfg=cfg,
                                              **dict(*rows))), args
        return GRADS[name]()

    plain, args = build()
    with_scopes = jax.jit(plain).lower(*args).compile()
    assert _scoped(with_scopes)
    got = with_scopes(*args)
    with monkeypatch.context() as m:
        m.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
        plain, args = build()
        bare = jax.jit(plain).lower(*args).compile()
        assert not _scoped(bare)
        want = bare(*args)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b))


def _scoped(compiled) -> bool:
    return any(part_runs(n, PARTS) for n in re.findall(
        r'op_name="(jit\([^"]*)"', compiled.as_text()))


# -- a family says what it is once -------------------------------------------

# what llm/model.py and llm/engine.py ask of a configuration class
ASKED = ("cache_layout", "serving_layout", "served_walk", "refuses",
         "block_length", "window")
# ``lm.cache_layout(cfg)`` as PR 44 returned it, for the benchmark's
# configurations (benchmarks/configs/*.json) at four layers
CACHE_LAYOUTS = {
    "mistral7b_serve_1chip": {"n_layers": 4, "n_kv_heads": 8,
                              "head_dim": 128},
    "sdar30b_a3b_serve_1chip": {"n_layers": 4, "n_kv_heads": 4,
                                "head_dim": 128},
    "olmo_hybrid7b_serve_1chip": {
        "n_layers": 1, "n_kv_heads": 32, "head_dim": 128, "state_layers": 3,
        "scan_chunk": 64,
        "state_rows": {"S": (3, (15, 96, 384), jnp.float32),
                       "conv": (9, (11520,), jnp.dtype("bfloat16"))}},
    "glm47_flash_serve_1chip": {"n_layers": 4, "latent_dim": 640},
    # (its own key is ``num_layers``, 4 in the file: two pool layers each)
    "longcat_flash_serve_1chip": {"n_layers": 8, "latent_dim": 640},
    # (the last four of its five layers: three window layers and the full)
    "trinity_mini_serve_1chip": {"n_layers": 1, "n_kv_heads": 4,
                                 "head_dim": 128, "window_layers": 3,
                                 "window": 2048},
}
# how a program found a family out for itself before it was told
SNIFFED = ('"lin" in', '"experts" in', '"wkv_b"', '"w_uk"',
           '"dense" in params', 'getattr(cfg, "block_length"',
           'getattr(cfg, "cache_layout"', "getattr(model_cfg",
           "getattr(self.model_cfg")


@pytest.mark.parametrize("config", list(CACHE_LAYOUTS))
def test_a_configuration_class_answers_everything_it_is_asked(config):
    """Each of the four served families: every question of the seam is
    answered by the class itself (no ``getattr`` default is reached), by
    class members that are no dataclass fields (``hash`` and ``==`` are
    what they were), and what it says it caches is what it cached."""
    from benchmarks import common

    c = common.load_json("configs", config + ".json")
    cfg = common.module("families", c["family"]).model_config(
        {**c, "num_hidden_layers": 4})
    for name in ASKED:
        assert hasattr(type(cfg), name), name
    fields = {f.name for f in dataclasses.fields(cfg)}
    assert not fields & (set(ASKED) - {"block_length"})
    if cfg.block_length:  # a field where blocks are how it generates
        assert "block_length" in fields and cfg.mask_token_id >= 0
    assert cfg == dataclasses.replace(cfg)
    assert hash(cfg) == hash(dataclasses.replace(cfg))
    assert lm.cache_layout(cfg) == cfg.cache_layout() == CACHE_LAYOUTS[config]
    CacheConfig(**cfg.cache_layout(), max_slots=2,  # the engine's own call
                window_pages=8 if cfg.window else 0)
    for feature, why in cfg.refuses.items():  # sentences ``lm.refuse`` fills
        assert "{where}" in why and "{" not in why.format(cfg=cfg, where="x")


def test_only_serving_layout_knows_a_family_by_its_tree():
    """The engine and the programs hold none of the ways they once found a
    family out, but for ``lm.serving_layout``'s fallback for a caller with
    a tree and no configuration."""
    import inspect

    from ray_tpu.llm import engine

    fallback = inspect.getsource(lm.serving_layout)
    assert '"lin" in' in fallback
    for module in (lm, engine):
        source = inspect.getsource(module).replace(fallback, "")
        assert not [s for s in SNIFFED if s in source]
