"""LLM engine tests: paged decode must match full-forward generation.

The reference trusts vLLM's kernels; here the paged path is ours, so the
core invariant is exactness vs the training-side forward
(/root/reference has no analogue — net-new per SURVEY.md §7 step 8)."""

import threading
import time

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.models import llama


@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=256, dtype="float32", remat=False)
    params = llama.init(cfg, jax.random.PRNGKey(0))
    return params, cfg


def reference_greedy(params, cfg, prompt, n_new):
    """Greedy generation via the full training forward (no cache)."""
    toks = list(prompt)
    for _ in range(n_new):
        logits = llama.apply(params, jnp.asarray([toks]), cfg)
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def make_engine(tiny_model, **kw):
    params, cfg = tiny_model
    ecfg = EngineConfig(max_slots=4, num_pages=64, page_size=8,
                        max_seq_len=256,
                        prefill_buckets=(16, 32, 64, 128), **kw)
    return LLMEngine(params, cfg, ecfg)


def test_greedy_matches_full_forward(tiny_model):
    params, cfg = tiny_model
    engine = make_engine(tiny_model)
    prompt = [1, 17, 93, 5, 42, 7]
    want = reference_greedy(params, cfg, prompt, 12)
    got = engine.generate(prompt, SamplingParams(max_tokens=12))
    engine.stop()
    assert got == want


def test_concurrent_requests_match_solo_runs(tiny_model):
    params, cfg = tiny_model
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(1, 128, size=n))
               for n in (3, 9, 14, 30, 6, 21)]
    want = [reference_greedy(params, cfg, p, 8) for p in prompts]

    engine = make_engine(tiny_model)
    engine.start()
    reqs = [engine.submit(p, SamplingParams(max_tokens=8)) for p in prompts]
    got = []
    for r in reqs:
        toks = []
        while True:
            item = r.out_queue.get(timeout=120)
            if item is None:
                break
            if isinstance(item, Exception):
                raise item
            toks.append(item)
        got.append(toks)
    engine.stop()
    assert got == want
    # continuous batching actually batched: fewer decode rounds than the
    # sum of solo decodes would need
    assert engine.stats()["decode_steps"] < sum(8 for _ in prompts)


def test_stop_tokens_and_max_tokens(tiny_model):
    engine = make_engine(tiny_model)
    prompt = [3, 14, 15]
    full = engine.generate(prompt, SamplingParams(max_tokens=10))
    assert len(full) == 10
    # stop on a generated token whose FIRST occurrence is at its index
    # (stop fires at first occurrence, so earlier repeats would shift it)
    idx = next(i for i in range(1, 10) if full[i] not in full[:i])
    stop = full[idx]
    stopped = engine.generate(
        prompt, SamplingParams(max_tokens=10, stop_token_ids=(stop,)))
    engine.stop()
    assert stopped == full[:idx]


def test_page_exhaustion_queues_requests(tiny_model):
    # 15 usable pages (page 0 reserved), each request needs 5 pages
    engine = make_engine(tiny_model)
    engine.cfg.num_pages = 16
    from ray_tpu.llm.paged_cache import PageAllocator

    engine.allocator = PageAllocator(16)
    engine.start()
    prompts = [[i + 1] * 8 for i in range(6)]
    reqs = [engine.submit(p, SamplingParams(max_tokens=30))
            for p in prompts]
    outs = []
    for r in reqs:
        toks = []
        while True:
            item = r.out_queue.get(timeout=120)
            if item is None:
                break
            if isinstance(item, Exception):
                raise item
            toks.append(item)
        outs.append(toks)
    engine.stop()
    assert all(len(o) == 30 for o in outs)


def test_temperature_sampling_seeded(tiny_model):
    engine = make_engine(tiny_model)
    p = SamplingParams(max_tokens=8, temperature=0.8, seed=42)
    a = engine.generate([5, 6, 7], p)
    b = engine.generate([5, 6, 7], SamplingParams(
        max_tokens=8, temperature=0.8, seed=42))
    c = engine.generate([5, 6, 7], SamplingParams(
        max_tokens=8, temperature=0.8, seed=43))
    engine.stop()
    assert a == b
    assert len(a) == 8
    assert a != c or True  # different seed usually differs; no hard assert


# -- the seam: each program's logits against the training forward -----------

@pytest.mark.parametrize("program",
                         ["prefill", "prefill_with_prefix", "decode_step"])
def test_program_logits_match_full_forward(program):
    """The engine's programs called directly, on scattered pages: the
    logits they return for the prompt's last token are ``llama.apply``'s
    at that position, whichever way the program attends."""
    import dataclasses

    from ray_tpu.llm import model as lm
    from ray_tpu.llm.paged_cache import CacheConfig, init_cache

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=128),
                              dtype="float32")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    ps, table_width, n = 8, 4, 21
    pages = np.asarray([3, 5, 2], np.int32)  # 24 positions; page 0 is null
    prompt = np.random.default_rng(0).integers(1, 128, size=n).astype(
        np.int32)
    want = np.asarray(llama.apply(params, jnp.asarray(prompt[None]), cfg))[0]
    cache = init_cache(CacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, num_pages=8, page_size=ps, dtype=cfg.dtype))
    table = np.zeros(table_width, np.int32)
    table[:len(pages)] = pages

    def prefill_args(start, stop, bucket):
        """tokens[start:stop] padded to the bucket, and where they land."""
        tokens = np.zeros(bucket, np.int32)
        tokens[:stop - start] = prompt[start:stop]
        positions = start + np.arange(bucket, dtype=np.int32)
        page_rows = table[positions // ps]  # past the pages: the null page
        return (jnp.asarray(tokens), jnp.asarray(page_rows),
                jnp.int32(stop - start), jnp.asarray(positions % ps),
                jnp.asarray(positions))

    def prefill(stop, bucket):
        tokens, rows, true_len, slots, _ = prefill_args(0, stop, bucket)
        return lm.prefill(params, tokens, *cache, rows, true_len, slots, cfg)

    if program == "prefill":
        got, *_ = prefill(n, 32)
    elif program == "prefill_with_prefix":
        _, _, *cache, _ = prefill(2 * ps, 16)  # a 2-page resident prefix
        tokens, rows, true_len, slots, positions = prefill_args(2 * ps, n, 16)
        got, *_ = lm.prefill_with_prefix(
            params, tokens, *cache, rows, true_len, slots,
            jnp.asarray(table), positions, cfg)
    else:
        _, _, *cache, _ = prefill(n - 1, 32)
        # slot 1 decodes the prompt's last token; slot 0 is empty
        got, *_ = lm.decode_step(
            params, jnp.asarray([0, prompt[-1]], jnp.int32), *cache,
            jnp.asarray(np.stack([np.zeros_like(table), table])),
            jnp.asarray([0, n - 1], jnp.int32), jnp.asarray([False, True]),
            cfg)
        got = got[1]
    assert got.dtype == jnp.float32
    np.testing.assert_allclose(np.asarray(got), want[n - 1], atol=2e-5,
                               rtol=2e-5)


@pytest.mark.parametrize("program", ["prefill", "prefill_with_prefix"])
def test_prefill_writes_its_rows_and_nothing_else(program):
    """Both prefills scatter into the whole pool in place: over a pool of
    noise, every row of every layer that ``page_rows``/``slot_positions``
    does not name comes back bit-identical, and the named rows hold the k
    and v of a plain layer-by-layer forward (padding rows included; the
    null page may hold scratch)."""
    import dataclasses

    from ray_tpu.llm import model as lm

    cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=128),
                              dtype="float32")
    params = llama.init(cfg, jax.random.PRNGKey(0))
    ps, n = 8, 21
    table = np.asarray([3, 5, 2, 0], np.int32)  # 24 positions, then null
    prompt = np.random.default_rng(1).integers(1, 128, size=n).astype(
        np.int32)
    shape = (cfg.n_layers, 8, ps, cfg.n_kv_heads, cfg.head_dim)
    cache = [jax.random.normal(jax.random.PRNGKey(i), shape, jnp.float32)
             for i in (1, 2)]

    def plain_kv(tokens, mask):
        """Per-layer k and v [n_layers, L, Hkv, d] of a dense forward."""
        rep = cfg.n_heads // cfg.n_kv_heads
        ks, vs = [], []

        def attend(q, k, v, _):
            ks.append(k)
            vs.append(v)
            scores = jnp.einsum("qhd,khd->hqk", q, jnp.repeat(k, rep, 1))
            scores = jnp.where(mask[None], scores / cfg.head_dim ** 0.5,
                               -jnp.inf)
            return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1),
                              jnp.repeat(v, rep, 1)), None

        x = params["embed"][tokens]
        for li in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[li], params["layers"])
            x, _ = llama.layer(cfg, p, x, jnp.arange(len(tokens)), attend)
        return np.stack(ks), np.stack(vs)

    tokens = np.zeros(32, np.int32)
    tokens[:n] = prompt
    positions = np.arange(32)
    causal = positions[None, :] <= positions[:, None]
    if program == "prefill":
        start = 0
        want = plain_kv(tokens, causal & (positions[None, :] < n))
    else:  # pages 3 and 5 resident, the suffix lands on page 2
        start = 2 * ps
        _, _, *cache, _ = lm.prefill(
            params, jnp.asarray(prompt[:start]), *cache,
            jnp.asarray(table[positions[:start] // ps]), jnp.int32(start),
            jnp.asarray(positions[:start] % ps), cfg)
        want = plain_kv(tokens, causal)
    before = [np.asarray(c) for c in cache]
    written = positions[start:]
    rows, slots = table[written // ps], written % ps
    # the suffix program also takes the page table and absolute positions
    through = (jnp.asarray(table), jnp.asarray(written)) if start else ()
    _, _, *after, _ = getattr(lm, program)(
        params, jnp.asarray(tokens[start:]), *cache, jnp.asarray(rows),
        jnp.int32(n - start), jnp.asarray(slots), *through, cfg)
    named = np.zeros(shape[1:3], bool)
    named[rows, slots] = True
    real = rows != 0
    for was, got, ref in zip(before, after, want):
        got = np.asarray(got)
        np.testing.assert_array_equal(got[:, ~named], was[:, ~named])
        np.testing.assert_allclose(got[:, rows[real], slots[real]],
                                   ref[:, written[real]], atol=1e-5,
                                   rtol=1e-5)


# -- the serving layout: one stacked wqkv a layer ----------------------------

def _family(name):
    """(float32 configuration, three-weight parameters) of a family."""
    import dataclasses

    from ray_tpu.models import sdar_moe

    if name == "llama":
        cfg = dataclasses.replace(llama.LlamaConfig.tiny(vocab_size=128),
                                  dtype="float32")
        return cfg, llama.init(cfg, jax.random.PRNGKey(0))
    cfg = sdar_moe.SDARMoEConfig.tiny(512, remasking_strategy="sequential")
    return cfg, sdar_moe.init(cfg, jax.random.PRNGKey(0))


@pytest.mark.parametrize("family", ["llama", "sdar_moe"])
def test_serving_layout_stacks_qkv_and_touches_nothing_else(family):
    cfg, params = _family(family)
    served = llama.serving_layout(params)
    attn = served["layers"]["attn"]
    assert not {"wq", "wk", "wv"} & set(attn)
    nq, nkv = cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    assert attn["wqkv"].shape == (cfg.n_layers, cfg.d_model, nq + 2 * nkv)
    was = params["layers"]["attn"]
    for name, cols in (("wq", slice(0, nq)), ("wk", slice(nq, nq + nkv)),
                       ("wv", slice(nq + nkv, None))):
        np.testing.assert_array_equal(np.asarray(attn["wqkv"][..., cols]),
                                      np.asarray(was[name]))
    # every other leaf is the array it was, not a copy of it
    def others(tree):
        return {**tree, "layers": {**tree["layers"], "attn": {
            k: v for k, v in tree["layers"]["attn"].items()
            if k not in ("wq", "wk", "wv", "wqkv")}}}

    rest, want = others(served), others(params)
    assert jax.tree.structure(rest) == jax.tree.structure(want)
    assert all(a is b for a, b in zip(jax.tree.leaves(rest),
                                      jax.tree.leaves(want)))
    assert set(was) >= {"wq", "wk", "wv"}  # the caller's tree is not edited
    assert llama.serving_layout(served) is served


@pytest.mark.parametrize("family,program", [
    ("llama", "prefill"), ("llama", "prefill_with_prefix"),
    ("llama", "decode_step"), ("llama", "decode_step_greedy"),
    ("llama", "decode_step_greedy_chained"), ("sdar_moe", "prefill"),
    ("sdar_moe", "prefill_with_prefix"), ("sdar_moe", "block_step")])
def test_programs_on_the_serving_layout_equal_the_three_weight_tree(
        family, program):
    """Every served program over scattered pages, once with ``wq``, ``wk``,
    ``wv`` and once with the stacked ``wqkv``: the same logits (tokens,
    records) and the same K/V rows in the pools, in float32 to 1e-6."""
    from ray_tpu.llm import model as lm
    from ray_tpu.llm.paged_cache import CacheConfig, init_cache

    cfg, params = _family(family)
    ps, n = 8, 20  # five whole blocks of 4
    table = np.asarray([3, 5, 2, 0], np.int32)
    prompt = np.random.default_rng(2).integers(1, 100, size=n).astype(
        np.int32)

    def run(params):
        cache = init_cache(CacheConfig(
            n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
            head_dim=cfg.head_dim, num_pages=8, page_size=ps,
            dtype=cfg.dtype))

        def prefill(start, stop, bucket, through_table):
            tokens = np.zeros(bucket, np.int32)
            tokens[:stop - start] = prompt[start:stop]
            pos = start + np.arange(bucket, dtype=np.int32)
            rest = ((jnp.asarray(table), jnp.asarray(pos))
                    if through_table else ())
            return getattr(
                lm, "prefill_with_prefix" if through_table else "prefill")(
                params, jnp.asarray(tokens), *cache,
                jnp.asarray(table[pos // ps]), jnp.int32(stop - start),
                jnp.asarray(pos % ps), *rest, cfg)

        if program == "prefill":
            return prefill(0, n, 32, False)
        _, _, *cache, _ = prefill(0, 2 * ps, 16, False)
        if program == "prefill_with_prefix":
            return prefill(2 * ps, n, 16, True)
        tables = jnp.asarray(np.stack([np.zeros_like(table), table]))
        active = jnp.asarray([False, True])
        if program == "block_step":  # the block at 16..19, two masks left
            B = cfg.block_length
            tokens = np.full((2, B), cfg.mask_token_id, np.int32)
            tokens[1, :2] = prompt[16:18]
            masked = np.ones((2, B), bool)
            masked[1, :2] = False
            return lm.block_step(
                params, *cache, tables, active, jnp.asarray(tokens),
                jnp.asarray(masked), jnp.asarray([0, 16], jnp.int32),
                jnp.zeros(2, jnp.int32), cfg)
        carry = ((jnp.int32(3), jnp.zeros(lm.acc_shape(2, ()), jnp.int32))
                 if program == "decode_step_greedy_chained" else ())
        return getattr(lm, program)(
            params, jnp.asarray([0, prompt[16]], jnp.int32), *cache, tables,
            jnp.asarray([0, 16], jnp.int32), active, *carry, cfg)

    want, got = run(params), run(llama.serving_layout(params))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert g.dtype == w.dtype
        if jnp.issubdtype(g.dtype, jnp.floating):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=1e-6, rtol=1e-6)
        else:  # greedy tokens, a pass's record and state, experts read
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("handed", ["three_weights", "serving_layout"])
def test_engine_holds_the_serving_layout(tiny_model, handed):
    """Whichever tree it is handed, the engine keeps the stacked one and no
    ``wq``, ``wk``, ``wv``, and generates the full forward's tokens."""
    params, cfg = tiny_model
    tree = params if handed == "three_weights" else llama.serving_layout(
        params)
    engine = make_engine((tree, cfg))
    attn = engine.params["layers"]["attn"]
    assert "wqkv" in attn and not {"wq", "wk", "wv"} & set(attn)
    if handed == "serving_layout":
        assert engine.params is tree
    prompt = [9, 2, 77, 31, 5, 64, 12, 3, 101]
    got = engine.generate(prompt, SamplingParams(max_tokens=10))
    engine.stop()
    assert got == reference_greedy(params, cfg, prompt, 10)


def test_engine_lets_go_of_the_three_weights(tiny_model):
    """Once the caller drops its tree, ``wq``, ``wk`` and ``wv`` are freed:
    the engine's resident weights are the tree's own size, not that plus a
    second copy of the projections."""
    import gc
    import weakref

    _, cfg = tiny_model
    params = llama.init(cfg, jax.random.PRNGKey(1))
    attn = params["layers"]["attn"]
    gone = [weakref.ref(attn[k]) for k in ("wq", "wk", "wv")]
    kept = weakref.ref(attn["wo"])
    engine = make_engine((params, cfg))
    del params, attn
    gc.collect()
    assert all(r() is None for r in gone) and kept() is not None
    assert kept() is engine.params["layers"]["attn"]["wo"]
