"""The paged decode kernel against the dense formulation it replaced.

The reference below is what ``llm/model.py``'s decode step used to do per
layer (gather every slot's whole page table, repeat K and V to the query
heads' width, mask by position, float32 softmax), kept here in float32 as
the thing the kernel must agree with.  On the CPU the kernel runs through
the Pallas interpreter: the same code the chip compiles
(tests/test_tpu_compile.py compiles it for a described v5e).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.models import llama
from ray_tpu.ops.paged_attention import paged_decode_attention

PAGE_SIZE, PAGES_PER_SEQ, N_KV, SLOTS, LAYERS = 16, 20, 2, 4, 2
FULL = PAGE_SIZE * PAGES_PER_SEQ


def dense_reference(q, k_pool, v_pool, page_tables, lengths, layer,
                    window=None):
    B, H, d = q.shape
    n_kv = k_pool.shape[3]
    T = page_tables.shape[1] * k_pool.shape[2]
    keys = k_pool[layer][page_tables].reshape(B, T, n_kv, d)
    vals = v_pool[layer][page_tables].reshape(B, T, n_kv, d)
    keys = jnp.repeat(keys, H // n_kv, axis=2).astype(jnp.float32)
    vals = jnp.repeat(vals, H // n_kv, axis=2).astype(jnp.float32)
    scores = jnp.einsum("bhd,bthd->bht", q.astype(jnp.float32), keys,
                        precision="highest") / (d ** 0.5)
    mask = jnp.arange(T)[None] < lengths[:, None]
    if window:  # the last ``window`` positions, the query's own among them
        mask &= jnp.arange(T)[None] >= (lengths - window)[:, None]
    scores = jnp.where(mask[:, None, :], scores, -1e30)
    out = jnp.einsum("bht,bthd->bhd", jax.nn.softmax(scores, axis=-1), vals,
                     precision="highest")
    return jnp.where((lengths > 0)[:, None, None], out, 0.0)


def _pool_and_tables(lengths, head_dim, dtype, seed=0):
    """A pool of loud finite garbage everywhere (the null page 0, the other
    layer, the rows past each length inside its last page, pages no table
    names) with unit-scale K and V only at the positions that count."""
    rng = np.random.default_rng(seed)
    num_pages = 1 + SLOTS * PAGES_PER_SEQ
    shape = (LAYERS, num_pages, PAGE_SIZE, N_KV, head_dim)
    k = rng.normal(size=shape) * 50.0
    v = rng.normal(size=shape) * 1e4
    tables = np.zeros((SLOTS, PAGES_PER_SEQ), np.int32)
    pages = rng.permutation(np.arange(1, num_pages)).reshape(
        SLOTS, PAGES_PER_SEQ)
    for b, n in enumerate(lengths):
        used = -(-n // PAGE_SIZE)
        tables[b, :used] = pages[b, :used]
        for t in range(n):
            page, row = pages[b, t // PAGE_SIZE], t % PAGE_SIZE
            k[1, page, row] = rng.normal(size=(N_KV, head_dim))
            v[1, page, row] = rng.normal(size=(N_KV, head_dim))
    return (jnp.asarray(k, dtype), jnp.asarray(v, dtype),
            jnp.asarray(tables))


@pytest.mark.parametrize("lengths", [
    (1, 1, 1, 1), (15, 15, 15, 15), (16, 16, 16, 16), (17, 17, 17, 17),
    (FULL, FULL, FULL, FULL), (1, 130, 47, FULL), (0, 33, 0, 257),
    (0, 0, 0, 0),
], ids=["len1", "len15", "len16", "len17", "full_table", "mixed",
        "inactive_slots", "all_inactive"])
@pytest.mark.parametrize("head_dim", [64, 128])
@pytest.mark.parametrize("group", [1, 4, 8])
def test_kernel_matches_the_dense_formulation(group, head_dim, lengths):
    k_pool, v_pool, tables = _pool_and_tables(lengths, head_dim, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(7),
                          (SLOTS, N_KV * group, head_dim), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    got = paged_decode_attention(q, k_pool, v_pool, tables, lens, 1)
    want = dense_reference(q, k_pool, v_pool, tables, lens, 1)
    assert got.shape == q.shape and got.dtype == q.dtype
    # a leak of one masked row would show as an error of order 1e4
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    for b, n in enumerate(lengths):
        if n == 0:
            assert not np.asarray(got[b]).any()


def _null_behind(tables, lengths, window):
    """The page table as the engine leaves a window layer's: the null page
    where a page lies wholly behind ``length - window``."""
    tables = np.array(tables)
    for b, n in enumerate(lengths):
        tables[b, :max(0, n - window) // PAGE_SIZE] = 0
    return jnp.asarray(tables)


@pytest.mark.parametrize("lengths", [
    (40, 40, 40, 40), (64, 64, 64, 64), (65, 65, 65, 65),
    (FULL, FULL, FULL, FULL), (1, 130, 47, FULL), (0, 90, 0, 257),
], ids=["under", "at", "over_by_one", "full_table", "ragged",
        "inactive_slots"])
@pytest.mark.parametrize("window", [64, 70, 1], ids=["whole_pages",
                                                     "inside_a_page", "one"])
@pytest.mark.parametrize("pages_per_block", [None, 3])
def test_kernel_with_a_window_matches_the_dense_formulation(
        pages_per_block, window, lengths):
    """Lengths under, at and over the window; a bound that falls inside a
    page (70 = 4 pages and 6 rows) and inside a block; ragged lengths and
    inactive slots; the pages behind the bound are the NULL page, whose
    loud garbage would show if one were copied and scored."""
    k_pool, v_pool, tables = _pool_and_tables(lengths, 64, jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(7), (SLOTS, N_KV * 4, 64),
                          jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    want = dense_reference(q, k_pool, v_pool, tables, lens, 1, window)
    got = paged_decode_attention(
        q, k_pool, v_pool, _null_behind(tables, lengths, window), lens, 1,
        window=window, pages_per_block=pages_per_block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    for b, n in enumerate(lengths):
        if n == 0:
            assert not np.asarray(got[b]).any()


def test_no_window_is_bit_for_bit_the_kernel_it_was_and_a_window_may_be_traced():
    """``window=None`` and ``window=0`` are the program without a bound
    (one prefetched scalar fewer: the same lowering as before the bound
    existed), and so bit-equal to each other and to a window that skips
    nothing; a traced scalar bounds as a whole number does."""
    lengths = (5, 300, 0, 64)
    k_pool, v_pool, tables = _pool_and_tables(lengths, 128, jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(3), (SLOTS, 8, 128),
                          jnp.bfloat16)
    lens = jnp.asarray(lengths, jnp.int32)
    plain = paged_decode_attention(q, k_pool, v_pool, tables, lens, 1)
    for window in (0, None, FULL):
        got = paged_decode_attention(q, k_pool, v_pool, tables, lens, 1,
                                     window=window)
        assert np.array_equal(np.asarray(got), np.asarray(plain))

    def text(**kw):
        return jax.jit(lambda: paged_decode_attention(
            q, k_pool, v_pool, tables, lens, 1, **kw)).lower().as_text()

    assert text() == text(window=None) == text(window=0) != text(window=64)
    traced = jax.jit(lambda w: paged_decode_attention(
        q, k_pool, v_pool, tables, lens, 1, window=w))(jnp.int32(100))
    whole = paged_decode_attention(q, k_pool, v_pool, tables, lens, 1,
                                   window=100)
    assert np.array_equal(np.asarray(traced), np.asarray(whole))
    with pytest.raises(ValueError, match="1 or more positions"):
        paged_decode_attention(q, k_pool, v_pool, tables, lens, 1, window=-4)


def test_bf16_pool_float32_softmax_and_a_traced_layer():
    """The serving dtypes: bf16 pool and query, float32 scores and state;
    the layer arrives traced, as the decode step's scan hands it over; the
    block size does not change the answer."""
    lengths = (5, 300, 0, 64)
    k_pool, v_pool, tables = _pool_and_tables(lengths, 128, jnp.bfloat16)
    q = jax.random.normal(jax.random.PRNGKey(3), (SLOTS, 8, 128),
                          jnp.bfloat16)
    lens = jnp.asarray(lengths, jnp.int32)
    want = np.asarray(dense_reference(q, k_pool, v_pool, tables, lens, 1))
    for pages_per_block in (8, 3):
        got = jax.jit(
            lambda layer: paged_decode_attention(
                q, k_pool, v_pool, tables, lens, layer,
                pages_per_block=pages_per_block))(jnp.int32(1))
        assert got.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(got, np.float32), want,
                                   atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("q_shape,pool_shape,match", [
    ((4, 6, 64), (2, 9, 16, 4, 64), "multiple of the pool's KV heads"),
    ((4, 8, 64), (2, 9, 16, 4, 128), "same head_dim"),
    ((4, 8, 64), (9, 16, 4, 64), r"\[layers, pages, page_size"),
])
def test_shapes_the_kernel_cannot_take_are_refused_by_name(q_shape,
                                                           pool_shape, match):
    pool = jnp.zeros(pool_shape, jnp.float32)
    with pytest.raises(ValueError, match=match):
        paged_decode_attention(
            jnp.zeros(q_shape, jnp.float32), pool, pool,
            jnp.zeros((4, 3), jnp.int32), jnp.zeros((4,), jnp.int32), 0)


# -- through the engine ------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_model():
    cfg = llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=64, dtype="float32", remat=False)
    return llama.init(cfg, jax.random.PRNGKey(0)), cfg


def full_forward_greedy(params, cfg, prompt, n_new, pad_to=64):
    """Greedy generation by the training-side forward, no cache: the whole
    sequence again for every token (padded to one length, which a causal
    model does not see, so one compilation serves them all)."""
    apply = jax.jit(lambda toks: llama.apply(params, toks, cfg))
    toks = list(prompt)
    for _ in range(n_new):
        padded = jnp.asarray([toks + [0] * (pad_to - len(toks))])
        toks.append(int(jnp.argmax(apply(padded)[0, len(toks) - 1])))
    return toks[len(prompt):]


def _drain(req):
    out = []
    while True:
        item = req.out_queue.get(timeout=300)
        if item is None:
            return out
        if isinstance(item, Exception):
            raise item
        out.append(item)


def test_engine_greedy_equals_full_forward_across_pages_and_preemption(
        tiny_model, monkeypatch):
    """Decode through the kernel: one request alone across two page
    boundaries, then three at once against a pool too small for them, so
    that one is preempted and resumed; every stream equals the uncached
    full forward, and the pages the kernel walked are counted."""
    monkeypatch.setenv("RTPU_DEBUG_ALLOCATOR", "1")
    params, cfg = tiny_model
    engine = LLMEngine(params, cfg, EngineConfig(
        max_slots=4, num_pages=12, page_size=8, max_seq_len=64,
        prefill_buckets=(8, 16, 32, 64)))
    try:
        solo = [1, 17, 93, 5, 42, 7]
        got = engine.generate(solo, SamplingParams(max_tokens=12))
        assert got == full_forward_greedy(params, cfg, solo, 12)
        st = engine.stats()
        # the first token comes from prefill; decode then feeds positions
        # 6, 7, ... one a dispatched step (a burst may overshoot the end)
        assert st["decode_steps"] >= 11
        assert st["decode_pages_read"] == sum(
            pos // 8 + 1 for pos in range(6, 6 + st["decode_steps"]))

        prompts = [[9, 3, 77, 12, 51, 6], [2, 40, 8, 19, 100, 64, 31],
                   [11 + i for i in range(13)]]
        reqs = [engine.submit(p, SamplingParams(max_tokens=26))
                for p in prompts]
        streams = [_drain(r) for r in reqs]
        # 3 x (4 or 5 pages) against 11 allocatable: one had to go
        assert engine.stats()["preempted"] > 0
        for p, stream in zip(prompts, streams):
            assert stream == full_forward_greedy(params, cfg, p, 26)
    finally:
        engine.stop()
