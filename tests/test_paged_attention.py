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


# -- tables with runs: small pages move several to a copy ---------------------

def _tables_of(order, lengths, pages_per_seq=PAGES_PER_SEQ, seed=0):
    """A table [SLOTS, pages_per_seq] whose entries up to each length are
    ``rows`` (a slot's ids in a row, from an id that no group of four is
    aligned to), ``shuffled`` (no two in a row) or ``mixed``: rows with a
    run broken INSIDE a group (entries 6 and 7 swapped), a group that is a
    run beside one that is not, a run that crosses the block's boundary
    (entries 12-19: the block holds 16 pages) and one that ends at the
    slot's last page; entries past a length are the null page."""
    rng = np.random.default_rng(seed)
    n = SLOTS * pages_per_seq
    if order == "shuffled":
        pages = rng.permutation(np.arange(1, n + 1))
        # (a permutation may put two ids in a row by chance: not four)
        pages = pages.reshape(SLOTS, pages_per_seq)
    else:
        pages = 1 + np.arange(n).reshape(SLOTS, pages_per_seq)
        pages = pages[::-1].copy()  # slot 0's ids are the highest
        if order == "mixed":
            pages[:, [6, 7]] = pages[:, [7, 6]]
            pages[1:, 8:12] = pages[1:, 8:12][:, ::-1]
    tables = np.zeros((SLOTS, pages_per_seq), np.int32)
    for b, length in enumerate(lengths):
        used = -(-length // PAGE_SIZE)
        tables[b, :used] = pages[b, :used]
    return tables, n + 1


def _kv_pools(tables, num_pages, lengths, n_kv, head_dim, dtype, seed=0):
    """Loud finite garbage everywhere, unit-scale K and V at the positions
    the tables and lengths name (layer 1)."""
    rng = np.random.default_rng(seed)
    shape = (LAYERS, num_pages, PAGE_SIZE, n_kv, head_dim)
    k = rng.normal(size=shape) * 50.0
    v = rng.normal(size=shape) * 1e4
    for b, length in enumerate(lengths):
        for pg in range(-(-length // PAGE_SIZE)):
            rows = min(PAGE_SIZE, length - pg * PAGE_SIZE)
            k[1, tables[b, pg], :rows] = rng.normal(
                size=(rows, n_kv, head_dim))
            v[1, tables[b, pg], :rows] = rng.normal(
                size=(rows, n_kv, head_dim))
    return jnp.asarray(k, dtype), jnp.asarray(v, dtype)


RUN_LENGTHS = [(FULL, FULL, FULL, FULL), (FULL, 280, 47, 0),
               (257, 64, 0, 65)]
RUN_IDS = ["full_table", "a_last_group_in_part", "zero_and_short"]


@pytest.mark.parametrize("lengths", RUN_LENGTHS, ids=RUN_IDS)
@pytest.mark.parametrize("window", [None, 70],
                         ids=["no_window", "a_start_inside_a_block"])
@pytest.mark.parametrize("pages_per_block", [None, 8])
@pytest.mark.parametrize("order", ["rows", "mixed", "shuffled"])
@pytest.mark.parametrize("n_kv", [2, 4])
def test_tables_with_runs_match_the_dense_formulation(
        n_kv, order, pages_per_block, window, lengths):
    """Small pages (2 and 4 KV heads): a block that is reached whole is
    copied in a straight line and waited for once, four pages a copy where
    every aligned group of it is ids in a row; every answer is the dense
    formulation's whatever the table looks like (blocks of 16 pages: one
    whole block and an edge; of 8: a block with a broken group beside one
    in runs, and a run that crosses a block's boundary), the null page's
    and the neighbours' loud garbage would show if a copy took one page too
    many; the layer arrives as an array, traced by the kernel's own jit."""
    from ray_tpu.ops import paged_attention as pa

    tables, num_pages = _tables_of(order, lengths)
    d = 256 // n_kv  # a float32 page of 16 KB
    k_pool, v_pool = _kv_pools(tables, num_pages, lengths, n_kv, d,
                               jnp.float32)
    ppb, run = pa.walk_blocks(k_pool.shape, 4, PAGES_PER_SEQ, pages_per_block)
    assert (ppb, run) == (pages_per_block or 16, 4)
    lens = jnp.asarray(lengths, jnp.int32)
    starts = None if window is None else np.maximum(
        np.asarray(lengths) - window, 0)
    kinds = np.asarray(pa.block_kinds(tables, np.asarray(lengths), starts,
                                      PAGE_SIZE, ppb, run))
    assert kinds.shape == (SLOTS, PAGES_PER_SEQ // ppb)
    if order == "shuffled":
        assert (kinds < 2).all()
    if lengths[0] == FULL and window is None:
        # slot 0 whole: every block reached; ``mixed`` breaks its first
        want = {"rows": 2, "mixed": 1, "shuffled": 1}[order]
        assert kinds[0, 0] == want and (kinds[0, 1:] == min(
            2, want + (order != "shuffled"))).all()
    if lengths[-1] == 0:
        assert not kinds[-1].any()
    q = jax.random.normal(jax.random.PRNGKey(7), (SLOTS, n_kv * 4, d),
                          jnp.float32)
    want = dense_reference(q, k_pool, v_pool, jnp.asarray(tables), lens, 1,
                           window)
    served = jnp.asarray(tables) if window is None else _null_behind(
        tables, lengths, window)
    got = paged_decode_attention(q, k_pool, v_pool, served, lens,
                                 jnp.int32(1), window=window,
                                 pages_per_block=pages_per_block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)
    for b, length in enumerate(lengths):
        if length == 0:
            assert not np.asarray(got[b]).any()


@pytest.mark.parametrize("lengths", RUN_LENGTHS, ids=RUN_IDS)
@pytest.mark.parametrize("order", ["rows", "mixed", "shuffled"])
def test_lists_with_runs_match_the_dense_formulation(order, lengths):
    """``heads_apart``: a list a KV head of a slot, each its own lengths;
    a list's groups move merged as a table's do, and a KV head's query
    heads see their own head's rows of their own list's pages alone."""
    n_kv = 2
    # head g of slot b is "slot" b * n_kv + g of a table of 2 x SLOTS rows
    both = tuple(x for length in lengths
                 for x in (length, max(0, length - 33)))
    rng = np.random.default_rng(3)
    n = len(both) * PAGES_PER_SEQ
    pages = 1 + np.arange(n).reshape(len(both), PAGES_PER_SEQ)
    if order == "shuffled":
        pages = rng.permutation(pages.reshape(-1)).reshape(pages.shape)
    elif order == "mixed":
        pages[:, [6, 7]] = pages[:, [7, 6]]
    lists = np.zeros_like(pages, dtype=np.int32)
    for i, length in enumerate(both):
        used = -(-length // PAGE_SIZE)
        lists[i, :used] = pages[i, :used]
    shape = (LAYERS, n + 1, PAGE_SIZE, n_kv, 128)
    k_pool = jnp.asarray(rng.normal(size=shape), jnp.float32)
    v_pool = jnp.asarray(rng.normal(size=shape), jnp.float32)
    q = jax.random.normal(jax.random.PRNGKey(5), (SLOTS, 8, 128),
                          jnp.float32)
    lens = jnp.asarray(both, jnp.int32).reshape(SLOTS, n_kv)
    got = paged_decode_attention(
        q, k_pool, v_pool, jnp.asarray(lists).reshape(SLOTS, n_kv, -1), lens,
        1, heads_apart=True)
    # a list is a slot of its own over a pool of its KV head's rows alone
    for g in range(n_kv):
        mine = slice(g * 4, (g + 1) * 4)
        want = dense_reference(
            q[:, mine], k_pool[:, :, :, g:g + 1], v_pool[:, :, :, g:g + 1],
            jnp.asarray(lists[g::n_kv]), lens[:, g], 1)
        np.testing.assert_allclose(np.asarray(got[:, mine]),
                                   np.asarray(want), atol=2e-5, rtol=2e-5)


def _latent_reference(q, pool, tables, lengths, layer, value_dim, sm_scale):
    B = q.shape[0]
    T = tables.shape[1] * pool.shape[2]
    rows = pool[layer][tables].reshape(B, T, -1).astype(jnp.float32)
    scores = sm_scale * jnp.einsum("bhw,btw->bht", q.astype(jnp.float32),
                                   rows, precision="highest")
    mask = jnp.arange(T)[None] < lengths[:, None]
    p = jax.nn.softmax(jnp.where(mask[:, None], scores, -1e30), axis=-1)
    out = jnp.einsum("bht,btv->bhv", p, rows[..., :value_dim],
                     precision="highest")
    return jnp.where((lengths > 0)[:, None, None], out, 0.0)


@pytest.mark.parametrize("lengths", [
    (640, 640, 640, 640), (640, 600, 47, 0), (513, 64, 0, 65)],
    ids=RUN_IDS)
@pytest.mark.parametrize("pages_per_block", [None, 8])
@pytest.mark.parametrize("order", ["rows", "mixed", "shuffled"])
def test_latent_tables_with_runs_match_the_dense_formulation(
        order, pages_per_block, lengths):
    """The latent pool (one copy a page, 32 pages a block, or 8): the same
    whole blocks and runs, across a block's boundary and up to a slot's
    last page."""
    from ray_tpu.ops import paged_attention as pa

    P = 40
    tables, num_pages = _tables_of(order, lengths, P)
    rng = np.random.default_rng(1)
    pool = rng.normal(size=(LAYERS, num_pages, PAGE_SIZE, 256)) * 30.0
    for b, length in enumerate(lengths):
        for pg in range(-(-length // PAGE_SIZE)):
            rows = min(PAGE_SIZE, length - pg * PAGE_SIZE)
            pool[1, tables[b, pg], :rows] = rng.normal(size=(rows, 256))
    pool = jnp.asarray(pool, jnp.float32)
    assert pa.walk_blocks(pool.shape, 4, P, pages_per_block) == (
        pages_per_block or 32, 4)
    q = jax.random.normal(jax.random.PRNGKey(2), (SLOTS, 8, 256),
                          jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    got = pa.paged_latent_decode_attention(
        q, pool, jnp.asarray(tables), lens, jnp.int32(1), value_dim=128,
        sm_scale=0.0625, pages_per_block=pages_per_block)
    want = _latent_reference(q, pool, jnp.asarray(tables), lens, 1, 128,
                             0.0625)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5,
                               rtol=2e-5)


def test_a_pool_of_large_pages_lowers_to_the_text_it_lowered_to_before():
    """Who moves several pages a copy is read from the pool's shape: a page
    at or over ``COPY_BYTES`` (8 KV heads x 128 in bf16: Mistral's;
    Olmo-Hybrid's 32) keeps the page-by-page program TEXT FOR TEXT, with or
    without a window; a smaller page (4 KV heads) does not.  The digests
    are of the parent commit's lowering (PR 59's tree, this container's
    JAX: ``jax.jit(...).lower(...).as_text()`` of the calls below)."""
    import hashlib

    from ray_tpu.ops import paged_attention as pa

    def text(n_kv, **kw):
        pool = jnp.zeros((2, 90, 16, n_kv, 128), jnp.bfloat16)
        return jax.jit(lambda q, k, v, t, n, layer: paged_decode_attention(
            q, k, v, t, n, layer, **kw)).lower(
                jnp.zeros((4, 32, 128), jnp.bfloat16), pool, pool,
                jnp.zeros((4, 20), jnp.int32), jnp.zeros((4,), jnp.int32),
                jnp.int32(1)).as_text()

    def digest(t):
        return hashlib.sha256(t.encode()).hexdigest()[:16]

    assert pa.walk_blocks((2, 90, 16, 8, 128), 2, 20) == (16, 1)
    assert pa.walk_blocks((2, 90, 16, 32, 128), 2, 20) == (16, 1)
    assert pa.walk_blocks((2, 90, 16, 4, 128), 2, 20) == (16, 4)
    assert pa.walk_blocks((2, 90, 16, 4, 128), 2, 20, 6) == (6, 1)
    assert pa.walk_blocks((8, 90, 16, 640), 2, 256) == (32, 4)  # latent
    assert digest(text(8)) == "a973b9cfe013f283"
    assert digest(text(32)) == "41423deb2a9788ff"
    assert digest(text(8, window=64)) == "55764b10c4c2ac90"
    small = text(4)
    assert digest(small) != "0d5b898c2e84982f"  # the parent's for 4 heads
    # and with the rule switched off the small page lowers as the large
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(pa, "walk_blocks", lambda *a: (16, 1))
        jax.clear_caches()
        assert digest(text(4)) == "0d5b898c2e84982f"
    jax.clear_caches()


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


@pytest.fixture(scope="module")
def long_model():
    cfg = llama.LlamaConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=512, dtype="float32", remat=False)
    return llama.init(cfg, jax.random.PRNGKey(0)), cfg


def test_the_engine_counts_the_pages_that_move_in_runs(long_model,
                                                       monkeypatch):
    """``decode_pages_in_runs`` beside ``decode_pages_read``, reckoned on
    the host from the tables a burst is handed: on a fresh pool a long
    prompt's pages are ids in a row and nearly all of what its decode steps
    read lies in a block that moves four pages a copy (all but the pages
    past the last whole block of 32); on a pool with no two free
    neighbours none does, and the tokens are the same; a table shuffled by
    hand counts none; the count never exceeds the pages read."""
    monkeypatch.setenv("RTPU_DEBUG_ALLOCATOR", "1")
    params, cfg = long_model
    prompt = [1 + (7 * i) % 120 for i in range(300)]  # 38 pages of 8

    def engine_():
        return LLMEngine(params, cfg, EngineConfig(
            max_slots=4, num_pages=200, page_size=8, max_seq_len=512,
            prefill_buckets=(64, 512)))

    def serve(fragment: bool):
        engine = engine_()
        try:
            assert (engine._walk_block, engine._run_pages) == (32, 4)
            if fragment:  # every other page held: no free neighbours left
                held = engine.allocator.allocate(199)
                engine.allocator.free(held[::2])
            out = engine.generate(prompt, SamplingParams(max_tokens=17))
            return out, engine.stats()
        finally:
            engine.stop()

    out, st = serve(False)
    read, runs = st["decode_pages_read"], st["decode_pages_in_runs"]
    # positions 300..: 38 to 40 pages reached a step, the first 32 of them
    # one whole block, every group of it a run
    assert read == sum(pos // 8 + 1
                       for pos in range(300, 300 + st["decode_steps"]))
    assert runs == 32 * st["decode_steps"]
    assert 0.75 * read < runs <= read
    scattered, st = serve(True)
    assert scattered == out
    assert st["decode_pages_read"] == read
    assert st["decode_pages_in_runs"] == 0

    from ray_tpu.ops import paged_attention as pa

    engine = engine_()
    try:
        rows = np.zeros((2, 64), np.int32)
        rows[0] = np.arange(40, 104)
        rows[1, :40] = np.arange(40, 0, -1)  # in a row, the wrong way
        at = np.asarray([300, 300])
        assert engine._pages_in_runs(rows, at, 1) == 32
        assert engine._pages_in_runs(rows, at, 8) == 8 * 32
        # the step that reaches the table's last page has two whole blocks
        assert engine._pages_in_runs(rows, np.asarray([503, 0]), 2) == 32 + 64
        # under a window the blocks wholly behind the start do not count
        assert engine._pages_in_runs(rows, np.asarray([511, 0]), 1,
                                     window=300) == 32
        assert engine._pages_in_runs(rows, np.asarray([511, 0]), 1,
                                     window=200) == 0
        rows[0] = np.random.default_rng(0).permutation(rows[0])
        assert engine._pages_in_runs(rows, at, 8) == 0
        # and it is the kernel's own rule, step for step
        rng = np.random.default_rng(4)
        rows = 1 + np.arange(128).reshape(2, 64).astype(np.int32)
        rows[1, 40] = 0  # a broken group: its block moves page by page
        at = rng.integers(200, 505, 2)
        for window in (0, 90, 300):
            want = 0
            for j in range(8):
                n = np.minimum(at + j + 1, 512)
                want += 32 * int((pa.block_kinds(
                    rows, n, np.maximum(n - window, 0) if window else None,
                    8, 32, 4) == 2).sum())
            assert engine._pages_in_runs(rows, at, 8, window) == want
    finally:
        engine.stop()
