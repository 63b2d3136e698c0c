"""MiniCPM-SALA (models/minicpm_sala.py): block-sparse attention layers and
fixed-decay linear-attention layers, served through the paged engine, held
to ``benchmarks/reference/minicpm_sala.py`` on the CPU at ``tiny()`` sizes
(2 KV heads, pages of 8, blocks of 4 pages, ``dense_len`` 8 blocks, both
kinds of layer), float32.

TOL: both sides compute in float32, in another ORDER (the program by
chunks, key blocks and pages, at default matmul precision in the stream;
the reference whole, at ``highest``): logits of O(1) agree to a few 1e-5;
1e-3 leaves room and a wrong mask, block or state moves them by 1e-1.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import minicpm_sala as family
from benchmarks.reference import minicpm_sala as ref
from ray_tpu.llm import model as lm
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.paged_cache import CacheConfig, init_state
from ray_tpu.models import afmoe, glm_moe_lite, llama, olmo_hybrid, sdar_moe
from ray_tpu.models import minicpm_sala as ms
from ray_tpu.ops import block_sparse, lightning
from ray_tpu.ops.paged_attention import paged_decode_attention

TOL = 1e-3
CFG = ms.MiniCPMSALAConfig.tiny()
PS = CFG.kernel_stride


def config_file(cfg=CFG, **engine) -> dict:
    """``cfg`` as a benchmark configuration file has it."""
    return {
        "vocab_size": cfg.vocab_size, "hidden_size": cfg.d_model,
        "intermediate_size": cfg.d_ff, "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
        "lightning_nh": cfg.lightning_heads,
        "lightning_head_dim": cfg.lightning_head_dim,
        "mixer_types": list(cfg.mixer_types), "scale_emb": cfg.scale_emb,
        "scale_depth": cfg.scale_depth, "dim_model_base": cfg.dim_model_base,
        "published": {"num_hidden_layers": cfg.depth_base},
        "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
        "max_position_embeddings": cfg.max_seq_len, "dtype": cfg.dtype,
        "sparse_config": {k: getattr(cfg, k) for k in (
            "kernel_size", "kernel_stride", "block_size", "init_blocks",
            "window_size", "topk", "dense_len")},
        "engine": {"page_size": PS, **engine}}


C = config_file()


@pytest.fixture(scope="module")
def params():
    return ms.init(CFG, jax.random.PRNGKey(0))


def _engine(params, buckets=(32, 64, 128), slots=4, pages=300, cfg=CFG):
    return LLMEngine(params, cfg, EngineConfig(
        max_slots=slots, page_size=PS, max_seq_len=512, num_pages=pages,
        prefill_buckets=buckets))


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(3, CFG.vocab_size, n).tolist()


def _gaps(params, prompt, out):
    """How far each of ``out`` lies under the reference's best on the
    sequence's own history."""
    return ref.verify(C, params, prompt, out, len(out), 512)[0]


def test_the_family_file_and_the_model_agree_on_the_configuration():
    assert family.model_config(C) == CFG
    shapes = jax.eval_shape(lambda k: ms.init(CFG, k), jax.random.PRNGKey(0))
    assert family.n_params(C) == sum(
        x.size for x in jax.tree.leaves(shapes))
    layout = CFG.cache_layout()
    assert layout["n_layers"] == 2 and layout["state_layers"] == 3
    assert set(layout["page_rows"]) == {"pooled_k"}
    assert CFG.runs() == ((ms.SPARSE, 0, 1), (ms.LINEAR, 0, 2),
                          (ms.SPARSE, 1, 1), (ms.LINEAR, 2, 1))
    tree = CFG.serving_layout(ms.init(CFG, jax.random.PRNGKey(1)))
    assert CFG.serving_layout(tree) is tree
    assert [jax.tree.leaves(r)[0].shape[0] for r in tree["layers"]] \
        == [1, 2, 1, 1]


@pytest.mark.parametrize("n", [40, 256, 257, 400])
def test_pinned_program_layers_against_the_reference(params, n):
    """The program's layers (cacheless, the reference's choice handed in)
    against the reference, under, at and past ``dense_len``; and the
    program's own choice is the reference's at float32."""
    tokens = np.zeros(512, np.int32)
    tokens[:n] = _prompt(n, n)
    rows = jnp.asarray([n // 2, n - 1], jnp.int32)
    want, sel = ref.logits_and_selection(C, params, jnp.asarray(tokens), rows)
    got, own = family.pinned_logits(C, params, jnp.asarray(tokens), rows, sel)
    assert float(jnp.max(jnp.abs(got - want))) < TOL
    # (every block that holds a key the query sees: the reference marks
    # no other, the program's dense rule marks them all)
    there = (np.arange(sel.shape[-1]) * CFG.block_size
             <= np.arange(n)[:, None, None])
    assert bool(jnp.all((own[:, :n] & there) == sel[:, :n]))
    if n > CFG.dense_len:  # the rule chose: topk blocks, not all of them
        assert int(sel[0, n - 1, 0].sum()) == CFG.topk
        assert -(-n // CFG.block_size) > CFG.topk


# (a chunk's outputs are float32 sums of ``chunk`` terms in another order
# than the recurrence's, on outputs of ~10: a chunk of 64 stays under 1e-4
# absolute as it always has; one of 128, the file's ``CHUNK``, reads 1.3e-4
# and is held to 2e-4, 2e-5 of the outputs' size)
@pytest.mark.parametrize("chunk,o_tol", [(64, 1e-4), (128, 2e-4)])
@pytest.mark.parametrize("start", [0, 1])
def test_chunked_against_recurrent_from_a_state(start, chunk, o_tol):
    """``chunked`` (with padded positions that change nothing) and
    ``decode_update`` against ``recurrent``, from a non-zero S0."""
    L, H, d = 150, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    q, k, v = (jax.random.normal(key, (L, H, d)) for key in ks[:3])
    S0 = start * jax.random.normal(ks[3], (H, d, d))
    g = jnp.broadcast_to(lightning.log_decays(H), (L, H))
    want_o, want_S = lightning.recurrent(q, k, v, g, S0)
    real = (jnp.arange(L + 42) < L)[:, None]
    pad = lambda x: jnp.pad(x, ((0, 42),) + ((0, 0),) * (x.ndim - 1))  # noqa: E731
    # (v of a padded position is never read: its key is zero)
    _, S = lightning.chunked(
        pad(q), jnp.where(real[..., None], pad(k), 0.0),
        pad(v) + 5.0 * ~real[..., None], jnp.where(real, pad(g), 0.0), S0,
        chunk=chunk)
    o, S2 = lightning.chunked(pad(q), jnp.where(real[..., None], pad(k), 0.0),
                              pad(v), jnp.where(real, pad(g), 0.0), S0,
                              chunk=chunk)
    assert float(jnp.max(jnp.abs(o[:L] - want_o))) < o_tol
    assert float(jnp.max(jnp.abs(S2 - want_S))) < 1e-4
    assert float(jnp.max(jnp.abs(S - want_S))) < 1e-4
    # the decode form, two slots of which one is live, layer 1 of 2
    state = jnp.zeros((2, 2, H, d, d)).at[1, 0].set(S0).at[1, 1].set(7.0)
    for t in range(5):
        two = lambda x: jnp.stack([x[t], x[t]])  # noqa: E731
        o1, state = lightning.decode_update(
            state, 1, two(q), two(k), two(v), two(g),
            jnp.asarray([True, False]))
        assert float(jnp.max(jnp.abs(o1[0] - want_o[t]))) < 1e-4
        assert float(jnp.max(jnp.abs(o1[1]))) == 0.0
    assert float(jnp.max(jnp.abs(state[1, 1] - 7.0))) == 0.0
    assert float(jnp.max(jnp.abs(state[0]))) == 0.0


@pytest.mark.parametrize("heads,groups", [(8, (2, 4, 8)), (16, (8, 16))])
def test_the_whole_slot_in_a_block_equals_one_head_a_block(heads, groups):
    """A fixed decay, a head its own key: the block the rule chooses (every
    head of a slot, as many keys) and each smaller one give, BIT FOR BIT,
    what one head a block gives, and that is the recurrence; dead slots
    between live ones keep their rows."""
    d, live = 16, (True, False, False, True, False, True, True)
    B = len(live)
    ks = jax.random.split(jax.random.PRNGKey(heads), 4)
    q, k, v = (jax.random.normal(key, (B, heads, d)) for key in ks[:3])
    S = jax.random.normal(ks[3], (3, B, heads, d, d))
    g = jnp.broadcast_to(lightning.log_decays(heads), (B, heads))
    active = jnp.asarray(live)

    def update(group):
        return lightning._decode_update(S, 2, q, k, v, g, active, group=group,
                                        interpret=True)

    assert lightning._heads_a_block(heads, heads, d, d) == heads
    o1, S1 = update(1)
    for got_o, got_S in (lightning.decode_update(S, 2, q, k, v, g, active),
                         *(update(group) for group in groups)):
        np.testing.assert_array_equal(got_o, o1)
        np.testing.assert_array_equal(got_S, S1)
    np.testing.assert_array_equal(S1[:2], S[:2])  # the other layers' rows
    for b in range(B):
        if not live[b]:
            np.testing.assert_array_equal(S1[2, b], S[2, b])
            assert not np.asarray(o1[b]).any()
            continue
        want_o, want_S = lightning.recurrent(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], g[b:b + 1], S[2, b])
        np.testing.assert_allclose(o1[b], want_o[0], atol=1e-4)
        np.testing.assert_allclose(S1[2, b], want_S, atol=1e-4)


@pytest.mark.parametrize("n", [24, 130, 200, 256, 257, 300, 391, 450, 512])
def test_selection_against_the_reference(n):
    """Forced blocks, ties (neighbouring blocks share a pooled row; here
    keys repeat too), a last block half full, contexts under, at and past
    ``dense_len``: the program's mask is the reference's choice."""
    r = np.random.default_rng(n)
    k = jnp.asarray(r.normal(size=(512, 2, 16)), jnp.float32)
    k = k.at[64:128].set(k[0:64])  # equal rows, equal scores
    q = jnp.asarray(r.normal(size=(4, 16)), jnp.float32)
    rows = block_sparse.pool_keys(CFG, k)
    assert float(jnp.max(jnp.abs(rows - ref.pooled_keys(C, k)))) < 1e-6
    got = block_sparse.block_mask(CFG, q[None], rows, jnp.asarray([n]))[0]
    want = ref.choose(C, q, ref.pooled_keys(C, k), n)
    there = np.arange(16) * CFG.block_size < n
    assert np.array_equal(np.asarray(got)[:, there], np.asarray(want)[:, there])
    if n > CFG.dense_len:
        assert int(want[0].sum()) == CFG.topk
        last = (n - 1) // CFG.block_size
        first = max(0, n - CFG.window_size) // CFG.block_size
        assert bool(want[0, 0]) and bool(want[:, first:last + 1].all())


# the published sizes (400 blocks a 25,600-token table), and the same with a
# window that forces every block: more than ``topk`` equals at the top
WIDE = ms.MiniCPMSALAConfig()
ALL_FORCED = dataclasses.replace(WIDE, window_size=400 * 64)
SCORES = {  # 400 block scores in [-1, 16] -> the case's
    "distinct": lambda b: b,
    "two_places": lambda b: np.round(b / 8, 2),
    "neighbours_equal": lambda b: np.repeat(np.round(b[..., ::2], 1), 2, -1),
    "half_incomplete": lambda b: np.where(b < 8, -1.0, np.round(b, 1)),
    "all_equal": lambda b: np.full_like(b, 0.25),
    "signed_zeros": lambda b: np.where(b < 12, np.where(b < 6, -0.0, 0.0), b),
}
# a context inside the window (every block it holds forced), one shorter
# than ``topk`` blocks, one under / at / past ``dense_len``, a block half
# full, the whole table
CONTEXTS = [1000, 2600, 8191, 8192, 8193, 12345, 25600]


def _ranked(sp, b, n):
    """The rule by RANKING every block against every other (the form
    ``chosen`` had until PR 50), in numpy: the oracle."""
    M = b.shape[-1]
    m = np.arange(M)
    n = np.asarray(n)[:, None, None]
    forced = (m < sp.init_blocks) | ((m + 1) * sp.block_size
                                     > n - sp.window_size)
    score = np.where(forced, np.float32(block_sparse._FORCED), b)
    score = np.where(m * sp.block_size < n, score,
                     np.float32(block_sparse._OUT))
    other, own = score[..., None, :], score[..., :, None]
    ahead = (other > own) | ((other == own) & (m[None, :] < m[:, None]))
    return (ahead.sum(axis=-1) < sp.topk) & (score > block_sparse._OUT / 2)


@pytest.mark.parametrize("n", CONTEXTS + ["every_block_forced"])
@pytest.mark.parametrize("scores", list(SCORES))
def test_the_threshold_chooses_what_the_ranking_chose(scores, n):
    """``chosen`` finds the ``topk``-th score and admits equals lowest
    index first; at 400 blocks and ``topk`` 64 its mask is the pairwise
    ranking's, tie for tie, and ``select`` lists those blocks ascending
    with their count."""
    sp, n = (ALL_FORCED, 25600) if n == "every_block_forced" else (WIDE, n)
    r = np.random.default_rng(len(scores) * 100003 + n)
    b = SCORES[scores](
        r.uniform(-1, 16, (3, 2, 400))).astype(np.float32)
    ns = np.asarray([n, n, max(1, n - 64)])
    want = _ranked(sp, b, ns)
    got = np.asarray(block_sparse.chosen(sp, jnp.asarray(b), jnp.asarray(ns)))
    assert np.array_equal(got, want)
    held = -(-ns // sp.block_size)
    assert np.array_equal(want.sum(axis=-1), np.minimum(held, sp.topk)[
        :, None].repeat(2, axis=1))
    blocks, count = (np.asarray(x) for x in block_sparse.select(
        sp, jnp.asarray(b), jnp.asarray(ns)))
    assert np.array_equal(count, want.sum(axis=-1))
    for q, g in np.ndindex(*count.shape):
        assert np.array_equal(blocks[q, g, :count[q, g]],
                              np.flatnonzero(want[q, g]))
        assert (blocks[q, g, count[q, g]:] == 400).all()


def test_sparse_decode_kernel_against_masked_dense_attention():
    """``paged_decode_attention`` with a list a KV head (interpret mode)
    against dense attention over the listed pages' positions, the last
    page as far as the list's length."""
    r = np.random.default_rng(0)
    B, H, G, d, pages, W = 3, 8, 2, 16, 40, 12
    pool_k, pool_v = (jnp.asarray(r.normal(size=(2, pages, PS, G, d)),
                                  jnp.float32) for _ in range(2))
    q = jnp.asarray(r.normal(size=(B, H, d)), jnp.float32)
    lists = r.integers(1, pages, (B, G, W)).astype(np.int32)
    lengths = np.array([[5 * PS + 3, W * PS], [1, 2 * PS], [0, 0]], np.int32)
    got = paged_decode_attention(q, pool_k, pool_v, jnp.asarray(lists),
                                 jnp.asarray(lengths), 1, heads_apart=True,
                                 pages_per_block=4)
    for b in range(B):
        for g in range(G):
            n = int(lengths[b, g])
            heads = slice(g * H // G, (g + 1) * H // G)
            if n == 0:
                assert float(jnp.abs(got[b, heads]).max()) == 0.0
                continue
            keys = pool_k[1, lists[b, g], :, g].reshape(-1, d)[:n]
            vals = pool_v[1, lists[b, g], :, g].reshape(-1, d)[:n]
            w = jax.nn.softmax(q[b, heads] @ keys.T * d ** -0.5, axis=-1)
            assert float(jnp.abs(got[b, heads] - w @ vals).max()) < 1e-5


@pytest.mark.parametrize("n", [200, 250, 400])
def test_a_prompt_whole_and_in_chunks_then_steps(params, monkeypatch, n):
    """The same prompt as ONE prefill and in chunks of 64 (the state and
    the pooled rows handed from chunk to chunk, key blocks of 64), then 24
    greedy steps, which for 250 cross ``dense_len``: the same tokens, each
    the reference's best on its own history."""
    monkeypatch.setattr(block_sparse, "KEY_BLOCK", 64)
    for f in (lm.prefill, lm.prefill_with_prefix):
        f.clear_cache()
    prompt, sp = _prompt(n, n), SamplingParams(max_tokens=24)
    whole = _engine(params, buckets=(64, 512))
    chunks = _engine(params, buckets=(32, 64))
    a, b = whole.generate(prompt, sp), chunks.generate(prompt, sp)
    whole.stop(), chunks.stop()
    assert chunks.stats()["prefill_chunks"] == -(-n // 64)
    assert whole.stats()["prefill_chunks"] == 0
    assert chunks.stats()["state_resets"] == 1 == whole.stats()["state_resets"]
    assert a == b
    assert max(_gaps(params, prompt, a)) < TOL
    for f in (lm.prefill, lm.prefill_with_prefix):
        f.clear_cache()


def _rows_of(engine, pages, slot):
    got = family.engine_rows(engine, pages)
    got["S"] = family.engine_state(engine, slot)
    return got


def test_rows_after_chunks_steps_a_preemption_and_its_recompute(params):
    """K/V pages, pooled-key rows and state rows the engine's programs left
    against the reference's: after the chunks alone, after steps, and after
    a preemption whose recompute goes through the chunks again."""
    engine = _engine(params, buckets=(32, 64))
    prompt = _prompt(230, 5)
    req = engine.submit(prompt, SamplingParams(max_tokens=80))
    engine._admit()
    while engine._slots[0].prefill_at is not None:
        engine._admit()
    s = engine._slots[0]

    def check(n):
        want = ref.rows_of(C, params, jnp.asarray(np.pad(
            prompt + s.generated, (0, 512))[:512], jnp.int32), states=n)
        got = _rows_of(engine, s.pages, 0)
        for name in ("k", "v"):
            assert float(jnp.abs(got[name][:, :n] - want[name][:, :n]).max()) \
                < TOL
        done = (n - CFG.kernel_size) // PS + 1
        assert float(jnp.abs(got["pooled"][:, :done]
                             - want["pooled"][:, :done]).max()) < TOL
        assert float(jnp.abs(got["S"] - want["S"]).max()) < TOL * 10

    check(230)  # the chunks alone (the first token is not yet fed)
    for _ in range(4):
        engine._decode_all()
    assert s.num_tokens == 230 + 32 > CFG.dense_len  # steps crossed it
    check(s.num_tokens)
    tokens_before = list(s.generated)
    engine._preempt(0, s)
    assert engine._slots[0] is None
    engine._admit()
    while engine._slots[0].prefill_at is not None:
        engine._admit()
    s = engine._slots[0]
    assert s.request.preempts == 1 and engine.stats()["state_resets"] == 2
    prompt = list(s.request.prompt_tokens)
    assert prompt[230:] == tokens_before
    check(len(prompt))
    engine._decode_all()
    check(s.num_tokens)
    del req


def test_two_slots_one_under_and_one_over_dense_len_in_one_step(params):
    """Two sequences decode together, one under ``dense_len`` (its list is
    its table) and one past it (its list is the selected blocks'): each
    continues as it does alone, and as the reference has it."""
    sp = SamplingParams(max_tokens=16)
    short, long = _prompt(60, 1), _prompt(420, 2)
    alone = []
    for p in (short, long):
        e = _engine(params)
        alone.append(e.generate(p, sp))
        e.stop()
    both = _engine(params)
    reqs = [both.submit(p, sp) for p in (short, long)]
    both.start()
    got = []
    for r in reqs:
        out = []
        while (item := r.out_queue.get(timeout=300)) is not None:
            out.extend(item if isinstance(item, (list, tuple)) else [item])
        got.append(out)
    both.stop()
    assert got == alone
    # what the steps counted on the device from their lists, against the
    # rule's arithmetic: 16 steps a slot (two bursts of 8), contexts 61..76
    # under ``dense_len`` and 421..436 past it, 2 sparse layers x 2 KV heads
    stats = both.stats()
    assert stats["state_slot_steps"] == 32
    under, past = np.arange(61, 77), np.arange(421, 437)
    ps, ppb = CFG.kernel_stride, CFG.block_size // CFG.kernel_stride
    sums = 2 * CFG.n_kv_heads
    assert stats["dense_rule_slot_steps"] == 2 * 16
    assert stats["sparse_pages_resident"] == sums * int(
        (-(-under // ps)).sum() + (-(-past // ps)).sum())
    last = (past - 1) % CFG.block_size // ps + 1  # pages of the last block
    assert stats["sparse_pages_read"] == sums * int(
        (-(-under // ps)).sum() + ((CFG.topk - 1) * ppb + last).sum())
    assert stats["sparse_blocks_selected"] == sums * int(
        (-(-under // CFG.block_size)).sum() + CFG.topk * 16)
    # rows of pooled keys: a prompt's whole pages less one, then a row a
    # step that fills a page (64, 72; 424, 432), in both sparse layers
    assert stats["index_rows_written"] == 2 * ((60 // ps - 1)
                                               + (420 // ps - 1) + 4)
    for p, o in zip((short, long), got):
        assert max(_gaps(params, p, o)) < TOL


@pytest.fixture(scope="module")
def decoded(params):
    """Two sequences an engine has prefilled in chunks and decoded for two
    bursts, one under ``dense_len`` and one past it: (engine, [(pages,
    held, the reference's rows of the same tokens with its queries,
    attention outputs and choice at the last cached position)])."""
    engine = _engine(params, buckets=(32, 64))
    prompts = [_prompt(150, 5), _prompt(330, 6)]
    for p in prompts:
        engine.submit(p, SamplingParams(max_tokens=64))
    while sum(s is not None and s.prefill_at is None
              for s in engine._slots) < 2:
        engine._admit()
    for _ in range(2):
        engine._decode_all()
    out = []
    for p, s in zip(prompts, engine._slots):
        assert list(s.request.prompt_tokens) == p
        held = s.num_tokens
        want = ref.verify(C, params, p, s.generated, 1, 512,
                          q_at=held - 1)[1]
        out.append((list(s.pages), held, want))
    assert out[0][1] <= CFG.dense_len < out[1][1]
    yield engine, out
    engine.stop()


@pytest.mark.parametrize("fault", [None, "list_page_shifted",
                                   "other_heads_columns"])
def test_served_attention_is_the_decode_steps_own(decoded, monkeypatch,
                                                  fault):
    """(f) of the chip's ``correct``: the decode step's lists and paged
    kernel over the ENGINE's pools under the reference's choice give the
    reference's attention output, under ``dense_len`` and past it; a list
    that begins a page late and the other KV head's columns (the chip's
    controls) move it a thousand times further.  The step's own lists hold
    the pages of the reference's choice."""
    from ray_tpu.ops import paged_attention

    for mod, name in ((block_sparse, "lists_from"),
                      (paged_attention, "paged_decode_attention"),
                      (lm, "paged_decode_attention")):
        monkeypatch.setattr(mod, name, getattr(mod, name))
    if fault:
        getattr(family, f"plant_{fault}")()
    engine, seqs = decoded
    for pages, held, want in seqs:
        sel = np.asarray(want["selection"][:, held - 1])
        got = family.served_attention(engine, pages, want["q"], held, sel)
        err = float(jnp.max(jnp.abs(got - want["o"])))
        assert err < TOL if fault is None else err > 0.05, (fault, held)
        if held <= CFG.dense_len or fault == "other_heads_columns":
            continue
        for li in range(sel.shape[0]):
            own = family.engine_selection(engine, pages, want["q"][li],
                                          held, li)
            theirs = family.selected_pages(engine, pages, sel[li], held)
            assert (own == theirs) == (fault is None)


@pytest.mark.parametrize("fault", ["state_bf16", "topk_short"])
def test_the_planted_faults_show(params, monkeypatch, fault):
    """The faults the chip's controls plant move what ``correct`` compares
    far past what the clean path reads."""
    if fault == "state_bf16":
        H, d, T = 4, 16, 300
        q, k, v = (jax.random.normal(key, (T, H, d))
                   for key in jax.random.split(jax.random.PRNGKey(0), 3))
        want = ref.state_at(C, k, v, T)

        def rel():
            S = family.recurrence_outputs(C, q, k, v, 260, 64)[1]
            return float(jnp.sqrt(jnp.sum((S - want) ** 2)
                                  / jnp.sum(want ** 2)))

        clean = rel()
        monkeypatch.setattr(lightning, "chunked", lightning.chunked)
        monkeypatch.setattr(lightning, "decode_update",
                            lightning.decode_update)
        family.plant_state_bf16()
        assert clean < 1e-5 and rel() > 1e-3
        return
    n = 400
    tokens = np.zeros(512, np.int32)
    tokens[:n] = _prompt(n, 9)
    rows = jnp.asarray([n - 1], jnp.int32)
    _, sel = ref.logits_and_selection(C, params, jnp.asarray(tokens), rows)
    short = config_file(ms.MiniCPMSALAConfig.tiny(topk=5))
    _, own = family.pinned_logits(short, params, jnp.asarray(tokens), rows,
                                  sel)
    past = slice(CFG.dense_len, n)
    share = float((sel[:, past] & own[:, past]).sum() / sel[:, past].sum())
    assert share <= 5 / 6 + 1e-6


# ---------------------------------------------------------------------------
# The pooled keys in SLOT order (paged_cache.py ``CacheConfig``): the decode
# step reads a slot's rows where they lie, and its lists' page ids a block
# an index.

def _complete(n):
    """Rows of pooled keys a context of n positions completes."""
    return max(n // PS - (CFG.kernel_size // PS - 1), 0)


def _slot_rows_are_the_tables(engine, homes=None):
    """Every live slot's rows in slot order are ``pooled_k`` through its
    table, bit for bit, over the rows its context completes; each
    sequence sits in the slot its last prefill named.  Returns the rows
    compared."""
    by_page = np.asarray(engine.state["pooled_k"])
    by_slot = np.asarray(engine.state["pooled_k_by_slot"])
    assert by_slot.shape == (2, len(engine._slots), 512 // PS,
                             CFG.n_kv_heads, CFG.head_dim)
    seen = 0
    for i, s in enumerate(engine._slots):
        if s is None:
            continue
        if homes is not None:
            assert homes[id(s.request)] == i
        j = _complete(s.num_tokens)
        for li in range(2):
            assert np.array_equal(by_slot[li, i, :j],
                                  by_page[li, np.asarray(s.pages[:j], int)])
            assert j < 2 or np.abs(by_slot[li, i, :j]).min(axis=(1, 2)).all()
        seen += j
    return seen


def _prefill_homes(engine):
    """request -> the slot its last prefill program named (wraps
    ``engine._prefill``): where a live sequence's rows were written."""
    homes, prefill = {}, engine._prefill

    def noted(req, pages, rng, prefix_len=0, slot=None, *a, **kw):
        homes[id(req)] = slot
        return prefill(req, pages, rng, prefix_len, slot, *a, **kw)

    engine._prefill = noted
    return homes


def _seated(engine, count):
    return sum(s is not None and s.prefill_at is None
               for s in engine._slots) == count


def _lists_by_entry(engine, i, q):
    """What slot ``i``'s decode step would list for the queries q [sparse
    layers, H, d] at its context, from its rows in slot order: per layer
    and KV head the table ENTRIES listed (page ids differ from engine to
    engine) as far as the list's length, and the lengths."""
    s = engine._slots[i]
    table = jnp.asarray(family._table(engine, s.pages))[None]
    entry = {page: j for j, page in enumerate(s.pages)}
    out = []
    for li in range(2):
        lists, held = block_sparse.page_lists(
            CFG, q[li][None], engine.state["pooled_k_by_slot"][li, i][None],
            table, jnp.asarray([s.num_tokens], jnp.int32),
            block_sparse.list_width(CFG, table.shape[1]))
        lists, held = np.asarray(lists[0]), np.asarray(held[0])
        out.append([[entry[p] for p in lists[g, :-(-held[g] // PS)].tolist()]
                    + [int(held[g])] for g in range(CFG.n_kv_heads)])
    return out


def _fresh_run(params, prompt, bursts):
    """(tokens, lists) of ``prompt`` alone in a fresh engine after its
    chunks and ``bursts`` bursts of 8 steps."""
    engine = _engine(params, buckets=(32, 64))
    engine.submit(prompt, SamplingParams(max_tokens=200))
    while not _seated(engine, 1):
        engine._admit()
    for _ in range(bursts):
        engine._decode_all()
    s = engine._slots[0]
    assert s.num_tokens > CFG.dense_len  # the sparse rule chose its lists
    return list(s.generated), _lists_by_entry(engine, 0, _QUERIES)


_QUERIES = jnp.asarray(np.random.default_rng(11).standard_normal(
    (2, CFG.n_heads, CFG.head_dim)), jnp.float32)


@pytest.mark.parametrize("case", [
    "first_chunk", "later_chunks", "steps_that_complete_a_row",
    "steps_that_complete_none", "released_then_a_shorter_prompt",
    "preempted_then_resumed"])
def test_the_slot_order_holds_what_the_tables_reach(params, case):
    """``pooled_k_by_slot`` through the engine, two live slots, prompts of
    four and three chunks of 64: after a first chunk, after each later one
    (the other slot decoding between them), after steps that fill a page
    (a row completed) and steps that do not, every live slot's rows equal
    ``pooled_k[li, table[:j]]`` bit for bit over the rows its context
    completes, and nobody sits in a slot no prefill of theirs named.  A
    slot released and taken by a SHORTER prompt (stale rows past its
    context, another tenant's), and a sequence preempted and resumed, give
    the tokens and lists of a fresh engine."""
    engine = _engine(params, buckets=(32, 64))
    homes = _prefill_homes(engine)
    sp = SamplingParams(max_tokens=200)
    if case == "released_then_a_shorter_prompt":
        engine.submit(_prompt(400, 3), SamplingParams(max_tokens=9))
        while not _seated(engine, 1):
            engine._admit()
        stale = np.asarray(engine.state["pooled_k_by_slot"][:, 0, 41:49])
        engine._decode_all()
        assert engine._slots[0] is None  # finished and released
        short = _prompt(270, 4)
        engine.submit(short, sp)
        while not _seated(engine, 1):
            engine._admit()
            assert _slot_rows_are_the_tables(engine, homes) > 0
        assert engine._slots[0] is not None  # the same slot, rows 0-32
        for _ in range(2):
            engine._decode_all()
        # the last tenant's rows lie past what the shorter prompt's chunks
        # and steps wrote (rows 0-39) yet, and change nothing
        assert np.array_equal(stale, np.asarray(
            engine.state["pooled_k_by_slot"][:, 0, 41:49]))
        assert np.abs(stale).max() > 0
        got = (list(engine._slots[0].generated),
               _lists_by_entry(engine, 0, _QUERIES))
        assert got == _fresh_run(params, short, 2)
        return
    if case == "preempted_then_resumed":
        prompt = _prompt(270, 7)
        engine.submit(prompt, sp)
        while not _seated(engine, 1):
            engine._admit()
        engine._decode_all()
        s = engine._slots[0]
        engine._preempt(0, s)
        while not _seated(engine, 1):
            engine._admit()  # from position 0, the tokens folded in
            assert _slot_rows_are_the_tables(engine, homes) > 0
        engine._decode_all()
        s = engine._slots[0]
        # the first token and a burst's eight folded into the prompt
        folded = list(s.request.prompt_tokens)
        assert s.request.preempts == 1 and folded[:270] == prompt
        assert len(folded) == 279 and s.num_tokens == 279 + 8
        got = list(s.generated), _lists_by_entry(engine, 0, _QUERIES)
        assert got == _fresh_run(params, folded, 1)
        # and the sequence goes on as it would have, never preempted
        assert (folded[270:] + got[0])[:17] == _fresh_run(params, prompt,
                                                         2)[0]
        return
    a, b = _prompt(230, 5), _prompt(150, 6)
    engine.submit(a, sp)
    engine._admit()  # a's first chunk
    assert engine._slots[0].prefill_at == 64
    if case == "first_chunk":
        assert _slot_rows_are_the_tables(engine, homes) == 2 * 7 // 2
        return
    while not _seated(engine, 1):
        engine._admit()
        if case == "later_chunks":
            assert _slot_rows_are_the_tables(engine, homes) > 0
    engine.submit(b, sp)
    chunks = 0
    while not _seated(engine, 2):
        engine._admit()  # b's chunks into slot 1, a's bursts between them
        engine._decode_all()
        chunks += 1
        if case == "later_chunks":
            assert _slot_rows_are_the_tables(engine, homes) > 0
    assert chunks == 3 and [s.request.prompt_tokens for s in engine._slots[
        :2]] == [a, b]
    if case == "later_chunks":
        return
    if case == "steps_that_complete_a_row":
        # bursts of 8 steps over pages of 8: every burst fills a page a slot
        for _ in range(3):
            before = [_complete(s.num_tokens) for s in engine._slots[:2]]
            engine._decode_all()
            assert [_complete(s.num_tokens) for s in engine._slots[:2]] == [
                j + 1 for j in before]
            assert _slot_rows_are_the_tables(engine, homes) == sum(before) + 2
        return
    # a request waits and a slot is free: the engine steps ONE token a call
    engine.submit(_prompt(40, 8), sp)
    rose = []
    for _ in range(9):
        before = sum(_complete(s.num_tokens) for s in engine._slots[:2])
        tokens = [s.num_tokens for s in engine._slots[:2]]
        engine._decode_all()
        assert [s.num_tokens for s in engine._slots[:2]] == [
            t + 1 for t in tokens]
        rose.append(_slot_rows_are_the_tables(engine, homes) - before)
    # most steps complete no row and write none; one in eight a slot does
    assert rose.count(0) >= 5 and 0 < sum(rose) <= 4


def _attend_by_key_block(sp, q, positions, picked, keys_of, T, ends):
    """``block_sparse.attend_under`` as it was before PR 55, plain
    ``jax.numpy``: a ``fori_loop`` over key blocks of 512 positions under a
    running softmax, a block's scores [G, rep, L, 512] whole (kept here as
    the reference of the kernel)."""
    import math

    L, H, d = q.shape
    G, bs = picked.shape[1], sp.block_size
    rep, f32 = H // G, jnp.float32
    kb = math.gcd(T, 512)
    kb = kb if kb % bs == 0 else T
    qg = q.reshape(L, G, rep, d)

    def block(i, carry):
        m, l, acc = carry
        k, v = keys_of(i * kb, kb)
        s = jnp.einsum("qgrd,kgd->grqk", qg, k.astype(q.dtype),
                       preferred_element_type=f32) * d ** -0.5
        seen = jnp.repeat(jax.lax.dynamic_slice_in_dim(
            picked, i * (kb // bs), kb // bs, axis=2), bs, axis=2)
        seen &= (i * kb + jnp.arange(kb))[None, None, :] \
            <= positions[:, None, None]
        seen = seen.transpose(1, 0, 2)[:, None]  # [G, 1, L, kb]
        s = jnp.where(seen, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        return (m_new, l * alpha + p.sum(axis=-1, keepdims=True),
                acc * alpha + jnp.einsum(
                    "grqk,kgd->grqd", p.astype(v.dtype), v,
                    preferred_element_type=f32))

    _, l, acc = jax.lax.fori_loop(
        0, jnp.clip((ends + kb - 1) // kb, 1, T // kb), block,
        (jnp.full((G, rep, L, 1), -1e30, f32),
         jnp.zeros((G, rep, L, 1), f32), jnp.zeros((G, rep, L, d), f32)))
    return (acc / l).transpose(2, 0, 1, 3).reshape(L, H, d).astype(q.dtype)


# (queries L, key positions T, the first query's position, real queries,
# blocks nobody may select, dtype): contexts 1..512, ``dense_len`` 256
ATTEND_CASES = {
    "first_chunk_under_dense_len": (128, 128, 0, 128, None, "float32"),
    "behind_a_prefix_past_dense_len": (64, 512, 400, 64, None, "float32"),
    "a_chunk_across_dense_len": (64, 512, 224, 64, None, "float32"),
    "padded_last_chunk": (64, 512, 300, 40, None, "float32"),
    "whole_key_tiles_unselected": (64, 512, 400, 64, (2, 6), "float32"),
    "no_bucket_three_blocks": (96, 96, 0, 96, None, "float32"),
    "bfloat16_products": (64, 512, 400, 64, None, "bfloat16"),
}


@pytest.mark.parametrize("case", list(ATTEND_CASES))
def test_the_prefills_kernel_against_attention_by_key_block(case,
                                                            monkeypatch):
    """``attend_under`` (Pallas, through the interpreter here) against the
    ``jax.numpy`` body it replaces on every real query's row, the offset
    and the end TRACED as the suffix prefill hands them; and the two sums
    ``selected_attention`` hands back are the counts of the kernel's own
    table, by their definition over (KV head, query tile, key tile).  The
    test, not the program, makes the tiles small (16 queries, 2 blocks of
    keys), so that a call has several of each and some to skip."""
    L, T, start, real, barred, dtype = ATTEND_CASES[case]
    monkeypatch.setattr(block_sparse, "KEY_BLOCK", 2 * CFG.block_size)
    monkeypatch.setattr(block_sparse, "_TILE_ROWS",
                        16 * CFG.n_heads // CFG.n_kv_heads)
    H, G, d, bs = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim, CFG.block_size
    tq, tk = block_sparse._tile_sizes(L, T, H // G, bs)
    assert (tq, tk) == (16, 32 if T == 96 else 64)
    keys = jax.random.split(jax.random.PRNGKey(sum(map(ord, case))), 3)
    q, k, v = (jax.random.normal(key, shape, jnp.float32).astype(dtype)
               for key, shape in zip(keys, ((L, H, d), (T, G, d), (T, G, d))))
    rows = block_sparse.pool_keys(CFG, k).astype(k.dtype)

    @jax.jit
    def both(start, ends):
        positions = start + jnp.arange(L)
        picked = block_sparse.masks(CFG, q, positions, rows, T)
        if barred:
            picked = picked.at[:, :, slice(*barred)].set(False)
        keys_of = lambda at, n: (  # noqa: E731
            jax.lax.dynamic_slice_in_dim(k, at, n),
            jax.lax.dynamic_slice_in_dim(v, at, n))
        args = (CFG, q, positions, picked, keys_of, T, ends)
        with jax.named_scope("sparse_attn/attend"):
            got = block_sparse.attend_under(*args)
        return (_attend_by_key_block(*args), got, picked,
                block_sparse._attend_over(*args))

    want, got, picked, (again, counted) = both(jnp.int32(start),
                                               jnp.int32(start + real))
    assert got.shape == want.shape and got.dtype == want.dtype
    assert np.array_equal(np.asarray(got), np.asarray(again))
    err = np.abs(np.asarray(got[:real], np.float32)
                 - np.asarray(want[:real], np.float32)).max()
    assert err < (2e-2 if dtype == "bfloat16" else 1e-5)
    # the table by its definition: a key tile is reached where a query of
    # the tile sees a position of it, visited where one also selected the
    # block that position lies in
    pos = start + np.arange(L)
    sees = ((np.arange(T) <= pos[:, None])
            & (np.arange(T) < start + real))  # [L, T]
    chose = np.repeat(np.asarray(picked), bs, axis=2)  # [L, G, T]
    by_tile = lambda x: x.reshape(  # noqa: E731
        L // tq, tq, -1, T // tk, tk).any(axis=(1, 4))
    reached = by_tile(np.broadcast_to(sees[:, None], chose.shape))
    visited = by_tile(chose & sees[:, None])
    assert int(counted["sparse_prefill_tiles_causal"]) == reached.sum()
    assert int(counted["sparse_prefill_tiles_visited"]) == visited.sum()
    assert not (visited & ~reached).any() and visited[..., 0].all()
    if barred:  # blocks 2-5 are key tiles 1 and 2, whole
        assert reached[..., 1:3].all() and not visited[..., 1:3].any()
    # no tile at or past the end, whatever a padded query's position
    assert not reached[..., -(-(start + real) // tk):].any()


def test_prefill_spans_carry_the_tiles_the_kernel_was_handed(params,
                                                              monkeypatch):
    """A sampled loop: every ``llm.prefill`` span of a prompt in chunks
    names the key tiles its sparse layers' kernel reached and worked on,
    and they add up to the counters.  At these sizes a chunk's call is ONE
    tile a KV head a sparse layer (a bucket of 128 queries; 128 keys for
    the first chunk, the table's 512 behind a prefix)."""
    from ray_tpu.util import tracing

    recs = []
    monkeypatch.setenv("RTPU_TRACE_SAMPLE", "1.0")
    orig = tracing._record
    monkeypatch.setattr(tracing, "_record",
                        lambda r: (recs.append(r), orig(r))[1])
    engine = _engine(params)
    with tracing.serving_span("openai.request", path="/v1/x"):
        engine.generate(_prompt(300, 4), SamplingParams(max_tokens=2))
    stats = engine.stats()
    engine.stop()
    chunks = [r["args"] for r in recs if r["name"] == "llm.prefill"]
    assert len(chunks) == 3
    names = ("sparse_prefill_tiles_causal", "sparse_prefill_tiles_visited")
    for name in names:
        assert [c[name] for c in chunks] == [2 * CFG.n_kv_heads] * 3
        assert stats[name] == sum(c[name] for c in chunks)


def _elementwise_lists_from(sp, blocks, count, tables, n, width):
    """``block_sparse.lists_from`` as it was before PR 52: a page id an
    index (kept here as the reference of the form by block)."""
    B, P = tables.shape
    G = blocks.shape[1]
    ppb = sp.block_size // sp.kernel_stride
    pages = (blocks[..., None] * ppb + jnp.arange(ppb)).reshape(B, G, -1)
    sparse = jnp.where(
        pages < P, jnp.take_along_axis(
            tables[:, None, :], jnp.minimum(pages, P - 1), axis=2), 0)
    short = width - sparse.shape[-1]
    sparse = (jnp.pad(sparse, ((0, 0), (0, 0), (0, short))) if short >= 0
              else sparse[..., :width])
    dense = jnp.pad(tables, ((0, 0), (0, max(0, width - P))))[:, None, :width]
    under = (n <= sp.dense_len)[:, None]
    held = (count - 1) * sp.block_size + ((n - 1) % sp.block_size + 1)[:, None]
    lengths = jnp.where(under, n[:, None], held)
    return (jnp.where(under[..., None], dense, sparse).astype(jnp.int32),
            jnp.where((n > 0)[:, None], lengths, 0).astype(jnp.int32))


# a context for each case (tables of 64 pages of 8, blocks of 4 pages,
# topk 6, dense_len 256), and how many blocks the selection holds
SELECTIONS = {
    "fewer_than_topk_selected": (300, 4),  # the sentinel M behind them
    "topk_selected": (411, CFG.topk),
    "under_dense_len": (200, CFG.topk),  # the list is the table's own
    "not_live": (0, 0),
    "last_block_partly_filled": (293, CFG.topk),  # 5 of its 32 positions
    "the_tables_last_block": (512, CFG.topk),
}
WIDTHS = {"width_below": CFG.topk * 4 - 8, "width_of_topk_blocks":
          CFG.topk * 4, "width_above": block_sparse.list_width(CFG, 64)}


@pytest.mark.parametrize("width", list(WIDTHS))
@pytest.mark.parametrize("case", list(SELECTIONS))
def test_lists_by_block_against_lists_by_page_id(case, width):
    """``lists_from`` gathers the table a block (4 entries) an index: the
    lists and lengths of the element-wise form it replaces, element for
    element, over seeded selections: fewer than ``topk`` blocks (the
    sentinel M), a slot under ``dense_len``, one that is not live, a last
    block partly filled, the table's own last block, at a ``width`` under,
    at and over ``topk`` blocks' pages."""
    n, held = SELECTIONS[case]
    P, ppb, K = 64, CFG.block_size // PS, CFG.topk
    M = P // ppb
    rng = np.random.default_rng(sum(map(ord, case + width)))
    B = 3
    ns = np.asarray([n, n, max(n - 1, 0) if n else 0], np.int32)
    blocks = np.full((B, CFG.n_kv_heads, K), M, np.int32)
    count = np.zeros((B, CFG.n_kv_heads), np.int32)
    for b in range(B):
        reached = -(-int(ns[b]) // CFG.block_size)
        for g in range(CFG.n_kv_heads):
            c = min(held, reached)
            if c:  # the query's own block is always selected, the last
                rest = rng.choice(reached - 1, c - 1, replace=False)
                blocks[b, g, :c] = np.sort(np.append(rest, reached - 1))
            count[b, g] = c
    tables = np.zeros((B, P), np.int32)
    for b in range(B):
        m = -(-int(ns[b]) // PS)
        tables[b, :m] = rng.permutation(np.arange(1, 300))[:m]
    args = (CFG, jnp.asarray(blocks), jnp.asarray(count),
            jnp.asarray(tables), jnp.asarray(ns), WIDTHS[width])
    want = _elementwise_lists_from(*args)
    got = block_sparse.lists_from(*args)
    for w, g in zip(want, got):
        assert w.shape == g.shape and w.dtype == g.dtype
        assert np.array_equal(np.asarray(w), np.asarray(g))
    if case == "fewer_than_topk_selected":
        assert (np.asarray(got[0])[:, :, held * ppb:] == 0).all()
    if case == "not_live":
        assert not np.asarray(got[1]).any()


def test_lists_by_block_want_a_table_of_whole_blocks():
    """A table that ends inside a block is refused by name, as the suffix
    prefill refuses it (the engine's tables are ``max_seq_len / page_size``
    entries, whole blocks for every configuration it serves)."""
    with pytest.raises(ValueError, match="whole number of blocks"):
        block_sparse.lists_from(
            CFG, jnp.zeros((1, 2, CFG.topk), jnp.int32),
            jnp.ones((1, 2), jnp.int32), jnp.zeros((1, 63), jnp.int32),
            jnp.asarray([300], jnp.int32), 24)


@pytest.mark.parametrize("seed", [0, 1])
def test_page_lists_over_the_slot_order_and_through_the_tables(seed):
    """``page_lists`` over each slot's rows in slot order (whatever lies
    past the rows its context completes: here noise, in the engine the last
    tenant's) against ``page_lists`` over ``pooled[li, tables]``: lists and
    lengths equal element for element."""
    rng = np.random.default_rng(seed)
    B, P, pages = 5, 64, 300
    ns = np.asarray([0, 200, 257, 390, 512], np.int32)
    pooled = jnp.asarray(rng.standard_normal(
        (pages, CFG.n_kv_heads, CFG.head_dim)), jnp.float32)
    tables = np.zeros((B, P), np.int32)
    for b in range(B):
        m = -(-int(ns[b]) // PS)
        tables[b, :m] = rng.permutation(np.arange(1, pages))[:m]
    by_slot = rng.standard_normal(
        (B, P, CFG.n_kv_heads, CFG.head_dim)).astype(np.float32)
    for b in range(B):
        j = _complete(int(ns[b]))
        by_slot[b, :j] = np.asarray(pooled)[tables[b, :j]]
    q = jnp.asarray(rng.standard_normal((B, CFG.n_heads, CFG.head_dim)),
                    jnp.float32)
    tables, ns = jnp.asarray(tables), jnp.asarray(ns)
    width = block_sparse.list_width(CFG, P)
    want = block_sparse.page_lists(CFG, q, pooled[tables], tables, ns, width)
    got = block_sparse.page_lists(CFG, q, jnp.asarray(by_slot), tables, ns,
                                  width)
    for w, g in zip(want, got):
        assert np.array_equal(np.asarray(w), np.asarray(g))
    assert np.asarray(got[1])[0].tolist() == [0, 0]
    assert (np.asarray(got[1])[3] == (CFG.topk - 1) * CFG.block_size
            + (390 - 1) % CFG.block_size + 1).all()


OTHER_FAMILIES = {
    "llama": llama.LlamaConfig, "sdar_moe": sdar_moe.SDARMoEConfig,
    "olmo_hybrid": olmo_hybrid.OlmoHybridConfig,
    "glm_moe_lite": glm_moe_lite.GLMMoELiteConfig,
    "afmoe": afmoe.AfmoeConfig}


@pytest.mark.parametrize("name", list(OTHER_FAMILIES))
def test_init_state_of_the_other_families_is_what_it_was(name):
    """Only a family that declares ``page_rows`` gets rows in slot order:
    at the sizes an engine hands over (``max_pages_per_seq`` among them)
    the five other families' state is what it was, None or the declared
    state rows [count, max_slots, *shape] key for key; this family's gains
    the twin, [sparse layers, max_slots, max_pages_per_seq, G, d]."""
    sizes = dict(num_pages=64, page_size=PS, max_slots=3,
                 max_pages_per_seq=40)
    layout = OTHER_FAMILIES[name].tiny().cache_layout()
    if layout.get("window"):
        sizes["window_pages"] = 8
    state = init_state(CacheConfig(**layout, **sizes))
    rows = layout.get("state_rows")
    assert "page_rows" not in layout
    if not rows:
        assert state is None
    else:
        assert name == "olmo_hybrid" and sorted(state) == ["S", "conv"]
        assert {k: (v.shape, v.dtype) for k, v in state.items()} == {
            k: ((count, 3, *shape), dt)
            for k, (count, shape, dt) in rows.items()}
    own = init_state(CacheConfig(**CFG.cache_layout(), **sizes))
    assert {k: v.shape for k, v in own.items()} == {
        "S": (3, 3, 4, 16, 16), "pooled_k": (2, 64, 2, 16),
        "pooled_k_by_slot": (2, 3, 40, 2, 16)}
