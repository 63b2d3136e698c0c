"""Olmo-Hybrid (models/olmo_hybrid.py): the gated delta rule's two forms
(ops/gated_delta.py), the model against the benchmark's float32 reference,
and the engine serving it: pages for the full layers, a state row a slot for
the linear ones.  Small sizes, seeded weights, the CPU; LOGITS are compared,
not tokens."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import olmo_hybrid as reference
from ray_tpu.llm import model as lm
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.paged_cache import CacheConfig, init_cache, init_state
from ray_tpu.models import llama, olmo_hybrid
from ray_tpu.ops import gated_delta

VOCAB = 512
TOL = 5e-4  # float32 against float32 "highest": 3e-5 measured, logits ~1 rms


def _cfg(**kw):
    return olmo_hybrid.OlmoHybridConfig.tiny(VOCAB, **kw)


def _file(cfg):
    """The configuration as the benchmark's reference reads it."""
    return {"num_attention_heads": cfg.n_heads, "head_dim": cfg.head_dim,
            "hidden_size": cfg.d_model, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta,
            "linear_num_value_heads": cfg.lin_heads,
            "linear_key_head_dim": cfg.lin_key_dim,
            "linear_value_head_dim": cfg.lin_value_dim,
            "linear_allow_neg_eigval": True}


@pytest.fixture(scope="module")
def params():
    return olmo_hybrid.init(_cfg(), jax.random.PRNGKey(0))


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(3, VOCAB, size=n).tolist()


def _reference_logits(cfg, params, tokens):
    return np.asarray(reference.logits(
        _file(cfg), params, jnp.asarray(tokens, jnp.int32)[None])[0])


# -- ops/gated_delta.py ------------------------------------------------------

def _draw(seed, L, H=4, dk=16, dv=32, beta_over_one=False):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(ks[0], (L, H, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (L, H, dk)))
    v = jax.random.normal(ks[2], (L, H, dv))
    g = -jnp.exp(jax.random.uniform(ks[3], (L, H), minval=jnp.log(1e-3),
                                    maxval=jnp.log(0.1)))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[4], (L, H)) * 2)
    if beta_over_one:
        beta = 1.0 + beta / 2
    return q, k, v, g, beta, jax.random.normal(ks[5], (H, dv, dk)) * 0.3


@pytest.mark.parametrize("length", [1, 63, 64, 65, 150, 300])
def test_chunked_equals_the_recurrence(length):
    """Lengths that are no multiple of the chunk, a nonzero initial state,
    beta on both sides of 1."""
    q, k, v, g, beta, S0 = _draw(length, length)
    o, S = gated_delta.recurrent(q, k, v, g, beta, S0)
    o2, S2 = jax.jit(gated_delta.chunked)(q, k, v, g, beta, S0)
    np.testing.assert_allclose(o2, o, atol=1e-5)
    np.testing.assert_allclose(S2, S, atol=1e-5)


@pytest.mark.parametrize("chunk", [16, 64])
def test_chunked_with_every_beta_over_one(chunk):
    """beta in (1, 2): the eigenvalue 1 - beta of a write is negative
    (``linear_allow_neg_eigval``); from a zero state."""
    q, k, v, g, beta, S0 = _draw(7, 200, beta_over_one=True)
    o, S = gated_delta.recurrent(q, k, v, g, beta, 0 * S0)
    o2, S2 = gated_delta.chunked(q, k, v, g, beta, 0 * S0, chunk=chunk)
    assert float(beta.min()) > 1.0
    np.testing.assert_allclose(o2, o, atol=1e-5)
    np.testing.assert_allclose(S2, S, atol=1e-5)


def test_padding_tokens_change_nothing():
    """g = 0 and beta = 0 behind a sequence: the state after them is the
    state before them (how a prefill's bucket is padded)."""
    q, k, v, g, beta, S0 = _draw(3, 100)
    real = (jnp.arange(100) < 70)[:, None]
    _, S = gated_delta.chunked(q, k, v, jnp.where(real, g, 0.0),
                               jnp.where(real, beta, 0.0), S0)
    _, S70 = gated_delta.recurrent(q[:70], k[:70], v[:70], g[:70], beta[:70],
                                   S0)
    np.testing.assert_allclose(S, S70, atol=1e-5)


def test_state_packs_and_unpacks():
    S = jax.random.normal(jax.random.PRNGKey(0), (3, 5, 4, 32, 16))
    pack = gated_delta.head_pack(4, 32)
    assert pack == 4 and gated_delta.head_pack(30, 192) == 2
    assert gated_delta.head_pack(3, 32) == 1  # the count does not allow it
    packed = gated_delta.pack_state(S, pack)
    assert packed.shape == (3, 5, 1, 16, 128)
    assert jnp.array_equal(gated_delta.unpack_state(packed, pack), S)


@pytest.mark.parametrize("live", [(True, False, True, True, False, True),
                                  (False,) * 6, (True,) * 6])
def test_decode_update_touches_live_slots_of_one_layer_only(live):
    H, dk, dv, B = 4, 16, 32, 6
    pack = gated_delta.head_pack(H, dv)
    S = jax.random.normal(jax.random.PRNGKey(7), (3, B, H, dv, dk)) * 0.3
    q, k, v, g, beta, _ = _draw(9, B)
    active = jnp.asarray(live)
    o, packed = gated_delta.decode_update(
        gated_delta.pack_state(S, pack), jnp.int32(1), q, k, v, g, beta,
        active, pack=pack)
    after = gated_delta.unpack_state(packed, pack)
    assert jnp.array_equal(after[0], S[0]) and jnp.array_equal(after[2], S[2])
    for b in range(B):
        if not live[b]:
            assert jnp.array_equal(after[1, b], S[1, b])
            assert not np.asarray(o[b]).any()
            continue
        want_o, want_S = gated_delta.recurrent(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], g[b:b + 1], beta[b:b + 1],
            S[1, b])
        np.testing.assert_allclose(o[b], want_o[0], atol=1e-6)
        np.testing.assert_allclose(after[1, b], want_S, atol=1e-6)


@pytest.mark.parametrize("H,dv,groups", [(16, 64, (2, 4, 8)),
                                         (10, 64, (5,))])
def test_every_pack_in_a_block_equals_one_pack_a_block(H, dv, groups):
    """The block the rule chooses (a slot's whole state: every head pack)
    and each smaller one give, BIT FOR BIT, what one pack a block gives,
    and that is the recurrence; dead slots between live ones keep their
    rows."""
    dk, live = 16, (False, True, False, False, True, True, False)
    B = len(live)
    pack = gated_delta.head_pack(H, dv)
    packs = H // pack
    assert packs == groups[-1] > 1
    q, k, v, g, beta, _ = _draw(H, B, H=H, dk=dk, dv=dv)
    S = jax.random.normal(jax.random.PRNGKey(dv), (2, B, H, dv, dk)) * 0.3
    packed = gated_delta.pack_state(S, pack)
    active = jnp.asarray(live)

    def update(group):
        return gated_delta._decode_update(
            packed, jnp.int32(0), q, k, v, g, beta, active, pack=pack,
            group=group, interpret=True)

    assert gated_delta._packs_a_block(packs, dk, pack * dv) == packs
    o1, S1 = update(1)
    for got_o, got_S in (gated_delta.decode_update(
            packed, jnp.int32(0), q, k, v, g, beta, active, pack=pack),
                         *(update(group) for group in groups)):
        np.testing.assert_array_equal(got_o, o1)
        np.testing.assert_array_equal(got_S, S1)
    after = gated_delta.unpack_state(S1, pack)
    np.testing.assert_array_equal(after[1], S[1])  # the other layer's rows
    for b in range(B):
        if not live[b]:
            np.testing.assert_array_equal(after[0, b], S[0, b])
            assert not np.asarray(o1[b]).any()
            continue
        want_o, want_S = gated_delta.recurrent(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], g[b:b + 1], beta[b:b + 1],
            S[0, b])
        np.testing.assert_allclose(o1[b], want_o[0], atol=1e-6)
        np.testing.assert_allclose(after[0, b], want_S, atol=1e-6)


@pytest.mark.parametrize("packs,dk,width,block", [
    (15, 96, 384, 5),    # olmo_hybrid7b_serve_1chip: 0.74 MB of 2.21 a slot
    (64, 96, 384, 4),    # 7 would fit, 4 divides
    (14, 128, 1024, 2),  # 0.5 MB a pack: 2 fit, 7 do not
    (3, 2048, 1024, 1),  # a pack past the budget alone: one a block
])
def test_a_block_is_the_most_packs_whose_buffers_fit(packs, dk, width,
                                                     block):
    assert gated_delta._packs_a_block(packs, dk, width) == block
    assert packs % block == 0
    assert (4 * block * dk * width * 4 <= gated_delta.STATE_BLOCKS_BYTES
            or block == 1)


def test_decode_update_refuses_what_it_cannot_take_by_name(monkeypatch):
    q, k, v, g, beta, _ = _draw(0, 2, H=4, dk=12, dv=32)
    with pytest.raises(ValueError, match="packed float32 state"):
        gated_delta.decode_update(jnp.zeros((1, 2, 1, 12, 128), jnp.bfloat16),
                                  0, q, k, v, g, beta, jnp.ones(2, bool),
                                  pack=4)
    with pytest.raises(ValueError, match="do not hold 2 slots of 4 heads"):
        gated_delta.decode_update(jnp.zeros((1, 2, 2, 12, 64)), 0, q, k, v,
                                  g, beta, jnp.ones(2, bool), pack=4)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="whole tiles"):
        gated_delta.decode_update(jnp.zeros((1, 2, 1, 12, 128)), 0, q, k, v,
                                  g, beta, jnp.ones(2, bool), pack=4)


# -- the model ---------------------------------------------------------------

@pytest.mark.parametrize("layout", ["init", "serving"])
def test_forward_logits_equal_the_reference(params, layout):
    cfg = _cfg()
    tree = lm.serving_layout(params) if layout == "serving" else params
    tokens = jnp.asarray([_tokens(150, 1), _tokens(150, 2)], jnp.int32)
    got = jax.jit(olmo_hybrid.apply, static_argnames="cfg")(tree, tokens, cfg)
    want = reference.logits(_file(cfg), params, tokens)
    np.testing.assert_allclose(got, want, atol=TOL)


def test_serving_layout_stacks_what_shares_an_input(params):
    cfg = _cfg()
    tree = lm.serving_layout(params)
    mix, attn = tree["layers"]["lin"]["mix"], tree["layers"]["full"]["attn"]
    # b and a, four heads each, ride in a lane tile of their own
    width = 2 * cfg.lin_heads * (cfg.lin_key_dim + cfg.lin_value_dim) + 256
    assert mix["w_in"].shape == (6, cfg.d_model, width)
    assert not {"wq", "wk", "wv", "wg", "wb", "wa"} & set(mix)
    assert attn["wqkv"].shape == (2, cfg.d_model, 3 * 6 * 16)
    assert lm.serving_layout(tree) is tree
    # a Llama tree still goes where it went
    dense = llama.init(llama.LlamaConfig.tiny(), jax.random.PRNGKey(0))
    assert "wqkv" in lm.serving_layout(dense)["layers"]["attn"]


def test_layers_must_be_whole_periods():
    with pytest.raises(ValueError, match="not whole periods of 4"):
        _cfg(n_layers=6)


def test_a_model_declares_what_it_caches():
    """Pools over the full layers only, at a head count the paged kernel's
    tiles take, and state rows for the linear layers; a model that says
    nothing caches one K/V pool a layer."""
    hybrid = lm.cache_layout(olmo_hybrid.OlmoHybridConfig(n_layers=16))
    rows = hybrid.pop("state_rows")
    assert hybrid == {"n_layers": 4, "n_kv_heads": 32, "head_dim": 128,
                      "state_layers": 12,
                      "scan_chunk": gated_delta.CHUNK}  # what scan_chunks counts
    assert rows["S"] == (12, (15, 96, 384), jnp.float32)
    assert rows["conv"] == (36, (11520,), jnp.dtype("bfloat16"))
    dense = llama.LlamaConfig.tiny()
    assert lm.cache_layout(dense) == {"n_layers": 2, "n_kv_heads": 2,
                                      "head_dim": 32}
    assert init_state(CacheConfig(**lm.cache_layout(dense))) is None


@pytest.mark.parametrize("n", [70, 150])
def test_prefill_and_decode_programs_equal_the_reference(params, n):
    """``prefill`` into a slot's row, then ``decode_step`` through pages and
    state, against the reference's one full forward pass."""
    cfg, steps, ps, slots, slot = _cfg(), 12, 16, 4, 2
    tokens = _tokens(n + steps, seed=n)
    want = _reference_logits(cfg, params, tokens)
    tree = lm.serving_layout(params)
    cc = CacheConfig(**lm.cache_layout(cfg), num_pages=32, page_size=ps,
                     dtype="float32", max_slots=slots)
    (ck, cv), st = init_cache(cc), init_state(cc)
    # whatever the last tenant left: the prefill begins the row anew
    st = jax.tree.map(lambda x: x + 1, st)
    bucket, pages = 256, list(range(1, 13))
    padded = np.zeros(bucket, np.int32)
    padded[:n] = tokens[:n]
    rows = np.array([pages[i // ps] if i // ps < len(pages) else 0
                     for i in range(bucket)], np.int32)
    lg, counted, ck, cv, st = lm.prefill(
        tree, jnp.asarray(padded), ck, cv, jnp.asarray(rows), jnp.int32(n),
        jnp.asarray(np.arange(bucket) % ps), cfg, st, jnp.int32(slot))
    np.testing.assert_allclose(lg, want[n - 1], atol=TOL)
    assert counted == {}  # a dense walk counts nothing
    assert float(jnp.abs(st["S"][:, 0] - 1).max()) == 0  # not its row
    tables = np.zeros((slots, 16), np.int32)
    tables[slot, :len(pages)] = pages
    active = np.arange(slots) == slot
    for j in range(steps):
        tok = np.zeros(slots, np.int32)
        tok[slot] = tokens[n + j]
        lg, _, ck, cv, st = lm.decode_step(
            tree, jnp.asarray(tok), ck, cv, jnp.asarray(tables),
            jnp.asarray(np.where(active, n + j, 0).astype(np.int32)),
            jnp.asarray(active), cfg, st)
        np.testing.assert_allclose(lg[slot], want[n + j], atol=TOL)


def test_prefill_with_prefix_refuses_recurrent_layers(params):
    cfg = _cfg()
    cc = CacheConfig(**lm.cache_layout(cfg), num_pages=8, dtype="float32")
    ck, cv = init_cache(cc)
    i32 = lambda *s: jnp.zeros(s, jnp.int32)  # noqa: E731
    with pytest.raises(ValueError, match="no model with recurrent layers"):
        lm.prefill_with_prefix(lm.serving_layout(params), i32(16), ck, cv,
                               i32(16), jnp.int32(4), i32(16), i32(4),
                               i32(16), cfg)


# -- the engine --------------------------------------------------------------

def _engine(params, cfg, **kw):
    engine = LLMEngine(params, cfg, EngineConfig(**{**dict(
        max_slots=4, num_pages=64, page_size=16, max_seq_len=512,
        prefill_buckets=(64, 128, 256, 512)), **kw}))
    engine.start()
    return engine


def _drain(req):
    out = []
    while True:
        item = req.out_queue.get(timeout=300)
        if item is None:
            return out
        if isinstance(item, Exception):
            raise item
        out.append(item)


class _Logits:
    """The logits the engine samples from, a request's in order: sampled
    requests go through ``decode_step`` and ``_sample_one`` on the host,
    which is made to keep what it is handed and choose greedily."""

    def __init__(self, engine):
        self.by_request = {}
        engine._sample_one = self._sample

    def _sample(self, logits, params, rng):
        self.by_request.setdefault(params.seed, []).append(np.array(logits))
        return int(np.argmax(logits))

    @staticmethod
    def params(tag, max_tokens):
        return SamplingParams(max_tokens=max_tokens, temperature=1.0,
                              seed=tag)


def _assert_follows_the_reference(cfg, params, prompt, out, logits):
    """Every logit row the engine sampled from is the reference's at that
    position of the engine's own sequence."""
    want = _reference_logits(cfg, params, prompt + out)
    assert len(logits) == len(out)
    for j, row in enumerate(logits):
        np.testing.assert_allclose(row, want[len(prompt) - 1 + j], atol=TOL)


def test_engine_logits_equal_the_references_full_forward(params):
    """A prompt longer than two scan chunks, then decoding through pages
    and state."""
    cfg, prompt = _cfg(), _tokens(150)
    engine = _engine(params, cfg)
    seen = _Logits(engine)
    try:
        out = _drain(engine.submit(prompt, seen.params(1, 20)))
        st = engine.stats()
    finally:
        engine.stop()
    _assert_follows_the_reference(cfg, params, prompt, out,
                                  seen.by_request[1])
    assert st["state_resets"] == 1 and st["state_slot_steps"] == 19
    assert st["scan_chunks"] == (256 // 64) * 6  # the bucket's, six layers
    assert st["prefill_tokens_saved"] == 0 and st["prefix_cache"] is None


def test_neighbouring_slots_keep_their_own_state(params):
    """Two sequences admitted at different times, into slots 0 and 1."""
    cfg, first, second = _cfg(), _tokens(90, 3), _tokens(130, 4)
    engine = _engine(params, cfg)
    seen = _Logits(engine)
    try:
        a = engine.submit(first, seen.params(1, 40))
        head = a.out_queue.get(timeout=300)  # a is decoding by now
        b = engine.submit(second, seen.params(2, 24))
        out_b = _drain(b)
        out_a = [head] + _drain(a)
        st = engine.stats()
    finally:
        engine.stop()
    # they decoded side by side: fewer steps than one after the other
    assert st["state_slot_steps"] == 39 + 23 > st["decode_steps"]
    _assert_follows_the_reference(cfg, params, first, out_a,
                                  seen.by_request[1])
    _assert_follows_the_reference(cfg, params, second, out_b,
                                  seen.by_request[2])


def test_a_released_slots_row_begins_anew_for_the_next(params):
    """One slot: the second sequence takes the row the first one left."""
    cfg, first, second = _cfg(), _tokens(100, 5), _tokens(80, 6)
    engine = _engine(params, cfg, max_slots=1)
    seen = _Logits(engine)
    try:
        _drain(engine.submit(first, seen.params(1, 12)))
        left = np.asarray(engine.state["S"][:, 0])
        out = _drain(engine.submit(second, seen.params(2, 12)))
        assert engine.stats()["state_resets"] == 2
    finally:
        engine.stop()
    assert np.abs(left).max() > 0  # the first tenant did leave a state
    _assert_follows_the_reference(cfg, params, second, out,
                                  seen.by_request[2])


def test_a_preempted_sequence_resumes_to_the_same_logits(params,
                                                         monkeypatch):
    """A pool too small for three: one is evicted mid-decode, its pages
    freed, no prefix registered, and its resume prefill recomputes prompt
    and answer from position 0 into a fresh row."""
    monkeypatch.setenv("RTPU_DEBUG_ALLOCATOR", "1")
    cfg = _cfg()
    prompts = [_tokens(n, 10 + n) for n in (40, 50, 70)]
    engine = _engine(params, cfg, num_pages=14, max_seq_len=256,
                     prefill_buckets=(64, 128, 256))
    seen = _Logits(engine)
    try:
        reqs = [engine.submit(p, seen.params(i, 60))
                for i, p in enumerate(prompts)]
        outs = [_drain(r) for r in reqs]
        st = engine.stats()
    finally:
        engine.stop()
    assert st["preempted"] > 0 and st["prefill_tokens_saved"] == 0
    assert st["state_resets"] == 3 + st["preempted"]
    for i, (prompt, out) in enumerate(zip(prompts, outs)):
        assert len(out) == 60
        want = _reference_logits(cfg, params, prompt + out)
        # a resume prefill samples the token after prompt + answer so far:
        # every sampled row, resumed or not, is the reference's at the
        # position of the token it chose
        rows = seen.by_request[i]
        assert len(rows) == 60
        for j, row in enumerate(rows):
            np.testing.assert_allclose(row, want[len(prompt) - 1 + j],
                                       atol=TOL)


def test_a_prefix_hit_is_not_taken(params):
    """The same prompt twice: no index of pages is built, so the second is
    computed whole, and answers as the first did."""
    cfg, prompt = _cfg(), _tokens(100, 8)
    engine = _engine(params, cfg)
    try:
        assert engine.prefix_cache is None
        first = engine.generate(prompt, SamplingParams(max_tokens=8))
        again = engine.generate(prompt, SamplingParams(max_tokens=8))
        st = engine.stats()
    finally:
        engine.stop()
    assert first == again
    assert st["prefill_tokens_saved"] == 0 and st["resident_pages"] == 0
    assert st["prefills"] == 2 and st["state_resets"] == 2
