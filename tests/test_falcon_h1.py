"""Falcon-H1 (models/falcon_h1.py): a Mamba-2 mixer beside grouped-query
attention in every block, state rows and pages in ONE layer.  The
recurrence is ops/lightning.py's, made general (d_k unequal to d_v, keys and
queries a group of heads, the decay's log a token a head); the model against
the benchmark's float32 reference, cacheless and served (prefill, then
decode through pages AND rows); the engine serving it; the benchmark's own
comparison and every fault it plants.  Small sizes, seeded weights, the CPU;
LOGITS are compared, not tokens."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import in_worker_parallel_ssm
from benchmarks.families import falcon_h1 as family
from benchmarks.reference import falcon_h1 as reference
from ray_tpu.llm import model as lm
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.paged_cache import CacheConfig, init_cache, init_state
from ray_tpu.models import falcon_h1, minicpm_sala
from ray_tpu.ops import lightning

VOCAB = 512
TOL = 5e-4  # float32 against float32 "highest": 3e-6 measured, logits ~1 rms
_HI = jax.lax.Precision.HIGHEST


def _cfg(**kw):
    return falcon_h1.FalconH1Config.tiny(VOCAB, **kw)


def _file(cfg):
    """The configuration as the benchmark's family and reference read it:
    the published keys."""
    return {"family": "falcon_h1", "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "intermediate_size": cfg.d_ff, "mamba_n_heads": cfg.ssm_heads,
            "mamba_d_head": cfg.ssm_head_dim, "mamba_d_state": cfg.ssm_state,
            "mamba_n_groups": cfg.ssm_groups, "mamba_d_conv": cfg.conv_width,
            "mamba_d_ssm": cfg.d_ssm,
            "embedding_multiplier": cfg.embedding_multiplier,
            "lm_head_multiplier": cfg.lm_head_multiplier,
            "key_multiplier": cfg.key_multiplier,
            "attention_in_multiplier": cfg.attention_in_multiplier,
            "attention_out_multiplier": cfg.attention_out_multiplier,
            "ssm_in_multiplier": cfg.ssm_in_multiplier,
            "ssm_out_multiplier": cfg.ssm_out_multiplier,
            "ssm_multipliers": list(cfg.ssm_multipliers),
            "mlp_multipliers": list(cfg.mlp_multipliers),
            "max_position_embeddings": cfg.max_seq_len,
            "rope_theta": cfg.rope_theta, "rms_norm_eps": cfg.norm_eps,
            "dtype": cfg.dtype}


@pytest.fixture(scope="module")
def params():
    return falcon_h1.init(_cfg(), jax.random.PRNGKey(0))


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(3, VOCAB, size=n).tolist()


def _reference_logits(cfg, params, tokens):
    return np.asarray(reference.logits(
        _file(cfg), params, jnp.asarray(tokens, jnp.int32)[None])[0])


# -- ops/lightning.py --------------------------------------------------------

def _draw(seed, L, H=8, G=2, dk=32, dv=16):
    """Mamba-2's case: keys and queries a group, d_k unequal to d_v, the
    decay's log a token a head, head 0 decaying by exp(-1.6) a token (A 16
    at dt 0.1: a chunk's running product underflows float32)."""
    r = np.random.default_rng(seed)
    q, k = (jnp.asarray(r.standard_normal((L, G, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(r.standard_normal((L, H, dv)), jnp.float32)
    g = -jnp.asarray(r.uniform(1e-3, 0.2, (L, H)), jnp.float32)
    g = g.at[:, 0].set(-1.6)
    S0 = jnp.asarray(r.standard_normal((H, dk, dv)), jnp.float32)
    return q, k, v, g, S0


def _by_head(q, k, v, g, S0):
    """The definition with every head handed its group's key and query."""
    R = v.shape[1] // q.shape[1]
    q, k = jnp.repeat(q, R, axis=1), jnp.repeat(k, R, axis=1)

    def step(S, x):
        q, k, v, g = x
        S = S * jnp.exp(g)[:, None, None] + k[:, :, None] * v[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q, S, precision=_HI)

    S, o = jax.lax.scan(step, S0, (q, k, v, g))
    return o, S


@pytest.mark.parametrize("length", [1, 63, 64, 65, 150])
def test_chunked_equals_the_recurrence_with_grouped_keys(length):
    q, k, v, g, S0 = _draw(length, length)
    want_o, want_S = _by_head(q, k, v, g, S0)
    for form in (lightning.recurrent, lightning.chunked):
        o, S = form(q, k, v, g, S0)
        assert o.shape == v.shape and S.shape == S0.shape
        np.testing.assert_allclose(o, want_o, atol=2e-4, rtol=1e-5)
        np.testing.assert_allclose(S, want_S, atol=2e-5, rtol=1e-5)
    assert bool(jnp.all(jnp.isfinite(o)))  # the strongly decaying head too


def test_a_padded_tail_changes_nothing():
    """g = 0 and a zero key: the state stays, whatever v and q hold."""
    q, k, v, g, S0 = _draw(3, 100)
    real = (jnp.arange(100) < 70)[:, None]
    _, S = lightning.chunked(q, jnp.where(real[..., None], k, 0), v,
                             jnp.where(real, g, 0.0), S0)
    _, want = lightning.chunked(q[:70], k[:70], v[:70], g[:70], S0)
    np.testing.assert_allclose(S, want, atol=1e-6)


@pytest.mark.parametrize("shape", [(8, 2, 32, 16), (4, 4, 16, 16),
                                   (6, 1, 8, 16)])
def test_decode_update_equals_the_recurrence(shape):
    """One token a slot in place: live slots of one layer only, the heads
    of a group reading one key."""
    H, G, dk, dv = shape
    q, k, v, g, S0 = _draw(sum(shape), 12, H, G, dk, dv)
    want_o, want_S = _by_head(q, k, v, g, S0)
    B = 5
    live = jnp.asarray([True, False, True, True, False])
    state = jnp.ones((3, B, H, dk, dv), jnp.float32).at[1].set(S0)
    for t in range(12):
        at = lambda x: jnp.broadcast_to(x[t], (B, *x.shape[1:]))  # noqa: E731
        o, state = lightning.decode_update(
            state, jnp.int32(1), at(q), at(k), at(v), at(g), live)
        np.testing.assert_allclose(o[0], want_o[t], atol=2e-4, rtol=1e-5)
        assert float(jnp.abs(o[1]).max()) == 0
    np.testing.assert_allclose(state[1, 3], want_S, atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(state[1, 1], S0)  # not live: not written
    assert float(jnp.abs(state[0] - 1).max()) == 0  # another layer's rows


_LIVE = (False, True, False, True, True, False)  # dead slots between live


@pytest.mark.parametrize("shape,groups", [
    # heads of one key, every head of two keys, the whole slot
    ((8, 4, 16, 16), (2, 4, 8)),
    ((12, 2, 8, 16), (3, 6, 12)),
    ((8, 8, 16, 16), (4, 8)),  # a head its own key
])
def test_a_block_of_several_keys_equals_one_head_a_block(shape, groups):
    """The block the rule chooses (here a slot's whole state: every key)
    and each smaller one give, BIT FOR BIT, what one head a block gives,
    and that is the recurrence; a slot that is not live is not written."""
    H, G, dk, dv = shape
    B = len(_LIVE)
    q, k, v, g, _ = _draw(sum(shape), B, H, G, dk, dv)
    live = jnp.asarray(_LIVE)
    S = jnp.asarray(np.random.default_rng(H).standard_normal(
        (2, B, H, dk, dv)), jnp.float32)

    def update(group):
        return lightning._decode_update(S, jnp.int32(1), q, k, v, g, live,
                                        group=group, interpret=True)

    assert lightning._heads_a_block(H, G, dk, dv) == H
    o1, S1 = update(1)
    for got_o, got_S in (lightning.decode_update(S, jnp.int32(1), q, k, v, g,
                                                 live),
                         *(update(group) for group in groups)):
        np.testing.assert_array_equal(got_o, o1)
        np.testing.assert_array_equal(got_S, S1)
    np.testing.assert_array_equal(S1[0], S[0])  # another layer's rows
    for b in range(B):
        if not _LIVE[b]:
            np.testing.assert_array_equal(S1[1, b], S[1, b])
            assert not np.asarray(o1[b]).any()
            continue
        want_o, want_S = lightning.recurrent(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], g[b:b + 1], S[1, b])
        np.testing.assert_allclose(o1[b], want_o[0], atol=2e-4, rtol=1e-5)
        np.testing.assert_allclose(S1[1, b], want_S, atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("shape,heads", [
    ((32, 2, 256, 128), 32),    # falcon_h1_34b_serve_1chip: 4.19 MB a slot
    ((32, 32, 128, 128), 32),   # minicpm_sala_serve_1chip: 2.1 MB
    ((128, 8, 128, 128), 64),   # 8.4 MB a slot: four of the eight keys
    ((64, 2, 256, 128), 32),    # one key's heads, 4.19 MB of 8.4
    ((96, 2, 256, 128), 24),    # 48 heads a key: half a key's, no 32
    ((2, 1, 4096, 1024), 1),    # a head past the budget alone: one a block
])
def test_a_block_is_the_most_heads_whose_buffers_fit(shape, heads):
    """From the state's shape alone: whole keys or heads of one key, a
    divisor of the heads, four buffers of it inside the budget."""
    assert lightning._heads_a_block(*shape) == heads
    H, G, dk, dv = shape
    assert H % heads == 0 and (heads % (H // G) == 0 or (H // G) % heads == 0)
    assert (4 * heads * dk * dv * 4 <= lightning.STATE_BLOCKS_BYTES
            or heads == 1)


def test_decode_update_refuses_heads_that_share_no_key():
    q, k, v, g, S0 = _draw(0, 2, H=6, G=4)
    with pytest.raises(ValueError, match="do not hold"):
        lightning.decode_update(S0[None, None], 0, q[:1], k[:1], v[:1],
                                g[:1], jnp.ones(1, bool))
    with pytest.raises(ValueError, match="no whole number of heads"):
        lightning.chunked(q, k, v, g, S0)


# The parent's forms (ops/lightning.py before it took groups), frozen here:
# a fixed decay with a head its own key must come out BIT FOR BIT the same.

def _parent_recurrent(q, k, v, g, S0):
    f32 = jnp.float32

    def step(S, x):
        q, k, v, g = x
        S = S * jnp.exp(g)[:, None, None] + k[:, :, None] * v[:, None, :]
        return S, jnp.einsum("hk,hkv->hv", q, S, precision=_HI)

    S, o = jax.lax.scan(step, S0.astype(f32),
                        tuple(x.astype(f32) for x in (q, k, v, g)))
    return o, S


def _parent_chunked(q, k, v, g, S0, chunk=lightning.CHUNK):
    f32 = jnp.float32
    L, H, _ = q.shape
    n = -(-L // chunk)
    pad = n * chunk - L

    def chunks(x):
        x = jnp.pad(x.astype(f32), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
        return jnp.moveaxis(x.reshape(n, chunk, *x.shape[1:]), 1, 2)

    q, k, v, g = (chunks(x) for x in (q, k, v, g))
    gam = jnp.cumsum(g, axis=-1)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    diff = gam[..., :, None] - gam[..., None, :]
    ratio = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    qk = jnp.einsum("nhtd,nhid->nhti", q, k, precision=_HI) * ratio
    inside = jnp.einsum("nhti,nhiv->nhtv", qk, v, precision=_HI)
    q_in = q * jnp.exp(gam)[..., None]
    k_out = k * jnp.exp(gam[..., -1:] - gam)[..., None]
    wrote = jnp.einsum("nhtk,nhtv->nhkv", k_out, v, precision=_HI)
    decay = jnp.exp(gam[..., -1])

    def step(S, x):
        q_in, inside, wrote, decay = x
        o = inside + jnp.einsum("htk,hkv->htv", q_in, S, precision=_HI)
        return S * decay[:, None, None] + wrote, o

    S, o = jax.lax.scan(step, S0.astype(f32), (q_in, inside, wrote, decay))
    return jnp.moveaxis(o, 1, 2).reshape(n * chunk, H, -1)[:L], S


@pytest.mark.parametrize("form", ["recurrent", "chunked_plain"])
def test_the_fixed_decay_case_is_bit_for_bit_the_parents(form):
    cfg = minicpm_sala.MiniCPMSALAConfig.tiny()
    H, d, L = cfg.lightning_heads, cfg.lightning_head_dim, 150
    r = np.random.default_rng(7)
    q, k, v = (jnp.asarray(r.standard_normal((L, H, d)), jnp.float32)
               for _ in range(3))
    g = jnp.broadcast_to(lightning.log_decays(H), (L, H))
    S0 = jnp.asarray(r.standard_normal((H, d, d)), jnp.float32)
    parent = {"recurrent": _parent_recurrent,
              "chunked_plain": _parent_chunked}
    for got, want in zip(getattr(lightning, form)(q, k, v, g, S0),
                         parent[form](q, k, v, g, S0)):
        np.testing.assert_array_equal(got, want)


# -- the model against the reference ------------------------------------------

@pytest.mark.parametrize("layout", ["init", "serving"])
def test_forward_logits_equal_the_reference(params, layout):
    cfg, tokens = _cfg(), _tokens(150, 2)
    tree = params if layout == "init" else cfg.serving_layout(params)
    got = falcon_h1.apply(tree, jnp.asarray(tokens, jnp.int32)[None], cfg)[0]
    want = _reference_logits(cfg, params, tokens)
    assert 0.5 < want.std() < 3  # the multipliers mute no head
    np.testing.assert_allclose(got, want, atol=TOL)


def test_the_seeded_weights_mute_no_branch(params):
    """What follows a multiplier is of order one: a head's scores, both
    branches as they enter the stream (within a factor of 3)."""
    cfg, tokens = _cfg(), _tokens(120, 9)
    out = reference.forward(_file(cfg), params, tokens, [119], 120, 128)
    for li in range(cfg.n_layers):
        mixed, attended = (float(out[k][li])
                           for k in ("mixer_rms", "attention_rms"))
        assert 1 / 3 < mixed / attended < 3 and 0.2 < mixed < 3
        assert 0.5 < float(out["score_std"][li]) < 2


def test_a_model_declares_what_it_caches():
    cfg = falcon_h1.FalconH1Config()  # as published
    lay = cfg.cache_layout()
    assert lay["n_layers"] == lay["state_layers"] == 72  # one layer, both
    assert (lay["n_kv_heads"], lay["head_dim"]) == (4, 128)
    count, shape, dtype = lay["state_rows"]["S"]
    assert (count, shape, dtype) == (72, (32, 256, 128), jnp.float32)
    count, shape, dtype = lay["state_rows"]["conv"]
    assert (count, shape) == (72 * 3, (5120,)) and dtype == jnp.bfloat16
    assert set(cfg.refuses) == {"pd", "kv_tier", "prefix_cache"}
    assert (cfg.state_part, falcon_h1.CONV_PART) == ("ssm/state", "ssm/conv")
    with pytest.raises(ValueError, match="groups"):
        falcon_h1.FalconH1Config(ssm_heads=5)


def test_the_familys_arithmetic():
    """The issue's table: 430.12 M a layer; 4,194,304 + 30,720 B of rows a
    slot a layer; what the configuration file says it holds."""
    from benchmarks import common

    c = common.load_json("configs", "falcon_h1_34b_serve_1chip.json")
    assert family.attention_params(c) == 31_457_280
    assert family.mixer_matmul_params(c) == 47_349_760 + 20_971_520
    assert family.mlp_params(c) == 330_301_440
    assert family.layer_params(c) == 430_120_032
    assert family.state_bytes_per_layer(c) == 4_194_304
    assert family.tail_bytes_per_layer(c) == 30_720
    assert family.state_update_bytes(c, 50) == 2 * 50 * 6 * 4_194_304
    held = c["resident_bytes"]
    eng = c["engine"]
    assert held["weights"] == family.weight_bytes(c)
    assert held["state_rows"] == eng["max_slots"] * family.state_bytes_per_slot(c)
    assert held["page_pools"] == (eng["num_pages"] * eng["page_size"]
                                  * family.kv_bytes_per_token(c))
    assert 12.5e9 < sum(held.values()) < 14.4e9
    cfg = family.model_config(c)
    assert cfg == falcon_h1.FalconH1Config(n_layers=6)  # all else published
    lay = cfg.cache_layout()
    rows = sum(n * int(np.prod(shape)) * jnp.dtype(dt).itemsize
               for n, shape, dt in lay["state_rows"].values())
    assert rows == family.state_bytes_per_slot(c)


def _pools(cfg, slots=4, pages=32, ps=16):
    cc = CacheConfig(**lm.cache_layout(cfg), num_pages=pages, page_size=ps,
                     dtype="float32", max_slots=slots)
    return init_cache(cc), init_state(cc)


@pytest.mark.parametrize("n", [70, 150])
def test_prefill_and_decode_programs_equal_the_reference(params, n):
    """``prefill`` into a slot's rows AND its pages, then ``decode_step``
    through both, against the reference's one full forward pass."""
    cfg, steps, ps, slots, slot = _cfg(), 10, 16, 4, 2
    tokens = _tokens(n + steps, seed=n)
    want = _reference_logits(cfg, params, tokens)
    tree = cfg.serving_layout(params)
    (ck, cv), st = _pools(cfg, slots)
    # whatever the last tenant left: the prefill begins the rows anew
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
    assert counted == {}
    for name in ("S", "conv"):  # not its rows
        assert float(jnp.abs(st[name][:, 0] - 1).max()) == 0
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
    # the rows the programs left are the reference's
    ref = reference.forward(_file(cfg), tree, tokens, [0], n + steps, 256)
    np.testing.assert_allclose(st["S"][:, slot], ref["S"], atol=1e-4)
    np.testing.assert_allclose(
        st["conv"][:, slot].reshape(cfg.n_layers, 3, -1), ref["conv"],
        atol=1e-4)


def test_a_later_chunk_goes_on_from_the_slots_rows(params):
    """``prefill_with_prefix``: the state AND the convolution's tail that
    the first chunk left are the second one's."""
    cfg, ps, n, cut = _cfg(), 16, 100, 48
    tokens = _tokens(n, 11)
    want = _reference_logits(cfg, params, tokens)
    tree = cfg.serving_layout(params)
    (ck, cv), st = _pools(cfg)
    pages = np.arange(1, 17, dtype=np.int32)
    pos = np.arange(cut)
    _, _, ck, cv, st = lm.prefill(
        tree, jnp.asarray(tokens[:cut], jnp.int32), ck, cv,
        jnp.asarray(pages[pos // ps]), jnp.int32(cut), jnp.asarray(pos % ps),
        cfg, st, jnp.int32(1))
    pos = cut + np.arange(64)
    padded = np.zeros(64, np.int32)
    padded[:n - cut] = tokens[cut:]
    lg, _, ck, cv, st = lm.prefill_with_prefix(
        tree, jnp.asarray(padded), ck, cv, jnp.asarray(pages[pos // ps]),
        jnp.int32(n - cut), jnp.asarray(pos % ps), jnp.asarray(pages),
        jnp.asarray(pos), cfg, st, jnp.int32(1))
    np.testing.assert_allclose(lg, want[n - 1], atol=TOL)


# -- the engine ----------------------------------------------------------------

def _engine(params, cfg, **kw):
    engine = LLMEngine(params, cfg, EngineConfig(**{**dict(
        max_slots=4, num_pages=64, page_size=16, max_seq_len=512,
        prefill_buckets=(64, 128, 256)), **kw}))
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
    want = _reference_logits(cfg, params, prompt + out)
    assert len(logits) == len(out)
    for j, row in enumerate(logits):
        np.testing.assert_allclose(row, want[len(prompt) - 1 + j], atol=TOL)


def test_slots_join_leave_and_are_reused(params):
    """Two sequences admitted at different times decode side by side; a
    third takes the slot the first one left, its rows begun anew; every
    logit row is the reference's.  The state counters count it."""
    cfg = _cfg()
    first, second, third = _tokens(90, 3), _tokens(130, 4), _tokens(70, 5)
    engine = _engine(params, cfg, max_slots=2)
    seen = _Logits(engine)
    try:
        a = engine.submit(first, seen.params(1, 16))
        head = a.out_queue.get(timeout=300)  # a is decoding by now
        b = engine.submit(second, seen.params(2, 40))
        out_a = [head] + _drain(a)
        c = engine.submit(third, seen.params(3, 12))  # into a's slot
        out_c, out_b = _drain(c), _drain(b)
        st = engine.stats()
    finally:
        engine.stop()
    assert st["state_resets"] == 3
    assert st["state_slot_steps"] == 15 + 39 + 11 > st["decode_steps"]
    # (a prefill's scan runs the bucket's chunks in every layer: the steps
    # the kernel's grid takes along the sequence, ``lightning.CHUNK`` each)
    assert lightning.CHUNK == 128
    assert st["scan_chunks"] == (128 // 128 + 256 // 128 + 128 // 128) * 3
    assert st["prefix_cache"] is None and st["prefill_tokens_saved"] == 0
    for tag, (prompt, out) in enumerate(
            [(first, out_a), (second, out_b), (third, out_c)], 1):
        _assert_follows_the_reference(cfg, params, prompt, out,
                                      seen.by_request[tag])


def test_a_prompt_in_chunks_carries_state_and_tail(params):
    """A prompt over the largest bucket: the engine computes it in chunks,
    and the logits that follow are the reference's."""
    cfg, prompt = _cfg(), _tokens(150, 6)
    engine = _engine(params, cfg, prefill_buckets=(64,))
    seen = _Logits(engine)
    try:
        out = _drain(engine.submit(prompt, seen.params(1, 6)))
        st = engine.stats()
    finally:
        engine.stop()
    assert st["prefill_chunks"] == 3 and st["state_resets"] == 1
    _assert_follows_the_reference(cfg, params, prompt, out,
                                  seen.by_request[1])


def test_chunks_keep_their_rows_through_other_slots_steps(params):
    """A prompt over the largest bucket is computed in chunks, one a loop
    iteration, WHILE another request decodes: the steps between two chunks
    leave the chunked slot's state and convolution rows alone (a slot that
    takes no step keeps them), so its logits and the rows it leaves are the
    reference's; and they stay so once it has left and the other decodes on."""
    cfg, c = _cfg(), _file(_cfg())
    short, long = _tokens(50, 7), _tokens(150, 8)
    engine = _engine(params, cfg, max_slots=2, prefill_buckets=(64,))
    seen = _Logits(engine)
    try:
        a = engine.submit(short, seen.params(1, 60))
        head = a.out_queue.get(timeout=300)  # a decodes (slot 0) by now
        b = engine.submit(long, seen.params(2, 6))  # three chunks, slot 1
        out_b = _drain(b)
        assert a.produced < 60  # a was decoding throughout
        out_a = [head] + _drain(a)
        st = engine.stats()
        rows = jax.tree.map(np.asarray, family.engine_state(engine, 1))
    finally:
        engine.stop()
    assert st["prefill_chunks"] == 3 and st["state_resets"] == 2
    for tag, (prompt, out) in enumerate([(short, out_a), (long, out_b)], 1):
        _assert_follows_the_reference(cfg, params, prompt, out,
                                      seen.by_request[tag])
    seq = long + out_b[:-1]  # what slot 1's rows have taken
    ref = reference.forward(c, params, seq, [len(seq) - 1], len(seq), 160)
    np.testing.assert_allclose(rows["conv"], ref["conv"], atol=1e-5)
    np.testing.assert_allclose(rows["S"], ref["S"], atol=1e-4)


def test_the_chips_chunked_prompt_check_at_a_tiny_size():
    """``benchmarks/chunked_prompt_check.py`` (run on the chip at the cell's
    sizes): greedy requests, so BURSTS of eight steps run between the
    chunks, and its own weights and engine."""
    from benchmarks import chunked_prompt_check
    from benchmarks.runners import serve_parallel_ssm

    c = {**_file(_cfg()), "engine": dict(
        max_slots=2, num_pages=64, page_size=16, max_seq_len=512,
        prefill_buckets=[64])}
    got = chunked_prompt_check.check(c, 5, serve_parallel_ssm.CHECK,
                                     short=(50, 60), long=(150, 9),
                                     pad_to=160)
    assert got["ok"], got
    assert got["prefill_chunks"] == 3 and got["answer"] == 9
    assert got["state"] < 1e-4 and got["tail"] < 1e-4


# -- the benchmark's comparison, and the faults it plants ------------------------

def _check(params, cfg, fault=None):
    """``in_worker_parallel_ssm.served_check`` over an engine in this
    process, with ``fault`` planted through the benchmark's own ``plant``."""
    c = _file(cfg)
    overrides, undo = family.plant(fault, piece=64) if fault else ({}, None)
    jax.clear_caches()  # the programs are compiled anew, planted
    engine = None
    try:
        engine = _engine(params, family.model_config(c, **overrides),
                         max_slots=4)
        prompts = [_tokens(n, 20 + n) for n in (70, 100, 130)]
        steps = 12
        outputs = [engine.generate(p, SamplingParams(max_tokens=steps))
                   for p in prompts]
        # (the engine is idle: its answers are in)
        got = in_worker_parallel_ssm.served_check(
            c, engine.params, engine, family, reference,
            {"prompts": prompts, "outputs": outputs, "steps": steps,
             "pad_to": 160, "margin": 0.1})
        # (d): seven sequences through the four slots at once, slots taken
        # again as they come free; four of them judged, 8 positions each
        log, t0 = in_worker_parallel_ssm.note_finished(engine), time.time()
        for r in [engine.submit(_tokens(40 + 9 * i, 40 + i),
                                SamplingParams(max_tokens=10 + 3 * i))
                  for i in range(7)]:
            _drain(r)
        return {**got, **in_worker_parallel_ssm.window_check(
            c, engine.params, reference,
            {"t0_wall": t0, "seconds": time.time() + 1.0 - t0, "requests": 4,
             "positions": 8, "pad_to": 160, "margin": 0.1}, log)}
    finally:
        if engine is not None:
            engine.stop()
        if undo:
            undo()
            jax.clear_caches()


@pytest.fixture(scope="module")
def clean(params):
    return _check(params, _cfg())


def test_the_benchmarks_comparison_passes_the_served_path(clean):
    assert clean["logit_rms_error"] < 1e-4 and clean["positions"] == 36
    assert all(v < 1e-4 for v in clean["rows"].values())
    assert clean["within_margin_share"] == 1.0
    assert clean["replay_puts_first_share"] == 1.0
    assert clean["state_dtype"] == "float32" and clean["all_free_after"]
    # (d): what the engine finished under load, on its own history
    assert clean["window_finished"] == 7 and clean["window_positions"] == 32
    assert clean["window_within_margin_share"] == 1.0
    assert clean["window_furthest_under_best"] < 1e-3


# fault -> what of the comparison sees it: (a) the logits, (b) which rows
CAUGHT = {
    "state_in_bf16": ("state", "first_state_by_head"),
    "key_multiplier_left_out": ("kv", "first_kv"),
    "mup_vector_left_out": ("state", "tail"),
    "dt_bias_left_out": ("state",),
    "gate_after_norm": (),
    "tail_one_late": ("tail",),
}


@pytest.mark.parametrize("fault", family.FAULTS)
def test_a_planted_fault_is_caught(params, clean, fault):
    got = _check(params, _cfg(), fault)
    # (a): the logits through pages and rows, every fault
    assert got["logit_rms_error"] > 20 * clean["logit_rms_error"]
    for name in CAUGHT[fault]:  # (b): the rows it moves
        assert got["rows"][name] > 20 * max(clean["rows"][name], 1e-6)
    if fault != "state_in_bf16":  # (c), (d): tokens see a structural fault
        for share, furthest in (
                ("within_margin_share", "furthest_under_best"),
                ("window_within_margin_share", "window_furthest_under_best")):
            assert got[share] < 0.6 and got[furthest] > 1.0
    assert set(CAUGHT) == set(family.FAULTS)
