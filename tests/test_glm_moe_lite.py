"""GLM-4.7-Flash (models/glm_moe_lite.py): latent attention in both forms,
the sigmoid router with its choice bias and shared expert (models/moe.py),
the paged latent decode kernel (ops/paged_attention.py), and the engine
serving it through latent pages, against the benchmark's plain float32
reference (``benchmarks/reference/glm4_moe_lite.py``: non-absorbed attention,
no cache, every expert computed).  Small sizes, seeded weights, the CPU;
LOGITS are compared, not tokens.

Tolerances.  Program and reference both compute in float32 here, in another
order of operations: 3e-6 to 6e-6 measured on logits of 1.0 rms, so TOL =
2e-4 leaves room for another XLA's fusions and a path computed in bf16
(3e-2 and up, asserted below) fails by two orders of magnitude.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import glm4_moe_lite as reference
from ray_tpu.llm import model as lm
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.paged_cache import CacheConfig, init_cache
from ray_tpu.models import glm_moe_lite as glm
from ray_tpu.models import llama, moe
from ray_tpu.ops.paged_attention import paged_latent_decode_attention

VOCAB = 512
TOL = 2e-4
PS = 4  # page size of the engines below


def _cfg(**kw):
    return glm.GLMMoELiteConfig.tiny(VOCAB, **kw)


def _file(cfg):
    """The configuration as the benchmark's reference reads it."""
    return {"num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta,
            "num_experts_per_tok": cfg.experts_per_token,
            "norm_topk_prob": cfg.norm_topk_prob,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "n_routed_experts": cfg.n_experts,
            "first_k_dense_replace": cfg.n_dense_layers,
            "n_group": 1, "topk_group": 1}


@pytest.fixture(scope="module")
def params():
    return glm.init(_cfg(), jax.random.PRNGKey(0))


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(3, VOCAB, size=n).tolist()


def _reference_logits(cfg, params, tokens):
    return np.asarray(reference.logits(
        _file(cfg), params, jnp.asarray(tokens, jnp.int32)[None])[0])


# -- the model against the reference ----------------------------------------

def test_the_tiny_config_is_a_hard_one():
    """A value head that is neither the score's width nor its nope part,
    heads that are not d_model / n_heads, one dense layer before the sparse
    ones, a pool row that is padded."""
    cfg = _cfg()
    assert cfg.v_head_dim not in (cfg.head_dim, cfg.qk_nope_head_dim)
    assert cfg.n_heads * cfg.v_head_dim != cfg.d_model
    assert cfg.n_dense_layers == 1 and cfg.n_layers == 3
    assert cfg.latent_dim == 48 and cfg.latent_width == 128


@pytest.mark.parametrize("absorbed", [False, True])
@pytest.mark.parametrize("layout", ["training", "serving"])
def test_apply_matches_the_reference_in_both_forms(params, absorbed, layout):
    """The rebuilt form (prefills) and the absorbed form (decode) are the
    same function, over either parameter layout."""
    cfg = _cfg()
    tree = params if layout == "training" else lm.serving_layout(params)
    tokens = _tokens(40)
    got = glm.apply(tree, jnp.asarray(tokens, jnp.int32)[None], cfg,
                    absorbed=absorbed)[0]
    np.testing.assert_allclose(got, _reference_logits(cfg, params, tokens),
                               atol=TOL)


def test_a_bf16_stand_in_for_float32_fails_the_tolerance(params):
    cfg = _cfg()
    tokens = _tokens(40)
    rounded = jax.tree.map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = glm.apply(rounded, jnp.asarray(tokens, jnp.int32)[None], cfg)[0]
    off = np.abs(np.asarray(got) - _reference_logits(cfg, params, tokens))
    assert off.max() > 50 * TOL


def test_serving_layout_stacks_and_splits(params):
    cfg = _cfg()
    tree = lm.serving_layout(params)
    for part in ("dense", "layers"):
        a, was = tree[part]["attn"], params[part]["attn"]
        nl = was["wq_a"].shape[0]
        assert not {"wq_a", "wkv_a", "wkv_b", "wq_b"} & set(a)
        H, nope = cfg.n_heads, cfg.qk_nope_head_dim
        heads = was["wq_b"].reshape(nl, -1, H, cfg.head_dim)
        assert np.array_equal(  # every head's nope part, then the ropes
            a["wq_up"][..., :H * nope].reshape(nl, -1, H, nope),
            heads[..., :nope])
        assert np.array_equal(
            a["wq_up"][..., H * nope:].reshape(nl, -1, H, cfg.head_dim - nope),
            heads[..., nope:])
        assert a["w_a"].shape == (nl, cfg.d_model,
                                  cfg.q_lora_rank + cfg.latent_dim)
        assert a["w_uk"].shape == (nl, cfg.n_heads, cfg.qk_nope_head_dim,
                                   cfg.kv_lora_rank)
        assert a["w_uv"].shape == (nl, cfg.n_heads, cfg.kv_lora_rank,
                                   cfg.v_head_dim)
    assert lm.serving_layout(tree) is tree  # already laid out


# -- routing ----------------------------------------------------------------

def _route_inputs(seed=0, n=64, d=32, e=8):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (n, d)),
            jax.random.normal(ks[1], (d, e)) * d ** -0.5,
            0.3 * jax.random.normal(ks[2], (e,)))


def test_route_chooses_by_score_plus_bias_and_weighs_by_score():
    h, router, bias = _route_inputs()
    weights, chosen = moe.route(h, router, 2, True, bias, 1.8)
    s = jax.nn.sigmoid(h @ router)
    want_i = np.argsort(-(np.asarray(s) + np.asarray(bias)), axis=1)[:, :2]
    assert np.array_equal(np.sort(chosen, 1), np.sort(want_i, 1))
    picked = np.take_along_axis(np.asarray(s), np.asarray(chosen), 1)
    np.testing.assert_allclose(
        weights, 1.8 * picked / picked.sum(1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(weights.sum(1), 1.8, rtol=1e-6)
    # the bias flips choices: without it other experts are taken somewhere,
    # and where the SET is the same the weights are the same (bias-free)
    _, plain = moe.route(h, router, 2, True, 0 * bias, 1.8)
    flipped = np.sort(plain, 1) != np.sort(chosen, 1)
    assert flipped.any() and not flipped.all()
    # and it is the reference's choice (one group: no group limit)
    c = {"num_experts_per_tok": 2, "norm_topk_prob": True,
         "routed_scaling_factor": 1.8}
    ref_w, ref_i = reference.choose(c, s, bias)
    assert np.array_equal(ref_i, chosen)
    np.testing.assert_allclose(ref_w, weights, rtol=1e-6)


def test_route_without_a_bias_is_the_softmax_router_it_was():
    h, router, _ = _route_inputs(1)
    weights, chosen = moe.route(h, router, 2)
    probs = jax.nn.softmax(h @ router, -1)
    top_p, top_i = jax.lax.top_k(probs, 2)
    assert np.array_equal(chosen, top_i)
    np.testing.assert_allclose(weights, top_p / top_p.sum(1, keepdims=True),
                               rtol=1e-6)


def test_the_seeded_bias_flips_a_choice_in_the_model(params):
    cfg = _cfg()
    p = jax.tree.map(lambda w: w[0], {k: params["layers"][k]
                                      for k in ("router", "router_bias")})
    h = jax.random.normal(jax.random.PRNGKey(3), (256, cfg.d_model))
    _, with_bias = moe.route(h, p["router"], 2, True, p["router_bias"], 1.8)
    _, without = moe.route(h, p["router"], 2, True, 0 * p["router_bias"], 1.8)
    assert (np.sort(with_bias, 1) != np.sort(without, 1)).any()


def test_the_shared_expert_is_counted_once(params):
    cfg = _cfg()
    layers = params["layers"]
    p = jax.tree.map(lambda w: w[1], {k: layers[k] for k in (
        "router", "router_bias", "shared")})
    h = jax.random.normal(jax.random.PRNGKey(4), (24, cfg.d_model))
    kw = dict(top_k=2, bias=p["router_bias"], scale=1.8)
    with_shared, hit = moe.routed_mlp(h, p["router"], layers["experts"], 1,
                                      shared=p["shared"], **kw)
    without, hit2 = moe.routed_mlp(h, p["router"], layers["experts"], 1, **kw)
    sh = p["shared"]
    want = (jax.nn.silu(h @ sh["w_gate"]) * (h @ sh["w_up"])) @ sh["w_down"]
    np.testing.assert_allclose(with_shared - without, want, atol=1e-5)
    assert int(hit) == int(hit2) <= cfg.n_experts
    # and the whole feed-forward is the reference's
    c = _file(cfg)
    ref, _, _ = reference._experts(c, h, p["router"], p["router_bias"],
                                   jax.tree.map(lambda w: w[1],
                                                layers["experts"]))
    np.testing.assert_allclose(without, ref, atol=TOL)


# -- the kernel -------------------------------------------------------------

def _pool_case(seed=0, L=2, pages=12, ps=4, W=128, used=112, B=5, H=3, P=6):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(L, pages, ps, W)),
                       jnp.float32).at[..., used:].set(0)
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    tables = jnp.asarray(rng.integers(1, pages, (B, P)), jnp.int32)
    return q, pool, tables


def _dense_latent_attention(q, pool, tables, lengths, layer, V, scale):
    B, P = tables.shape
    rows = pool[layer][tables].reshape(B, P * pool.shape[2], -1)
    s = jnp.einsum("bhw,btw->bht", q, rows) * scale
    mask = jnp.arange(rows.shape[1])[None, None, :] < lengths[:, None, None]
    p = jax.nn.softmax(jnp.where(mask, s, -1e30), -1)
    out = jnp.einsum("bht,btv->bhv", p, rows[..., :V])
    return out * (lengths > 0)[:, None, None]


@pytest.mark.parametrize("pages_per_block", [1, 2, 6])
@pytest.mark.parametrize("lengths", [[0, 5, 24, 1, 13], [24, 24, 24, 24, 24],
                                     [0, 0, 7, 0, 0]])
def test_latent_kernel_matches_dense_attention(lengths, pages_per_block):
    """Ragged lengths, blocks that end mid-page, inactive slots (zeros),
    a slot alone between inactive ones; the interpreter on the CPU."""
    q, pool, tables = _pool_case()
    lengths = jnp.asarray(lengths, jnp.int32)
    got = paged_latent_decode_attention(
        q, pool, tables, lengths, 1, value_dim=96, sm_scale=0.1,
        pages_per_block=pages_per_block)
    want = _dense_latent_attention(q, pool, tables, lengths, 1, 96, 0.1)
    assert got.shape == (5, 3, 96)
    np.testing.assert_allclose(got, want, atol=1e-5)
    assert not np.asarray(got)[np.asarray(lengths) == 0].any()


def test_latent_kernel_takes_a_traced_layer_and_bf16_pools():
    q, pool, tables = _pool_case(1)
    lengths = jnp.asarray([3, 9, 0, 24, 17], jnp.int32)
    pool16 = pool.astype(jnp.bfloat16)
    got = jax.jit(lambda li: paged_latent_decode_attention(
        q, pool16, tables, lengths, li, value_dim=64, sm_scale=0.1))(
            jnp.int32(0))
    want = _dense_latent_attention(
        q.astype(jnp.bfloat16).astype(jnp.float32),
        pool16.astype(jnp.float32), tables, lengths, 0, 64, 0.1)
    assert got.dtype == q.dtype
    np.testing.assert_allclose(got, want, atol=2e-2)  # p rounded to bf16


def test_latent_kernel_refuses_by_name(monkeypatch):
    q, pool, tables = _pool_case()
    lengths = jnp.ones((5,), jnp.int32)
    kw = dict(value_dim=96, sm_scale=0.1)
    with pytest.raises(ValueError, match="one pool"):
        paged_latent_decode_attention(q, pool[..., None], tables, lengths, 0,
                                      **kw)
    with pytest.raises(ValueError, match="same width"):
        paged_latent_decode_attention(q[..., :64], pool, tables, lengths, 0,
                                      **kw)
    with pytest.raises(ValueError, match="value_dim"):
        paged_latent_decode_attention(q, pool, tables, lengths, 0,
                                      value_dim=129, sm_scale=0.1)
    with pytest.raises(ValueError, match="lead with q's batch"):
        paged_latent_decode_attention(q, pool, tables[:3], lengths, 0, **kw)
    # on the chip: whole tiles only (here the interpreter takes any shape)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="whole tiles"):
        paged_latent_decode_attention(q, pool, tables, lengths, 0, **kw)
    with pytest.raises(ValueError, match="whole tiles"):  # page_size 4
        paged_latent_decode_attention(q, pool, tables, lengths, 0,
                                      value_dim=128, sm_scale=0.1)


# -- the latent page --------------------------------------------------------

def test_cache_layout_declares_one_latent_pool_and_its_bytes():
    cfg = glm.GLMMoELiteConfig(n_layers=8)
    layout = lm.cache_layout(cfg)
    assert layout == {"n_layers": 8, "latent_dim": 640}
    cc = CacheConfig(**layout, num_pages=12288, page_size=16)
    assert cc.bytes_per_token == 8 * 1280  # 1,280 a token a layer, bf16
    assert cc.bytes_per_token * cc.tokens_capacity == 2013265920
    # K and V of these head counts would be 16 x that
    kv = CacheConfig(n_layers=8, n_kv_heads=20, head_dim=256, num_pages=4)
    assert kv.bytes_per_token == 16 * cc.bytes_per_token
    pool, none = init_cache(CacheConfig(**layout, num_pages=4))
    assert pool.shape == (8, 4, 16, 640) and pool.dtype == jnp.bfloat16
    assert none is None  # no V pool, of any size
    with pytest.raises(ValueError, match="one of the two"):
        CacheConfig(n_layers=2, n_kv_heads=2, head_dim=8, latent_dim=128)
    with pytest.raises(ValueError, match="one of the two"):
        CacheConfig(n_layers=2)


def test_copy_page_copies_the_one_pool():
    pool, _ = init_cache(CacheConfig(n_layers=2, latent_dim=128, num_pages=4,
                                     page_size=PS, dtype="float32"))
    pool = pool.at[:, 1].set(7.0)
    pool, none = lm.copy_page(pool, None, jnp.int32(1), jnp.int32(3))
    assert none is None
    assert np.all(np.asarray(pool[:, 3]) == 7.0) and not np.asarray(
        pool[:, 2]).any()


# -- the programs through latent pages --------------------------------------

def _engine(params, **kw):
    cfg = _cfg()
    return LLMEngine(params, cfg, EngineConfig(**{**dict(
        max_slots=4, page_size=PS, max_seq_len=128, num_pages=64,
        prefill_buckets=(16, 32, 64)), **kw}))


def _greedy(engine, prompt, n):
    return engine.generate(prompt, SamplingParams(max_tokens=n,
                                                  temperature=0.0))


def test_prefill_then_decode_steps_match_the_reference_logits(params):
    """The programs themselves: ``prefill`` writes a prompt's latent rows
    and attends in the rebuilt form, ``decode_step`` N times in the absorbed
    form through the kernel; every step's LOGITS against the reference's
    full forward pass over the same tokens, and the rows left in the pool
    against its ``c_kv | k_rope``."""
    cfg = _cfg()
    tree = lm.serving_layout(params)
    pool, _ = init_cache(CacheConfig(**lm.cache_layout(cfg), num_pages=16,
                                     page_size=PS, dtype="float32"))
    seq, n, steps, B, P = _tokens(21), 13, 8, 3, 8
    pages = np.arange(1, 1 + P)
    padded = np.zeros(16, np.int32)
    padded[:n] = seq[:n]
    pos = np.arange(16)
    logits, counted, pool, none, state = lm.prefill(
        tree, jnp.asarray(padded), pool, None,
        jnp.asarray(pages[pos // PS], jnp.int32), jnp.int32(n),
        jnp.asarray(pos % PS, jnp.int32), cfg)
    assert none is None and state is None and list(counted) == [
        "experts_read"]
    assert 0 < int(counted["experts_read"]) <= 2 * cfg.n_experts
    want = _reference_logits(cfg, params, seq)
    np.testing.assert_allclose(logits, want[n - 1], atol=TOL)
    tables = np.zeros((B, P), np.int32)
    tables[1] = pages  # slot 1 holds the sequence, 0 and 2 are inactive
    active = jnp.asarray([False, True, False])
    for t in range(n, n + steps):
        logits, _, pool, _, _ = lm.decode_step(
            tree, jnp.asarray([0, seq[t], 0], jnp.int32), pool, None,
            jnp.asarray(tables), jnp.asarray([0, t, 0], jnp.int32), active,
            cfg)
        np.testing.assert_allclose(logits[1], want[t], atol=TOL)
    rows = reference.latent_rows(_file(cfg), params,
                                 jnp.asarray(seq, jnp.int32)[None])[:, 0]
    held = pool[:, pages].reshape(cfg.n_layers, P * PS, -1)
    np.testing.assert_allclose(held[:, :n + steps, :cfg.latent_dim],
                               rows[:, :n + steps], atol=TOL)
    assert not np.asarray(held[..., cfg.latent_dim:]).any()  # the zero tail


def test_engine_tokens_hold_against_the_reference_on_their_history(params):
    """Greedy through the engine: several prompts at once, a prefix hit
    (``prefill_with_prefix`` gathers latent rows through the page table and
    up-projects them), and a sequence preempted and resumed: every token's
    logit is the reference's best on the engine's own history."""
    engine = _engine(params)
    prompts = [_tokens(n, seed=n) for n in (9, 20, 33)]
    outs = [_greedy(engine, p, 12) for p in prompts]
    again = _greedy(engine, prompts[2], 12)  # by now a prefix hit
    assert again == outs[2]
    stats = engine.stats()
    assert stats["prefill_tokens_saved"] >= 32
    gaps = reference.verify(_file(_cfg()), params, prompts + prompts[2:],
                            outs + [again], 12, 64)
    assert max(g for row in gaps for g in row) < TOL
    engine.stop()


def test_a_preempted_sequence_resumes_through_latent_pages(params):
    """A pool too small for two growing sequences: one is preempted, its
    pages' rows registered, and its resume prefill (a prefix hit over
    latent rows written by prefill AND by decode steps) continues it."""
    engine = _engine(params, num_pages=18, max_slots=2)
    prompts = [_tokens(24, seed=5), _tokens(24, seed=6)]
    engine.start()
    reqs = [engine.submit(p, SamplingParams(max_tokens=20, temperature=0.0))
            for p in prompts]
    outs = []
    for r in reqs:
        toks = []
        while (item := r.out_queue.get(timeout=120)) is not None:
            assert not isinstance(item, Exception), item
            toks.extend(item if isinstance(item, list) else [item])
        outs.append(toks)
    assert engine.stats()["preempted"] >= 1
    gaps = reference.verify(_file(_cfg()), params, prompts, outs, 20, 64)
    assert max(g for row in gaps for g in row) < TOL
    engine.stop()


def test_counters(params):  # (refusals: tests/test_family_refusals.py)
    engine = _engine(params)
    assert engine.cache_v is None and engine.kv_tier is None
    assert engine.prefix_cache is not None  # latent pages are pages
    _greedy(engine, _tokens(19), 9)
    stats = engine.stats()
    assert stats["latent_pages_read"] == stats["decode_pages_read"] > 0
    # two sparse layers, at most 8 experts each, a prefill and 8+ steps
    assert 0 < stats["experts_read"] <= 2 * 8 * (1 + stats["decode_steps"])
    engine.stop()


def test_spans_say_what_the_steps_read(params, monkeypatch):
    """A sampled loop: every ``llm.loop.decode_emit`` replay of this model
    names its ``steps``, the ``experts_read`` by them and the
    ``latent_pages_read``, and they add up to the counters; ``llm.prefill``
    keeps ``experts_read``."""
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.util import tracing

    recs = []
    monkeypatch.setenv("RTPU_TRACE_SAMPLE", "1.0")
    orig = tracing._record
    monkeypatch.setattr(tracing, "_record",
                        lambda r: (recs.append(r), orig(r))[1])
    engine = _engine(params)
    with tracing.serving_span("openai.request", path="/v1/x"):
        _greedy(engine, _tokens(19), 20)  # its spans go under the request's
    stats = engine.stats()
    engine.stop()
    bursts = [r["args"] for r in recs
              if r["name"] == engine_mod.P_DECODE_EMIT
              and "latent_pages_read" in r["args"]]
    assert bursts and all({"steps", "experts_read", "tokens"} <= set(a)
                          for a in bursts)
    assert sum(a["steps"] for a in bursts) == stats["decode_steps"]
    assert sum(a["latent_pages_read"] for a in bursts) == stats[
        "latent_pages_read"]
    (prefill,) = [r["args"] for r in recs if r["name"] == "llm.prefill"]
    assert prefill["experts_read"] > 0
    assert (sum(a["experts_read"] for a in bursts) + prefill["experts_read"]
            == stats["experts_read"])


def test_a_dense_engine_counts_no_latent_pages():
    cfg = llama.LlamaConfig.tiny(VOCAB)
    engine = LLMEngine(llama.init(cfg, jax.random.PRNGKey(0)), cfg,
                       EngineConfig(max_slots=2, page_size=PS, num_pages=32,
                                    max_seq_len=64, prefill_buckets=(16,)))
    _greedy(engine, _tokens(7), 4)
    stats = engine.stats()
    assert stats["decode_pages_read"] > 0 == stats["latent_pages_read"]
    assert stats["experts_read"] == 0
    engine.stop()
