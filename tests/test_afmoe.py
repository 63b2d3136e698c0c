"""Trinity-Mini's family (models/afmoe.py) against the plain float32
reference (benchmarks/reference/afmoe.py) on seeded weights, on the CPU:
the model, its router, window and full attention, a prompt computed whole
against the same prompt in chunks, chunks then decode steps across the
window (and across a preemption), the window layers' pages given back while
a sequence lives, and what the family refuses by name.

TOL: every comparison below is float32 against float32 over five layers
whose sandwich norms keep the stream near 1 rms; the two sides differ by
the ORDER of float32 sums (one stacked product against five, a softmax a
row against a kernel's running one), which measures 1-3e-5 here.  2e-4
leaves that room and fails every planted fault below (the least of them
reads 4e-3) and a bf16 stand-in (3e-2).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import afmoe as reference
from ray_tpu.llm import model as lm
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.paged_cache import CacheConfig, init_cache
from ray_tpu.models import afmoe, llama, moe

VOCAB = 512
TOL = 2e-4
PS = 8  # page size: the tiny window (32) is four pages


def _cfg(**kw):
    return afmoe.AfmoeConfig.tiny(VOCAB, **kw)


def _file(cfg, **kw):
    """The configuration as the benchmark's reference reads it."""
    return {**{"hidden_size": cfg.d_model,
               "num_attention_heads": cfg.n_heads,
               "num_key_value_heads": cfg.n_kv_heads,
               "head_dim": cfg.head_dim, "rms_norm_eps": cfg.norm_eps,
               "rope_theta": cfg.rope_theta,
               "num_experts": cfg.n_experts,
               "num_experts_per_tok": cfg.experts_per_token,
               "route_norm": cfg.norm_topk_prob,
               "route_scale": cfg.routed_scaling_factor,
               "num_dense_layers": cfg.n_dense_layers,
               "layer_types": list(cfg.layer_types),
               "sliding_window": cfg.sliding_window,
               "mup_enabled": cfg.mup_enabled, "score_func": "sigmoid",
               "n_group": 1, "topk_group": 1}, **kw}


@pytest.fixture(scope="module")
def params():
    return afmoe.init(_cfg(), jax.random.PRNGKey(0))


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(3, VOCAB, size=n).tolist()


def _reference_logits(c, params, tokens):
    return np.asarray(reference.logits(
        c, params, jnp.asarray(tokens, jnp.int32)[None])[0])


def _err(a, b):
    return float(np.max(np.abs(np.asarray(a) - np.asarray(b))))


# -- the model against the reference ----------------------------------------

def test_the_tiny_config_is_a_hard_one():
    cfg = _cfg()
    assert cfg.layer_types[:4] == (afmoe.SLIDING,) * 3 + (afmoe.FULL,)
    assert cfg.layer_types[4] == afmoe.SLIDING and cfg.n_dense_layers == 1
    assert cfg.sliding_window == 4 * PS and cfg.embed_scale == 8.0
    assert cfg.kinds() == (("window", 0), ("window", 1), ("window", 2),
                           ("full", 0), ("window", 3))
    full = afmoe.AfmoeConfig()  # the published pattern: every fourth
    assert full.layer_types[:8] == ((afmoe.SLIDING,) * 3 + (afmoe.FULL,)) * 2
    assert sum(t == afmoe.FULL for t in full.layer_types) == 8


@pytest.mark.parametrize("layout", ["training", "serving"])
def test_apply_matches_the_reference(params, layout):
    """80 positions: two and a half windows, so the window's mask and the
    full layer's lack of a rotation both matter."""
    cfg, seq = _cfg(), _tokens(80)
    tree = afmoe.serving_layout(params) if layout == "serving" else params
    got = afmoe.apply(tree, jnp.asarray(seq)[None], cfg)[0]
    want = _reference_logits(_file(cfg), params, seq)
    assert _err(got, want) < TOL
    assert 0.5 < float(np.sqrt(np.mean(want ** 2))) < 2.0  # logits ~1 rms


def _without_gate(p):
    """Attention's gate left out: sigmoid(0) is a constant."""
    def cut(a):
        return {**a, "wg": jnp.zeros_like(a["wg"])}
    return {**p, "dense": {**p["dense"], "attn": cut(p["dense"]["attn"])},
            "layers": {**p["layers"], "attn": cut(p["layers"]["attn"])}}


def _qk_norm_weights(p):
    """The QK norm's weights another vector than the model's ones."""
    def change(a):
        ramp = jnp.linspace(0.5, 1.5, a["q_norm"].shape[-1])
        return {**a, "q_norm": a["q_norm"] * ramp}
    return {**p, "dense": {**p["dense"], "attn": change(p["dense"]["attn"])},
            "layers": {**p["layers"], "attn": change(p["layers"]["attn"])}}


FAULTS = {
    "the_gate": (lambda c: c, _without_gate),
    "the_qk_norm": (lambda c: c, _qk_norm_weights),
    "the_window_a_page_short": (
        lambda c: {**c, "sliding_window": c["sliding_window"] - PS},
        lambda p: p),
    "a_full_layer_rotated": (
        lambda c: {**c, "layer_types": [afmoe.SLIDING] * 5,
                   "sliding_window": 1 << 20}, lambda p: p),
    "a_window_layer_not_rotated": (
        lambda c: {**c, "layer_types": [afmoe.FULL if i == 1 else t for i, t
                                        in enumerate(c["layer_types"])]},
        lambda p: p),
    "the_embedding_factor": (lambda c: {**c, "mup_enabled": False},
                             lambda p: p),
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_a_planted_fault_fails_the_tolerance(params, fault):
    """The reference with one thing wrong is NOT what ``apply`` computes:
    each mechanism is seen by the comparison above."""
    cfg, seq = _cfg(), _tokens(80)
    got = afmoe.apply(params, jnp.asarray(seq)[None], cfg)[0]
    in_file, in_params = FAULTS[fault]
    want = _reference_logits(in_file(_file(cfg)), in_params(params), seq)
    assert _err(got, want) > 20 * TOL


def test_a_bf16_stand_in_for_float32_fails_the_tolerance(params):
    cfg, seq = _cfg(dtype="bfloat16"), _tokens(80)
    low = jax.tree.map(lambda w: w.astype(jnp.bfloat16), params)
    got = afmoe.apply(low, jnp.asarray(seq)[None], cfg)[0]
    assert _err(got, _reference_logits(_file(cfg), params, seq)) > 20 * TOL


def test_serving_layout_holds_the_layers_apart_and_the_four_products_as_one(
        params):
    tree = afmoe.serving_layout(params)
    cfg = _cfg()
    assert len(tree["dense"]) == cfg.n_dense_layers
    assert len(tree["layers"]["each"]) == cfg.n_layers - cfg.n_dense_layers
    assert tree["layers"]["experts"] is params["layers"]["experts"]
    for p in (*tree["dense"], *tree["layers"]["each"]):
        a = p["attn"]
        assert not {"wq", "wk", "wv", "wg"} & set(a)
        assert a["wqkvg"].shape == (
            cfg.d_model, (2 * cfg.n_heads + 2 * cfg.n_kv_heads)
            * cfg.head_dim)
        assert p["attn_norm"].shape == (cfg.d_model,)
    assert afmoe.serving_layout(tree) is tree
    assert lm.serving_layout(tree) is tree
    assert "each" in lm.serving_layout(params)["layers"]
    # either layout, the same logits
    seq = jnp.asarray(_tokens(40))[None]
    np.testing.assert_allclose(afmoe.apply(tree, seq, cfg),
                               afmoe.apply(params, seq, cfg),
                               rtol=1e-5, atol=1e-5)


# -- the router at these settings -------------------------------------------

def test_route_at_these_settings(params):
    """sigmoid scores, the choice by score + bias, the weights the chosen
    scores over their sum times ``route_scale``: ``moe.route`` against the
    reference's ``choose``, and the seeded bias flips a choice."""
    cfg = _cfg()
    h = jax.random.normal(jax.random.PRNGKey(3), (64, cfg.d_model))
    router = params["layers"]["router"][0]
    bias = params["layers"]["router_bias"][0]
    w, e = moe.route(h, router, cfg.experts_per_token, cfg.norm_topk_prob,
                     bias, cfg.routed_scaling_factor)
    scores = jax.nn.sigmoid(h @ router)
    want_w, want_e = reference.choose(_file(cfg), scores, bias)
    assert (np.asarray(e) == np.asarray(want_e)).all()
    np.testing.assert_allclose(w, want_w, atol=1e-6)
    np.testing.assert_allclose(np.asarray(w).sum(1),
                               cfg.routed_scaling_factor, rtol=1e-5)
    _, unbiased = moe.route(h, router, cfg.experts_per_token, True,
                            jnp.zeros_like(bias), cfg.routed_scaling_factor)
    assert (np.sort(np.asarray(e), 1)
            != np.sort(np.asarray(unbiased), 1)).any()


def test_the_shared_expert_is_counted_once(params):
    """Doubling the shared expert's down-projection moves the logits;
    the reference with the same doubling follows."""
    cfg, seq = _cfg(), _tokens(24)
    twice = {**params, "layers": {**params["layers"], "shared": {
        **params["layers"]["shared"],
        "w_down": 2 * params["layers"]["shared"]["w_down"]}}}
    got = afmoe.apply(twice, jnp.asarray(seq)[None], cfg)[0]
    assert _err(got, _reference_logits(_file(cfg), twice, seq)) < TOL
    assert _err(got, _reference_logits(_file(cfg), params, seq)) > 20 * TOL


# -- what is cached ---------------------------------------------------------

def test_cache_layout_declares_two_pools_and_their_bytes():
    cfg = afmoe.AfmoeConfig(n_layers=5, n_dense_layers=1, layer_types=(
        afmoe.SLIDING,) * 4 + (afmoe.FULL,))
    layout = cfg.cache_layout()
    assert layout == {"n_layers": 1, "n_kv_heads": 4, "head_dim": 128,
                      "window_layers": 4, "window": 2048}
    cc = CacheConfig(**layout, num_pages=32768, page_size=16,
                     window_pages=8448)
    k, v = jax.eval_shape(lambda: init_cache(cc))
    assert k["full"].shape == (1, 32768, 16, 4, 128) == v["full"].shape
    assert k["window"].shape == (4, 8448, 16, 4, 128)
    nbytes = sum(x.size * 2 for x in jax.tree.leaves((k, v)))
    assert nbytes == 1073741824 + 1107296256  # 1.074 + 1.107 GB
    with pytest.raises(ValueError, match="bytes_per_token_at"):
        cc.bytes_per_token
    # a token a layer is 2,048 bytes; one-shape pools would hold 10,240 a
    # token; at 4,096 tokens the window layers hold 128 of 256 pages
    assert cc.bytes_per_token_at(2048) == 5 * 2048
    assert cc.bytes_per_token_at(4096) == (256 + 4 * 128) * 16 * 2048 / 4096
    assert cc.window_pages_per_seq(1024) == 193
    with pytest.raises(ValueError, match="window layers"):
        CacheConfig(n_layers=1, n_kv_heads=4, head_dim=128, window_layers=4)
    dense = CacheConfig(**llama.LlamaConfig.tiny().cache_layout())
    # (whole pages counted: 1,008 tokens of pages hold the 1,000)
    assert dense.bytes_per_token == dense.bytes_per_token_at(1008) == 512


# -- the programs -----------------------------------------------------------

def _pools(cfg, pages=40, wpages=24):
    cc = CacheConfig(**cfg.cache_layout(), num_pages=pages, page_size=PS,
                     dtype="float32", window_pages=wpages)
    return init_cache(cc)


def _by_position(pages, n):
    pos = np.arange(n)
    return jnp.asarray(np.asarray(pages)[pos // PS], jnp.int32)


def _prefill_in_chunks(cfg, tree, seq, n, chunk, pools, pages, wpages, P):
    """The prompt's first ``n`` tokens through ``prefill`` then
    ``prefill_with_prefix``, ``chunk`` tokens a call; a window layer's
    table has null entries behind the window, as the engine leaves it.
    Returns (the last call's logits, pools)."""
    ck, cv = pools
    for p0 in range(0, n, chunk):
        m = min(chunk, n - p0)
        toks = np.zeros(chunk, np.int32)
        toks[:m] = seq[p0:p0 + m]
        pos = p0 + np.arange(chunk)
        tables = {"full": np.zeros(P, np.int32),
                  "window": np.zeros(P, np.int32)}
        tables["full"][:len(pages)] = pages
        tables["window"][:len(wpages)] = wpages
        tables["window"][:max(0, p0 - cfg.window + 1) // PS] = 0
        rows = {k: jnp.asarray(t[np.minimum(pos // PS, P - 1)])
                for k, t in tables.items()}
        args = (rows, jnp.int32(m), jnp.asarray(pos % PS, jnp.int32))
        if p0 == 0:
            logits, _, ck, cv, _ = lm.prefill(
                tree, jnp.asarray(toks), ck, cv, *args, cfg)
        else:
            logits, _, ck, cv, _ = lm.prefill_with_prefix(
                tree, jnp.asarray(toks), ck, cv, *args,
                {k: jnp.asarray(t) for k, t in tables.items()},
                jnp.asarray(pos, jnp.int32), cfg)
    return logits, (ck, cv)


def _held(cfg, pools, pages, wpages):
    """K by layer as the two pools hold it: [layers, tokens, kv, d]."""
    ck = pools[0]
    rows = {"full": ck["full"][:, jnp.asarray(pages)],
            "window": ck["window"][:, jnp.asarray(wpages)]}
    return jnp.stack([
        rows[kind][i].reshape(-1, cfg.n_kv_heads, cfg.head_dim)
        for kind, i in cfg.kinds()])


@pytest.mark.parametrize("chunk", [16, 24, 40, 96])
def test_a_prompt_in_chunks_is_the_prompt_whole(params, chunk):
    """88 tokens in ONE ``prefill`` call (a bucket of 96) against the same
    in chunks of 16, 24 and 40 (the last padded): the token that follows
    has the reference's logits, and both pools hold the reference's K rows
    of every layer."""
    cfg, seq, n, P = _cfg(), _tokens(88), 88, 12
    tree = afmoe.serving_layout(params)
    pages, wpages = np.arange(1, 1 + P), np.arange(5, 5 + P)
    logits, pools = _prefill_in_chunks(cfg, tree, seq, n, chunk,
                                       _pools(cfg), pages, wpages, P)
    want = _reference_logits(_file(cfg), params, seq)
    assert _err(logits, want[n - 1]) < TOL
    k, _ = reference.kv_rows(_file(cfg), params, jnp.asarray(seq)[None])
    held = _held(cfg, pools, pages, wpages)
    assert _err(held[:, :n], k[:, 0, :n]) < TOL


@pytest.mark.parametrize("preempt_at", [None, 70])
def test_chunks_then_decode_steps_across_the_window(params, preempt_at):
    """A prompt of 52 tokens in chunks of 24, then 40 decode steps through
    the kernel with its bound, window pages behind the window nulled as
    the engine nulls them: every step's LOGITS are the reference's full
    forward pass's.  ``preempt_at``: at that position everything is thrown
    away and the sequence so far is computed again in chunks (what a
    preempted sequence's resume does) into other pages."""
    cfg, seq, n, steps, P, B = _cfg(), _tokens(92), 52, 40, 12, 3
    tree = afmoe.serving_layout(params)
    want = _reference_logits(_file(cfg), params, seq)
    pages, wpages = np.arange(1, 1 + P), np.arange(3, 3 + P)
    logits, (ck, cv) = _prefill_in_chunks(cfg, tree, seq, n, 24, _pools(cfg),
                                          pages, wpages, P)
    assert _err(logits, want[n - 1]) < TOL
    active = jnp.asarray([False, True, False])
    for t in range(n, n + steps):
        if t == preempt_at:
            pages, wpages = np.arange(20, 20 + P), np.arange(11, 11 + P)
            _, (ck, cv) = _prefill_in_chunks(
                cfg, tree, seq, t, 24, (ck, cv), pages, wpages, P)
        tables = {"full": np.zeros((B, P), np.int32),
                  "window": np.zeros((B, P), np.int32)}
        tables["full"][1], tables["window"][1] = pages, wpages
        tables["window"][1, :max(0, t - cfg.window + 1) // PS] = 0
        logits, counted, ck, cv, _ = lm.decode_step(
            tree, jnp.asarray([0, seq[t], 0], jnp.int32), ck, cv,
            {k: jnp.asarray(v) for k, v in tables.items()},
            jnp.asarray([0, t, 0], jnp.int32), active, cfg)
        assert _err(logits[1], want[t]) < TOL, t
    assert int(counted["experts_read"]) > 0


# -- the engine -------------------------------------------------------------

def _engine(params, **kw):
    return LLMEngine(params, _cfg(), EngineConfig(**{**dict(
        max_slots=3, page_size=PS, max_seq_len=160, num_pages=64,
        prefill_buckets=(16, 32)), **kw}))


def _greedy(engine, prompt, n):
    return engine.generate(prompt, SamplingParams(max_tokens=n))


def _drain(req):
    toks = []
    while (item := req.out_queue.get(timeout=180)) is not None:
        assert not isinstance(item, Exception), item
        toks.extend(item if isinstance(item, list) else [item])
    return toks


def test_engine_tokens_hold_against_the_reference_on_their_history(params):
    """Greedy through the engine: prompts under a bucket, over it (chunks)
    and over three windows, several at once; every token's logit is the
    reference's best on the engine's own history; every page of both kinds
    is back afterwards."""
    engine = _engine(params)
    engine.start()
    prompts = [_tokens(n, seed=n) for n in (9, 40, 100, 70)]
    reqs = [engine.submit(p, SamplingParams(max_tokens=24)) for p in prompts]
    outs = [_drain(r) for r in reqs]
    gaps = reference.verify(_file(_cfg()), params, prompts, outs, 24, 128)
    assert max(g for row in gaps for g in row) < TOL
    stats = engine.stats()
    assert stats["prefill_chunks"] == 2 + 4 + 3  # ceil(40, 100, 70 / 32)
    assert stats["window_pages_freed"] > 0
    assert engine.allocator.num_free() == 63
    assert engine.window_allocator.num_free() == 3 * (32 // PS + 4) - 1
    assert stats["full_pages_in_use"] == stats["window_pages_in_use"] == 0
    engine.stop()


def test_window_pages_stay_under_the_stated_maximum_and_are_reused(params):
    """One sequence at a time through a window pool that holds ONE
    sequence's maximum: a long sequence can only run if it gives its pages
    back as it goes, the next sequence reuses them, and neither's tokens
    move (each is the reference's on its own history)."""
    cfg = _cfg()
    most = CacheConfig(**cfg.cache_layout(), page_size=PS,
                       window_pages=99).window_pages_per_seq(32)
    assert most == (32 + 32) // PS + 1
    engine = _engine(params, max_slots=1, window_pages=most + 1)
    seen = []
    trim = engine._trim_window

    def watching(s):
        seen.append(sum(p != 0 for p in s.wpages))
        return trim(s)

    engine._trim_window = watching
    prompts = [_tokens(100, seed=7), _tokens(90, seed=8)]
    outs = [_greedy(engine, p, 40) for p in prompts]
    assert max(seen) <= most and engine.stats()["window_pages_freed"] > 20
    gaps = reference.verify(_file(cfg), params, prompts, outs, 40, 160)
    assert max(g for row in gaps for g in row) < TOL
    assert engine.window_allocator.num_free() == most
    engine.stop()
    with pytest.raises(ValueError, match="cannot admit one"):
        _engine(params, window_pages=most)


def test_a_preempted_sequence_resumes_in_chunks(params):
    """A full pool too small for two growing sequences: one is preempted
    and recomputed through the same chunks (no prefix index to resume
    from); both end on the reference's tokens."""
    engine = _engine(params, num_pages=20, max_slots=2)
    prompts = [_tokens(50, seed=5), _tokens(50, seed=6)]
    engine.start()
    reqs = [engine.submit(p, SamplingParams(max_tokens=40)) for p in prompts]
    outs = [_drain(r) for r in reqs]
    assert engine.stats()["preempted"] >= 1
    gaps = reference.verify(_file(_cfg()), params, prompts, outs, 40, 128)
    assert max(g for row in gaps for g in row) < TOL
    assert engine.allocator.num_free() == 19
    assert engine.window_allocator.num_free() == 2 * (32 // PS + 4) - 1
    engine.stop()


def test_what_the_family_refuses_it_refuses_by_name(params):
    engine = _engine(params, max_slots=1)
    assert engine.prefix_cache is None and engine.kv_tier is None
    said = _cfg().refuses
    assert set(said) == {"prefix_cache", "pd", "kv_tier"}
    for feature, call in (("pd", lambda: engine.prefill_extract([5, 6, 7])),
                          ("kv_tier", lambda: engine.kv_prehydrate([]))):
        with pytest.raises(ValueError, match="gives the rest back") as e:
            call()
        assert "32 positions" in str(e.value)
    assert engine._thread is None
    engine.stop()


def test_spans_and_counters_say_what_the_steps_read(params, monkeypatch):
    """A sampled loop: ONE ``llm.prefill`` span a chunk (``tokens`` through
    it, ``prefix_len`` before it, ``chunk`` of ``chunks``), and every
    ``llm.loop.decode_emit`` replay names the pages a layer of each kind
    walked and the window's bound skipped; they add up to the counters."""
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.util import tracing

    recs = []
    monkeypatch.setenv("RTPU_TRACE_SAMPLE", "1.0")
    orig = tracing._record
    monkeypatch.setattr(tracing, "_record",
                        lambda r: (recs.append(r), orig(r))[1])
    engine = _engine(params)
    with tracing.serving_span("openai.request", path="/v1/x"):
        _greedy(engine, _tokens(70), 30)
    stats = engine.stats()
    engine.stop()
    chunks = [r["args"] for r in recs if r["name"] == "llm.prefill"]
    assert [(a["prefix_len"], a["tokens"], a["chunk"], a["chunks"])
            for a in chunks] == [(0, 32, 0, 3), (32, 64, 1, 3),
                                 (64, 70, 2, 3)]
    assert all(a["experts_read"] > 0 for a in chunks)
    bursts = [r["args"] for r in recs
              if r["name"] == engine_mod.P_DECODE_EMIT
              and "window_pages_read" in r["args"]]
    assert bursts and all({"steps", "experts_read", "tokens",
                           "full_pages_read", "window_pages_skipped"}
                          <= set(a) for a in bursts)
    for name in ("window_pages_read", "window_pages_skipped",
                 "full_pages_read"):
        assert sum(a[name] for a in bursts) == stats[name] > 0
    assert (stats["window_pages_read"] + stats["window_pages_skipped"]
            == stats["full_pages_read"] == stats["decode_pages_read"])
    assert stats["prefill_chunks"] == 3 and stats["prefills"] == 3
    assert stats["admitted"] == 1


def test_a_whole_burst_runs_between_two_chunks(params, monkeypatch):
    """While a prompt is computed in chunks the live slots are not held to
    a step an iteration: nothing new can be admitted before the prompt's
    last chunk, so the burst between two chunks is the whole 8 steps even
    with another request waiting and a slot free."""
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.util import tracing

    recs = []
    monkeypatch.setenv("RTPU_TRACE_SAMPLE", "1.0")
    orig = tracing._record
    monkeypatch.setattr(tracing, "_record",
                        lambda r: (recs.append(r), orig(r))[1])
    engine = _engine(params, max_seq_len=256)
    engine.start()
    with tracing.serving_span("openai.request", path="/v1/x"):
        first = engine.submit(_tokens(10), SamplingParams(max_tokens=120))
        assert first.out_queue.get(timeout=120) is not None  # it decodes
        late = [engine.submit(_tokens(100, seed=s),
                              SamplingParams(max_tokens=4)) for s in (2, 3)]
    for req in (first, *late):
        _drain(req)
    engine.stop()
    order = [r for r in recs if r["name"] in ("llm.prefill",
                                              engine_mod.P_DECODE_HOST)]
    between = [nxt["args"]["burst"] for rec, nxt in zip(order, order[1:])
               if rec["name"] == "llm.prefill"
               and rec["args"].get("chunk", 0) + 1 < rec["args"].get(
                   "chunks", 1) and nxt["name"] == engine_mod.P_DECODE_HOST]
    assert len(between) >= 4 and set(between) == {8}


def test_a_dense_engine_takes_a_long_prompt_in_chunks_too():
    """A family that refuses nothing is sent a prompt of three buckets:
    the tokens are the ones a bucket that holds it whole gives."""
    cfg = llama.LlamaConfig.tiny(VOCAB)
    tree = llama.init(cfg, jax.random.PRNGKey(0))
    prompt = _tokens(75, seed=3)
    outs = []
    for buckets in ((32,), (128,)):
        engine = LLMEngine(tree, cfg, EngineConfig(
            max_slots=2, page_size=PS, num_pages=64, max_seq_len=128,
            prefill_buckets=buckets))
        outs.append(_greedy(engine, prompt, 12))
        stats = engine.stats()
        assert stats["prefill_chunks"] == (3 if buckets == (32,) else 0)
        assert "window_pages_in_use" not in stats
        assert stats["window_pages_read"] == 0
        engine.stop()
    assert outs[0] == outs[1]
