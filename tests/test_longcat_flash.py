"""LongCat-Flash-Chat (models/longcat_flash.py): the shortcut-connected
double layer, glm_moe_lite's latent attention with the two rank scales, the
softmax router that chooses with a bias, one chip's SHARE of a routed layer
with identity experts (models/moe.py ``dispatch_share``) and the engine
serving it through two latent pool layers a scanned layer, against the
benchmark's plain float32 reference
(``benchmarks/reference/longcat_flash.py``).  Small sizes, seeded weights,
the CPU; LOGITS are compared, not tokens.

Tolerances: as tests/test_glm_moe_lite.py (float32 on both sides in another
order of operations; a path in bf16 fails by two orders of magnitude).
Float32 on both sides takes the same columns, so the routing needs no
pinning here; on the chip it does (benchmarks/in_worker_shortcut_moe.py).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import longcat_flash as family
from benchmarks.reference import longcat_flash as reference
from ray_tpu.llm import model as lm
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.paged_cache import CacheConfig, init_cache
from ray_tpu.models import glm_moe_lite as glm
from ray_tpu.models import longcat_flash as lc
from ray_tpu.models import moe, sdar_moe
from ray_tpu.ops import grouped_matmul

VOCAB = 512
TOL = 2e-4
PS = 4  # page size of the engines below

# every expert held, and one of four chips' share of the 16
SHARES = {"whole": {}, "share": dict(n_experts_held=4, first_expert_held=8)}


def _cfg(share="share", **kw):
    return lc.LongCatFlashConfig.tiny(VOCAB, **{**SHARES[share], **kw})


def _file(cfg):
    """The configuration as the benchmark's reference and family read it."""
    return {"hidden_size": cfg.d_model, "ffn_hidden_size": cfg.d_ff,
            "expert_ffn_hidden_size": cfg.d_expert,
            "num_layers": cfg.n_layers, "vocab_size": cfg.vocab_size,
            "num_attention_heads": cfg.n_heads,
            "kv_lora_rank": cfg.kv_lora_rank,
            "q_lora_rank": cfg.q_lora_rank,
            "qk_nope_head_dim": cfg.qk_nope_head_dim,
            "qk_rope_head_dim": cfg.qk_rope_head_dim,
            "v_head_dim": cfg.v_head_dim, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "mla_scale_q_lora": True,
            "mla_scale_kv_lora": True, "moe_topk": cfg.experts_per_token,
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "n_routed_experts": cfg.n_experts_held,
            "first_expert_held": cfg.first_expert_held,
            "published": {"n_routed_experts": cfg.n_experts},
            "zero_expert_num": cfg.n_identity_experts,
            "max_position_embeddings": cfg.max_seq_len, "dtype": cfg.dtype}


@pytest.fixture(scope="module")
def trees():
    return {name: lc.init(_cfg(name), jax.random.PRNGKey(0))
            for name in SHARES}


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(3, VOCAB, size=n).tolist()


def _reference_logits(cfg, params, tokens):
    return np.asarray(reference.logits(
        _file(cfg), params, jnp.asarray(tokens, jnp.int32)[None])[0])


# -- the model against the reference ----------------------------------------

def test_the_tiny_config_is_a_hard_one():
    """Two double layers over four pool layers, identity columns behind the
    experts, a share that begins in the middle, a router wider than the
    share, the rank scales neither 1 nor each other."""
    cfg = _cfg()
    assert lm.cache_layout(cfg) == {"n_layers": 4, "latent_dim": 128}
    assert cfg.router_columns == 24 and cfg.n_identity_experts == 8
    assert 0 < cfg.first_expert_held < cfg.n_experts - cfg.n_experts_held
    assert 1.0 != cfg.q_lora_scale != cfg.kv_lora_scale != 1.0
    assert cfg.refuses is glm.GLMMoELiteConfig.refuses
    full = lc.LongCatFlashConfig()  # the published model, on one chip alone
    assert (full.n_heads, full.head_dim, full.v_head_dim) == (64, 192, 128)
    assert full.router_columns == 768 and full.latent_width == 640
    assert (full.q_lora_scale, full.kv_lora_scale) == (2.0, 12 ** 0.5)
    with pytest.raises(ValueError, match="not among the 512"):
        lc.LongCatFlashConfig(n_experts_held=16, first_expert_held=500)


@pytest.mark.parametrize("absorbed", [False, True])
@pytest.mark.parametrize("layout", ["training", "serving"])
@pytest.mark.parametrize("share", list(SHARES))
def test_apply_matches_the_reference_in_both_forms(trees, share, layout,
                                                   absorbed):
    cfg, params = _cfg(share), trees[share]
    tree = params if layout == "training" else lm.serving_layout(params)
    tokens = _tokens(40)
    got = lc.apply(tree, jnp.asarray(tokens, jnp.int32)[None], cfg,
                   absorbed=absorbed)[0]
    np.testing.assert_allclose(got, _reference_logits(cfg, params, tokens),
                               atol=TOL)


def test_a_bf16_stand_in_for_float32_fails_the_tolerance(trees):
    cfg, params = _cfg(), trees["share"]
    tokens = _tokens(40)
    rounded = jax.tree.map(
        lambda w: w.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = lc.apply(rounded, jnp.asarray(tokens, jnp.int32)[None], cfg)[0]
    off = np.abs(np.asarray(got) - _reference_logits(cfg, params, tokens))
    assert off.max() > 50 * TOL


@pytest.mark.parametrize("fault", ["identity_left_out", "q_scale_left_out",
                                   "kv_scale_left_out", "branch_late"])
def test_a_planted_fault_fails_the_tolerance(trees, fault):
    """What the chip's limits must catch, caught here at float32, planted as
    the benchmark's controls plant them
    (``in_worker_shortcut_moe.plant``): the identity picks left out, a rank
    scale left out, the routed branch fed from the SECOND feed-forward's
    input."""
    from benchmarks import in_worker_shortcut_moe

    cfg, params = _cfg(), trees["share"]
    tokens = _tokens(40)
    want = _reference_logits(cfg, params, tokens)
    run = lambda: np.asarray(lc.apply.__wrapped__(  # noqa: E731 - (not the
        # jit's cached trace)
        params, jnp.asarray(tokens, jnp.int32)[None], cfg)[0])
    take_out = in_worker_shortcut_moe.plant(fault)
    try:
        got = run()
    finally:
        take_out()
    assert np.abs(got - want).max() > 50 * TOL
    assert np.abs(run() - want).max() < TOL  # taken out again


def test_serving_layout_lays_out_both_sublayers(trees):
    cfg, params = _cfg(), trees["share"]
    tree = lm.serving_layout(params)
    for sub in lc.SUBLAYERS:
        a = tree["layers"][sub]["attn"]
        assert not {"wq_a", "wkv_a", "wkv_b", "wq_b"} & set(a)
        assert a["w_a"].shape == (cfg.n_layers, cfg.d_model,
                                  cfg.q_lora_rank + cfg.latent_dim)
        assert a["w_uk"].shape == (cfg.n_layers, cfg.n_heads,
                                   cfg.qk_nope_head_dim, cfg.kv_lora_rank)
    assert tree["layers"]["experts"] is params["layers"]["experts"]
    assert lm.serving_layout(tree) is tree  # already laid out
    assert cfg.serving_layout(params)["layers"]["first"]["attn"][
        "w_a"].shape == tree["layers"]["first"]["attn"]["w_a"].shape


def test_the_seeded_router_is_sharp_and_its_bias_steers(trees):
    """Logits of about ``router_logit_sd`` on a normed row: the top picks
    carry most of the softmax's mass; the seeded bias changes the chosen
    set of some rows and not of all."""
    cfg = lc.LongCatFlashConfig.tiny(VOCAB, d_model=256, n_experts=512,
                                     n_identity_experts=256,
                                     experts_per_token=12, n_layers=1,
                                     n_experts_held=1, d_expert=8)
    p = jax.tree.map(lambda w: w[0], {
        k: lc.init(cfg, jax.random.PRNGKey(2))["layers"][k]
        for k in ("router", "router_bias")})
    h = jax.random.normal(jax.random.PRNGKey(3), (512, cfg.d_model))
    logits = h @ p["router"]
    assert 2.2 < float(jnp.std(logits)) < 2.8
    kw = dict(renormalise=False, scale=6.0, scoring="softmax")
    w, with_bias = moe.route(h, p["router"], 12, bias=p["router_bias"], **kw)
    _, without = moe.route(h, p["router"], 12, bias=0 * p["router_bias"],
                           **kw)
    assert 0.4 < float(w.sum(1).mean()) / 6.0 < 0.75  # the top 12's mass
    flipped = (np.sort(with_bias, 1) != np.sort(without, 1)).any(1)
    assert 0.1 < flipped.mean() < 0.9
    assert 0.28 < float((with_bias >= 512).mean()) < 0.39  # identity picks


# -- routing ----------------------------------------------------------------

def _route_inputs(seed=0, n=64, d=32, e=24):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(ks[0], (n, d)),
            2.5 * jax.random.normal(ks[1], (d, e)) * d ** -0.5,
            0.02 * jax.random.normal(ks[2], (e,)))


@pytest.mark.parametrize("renormalise", [False, True])
@pytest.mark.parametrize("biased", [False, True])
@pytest.mark.parametrize("scoring", ["softmax", "sigmoid"])
def test_route_scores_and_chooses_apart(scoring, biased, renormalise):
    """How a column is scored and whether a bias steers the choice are two
    arguments: all four pairs, against plain numpy."""
    h, router, bias = _route_inputs()
    weights, chosen = moe.route(h, router, 4, renormalise,
                                bias if biased else None, 6.0, scoring)
    z = np.asarray(h @ router, np.float64)
    s = (np.exp(z) / np.exp(z).sum(1, keepdims=True) if scoring == "softmax"
         else 1 / (1 + np.exp(-z)))
    by = s + (np.asarray(bias) if biased else 0)
    want_i = np.argsort(-by, axis=1)[:, :4]
    assert np.array_equal(np.sort(chosen, 1), np.sort(want_i, 1))
    picked = np.take_along_axis(s, np.asarray(chosen), 1)
    if renormalise:
        picked = picked / picked.sum(1, keepdims=True)
    np.testing.assert_allclose(weights, 6.0 * picked, rtol=2e-5)


def test_route_is_the_references_choice_and_defaults_are_what_they_were():
    h, router, bias = _route_inputs(1)
    weights, chosen = moe.route(h, router, 4, False, bias, 6.0, "softmax")
    ref_w, ref_i = reference.choose(
        {"moe_topk": 4, "routed_scaling_factor": 6.0},
        jax.nn.softmax(h @ router, -1), bias)
    assert np.array_equal(ref_i, chosen)
    np.testing.assert_allclose(ref_w, weights, rtol=1e-6)
    assert not np.allclose(weights.sum(1), 6.0)  # not renormalised
    # the pairs the published models use stay the defaults
    for b, scoring in ((None, "softmax"), (bias, "sigmoid")):
        for got, want in zip(moe.route(h, router, 4, True, b, 1.8),
                             moe.route(h, router, 4, True, b, 1.8, scoring)):
            assert np.array_equal(got, want)
    with pytest.raises(ValueError, match="softmax or by sigmoid"):
        moe.route(h, router, 4, scoring="tanh")


# -- one chip's share of a layer --------------------------------------------

def _layer_inputs(cfg, params, n=40, seed=5):
    m = jax.random.normal(jax.random.PRNGKey(seed), (n, cfg.d_model))
    p = {k: params["layers"][k][1] for k in ("router", "router_bias")}
    return m, p


def _uncut(cfg, params, m, p, **kw):
    """The reference's whole ``MoE(m)`` of layer 1, every expert held."""
    return reference.moe(_file(cfg), m, p["router"], p["router_bias"],
                         params["layers"]["experts"], 1, 0, cfg.n_experts,
                         **kw)


def test_the_shares_add_up(trees):
    """Four chips hold four experts each of a layer's sixteen.  The held
    experts' parts of all four shares plus the identity part counted ONCE
    are the uncut reference's ``MoE(m)``: from the program's
    ``dispatch_share`` and from the reference given each share."""
    cfg, params = _cfg("whole"), trees["whole"]
    m, p = _layer_inputs(cfg, params)
    want, want_held, top_w, top_i = _uncut(cfg, params, m, p)
    ident = jnp.sum(jnp.where(top_i >= cfg.n_experts, top_w, 0), -1,
                    keepdims=True) * m
    np.testing.assert_allclose(want, want_held + ident, atol=1e-6)
    got = jnp.zeros_like(m)
    ref = jnp.zeros_like(m)
    counted = np.zeros(4, np.int64)
    for first in (0, 4, 8, 12):
        mine = jax.tree.map(lambda w: w[:, first:first + 4],
                            params["layers"]["experts"])
        s, n = moe.dispatch_share(m, top_w, top_i, mine, 1, first=first,
                                  columns=cfg.router_columns,
                                  identity=cfg.n_identity_experts)
        got = got + (s - ident)  # this share's held experts alone
        counted += np.asarray(n)
        whole, held, _, _ = reference.moe(
            _file(cfg), m, p["router"], p["router_bias"], mine, 1, first,
            cfg.n_experts)
        np.testing.assert_allclose(s, whole, atol=TOL)
        ref = ref + held
    np.testing.assert_allclose(got + ident, want, atol=TOL)
    np.testing.assert_allclose(ref + ident, want, atol=TOL)
    # every pick was computed on exactly one chip, or is an identity pick
    picks = top_i.size
    zero = int((np.asarray(top_i) >= cfg.n_experts).sum())
    assert counted[2] == 4 * zero  # each chip adds its own tokens' picks
    assert counted[1] == picks - zero  # dropless: every real pick, once
    assert counted[3] == 3 * (picks - zero)


@pytest.mark.parametrize("picks", ["identity", "absent", "one_held",
                                   "mixed"])
def test_a_token_whose_picks_are_all_of_one_kind(trees, picks):
    """All identity (no product at all), all on other chips (nothing), all
    rows of every token on ONE held expert (dropless, whatever the split),
    and one of each."""
    cfg, params = _cfg(), trees["share"]  # holds experts 8..11 of 16 + 8
    m, p = _layer_inputs(cfg, params, n=24)
    k = cfg.experts_per_token
    column = {"identity": [16, 19, 21, 23], "absent": [0, 3, 7, 12],
              "one_held": [9, 9, 9, 9], "mixed": [9, 2, 17, 11]}[picks]
    chosen = jnp.tile(jnp.asarray(column, jnp.int32), (m.shape[0], 1))
    weights = jax.random.uniform(jax.random.PRNGKey(7), (m.shape[0], k))
    got, counted = moe.dispatch_share(
        m, weights, chosen, params["layers"]["experts"], 1,
        first=cfg.first_expert_held, columns=cfg.router_columns,
        identity=cfg.n_identity_experts)

    def expert(e):  # column e through the held matrices
        w = jax.tree.map(lambda a: a[1, e - cfg.first_expert_held],
                         params["layers"]["experts"])
        return (jax.nn.silu(m @ w["w_gate"]) * (m @ w["w_up"])) @ w["w_down"]

    want = sum(weights[:, j:j + 1] * (
        m if c >= cfg.n_experts else expert(c) if 8 <= c < 12 else 0 * m)
        for j, c in enumerate(column))
    np.testing.assert_allclose(got, want, atol=1e-5)
    n = m.shape[0]
    assert np.asarray(counted).tolist() == {
        "identity": [0, 0, 4 * n, 0], "absent": [0, 0, 0, 4 * n],
        "one_held": [1, 4 * n, 0, 0], "mixed": [2, 2 * n, n, n]}[picks]
    assert dict(zip(moe.SHARE_COUNTED, counted))["experts_read"] <= 4


def test_dispatch_share_refuses_a_share_outside_the_experts(trees):
    cfg, params = _cfg(), trees["share"]
    m, _ = _layer_inputs(cfg, params, n=4)
    with pytest.raises(ValueError, match="not among the 16 columns"):
        moe.dispatch_share(m, jnp.ones((4, 4)), jnp.zeros((4, 4), jnp.int32),
                           params["layers"]["experts"], 0, first=14,
                           columns=24, identity=8)


def _parents_dispatch(hf, weights, chosen, experts, layer):
    """``moe.dispatch`` as it stood before a share existed (PR 52's tree),
    kept here word for word as the oracle."""
    (n, d), top_k = hf.shape, chosen.shape[1]
    e = experts["w_gate"].shape[1]
    m = n * top_k
    tile = moe.row_tile(m, e)
    n_tiles = min(m, -(-(m + e * (tile - 1)) // tile))
    flat = chosen.reshape(m)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=e)
    padded = -(-sizes // tile) * tile
    run_end = jnp.cumsum(padded)
    sorted_e = flat[order]
    rank = jnp.arange(m) - (jnp.cumsum(sizes) - sizes)[sorted_e]
    row_sorted = (run_end - padded)[sorted_e] + rank
    src = jnp.zeros(n_tiles * tile, jnp.int32).at[row_sorted].set(
        (order // top_k).astype(jnp.int32))
    tile_expert = jnp.searchsorted(
        run_end, jnp.arange(n_tiles) * tile, side="right")
    tile_expert = jnp.minimum(tile_expert, sorted_e[-1])
    out = grouped_matmul.grouped_mlp(
        hf[src], experts["w_gate"], experts["w_up"], experts["w_down"],
        tile_expert, run_end[-1] // tile, layer, tile=tile)
    row = jnp.zeros(m, jnp.int32).at[order].set(row_sorted.astype(jnp.int32))
    out = out[row].reshape(n, top_k, d).astype(jnp.float32)
    return (jnp.einsum("nk,nkd->nd", weights, out).astype(hf.dtype),
            jnp.sum(sizes > 0).astype(jnp.int32))


@pytest.mark.parametrize("name", ["glm_moe_lite", "sdar_moe"])
def test_dispatch_with_every_expert_held_is_bit_for_bit_what_it_was(name):
    """The three routed cells' program: every expert held, no identity
    column, through ``dispatch``, on GLM's and SDAR's tiny configurations,
    bit for bit the parent's output (and ``dispatch_share`` told the same
    gives the same numbers)."""
    module, cfg = {"glm_moe_lite": (glm, glm.GLMMoELiteConfig.tiny(VOCAB)),
                   "sdar_moe": (sdar_moe, sdar_moe.SDARMoEConfig.tiny(VOCAB))
                   }[name]
    layers = module.init(cfg, jax.random.PRNGKey(0))["layers"]
    router = layers["router"][0]
    h = jax.random.normal(jax.random.PRNGKey(1), (48, cfg.d_model))
    weights, chosen = moe.route(h, router, cfg.experts_per_token,
                                bias=layers.get("router_bias", [None])[0])
    got, hit = jax.jit(moe.dispatch)(h, weights, chosen, layers["experts"],
                                     jnp.int32(0))
    want, want_hit = jax.jit(_parents_dispatch)(
        h, weights, chosen, layers["experts"], jnp.int32(0))
    assert np.array_equal(got, want) and int(hit) == int(want_hit)
    shared, counted = moe.dispatch_share(
        h, weights, chosen, layers["experts"], 0, first=0,
        columns=cfg.n_experts, identity=0)
    np.testing.assert_allclose(shared, want, atol=1e-6)
    assert np.asarray(counted).tolist() == [int(hit), chosen.size, 0, 0]


def test_an_expert_too_wide_for_vmem_is_read_in_column_blocks(monkeypatch):
    """The grouped product's second form: the hidden width in whole-lane
    blocks, accumulated; the same numbers, and tiles no row reached stay
    unread."""
    rng = np.random.default_rng(0)
    d, f, e, tile = 64, 512, 3, 16
    x = jnp.asarray(rng.normal(size=(5 * tile, d)), jnp.float32)
    w = {k: jnp.asarray(rng.normal(size=(2, e, *s)) * 0.1, jnp.float32)
         for k, s in (("w_gate", (d, f)), ("w_up", (d, f)),
                      ("w_down", (f, d)))}
    tile_expert = jnp.asarray([0, 2, 2, 2, 2], jnp.int32)
    args = (x, w["w_gate"], w["w_up"], w["w_down"], tile_expert, 3, 1)
    assert grouped_matmul.f_block(d, f, 4) == f
    want = grouped_matmul.grouped_mlp(*args, tile=tile)
    monkeypatch.setattr(grouped_matmul, "WEIGHT_BLOCKS_BYTES",
                        2 * 3 * d * 128 * 4)
    assert grouped_matmul.f_block(d, f, 4) == 128
    got = grouped_matmul.grouped_mlp(*args, tile=tile)
    np.testing.assert_allclose(got[:3 * tile], want[:3 * tile], atol=1e-5)
    # the published widths: 3 x 6144 x 2048 in bf16 does not fit twice
    monkeypatch.undo()
    assert grouped_matmul.f_block(6144, 2048, 2) == 512
    assert grouped_matmul.f_block(2048, 1536, 2) == 1536  # GLM's, whole


# -- the programs through latent pages --------------------------------------

def test_prefill_then_decode_steps_match_the_reference_logits(trees):
    """``prefill`` writes a prompt's rows into TWO pool layers a scanned
    layer and attends in the rebuilt form, ``decode_step`` N times in the
    absorbed form through the kernel; every step's LOGITS against the
    reference's full forward pass, the rows left in all four pool layers
    against its ``c_kv | k_rope``, and what the steps counted."""
    cfg, params = _cfg(), trees["share"]
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
    assert none is None and state is None
    assert list(counted) == [moe.SHARE_COUNTED]
    did = dict(zip(moe.SHARE_COUNTED, np.asarray(counted[moe.SHARE_COUNTED])))
    picks = 16 * cfg.experts_per_token * cfg.n_layers
    assert (did["moe_local_rows"] + did["moe_zero_picks"]
            + did["moe_absent_picks"]) == picks
    assert 0 < did["experts_read"] <= cfg.n_layers * cfg.n_experts_held
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
    assert rows.shape[0] == 2 * cfg.n_layers
    held = pool[:, pages].reshape(2 * cfg.n_layers, P * PS, -1)
    np.testing.assert_allclose(held[:, :n + steps, :cfg.latent_dim],
                               rows[:, :n + steps], atol=TOL)
    assert not np.asarray(held[..., cfg.latent_dim:]).any()  # the zero tail


def test_pinned_routing_gives_the_references_logits_and_held_part(trees):
    """What the chip's comparison (a) runs: the program's layers with the
    REFERENCE's columns handed in, both forms; and its ``dispatch_share``
    on the reference's rows against the reference's held experts' part."""
    cfg, params = _cfg(), trees["share"]
    c = _file(cfg)
    tokens = jnp.asarray([_tokens(24, seed=2), _tokens(24, seed=3)])
    rows = jnp.asarray([[20, 23], [5, 9]])
    want, weights, chosen, m, held = reference.logits_and_routing(
        c, params, tokens, rows)
    assert weights.shape == (cfg.n_layers, 48, cfg.experts_per_token)
    for absorbed in (False, True):
        got = family.pinned_logits(c, params, tokens, rows, weights, chosen,
                                   absorbed)
        np.testing.assert_allclose(got, want, atol=TOL)
    at = lambda y: jnp.take_along_axis(  # noqa: E731
        y.reshape(cfg.n_layers, 2, 24, -1), rows[None, :, :, None], 2)
    got = family.held_part(
        c, params, m.reshape(cfg.n_layers, 4, -1),
        at(weights).reshape(cfg.n_layers, 4, -1),
        at(chosen).reshape(cfg.n_layers, 4, -1))
    np.testing.assert_allclose(got, held.reshape(got.shape), atol=TOL)


def test_the_pinned_control_reads_each_fault_and_takes_it_out(monkeypatch):
    """``control_pinned`` as the chip runs it, at the tiny size in float32:
    the clean reading passes both limits, the identity picks left out fail
    (a) and (a'), the held experts at 3 bits fail (a') alone (they are a
    few hundredths of the stream), and the clean reading after them is the
    clean reading before."""
    from benchmarks import common, in_worker_shortcut_moe
    from benchmarks.runners import serve_shortcut_moe as runner

    cfg = _cfg(dtype="float32")
    c = {**common.load_cell("serve_shortcut_moe_long_answer")["config_file"],
         **_file(cfg)}
    monkeypatch.setattr(common, "load_cell",
                        lambda name: {"config_file": c})
    monkeypatch.setattr(runner, "CHECK", {
        **runner.CHECK, "n_prompts": 2, "min_len": 70, "max_len": 100,
        "pad_to": 128})
    monkeypatch.setattr(in_worker_shortcut_moe, "FAULTS",
                        ("identity_left_out", "experts_3bit"))
    first = runner.control_pinned("any", 7)
    assert first["none"]["fell"] == []
    assert first["identity_left_out"]["fell"] == ["pinned_rms_max",
                                                  "held_rel_rms_max"]
    assert first["experts_3bit"]["fell"] == ["held_rel_rms_max"]
    monkeypatch.setattr(in_worker_shortcut_moe, "FAULTS", ())
    assert runner.control_pinned("any", 7)["none"] == first["none"]


def _engine(params, **kw):
    return LLMEngine(params, _cfg(), EngineConfig(**{**dict(
        max_slots=4, page_size=PS, max_seq_len=128, num_pages=64,
        prefill_buckets=(16, 32, 64)), **kw}))


def _greedy(engine, prompt, n):
    return engine.generate(prompt, SamplingParams(max_tokens=n,
                                                  temperature=0.0))


def test_engine_tokens_hold_against_the_reference_on_their_history(trees):
    """Greedy through the engine: several prompts, a prefix hit
    (``prefill_with_prefix`` gathers both sublayers' rows through the page
    table): every token's logit is the reference's best on the engine's
    own history, and the rows in the pool are found through its index."""
    params = trees["share"]
    engine = _engine(params)
    prompts = [_tokens(n, seed=n) for n in (9, 20, 33)]
    outs = [_greedy(engine, p, 12) for p in prompts]
    again = _greedy(engine, prompts[2], 12)  # by now a prefix hit
    assert again == outs[2]
    assert engine.stats()["prefill_tokens_saved"] >= 32
    gaps, want = reference.verify(_file(_cfg()), params,
                                  prompts + prompts[2:], outs + [again], 12,
                                  64, rows=True)
    assert max(g for row in gaps for g in row) < TOL
    pages = engine.prefix_cache.match(prompts[1] + outs[1])
    got = family.engine_rows(engine, pages)
    assert got.shape == (4, len(pages) * PS, _cfg().latent_dim)
    np.testing.assert_allclose(got, want[:, 1, :len(pages) * PS], atol=TOL)
    engine.stop()


def test_counters_and_spans_say_what_the_share_did(trees, monkeypatch):
    """``engine.stats()`` (what ``LLMServer.engine_stats`` hands out) and
    the ``llm.loop.decode_emit`` / ``llm.prefill`` spans carry the share's
    counters by name, and the picks add up."""
    from ray_tpu.llm import engine as engine_mod
    from ray_tpu.util import tracing

    recs = []
    monkeypatch.setenv("RTPU_TRACE_SAMPLE", "1.0")
    orig = tracing._record
    monkeypatch.setattr(tracing, "_record",
                        lambda r: (recs.append(r), orig(r))[1])
    engine = _engine(trees["share"])
    assert engine.cache_v is None and engine.cache_k.shape[0] == 4
    with tracing.serving_span("openai.request", path="/v1/x"):
        _greedy(engine, _tokens(19), 20)
    stats = engine.stats()
    engine.stop()
    cfg = _cfg()
    assert stats["latent_pages_read"] == stats["decode_pages_read"] > 0
    rows = 32 + 4 * stats["decode_steps"]  # the bucket, then every slot
    assert (stats["moe_local_rows"] + stats["moe_zero_picks"]
            + stats["moe_absent_picks"]
            == rows * cfg.experts_per_token * cfg.n_layers)
    assert 0 < stats["experts_read"] <= stats["moe_local_rows"]
    assert stats["moe_zero_picks"] > 0 < stats["moe_absent_picks"]
    bursts = [r["args"] for r in recs
              if r["name"] == engine_mod.P_DECODE_EMIT
              and "latent_pages_read" in r["args"]]
    assert bursts and all({"steps", "tokens", *moe.SHARE_COUNTED} <= set(a)
                          for a in bursts)
    (prefill,) = [r["args"] for r in recs if r["name"] == "llm.prefill"]
    for name in moe.SHARE_COUNTED:
        assert (sum(a[name] for a in bursts) + prefill[name]
                == stats[name]), name


# -- the benchmark's arithmetic ---------------------------------------------

def test_the_familys_counts_are_the_trees(trees):
    cfg, params = _cfg(), trees["share"]
    c = _file(cfg)
    assert family.n_params(c) == sum(
        x.size for x in jax.tree.leaves(params))
    assert family.model_config(c, max_seq_len=cfg.max_seq_len) == cfg
    assert family.latent_bytes_per_token(c) == CacheConfig(
        **lm.cache_layout(cfg), num_pages=4).bytes_per_token
    assert family.n_layers(c) == (0, cfg.n_layers)
    published = {**c, "hidden_size": 6144, "ffn_hidden_size": 12288,
                 "expert_ffn_hidden_size": 2048, "num_layers": 4,
                 "num_attention_heads": 64, "kv_lora_rank": 512,
                 "q_lora_rank": 1536, "qk_nope_head_dim": 128,
                 "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "n_routed_experts": 16, "zero_expert_num": 256,
                 "moe_topk": 12, "vocab_size": 16384,
                 "published": {"n_routed_experts": 512}}
    assert family.attention_params(published) == 90572800
    assert family.layer_params(published, 0) == 638874368
    assert family.weight_bytes(published) == 10345498624
    assert family.latent_bytes_per_token(published) == 10240
    assert family.expected_identity_share(published) == 1 / 3
    assert family.expected_local_rows(published, 64) == 16.0
    assert 8 < family.expected_experts_hit(published, 48) < 9
