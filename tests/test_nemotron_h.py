"""Nemotron-H (models/nemotron_h.py: Nemotron-3-Super): layers that are ONE
thing each, a Mamba-2 mixer at heads of HALF a lane tile (the state rows
packed two heads side by side), grouped-query attention without positions,
a LatentMoE feed-forward (two-matrix relu^2 experts in a latent, a chip's
share of them held) beside a shared expert.  The two operations it widened
(ops/grouped_matmul.py's two-matrix form, ops/lightning.py's packed rows);
the model against the benchmark's float32 reference, cacheless and served
(prefill, then decode through pages AND rows; a prompt in chunks while
another slot decodes); the four shares of a layer against the uncut
reference; the benchmark's own comparison and every fault it plants.  Small
sizes, seeded weights, the CPU; LOGITS are compared, not tokens."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.families import nemotron_h as family
from benchmarks.reference import nemotron_h as reference
from ray_tpu.llm import model as lm
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.paged_cache import CacheConfig, init_cache, init_state
from ray_tpu.models import moe, nemotron_h
from ray_tpu.ops import lightning
from ray_tpu.ops.grouped_matmul import grouped_mlp

VOCAB = 512
TOL = 5e-4  # float32 against float32 "highest": 3e-6 measured, logits ~1 rms


def _cfg(**kw):
    return nemotron_h.NemotronHConfig.tiny(VOCAB, **kw)


def _file(cfg):
    """The configuration as the benchmark's family and reference read it:
    the published keys, the share's experts under ``n_routed_experts``."""
    return {"family": "nemotron_h", "vocab_size": cfg.vocab_size,
            "hidden_size": cfg.d_model, "num_hidden_layers": cfg.n_layers,
            "hybrid_override_pattern": cfg.pattern,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "mamba_num_heads": cfg.ssm_heads,
            "mamba_head_dim": cfg.ssm_head_dim,
            "ssm_state_size": cfg.ssm_state, "n_groups": cfg.ssm_groups,
            "conv_kernel": cfg.conv_width,
            "n_routed_experts": cfg.n_experts_held,
            "published": {"n_routed_experts": cfg.n_experts},
            "first_expert_held": cfg.first_expert_held,
            "num_experts_per_tok": cfg.experts_per_token,
            "moe_latent_size": cfg.d_latent,
            "moe_intermediate_size": cfg.d_expert,
            "moe_shared_expert_intermediate_size": cfg.d_shared,
            "n_shared_experts": 1, "n_group": 1, "topk_group": 1,
            "norm_topk_prob": True, "mlp_hidden_act": "relu2",
            "routed_scaling_factor": cfg.routed_scaling_factor,
            "max_position_embeddings": cfg.max_seq_len,
            "layer_norm_epsilon": cfg.norm_eps, "dtype": cfg.dtype,
            "state_lanes": cfg.state_lanes,
            "router_bias_sd": 0.02, "router_logit_sd": 1.0}


@pytest.fixture(scope="module")
def params():
    return nemotron_h.init(_cfg(), jax.random.PRNGKey(0))


def _tokens(n, seed=1):
    return np.random.default_rng(seed).integers(3, VOCAB, size=n).tolist()


def _reference_logits(cfg, params, tokens):
    return np.asarray(reference.logits(
        _file(cfg), params, jnp.asarray(tokens, jnp.int32)[None])[0])


# -- ops/grouped_matmul.py: an expert of two matrices ------------------------

@pytest.mark.parametrize("gated", [False, True])
def test_grouped_mlp_in_either_form_equals_jnp(gated):
    """Rows sorted by expert through the kernel, ``relu(x W_up)^2 W_down``
    without a gate matrix and the SiLU-gated three with one, against the
    same products in ``jnp``; a tile past ``tiles_used`` is not compared."""
    r = np.random.default_rng(3)
    layers, e, d, f, tile = 2, 3, 32, 48, 8
    w = lambda *s: jnp.asarray(r.standard_normal(s) / s[-2] ** 0.5,  # noqa: E731
                               jnp.float32)
    w_gate, w_up, w_down = w(layers, e, d, f), w(layers, e, d, f), w(
        layers, e, f, d)
    x = jnp.asarray(r.standard_normal((5 * tile, d)), jnp.float32)
    tile_expert = jnp.asarray([0, 0, 2, 2, 2], jnp.int32)
    got = grouped_mlp(x, w_gate if gated else None, w_up, w_down,
                      tile_expert, 4, 1, tile=tile)
    for t in range(4):
        rows = x[t * tile:(t + 1) * tile]
        ex = int(tile_expert[t])
        up = rows @ w_up[1, ex]
        hidden = (jax.nn.silu(rows @ w_gate[1, ex]) * up if gated
                  else jnp.square(jax.nn.relu(up)))
        np.testing.assert_allclose(got[t * tile:(t + 1) * tile],
                                   hidden @ w_down[1, ex], atol=2e-5)


def test_the_shared_expert_takes_either_form():
    r = np.random.default_rng(4)
    x, up, down = (jnp.asarray(r.standard_normal(s), jnp.float32)
                   for s in ((5, 8), (8, 12), (12, 8)))
    np.testing.assert_allclose(
        moe.shared_mlp({"w_up": up, "w_down": down}, x),
        jnp.square(jax.nn.relu(x @ up)) @ down, rtol=1e-5)
    np.testing.assert_allclose(
        moe.shared_mlp({"w_gate": up, "w_up": up, "w_down": down}, x),
        (jax.nn.silu(x @ up) * (x @ up)) @ down, rtol=1e-5)


# -- ops/lightning.py: heads of half a lane, packed --------------------------

def _draw(seed, L, H=8, G=2, dk=32, dv=16):
    r = np.random.default_rng(seed)
    q, k = (jnp.asarray(r.standard_normal((L, G, dk)), jnp.float32)
            for _ in range(2))
    v = jnp.asarray(r.standard_normal((L, H, dv)), jnp.float32)
    g = -jnp.asarray(r.uniform(1e-3, 0.2, (L, H)), jnp.float32)
    S0 = jnp.asarray(r.standard_normal((H, dk, dv)), jnp.float32)
    return q, k, v, g, S0


@pytest.mark.parametrize("pack", [1, 2, 4])
def test_packing_lays_heads_side_by_side_and_back(pack):
    S = jnp.arange(8 * 3 * 5, dtype=jnp.float32).reshape(8, 3, 5)
    packed = lightning.pack_state(S, pack)
    assert packed.shape == (8 // pack, 3, pack * 5)
    for j in range(pack):  # head pack i + j in lanes [5 j, 5 j + 5)
        np.testing.assert_array_equal(packed[1, :, 5 * j:5 * j + 5],
                                      S[pack + j])
    np.testing.assert_array_equal(lightning.unpack_state(packed, pack), S)


@pytest.mark.parametrize("pack", [2, 4])
def test_chunked_hands_its_state_back_as_it_came(pack):
    q, k, v, g, S0 = _draw(5, 100)
    want_o, want_S = lightning.recurrent(q, k, v, g, S0)
    o, S = lightning.chunked(q, k, v, g, lightning.pack_state(S0, pack))
    np.testing.assert_allclose(o, want_o, atol=2e-4, rtol=1e-5)
    np.testing.assert_allclose(lightning.unpack_state(S, pack), want_S,
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("shape,pack", [((8, 2, 32, 16), 2),
                                        ((8, 2, 32, 16), 4),
                                        ((16, 4, 16, 8), 2)])
def test_decode_update_on_packed_rows_equals_the_recurrence(shape, pack):
    """A head of half (a quarter) of the rows' lanes: ``pack`` heads that
    read one key side by side, the same arithmetic a lane; dead slots
    between live ones keep their rows."""
    H, G, dk, dv = shape
    B, layers, layer = 5, 3, 1
    live = jnp.asarray([True, False, True, True, False])
    q, k, v, g, _ = _draw(7, B, H, G, dk, dv)
    r = np.random.default_rng(8)
    rows = jnp.asarray(r.standard_normal((layers, B, H, dk, dv)),
                       jnp.float32)
    packed = lightning.pack_state(rows, pack)
    o, after = lightning.decode_update(packed, layer, q, k, v, g, live)
    after = lightning.unpack_state(after, pack)
    for b in range(B):
        want_o, want_S = lightning.recurrent(
            q[b:b + 1], k[b:b + 1], v[b:b + 1], g[b:b + 1], rows[layer, b])
        if bool(live[b]):
            np.testing.assert_allclose(o[b], want_o[0], atol=2e-5)
            np.testing.assert_allclose(after[layer, b], want_S, atol=2e-5)
        else:
            assert float(jnp.abs(o[b]).max()) == 0
            np.testing.assert_array_equal(after[layer, b], rows[layer, b])
    for other in (0, 2):  # no other layer's rows
        np.testing.assert_array_equal(after[other], rows[other])


def test_heads_side_by_side_read_one_key():
    """Two heads in one row of lanes under two keys (a head its own key):
    refused by name, as rows that hold no such heads are."""
    q, k, v, g, _ = _draw(9, 3, H=4, G=4)
    rows = jnp.zeros((1, 3, 2, 32, 32), jnp.float32)
    with pytest.raises(ValueError, match="side by side in the lanes read "
                       "one key"):
        lightning.decode_update(rows, 0, q, k, v, g, jnp.ones(3, bool))
    with pytest.raises(ValueError, match="hold no 4 heads"):
        lightning.decode_update(jnp.zeros((1, 3, 4, 32, 24), jnp.float32),
                                0, q, k, v, g, jnp.ones(3, bool))


# -- the model against the reference -----------------------------------------

@pytest.mark.parametrize("layout", ["init", "serving"])
def test_forward_logits_equal_the_reference(params, layout):
    cfg = _cfg()
    tree = params if layout == "init" else cfg.serving_layout(params)
    tokens = _tokens(90, 2)
    got = nemotron_h.apply(tree, jnp.asarray(tokens, jnp.int32)[None], cfg)
    np.testing.assert_allclose(
        got[0], _reference_logits(cfg, tree, tokens), atol=TOL)


def test_a_model_declares_what_it_caches():
    cfg = _cfg()
    lay = cfg.cache_layout()
    assert (lay["n_layers"], lay["state_layers"]) == (1, 3)  # "MEM*EME"
    assert cfg.state_pack == 2  # heads of 16 in rows of 32 lanes
    assert lay["state_rows"]["S"] == (3, (4, 32, 32), jnp.float32)
    assert lay["state_rows"]["conv"][:2] == (9, (8 * 16 + 2 * 2 * 32,))
    assert set(cfg.refuses) == {"pd", "kv_tier", "prefix_cache"}
    # the published model: 88 layers, 40 : 40 : 8, two heads of 64 a row
    pub = nemotron_h.NemotronHConfig()
    assert (pub.n_layers, pub.count("M"), pub.count("E"),
            pub.count("*")) == (88, 40, 40, 8)
    assert pub.state_pack == 2 and pub.cache_layout()["state_rows"]["S"][1] \
        == (64, 128, 128)
    with pytest.raises(ValueError, match="a layer is one of"):
        _cfg(pattern="MXE")


def _pools(cfg, slots=4, pages=32, ps=16):
    cc = CacheConfig(**lm.cache_layout(cfg), num_pages=pages, page_size=ps,
                     dtype="float32", max_slots=slots)
    return init_cache(cc), init_state(cc)


def _rows_of(cfg, st, slot):
    """A slot's rows a head at a time, and its tails a layer."""
    n = cfg.count("M")
    return (lightning.unpack_state(st["S"][:, slot], cfg.state_pack),
            st["conv"][:, slot].reshape(n, cfg.conv_width - 1, -1))


# the tiny model's own pattern, and an irregular one that begins with a
# routed layer, ends on a mixer and has two kinds twice running
@pytest.mark.parametrize("pattern,n", [("MEM*EME", 150), ("E*MM*EEM", 90)])
def test_prefill_and_decode_programs_equal_the_reference(pattern, n):
    """``prefill`` into a slot's rows AND its pages, then ``decode_step``
    through both, against the reference's one full forward pass: logits,
    the state rows a head at a time, the convolution's tails."""
    cfg, steps, ps, slots, slot = _cfg(pattern=pattern), 8, 16, 4, 2
    params = nemotron_h.init(cfg, jax.random.PRNGKey(0))
    tokens = _tokens(n + steps, seed=n)
    want = _reference_logits(cfg, params, tokens)
    tree = cfg.serving_layout(params)
    (ck, cv), st = _pools(cfg, slots)
    assert ck.shape[0] == cfg.count("*")
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
    did = dict(zip(moe.SHARE_COUNTED, np.asarray(counted[moe.SHARE_COUNTED])))
    picks = bucket * cfg.experts_per_token * cfg.count("E")
    assert did["moe_zero_picks"] == 0
    assert did["moe_local_rows"] + did["moe_absent_picks"] == picks
    assert 0 < did["moe_local_rows"] < picks / 2  # a quarter is held
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
    S, tails = _rows_of(cfg, st, slot)
    np.testing.assert_allclose(S, ref["S"], atol=1e-4)
    np.testing.assert_allclose(tails, ref["conv"], atol=1e-4)


def test_a_later_chunk_goes_on_from_the_slots_rows(params):
    """``prefill_with_prefix``: the PACKED state and the convolution's tail
    that the first chunk left are the second one's."""
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


# -- the four shares of a layer ------------------------------------------------

def test_the_shares_add_up(params):
    """Four chips' shares of every routed layer (each its quarter of the
    experts: ``r W_lout`` of its own weighted sum), the shared expert
    counted ONCE, equal the uncut reference's ``Mix_E``; each share's part
    is the reference's given the same share; no pick is counted twice."""
    cfg = _cfg()
    whole = dataclasses.replace(cfg, n_experts_held=cfg.n_experts,
                                first_expert_held=0)
    full = nemotron_h.init(whole, jax.random.PRNGKey(5))
    tokens = jnp.asarray(_tokens(48, 6), jnp.int32)
    _, uncut = reference.layer_parts(_file(whole), full, tokens)
    u = uncut["u"]  # [E layers, s, d]: every share sees the same rows
    n, per = cfg.count("E"), cfg.n_experts_held
    total, rows = 0.0, 0
    for first in range(0, cfg.n_experts, per):
        share = dataclasses.replace(cfg, first_expert_held=first)
        cut = jax.tree.map(lambda w: w, full)
        cut["layers"] = {**full["layers"], "E": {
            **full["layers"]["E"], "experts": jax.tree.map(
                lambda w: w[:, first:first + per],
                full["layers"]["E"]["experts"])}}
        c = _file(share)
        _, theirs = reference.layer_parts(c, cut, tokens)
        np.testing.assert_allclose(theirs["u"][0], u[0], atol=1e-5)
        # the PROGRAM's share on the uncut reference's rows
        got, _, chosen = family.routed_part(
            c, share.serving_layout(cut), u)
        for j in range(n):
            want = reference.held_part(
                c, u[j], reference.layer_of(cut["layers"], "E", j),
                uncut["weights"][j], uncut["chosen"][j])
            np.testing.assert_allclose(got[j], want, atol=1e-4)
        np.testing.assert_array_equal(chosen, uncut["chosen"])
        total = total + got
        rows += int(jnp.sum((chosen >= first) & (chosen < first + per)))
    np.testing.assert_allclose(total, uncut["held"], atol=2e-4)
    assert rows == n * 48 * cfg.experts_per_token  # each pick on ONE chip


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


def test_chunks_keep_their_rows_through_other_slots_steps(params):
    """A prompt over the largest bucket is computed in THREE chunks, one a
    loop iteration, WHILE another request decodes: the steps between two
    chunks leave the chunked slot's packed state and convolution rows alone,
    so both requests' logits and the rows the chunked one leaves are the
    reference's; the share's counters count what its experts did."""
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
    assert st["state_slot_steps"] == 59 + 5
    assert st["moe_zero_picks"] == 0 < st["moe_local_rows"]
    assert 0 < st["experts_read"] <= cfg.n_experts_held * cfg.count("E") * (
        st["decode_steps"] + st["prefills"] + st["prefill_chunks"])
    for tag, (prompt, out) in enumerate([(short, out_a), (long, out_b)], 1):
        want = _reference_logits(cfg, params, prompt + out)
        assert len(seen.by_request[tag]) == len(out)
        for j, row in enumerate(seen.by_request[tag]):
            np.testing.assert_allclose(row, want[len(prompt) - 1 + j],
                                       atol=TOL)
    seq = long + out_b[:-1]  # what slot 1's rows have taken
    ref = reference.forward(c, params, seq, [len(seq) - 1], len(seq), 160)
    np.testing.assert_allclose(rows["conv"], ref["conv"], atol=1e-5)
    np.testing.assert_allclose(rows["S"], ref["S"], atol=1e-4)


# -- the benchmark's configuration and arithmetic --------------------------------

def test_the_familys_arithmetic():
    """The benchmark's count of the configuration: the published model's
    parameters from its keys, the share's resident bytes, what a decode
    step has to move."""
    from benchmarks import common

    c = common.load_json("configs", "nemotron3_super_120b_serve_1chip.json")
    published = {**c, **c["published"]}
    del published["published"]
    assert family.count(published, "M") == family.count(published, "E") == 40
    assert abs(family.n_params(published) / 1e9 - 120.67) < 0.01
    assert family.expert_params(c) == 5505024
    assert family.state_bytes_per_layer(c) == 128 * 128 * 64 * 4
    assert family.weight_bytes(c) == 2 * 4648163712
    e = c["engine"]
    assert c["resident_bytes"] == {
        "weights": family.weight_bytes(c) + 8960,
        "state_rows": e["max_slots"] * family.state_bytes_per_slot(c),
        "page_pools": e["num_pages"] * e["page_size"]
        * family.kv_bytes_per_token(c)}
    assert sum(c["resident_bytes"].values()) > 10e9  # of a 16 GB chip
    cfg = family.model_config(c)
    assert cfg == nemotron_h.NemotronHConfig(
        vocab_size=32768, pattern="MEMEMEMEM*E", n_experts_held=128)
    rows = sum(n * int(np.prod(shape)) * jnp.dtype(dt).itemsize
               for n, shape, dt in cfg.cache_layout()["state_rows"].values())
    assert rows == family.state_bytes_per_slot(c)  # no padding byte
    assert nemotron_h.PUBLISHED_PATTERN == c["published"][
        "hybrid_override_pattern"]
    # 22 picks of 64 rows over a quarter of 512 columns: ~120 of 128 read
    assert 100 < family.expected_experts_hit(c, 40) < 110
    assert family.expected_local_rows(c, 64) == 352
    assert set(c["reduced"]) == set(c["published"])


# -- the benchmark's comparison, and the faults it plants ------------------------

def _check(params, cfg, fault=None, served=True, pinned=True):
    """``in_worker_latent_moe_ssm.pinned_check`` (``pinned``) and then,
    ``served``, ``in_worker_parallel_ssm.served_check`` over an engine in
    this process, with ``fault`` planted through the benchmark's own
    ``plant``."""
    from benchmarks import in_worker_latent_moe_ssm, in_worker_parallel_ssm

    c = _file(cfg)
    overrides, undo = family.plant(fault, piece=64) if fault else ({}, None)
    jax.clear_caches()  # the programs are compiled anew, planted
    reference._program.cache_clear()
    engine = None
    try:
        tree = cfg.serving_layout(params)
        prompts = [_tokens(n, 20 + n) for n in (70, 100)]  # one bucket
        pinned = pinned and in_worker_latent_moe_ssm.pinned_check(
            c, tree, family, reference, prompts, 128)
        if not served:
            return {"pinned": pinned}
        engine = _engine(tree, family.model_config(c, **overrides),
                         max_slots=2, prefill_buckets=(128,))
        steps = 8
        outputs = [engine.generate(p, SamplingParams(max_tokens=steps))
                   for p in prompts]
        got = in_worker_parallel_ssm.served_check(
            c, engine.params, engine, family, reference,
            {"prompts": prompts, "outputs": outputs, "steps": steps,
             "pad_to": 128, "margin": 0.1})
        return {**got, "pinned": pinned}
    finally:
        if engine is not None:
            engine.stop()
        if undo:
            undo()
            jax.clear_caches()
            reference._program.cache_clear()


# the comparison and its faults over ONE layer of each kind: a check
# compiles every program anew (the fault is in them), and that is its cost
SHORT = "M*E"


@pytest.fixture(scope="module")
def short_params():
    return nemotron_h.init(_cfg(pattern=SHORT), jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def clean(short_params):
    return _check(short_params, _cfg(pattern=SHORT))


def test_the_benchmarks_comparison_passes_the_served_path(clean):
    assert clean["logit_rms_error"] < 1e-4 and clean["positions"] == 16
    assert all(v < 1e-4 for v in clean["rows"].values())
    assert clean["within_margin_share"] == 1.0
    assert clean["replay_puts_first_share"] == 1.0
    assert clean["state_dtype"] == "float32" and clean["all_free_after"]
    p = clean["pinned"]
    assert p["logit_rms_error"] < 1e-4 and p["held_rel_rms_error"] < 1e-4
    assert p["router_same_set_share"] == 1.0
    assert p["router_weight_rel_rms_error"] < 1e-5
    # the seeded weights mute no branch: every Mix(u) ~1 rms a layer, the
    # held quarter of the experts a visible part of the routed layer's
    read = p["seeded_weights"][0]
    for name in ("mixer_rms", "attention_rms", "routed_rms"):
        assert all(0.3 < x < 4 for x in read[name]), (name, read[name])
    assert all(0.2 < x for x in read["held_rms"])


# fault -> what of the comparison sees it: which of the pinned readings (p:
# logits under the reference's routing, h: the held experts' part, r: the
# router), which rows, and whether the logits through the cache do (None:
# a fault of the routed layer alone, which the pinned readings hold apart
# before any engine exists; the engine is not built for it here)
CAUGHT = {
    "state_in_bf16": ((), ("state", "first_state_by_head"), False),
    "relu_not_squared": (("logit_rms_error", "held_rel_rms_error"), (),
                         None),
    "experts_gated_silu": (("held_rel_rms_error",), (), None),
    "scale_left_out": (("router_weight_rel_rms_error",), (), None),
    "bias_left_out": (("router_same_set_share",), (), None),
    "shared_on_latent": (("logit_rms_error",), (), None),
    "rope_applied": (("logit_rms_error",), ("kv",), True),
    "tail_one_late": ((), ("tail",), True),
}


@pytest.mark.parametrize("fault", family.FAULTS)
def test_a_planted_fault_is_caught(short_params, clean, fault):
    pinned, rows, through_cache = CAUGHT[fault]
    got = _check(short_params, _cfg(pattern=SHORT), fault,
                 served=through_cache is not None, pinned=bool(pinned))
    for name in pinned:
        if name == "router_same_set_share":  # (1.0 clean; 16 experts here)
            assert got["pinned"][name] < 0.9
        else:
            assert got["pinned"][name] > 20 * max(clean["pinned"][name],
                                                   1e-6)
    for name in rows:
        assert got["rows"][name] > 20 * max(clean["rows"][name], 1e-6)
    if through_cache:
        assert got["logit_rms_error"] > 20 * clean["logit_rms_error"]
    assert set(CAUGHT) == set(family.FAULTS)
