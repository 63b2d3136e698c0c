"""SDAR-MoE (block-diffusion generation over dropless routed experts)
through the paged engine, against the benchmark's plain reference
(``benchmarks/reference/sdar_moe.py``: float32, cacheless, every expert
computed densely), at a tiny size on the CPU with seeded weights.

Tolerances.  Program and reference both compute in float32 here, in another
order (sorted and grouped against dense over all experts; a running softmax
over pages against one over the sequence), so they differ by rounding
alone: a few 1e-6 relative on activations of order one.  ``TOL`` = 2e-4
leaves that two orders of room and is two orders under what a mistake
costs: a router computed in bf16 moves a tie of the top-k, a token dropped
at an expert's capacity loses that expert's whole term, and either moves an
output by 1e-2 or more (``test_the_tolerance_catches``).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.reference import sdar_moe as reference
from ray_tpu.llm import model as lm
from ray_tpu.llm.config import EngineConfig, SamplingParams
from ray_tpu.llm.engine import LLMEngine
from ray_tpu.llm.paged_cache import CacheConfig, init_cache
from ray_tpu.models import moe, sdar_moe

TOL = 2e-4
VOCAB = 512
STRATEGIES = sdar_moe.STRATEGIES
# a confidence is about 1 / VOCAB with seeded weights: a threshold there
# makes the dynamic strategy take both of its branches
THRESHOLD = 1.6 / VOCAB


def _cfg(strategy="sequential", **kw):
    return sdar_moe.SDARMoEConfig.tiny(
        VOCAB, remasking_strategy=strategy, confidence_threshold=THRESHOLD,
        **kw)


def _file(cfg) -> dict:
    """The configuration as the benchmark's files spell it."""
    return {"num_experts": cfg.n_experts,
            "num_experts_per_tok": cfg.experts_per_token,
            "norm_topk_prob": cfg.norm_topk_prob,
            "num_attention_heads": cfg.n_heads,
            "num_key_value_heads": cfg.n_kv_heads, "head_dim": cfg.head_dim,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "sampler": {"block_length": cfg.block_length,
                        "denoising_steps": cfg.denoising_steps,
                        "mask_token_id": cfg.mask_token_id,
                        "remasking_strategy": cfg.remasking_strategy,
                        "confidence_threshold": cfg.confidence_threshold}}


@pytest.fixture(scope="module")
def params():
    return sdar_moe.init(_cfg(), jax.random.PRNGKey(0))


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(3, VOCAB - 1, size=n).tolist() for n in lengths]


def _engine(params, cfg, **kw):
    engine = LLMEngine(params, cfg, EngineConfig(**{**dict(
        max_slots=4, num_pages=64, page_size=8, max_seq_len=128,
        prefill_buckets=(16, 32, 64)), **kw}))
    engine.start()
    return engine


def _drain(req):
    out = []
    while True:
        item = req.out_queue.get(timeout=120)
        if item is None:
            return out
        if isinstance(item, Exception):
            raise item
        out.append(item)


def _expected(cfg, params, prompts, steps):
    cands, gaps = reference.greedy(_file(cfg), params, prompts, steps, 96)
    # a near-tie of the reference's own best two would make "the" token a
    # matter of rounding; with these seeds there is none
    assert min(g[1] for row in gaps for g in row) > 10 * TOL
    return [[c[0] for c in row] for row in cands]


# -- the layer and the forward pass ------------------------------------------

def _routed_inputs(cfg, n=48, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    d, e, f = cfg.d_model, cfg.n_experts, cfg.d_expert
    experts = {"w_gate": jax.random.normal(ks[0], (1, e, d, f)) * d ** -0.5,
               "w_up": jax.random.normal(ks[1], (1, e, d, f)) * d ** -0.5,
               "w_down": jax.random.normal(ks[2], (1, e, f, d)) * f ** -0.5}
    return (jax.random.normal(ks[3], (n, d)),
            jax.random.normal(ks[4], (d, e)) * d ** -0.5, experts)


def _reference_experts(cfg, h, router, experts):
    with jax.default_matmul_precision("highest"):
        return reference._experts(
            _file(cfg), h, router, {k: v[0] for k, v in experts.items()})[0]


def test_routed_layer_is_the_dense_sum_over_the_top_k():
    """Sorted by expert and multiplied in groups, against every expert
    computed for every token: no assignment is lost, whatever the split
    (48 tokens x top-2 over 8 experts is far from even)."""
    cfg = _cfg()
    h, router, experts = _routed_inputs(cfg)
    got, experts_hit = moe.routed_mlp(h, router, experts, 0,
                                      top_k=cfg.experts_per_token)
    assert int(experts_hit) == len(np.unique(
        moe.route(h, router, cfg.experts_per_token)[1]))
    np.testing.assert_allclose(
        got, _reference_experts(cfg, h, router, experts), atol=TOL, rtol=TOL)


@pytest.mark.parametrize("fault", ["bf16_router", "dropped_token"])
def test_the_tolerance_catches(fault):
    """What TOL is for: a router computed in bf16 (a near-tie of the top-k
    falls the other way for some token) and a token dropped at an expert's
    capacity (the training model's layer at a capacity the split passes)
    both miss the reference by far more than TOL."""
    cfg = _cfg()
    h, router, experts = _routed_inputs(cfg, n=256)
    want = _reference_experts(cfg, h, router, experts)
    if fault == "bf16_router":
        bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
        weights, chosen = moe.route(bf16(h), bf16(router),
                                    cfg.experts_per_token)
        exact = moe.route(h, router, cfg.experts_per_token)[1]
        assert (np.sort(chosen, -1) != np.sort(exact, -1)).any()
        one_hot = jnp.zeros((h.shape[0], cfg.n_experts)).at[
            jnp.arange(h.shape[0])[:, None], chosen].set(weights)
        dense = jnp.einsum("nd,edf->enf", h, experts["w_gate"][0])
        dense = jax.nn.silu(dense) * jnp.einsum(
            "nd,edf->enf", h, experts["w_up"][0])
        got = jnp.einsum("ne,end->nd", one_hot, jnp.einsum(
            "enf,efd->end", dense, experts["w_down"][0]))
    else:
        mcfg = moe.MoEConfig(
            d_model=cfg.d_model, d_ff=cfg.d_expert, n_experts=cfg.n_experts,
            experts_per_token=cfg.experts_per_token, capacity_factor=0.75)
        got = moe.moe_mlp(mcfg, h[None], router,
                          {k: v[0] for k, v in experts.items()})[0][0]
    assert float(jnp.max(jnp.abs(got - want))) > 50 * TOL


def test_one_layer_equals_the_reference(params):
    cfg = _cfg()
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, cfg.d_model))
    p = jax.tree.map(lambda w: w[0], params["layers"])
    want = reference.layer(_file(cfg), x, p)
    one = dataclasses.replace(cfg, n_layers=1)
    layers = jax.tree.map(lambda w: w[:1], params["layers"])

    def body(x, p, li, ffn):
        positions = jnp.arange(x.shape[1])
        mask = sdar_moe.block_causal(positions, positions,
                                     cfg.block_length)

        def attend(q, k, v, cache):
            return jax.vmap(lambda q, k, v: lm._masked_attention(
                cfg, q, k, v, mask))(q, k, v), cache

        return sdar_moe.layer(one, p, x, positions[None], attend, None,
                              ffn)[0]

    got, experts_read = sdar_moe.scan_layers(one, {"layers": layers}, body,
                                             x)
    assert 1 <= int(experts_read) <= cfg.n_experts
    np.testing.assert_allclose(got, want, atol=TOL, rtol=TOL)


def test_forward_logits_equal_the_reference(params):
    cfg = _cfg()
    tokens = jnp.asarray(_prompts([40, 40], seed=7), jnp.int32)
    np.testing.assert_allclose(
        sdar_moe.apply(params, tokens, cfg),
        reference.logits(_file(cfg), params, tokens), atol=TOL, rtol=TOL)


# -- the sampler ---------------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_fill_selects_what_generate_py_selects(strategy):
    """The device's selection against the reference's plain one, on random
    logits: every pattern of masks, both steps.  Near-tie rule: where two
    masked positions' confidences lie within 1e-6 of each other the
    selection may take either, so such a draw is left out (none of these
    is one)."""
    cfg = _cfg(strategy)
    B, T = cfg.block_length, cfg.denoising_steps
    rng = np.random.default_rng(11)
    patterns = [[bool(m >> j & 1) for j in range(B)] for m in range(1, 2 ** B)]
    logits = rng.normal(size=(len(patterns), B, VOCAB)).astype(np.float32)
    logits[:, :, 0] += rng.uniform(0, 3, size=(len(patterns), B))
    conf = np.asarray(jnp.exp(jnp.max(logits, -1)
                              - jax.scipy.special.logsumexp(logits, -1)))
    gap = np.abs(conf[:, :, None] - conf[:, None, :]) + np.eye(B)
    assert gap.min() > 1e-6
    n_ts = reference.num_transfer_tokens(B, T)
    assert tuple(n_ts) == sdar_moe.num_transfer_tokens(B, T) == (2, 2)
    for step in range(T):
        x0, fill = lm._fill(cfg, jnp.asarray(logits), jnp.asarray(patterns),
                            jnp.full(len(patterns), step, jnp.int32))
        assert (np.asarray(x0) == logits.argmax(-1)).all()
        for i, masked in enumerate(patterns):
            want = reference.select(strategy, masked, conf[i], n_ts[step],
                                    cfg.confidence_threshold)
            assert sorted(np.flatnonzero(fill[i])) == sorted(want)
    if strategy == "low_confidence_dynamic":  # both branches were taken
        over = [sum(c > THRESHOLD for c, m in zip(conf[i], p) if m)
                for i, p in enumerate(patterns)]
        assert min(over) < 2 <= max(over)


# -- prefill and passes through the pages --------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_prefill_and_passes_through_pages_equal_the_cacheless_forward(
        params, strategy):
    """Pass by pass: the prompt's whole blocks prefilled into scattered
    pages, then ``block_step`` over the open block against the reference's
    full forward of the sequence so far: the same positions filled with the
    same tokens in every pass.  The reference needs no final pass; the
    program's (mask-free input) must fill nothing and open the next block.
    Near-tie rule as in the selection test; a filled token must be the
    reference's best, or one whose logit lies under 10 TOL below it."""
    cfg = _cfg(strategy)
    B, ps, P = cfg.block_length, 8, 8
    prompts = _prompts([13, 16, 22, 7])  # r = 1, 0, 2, 3
    steps = 9
    ck, cv = init_cache(CacheConfig(
        n_layers=cfg.n_layers, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, num_pages=40, page_size=ps, dtype=cfg.dtype))
    S = len(prompts)
    # pages dealt out of order: slot i owns pages 1 + i, 1 + i + S, ...
    tables = np.array([[1 + i + S * j for j in range(P)] for i in range(S)],
                      np.int32)
    tokens = np.full((S, B), cfg.mask_token_id, np.int32)
    masked = np.ones((S, B), bool)
    starts = np.zeros(S, np.int32)
    for i, p in enumerate(prompts):
        full = len(p) - len(p) % B
        L = 32
        padded = np.zeros(L, np.int32)
        padded[:full] = p[:full]
        pos = np.arange(L)
        rows = np.where(pos < P * ps, tables[i][np.minimum(pos // ps, P - 1)],
                        0)
        _, _, ck, cv, _ = lm.prefill(
            params, jnp.asarray(padded), ck, cv, jnp.asarray(rows),
            jnp.int32(full), jnp.asarray(pos % ps), cfg)
        tokens[i, :len(p) - full] = p[full:]
        masked[i, :len(p) - full] = False
        starts[i] = full
    state = (jnp.asarray(tokens), jnp.asarray(masked), jnp.asarray(starts),
             jnp.zeros(S, jnp.int32))
    active = jnp.ones(S, bool)
    # a slot's passes with a mask-free input (the K/V made final) have no
    # counterpart in the cacheless reference: compare, slot by slot, the
    # reference's passes with the program's other passes, in order
    want = [[w for w in col if w is not None] for col in zip(
        *reference.passes(_file(cfg), params, prompts, steps, 96))]
    got = [[] for _ in prompts]
    while any(len(g) < len(w) for g, w in zip(got, want)):
        before = np.asarray(state[1])
        record, *state, ck, cv = lm.block_step(
            params, ck, cv, jnp.asarray(tables), active, *state, cfg)
        record = np.asarray(record)
        final = record[:, 2 * B].astype(bool)
        assert (final == ~before.any(1)).all()
        for i in range(S):
            after = record[i, B:2 * B].astype(bool)
            if final[i]:  # fills nothing, opens the next block
                assert not after.any() and np.asarray(state[1])[i].all()
                assert int(state[2][i]) == starts[i] + B
                starts[i] += B
            else:
                got[i].append((before[i].tolist(), np.flatnonzero(
                    before[i] & ~after).tolist(), record[i, :B].tolist()))
    for g, w in zip(got, want):
        for (masked_before, filled, block), ref in zip(g, w):
            assert masked_before == ref["masked"]
            assert filled == sorted(ref["filled"])
            for j in filled:
                top, gaps = ref["top"][j], ref["gaps"][j]
                assert block[j] == top[0] or (
                    block[j] in top and gaps[top.index(block[j])] < 10 * TOL)


# -- through the engine ----------------------------------------------------------

@pytest.mark.parametrize("strategy", STRATEGIES)
def test_engine_generates_the_references_tokens(params, strategy):
    """Prompts whose tails hold 0..3 tokens of the first block, at once
    in one batch, to a budget that is no multiple of the block; the
    counters tell passes, tokens and prefills apart."""
    cfg = _cfg(strategy)
    prompts = _prompts([12, 9, 18, 23, 3])  # r = 0, 1, 2, 3, and no prefill
    engine = _engine(params, cfg, max_slots=8)
    try:
        reqs = [engine.submit(p, SamplingParams(max_tokens=10))
                for p in prompts]
        got = [_drain(r) for r in reqs]
        st = engine.stats()
    finally:
        engine.stop()
    assert got == _expected(cfg, params, prompts, 10)
    assert st["tokens_generated"] == 50 and st["admitted"] == 5
    assert st["prefills"] == 4  # 3 tokens are no whole block: no program
    assert st["decode_steps"] >= st["block_slot_passes"] / 5
    # what was emitted was filled first; a block's last masks may be
    # filled past max_tokens
    assert st["masks_filled"] >= 50 and st["experts_read"] > 0
    if strategy == "sequential":
        # 2 filling passes and a final one a block of 4: 4 / 3, less what
        # the prompts' tails and the last, cut, blocks take off
        assert 1.0 < st["tokens_generated"] / st["block_slot_passes"] < 4 / 3


def test_a_stop_token_cuts_in_position_order(params):
    cfg = _cfg()
    prompt = _prompts([14])[0]
    want = _expected(cfg, params, [prompt], 12)[0]
    engine = _engine(params, cfg)
    try:
        got = engine.generate(prompt, SamplingParams(
            max_tokens=12, stop_token_ids=(want[5],)))
    finally:
        engine.stop()
    assert got == want[:want.index(want[5])]


def test_a_prefix_hit_inside_a_block_is_rounded_down_to_the_block(params):
    """A second prompt shares 13 tokens with a resident one: one page of 8
    and 5 tokens of the next.  The fifth was computed beside other tokens
    of its block, so the hit ends at 12; the tokens are the reference's."""
    cfg = _cfg()
    first = _prompts([22])[0]
    second = first[:13] + _prompts([9], seed=9)[0]
    engine = _engine(params, cfg)
    try:
        engine.generate(first, SamplingParams(max_tokens=4))
        saved = engine.stats()["prefill_tokens_saved"]
        got = engine.generate(second, SamplingParams(max_tokens=9))
        st = engine.stats()
    finally:
        engine.stop()
    assert st["prefill_tokens_saved"] - saved == 12 and st["cow_copies"] == 1
    assert got == _expected(cfg, params, [second], 9)[0]


def test_preempted_requests_resume_to_the_same_tokens(params, monkeypatch):
    """Three requests against a pool too small for them: one is preempted
    with a block open and resumes from its last final block."""
    monkeypatch.setenv("RTPU_DEBUG_ALLOCATOR", "1")
    cfg = _cfg()
    prompts = _prompts([6, 7, 13], seed=6)
    engine = _engine(params, cfg, num_pages=12, max_seq_len=64)
    try:
        reqs = [engine.submit(p, SamplingParams(max_tokens=26))
                for p in prompts]
        got = [_drain(r) for r in reqs]
        assert engine.stats()["preempted"] > 0
    finally:
        engine.stop()
    assert got == _expected(cfg, params, prompts, 26)


def test_a_block_must_lie_in_one_page(params):
    with pytest.raises(ValueError, match="a block lies in one page"):
        _engine(params, _cfg(), page_size=6)


# -- the benchmark's side ----------------------------------------------------------

def test_family_counts_at_the_published_sizes():
    from benchmarks import common
    from benchmarks.families import sdar_moe as f

    c = common.load_json("configs", "sdar30b_a3b_serve_1chip.json")
    assert f.expert_params(c) == 3 * 2048 * 768
    assert f.params_per_layer(c) == 623_120_640
    full = {**c, "num_hidden_layers": 48}
    assert abs(f.n_params(full) - 30.53e9) < 1e7  # "30B"
    active = 48 * f.active_matmul_params_per_layer(c) + 151936 * 2048
    assert abs(active - 3.04e9) < 1e7  # "A3B"
    assert abs(f.weight_bytes(c) - 11.21e9) < 1e7  # 8 layers, bf16
    assert f.kv_bytes_per_token(c) == 16 * 1024
    # uniform routing would reach every expert with 32 blocks of 4 rows x
    # top-8; the reference's router, whose tokens of one sequence choose
    # alike, reaches fewer
    assert 127.9 < f.expected_experts_hit(c, 32 * 4 * 8) < 128
    assert 118 < f.experts_reached_by_blocks(c, 16, 8) < 119
    assert 122 < f.experts_reached_by_blocks(c, 21.3, 10.7) < 124
    assert f.experts_reached_by_prompt(128) == 51.47
    assert 47 < f.experts_reached_by_prompt(96) < 48
    call = f.expert_bytes_per_call(c, 128)
    assert 0.99 < call / (128 * f.expert_params(c) * 2) < 1.01
    # and the experts are most of what a pass has to read
    assert 0.85 < 8 * call / f.pass_bytes(c, 32, 16384) < 0.95
    cfg = f.model_config(c)
    assert (cfg.n_experts, cfg.experts_per_token, cfg.block_length,
            cfg.denoising_steps, cfg.remasking_strategy) == (
                128, 8, 4, 2, "sequential")


def test_verify_judges_tokens_on_their_own_history(params):
    """``reference.verify``: the reference's own tokens lie 0 under its
    best everywhere; with one token swapped for the runner-up that position
    reads the runner-up's gap and the LATER ones are judged on the history
    that holds the swap (``greedy``'s comparison would have ended there);
    a token that is missing reads None."""
    cfg = _cfg()
    c, prompts = _file(cfg), _prompts([9, 14])
    cands, gaps = reference.greedy(c, params, prompts, 10, 96)
    own = [[x[0] for x in row] for row in cands]
    assert reference.verify(c, params, prompts, own, 10, 96) == [[0.0] * 10] * 2
    theirs = [list(own[0]), own[1][:7]]
    theirs[0][2] = cands[0][2][1]
    got = reference.verify(c, params, prompts, theirs, 10, 96)
    assert got[0][2] == pytest.approx(gaps[0][2][1], abs=TOL)
    assert got[0][:2] == [0.0, 0.0] and got[1] == [0.0] * 7 + [None] * 3
    # what follows the swap is another sequence: the reference's old
    # tokens are no longer all its best there
    assert max(got[0][3:]) > 0.0


@pytest.mark.parametrize("fault,least", [(None, 0.0), ("bf16", 1e-3),
                                         ("shifted", 0.1)])
def test_pinned_logits_see_precision_and_expert_identity(params, fault, least):
    """The benchmark's logit comparison (``in_worker_routed.pinned_check``:
    the program's layers handed the reference's expert sets): float32
    against float32 it differs by rounding alone, whatever the router's
    near-ties; weights rounded to bf16 or every expert index off by one
    show at once."""
    from benchmarks import in_worker_routed
    from benchmarks.families import sdar_moe as family

    cfg = _cfg()
    c = {**_file(cfg), "vocab_size": VOCAB, "hidden_size": cfg.d_model,
         "num_hidden_layers": cfg.n_layers,
         "moe_intermediate_size": cfg.d_expert,
         "max_position_embeddings": cfg.max_seq_len, "dtype": "float32"}
    prompts = _prompts([20, 33, 41], seed=5)
    served = params
    if fault == "bf16":
        served = jax.tree.map(
            lambda w: w.astype(jnp.bfloat16).astype(w.dtype), params)
    if fault == "shifted":
        experts = jax.tree.map(lambda w: jnp.roll(w, 1, axis=1),
                               params["layers"]["experts"])
        served = {**params, "layers": {**params["layers"],
                                       "experts": experts}}

    class Family:  # the reference reads ``params``, the program ``served``
        pinned_logits = staticmethod(
            lambda c, p, *a: family.pinned_logits(c, served, *a))

    got = in_worker_routed.pinned_check(c, params, Family, reference, prompts)
    assert got["positions"] == 3 * in_worker_routed.PINNED["rows"]
    assert 0.5 < got["logit_rms"] < 2.0
    if fault is None:
        assert got["logit_max_error"] < TOL
    else:
        assert got["logit_rms_error"] > least


def test_metric_readers_find_nothing_in_a_program_without_the_passes():
    """The new readers against the context of a run whose program has no
    ``jit_block_step``, no ``moe_grouped_mlp`` and no pass counts in its
    spans (the parent of the PR that added them): None, never an error;
    and against one that has them."""
    from benchmarks import common

    c = common.load_json("configs", "sdar30b_a3b_serve_1chip.json")
    names = ("block_pass_ms", "tokens_per_slot_pass",
             "moe_expert_hbm_roofline_share", "moe_pass_share")
    readers = {n: common.module("layer_metrics", n).read for n in names}
    t0 = 1000.0
    bare = {"config": c, "seconds": 10.0, "window": {"t0_wall": t0},
            "notes": [], "peaks": {"hbm_bytes_per_s": 819e9},
            "spans": [{"name": "llm.loop.decode_emit", "end_ts": t0 + 1,
                       "args": {"tokens": 8, "slots_released": 0}}],
            "device_trace": {
                "modules": {"jit_decode_step_greedy": {"count": 3,
                                                       "seconds": 0.06}},
                "ops": {"fusion.1": {"count": 3, "seconds": 0.01}},
                "wall_start": t0, "wall_started": t0, "t_lo_s": 0.0,
                "t_hi_s": 4.0}}
    assert {n: r(bare) for n, r in readers.items()} == dict.fromkeys(names)
    assert all(r({**bare, "device_trace": None, "spans": []}) is None
               for r in readers.values())
    full = {**bare, "spans": [
        {"name": "llm.loop.decode_emit", "end_ts": t0 + 1, "args": {
            "tokens": 256, "slots_released": 0, "slot_passes": 8 * 24,
            "masks_filled": 256, "blocks_final": 8 * 8,
            "experts_read": 8 * 8 * 110, "passes": 8}}],
        "max_slots": 32,
        "device_trace": {**bare["device_trace"], "modules": {
            "jit_block_step": {"count": 8, "seconds": 0.128}},
            "ops": {"moe_grouped_mlp.9": {"count": 64, "seconds": 0.1}}}}
    got = {n: r(full) for n, r in readers.items()}
    assert got["block_pass_ms"] == pytest.approx(16.0)
    assert got["tokens_per_slot_pass"] == pytest.approx(4 / 3)
    assert got["moe_pass_share"] == pytest.approx(100 * 0.1 / 0.128)
    # a pass of 16 blocks with masks (12.4 experts each by the reference's
    # router) and 8 without (14.3) has to reach 118.3 of 128 experts a layer
    # whatever the program says it read: 64 calls of 1.12 GB in 0.1 s
    # against 819 GB/s
    assert got["moe_expert_hbm_roofline_share"] == pytest.approx(87.7, abs=0.3)
