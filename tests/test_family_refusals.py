"""What a family DECLARES it cannot be served with (``cfg.refuses``: feature
-> why) is what the engine refuses, in the family's own sentence, or does not
build; what it does not declare, the engine serves.  One test over (family,
path): a family that lists a refusal the engine does not enforce fails here,
and so does an engine that refuses what no family listed."""

import re

import jax
import pytest

from ray_tpu.llm import model as lm
from ray_tpu.llm.engine import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.models import (afmoe, falcon_h1, glm_moe_lite, llama,
                            longcat_flash, minicpm_sala, nemotron_h,
                            olmo_hybrid, sdar_moe)

VOCAB = 128

# family -> (module, configuration, the words its refusals have always begun
# with)
FAMILIES = {
    "llama": (llama, llama.LlamaConfig.tiny(VOCAB), None),
    "sdar_moe": (sdar_moe, sdar_moe.SDARMoEConfig.tiny(VOCAB),
                 "SDARMoEConfig generates by diffusion over blocks of 4"),
    "olmo_hybrid": (olmo_hybrid, olmo_hybrid.OlmoHybridConfig.tiny(VOCAB),
                    "OlmoHybridConfig has recurrent layers .* does not "
                    "serve with"),
    "glm_moe_lite": (glm_moe_lite, glm_moe_lite.GLMMoELiteConfig.tiny(VOCAB),
                     "GLMMoELiteConfig caches latent rows"),
    # (a chip's share of the experts; latent rows as glm_moe_lite's, and
    # refused what it refuses, by the same sentences)
    "longcat_flash": (longcat_flash, longcat_flash.LongCatFlashConfig.tiny(
        VOCAB, n_experts_held=4, first_expert_held=8),
        "LongCatFlashConfig caches latent rows"),
    "afmoe": (afmoe, afmoe.AfmoeConfig.tiny(VOCAB),
              "AfmoeConfig keeps a window layer's pages only while"),
    # (pages of 16 below: a pooled row a page, a block four pages)
    "minicpm_sala": (minicpm_sala, minicpm_sala.MiniCPMSALAConfig.tiny(
        VOCAB, kernel_size=32, kernel_stride=16, block_size=64,
        window_size=128, dense_len=384),
        "MiniCPMSALAConfig keeps a state row a slot .* does not serve with"),
    # (state rows AND pages in every layer; a prompt in chunks carries the
    # state and the convolution's tail, as minicpm_sala's carries its state)
    "falcon_h1": (falcon_h1, falcon_h1.FalconH1Config.tiny(VOCAB),
                  "FalconH1Config has a recurrent mixer in every block .* "
                  "does not serve with"),
    # (layers that are one thing each: state rows for the mixers, pages for
    # the attention layers, a share of the experts; chunks carry the state)
    "nemotron_h": (nemotron_h, nemotron_h.NemotronHConfig.tiny(VOCAB),
                   "NemotronHConfig has recurrent mixer layers .* does not "
                   "serve with"),
}

# path -> (the feature it needs, where the refusal says it was asked)
PATHS = {
    "temperature": ("sampling", "temperature 0.7"),
    "prefill_extract": ("pd", "prefill_extract"),
    "submit_with_kv": ("pd", "submit_with_kv"),
    "kv_prehydrate": ("kv_tier", "kv_prehydrate"),
    "kv_tier": ("kv_tier", None),  # built, or let go
    "prefix_cache": ("prefix_cache", "prefill_with_prefix"),
    # a prompt over the largest prefill bucket (64 below), computed in
    # chunks or refused; and the program the later chunks run
    "chunked_prompt": ("chunked_prompt", "a prompt of 100 tokens, over the "
                       "largest prefill bucket 64,"),
    "suffix_prefill": ("suffix_prefill", "prefill_with_prefix"),
}
PROMPT = [5, 6, 7, 8, 9]


@pytest.fixture(scope="module")
def trees():
    return {name: module.init(cfg, jax.random.PRNGKey(0))
            for name, (module, cfg, _) in FAMILIES.items()}


def _drain(req):
    out = []
    while (item := req.out_queue.get(timeout=120)) is not None:
        assert not isinstance(item, Exception), item
        out.append(item)
    return out


def _ask(engine, path, refused):
    if path == "temperature":
        return engine.submit(PROMPT, SamplingParams(temperature=0.7))
    if path == "kv_prehydrate":
        return engine.kv_prehydrate([])
    if path == "chunked_prompt":
        if not refused:
            engine.start()
        got = _drain(engine.submit(list(range(5, 105)),
                                   SamplingParams(max_tokens=3)))
        assert len(got) == 3 and engine.stats()["prefill_chunks"] == 2
        return
    if path == "submit_with_kv" and refused:  # nothing to ship it
        return engine.submit_with_kv(PROMPT, 9, None, None)
    first, kv_k, kv_v, n = engine.prefill_extract(PROMPT)
    assert n == len(PROMPT) and kv_k.shape == kv_v.shape
    if path == "submit_with_kv":
        got = _drain(engine.submit_with_kv(
            PROMPT, first, kv_k, kv_v, SamplingParams(max_tokens=3)))
        assert len(got) == 2  # the shipped token was delivered elsewhere


def test_every_family_declares_only_features_this_test_asks_for():
    asked = {feature for feature, _ in PATHS.values()}
    for _, cfg, _ in FAMILIES.values():
        assert set(cfg.refuses) <= asked
    assert FAMILIES["llama"][1].refuses == {}
    # state beside the pages, and yet a prompt in chunks: the chunks carry it
    for chunks_carry in ("minicpm_sala", "falcon_h1", "nemotron_h"):
        assert set(FAMILIES[chunks_carry][1].refuses) == {
            "pd", "kv_tier", "prefix_cache"}


@pytest.mark.parametrize("path", list(PATHS))
@pytest.mark.parametrize("family", list(FAMILIES))
def test_the_engine_refuses_what_the_family_declares_and_nothing_else(
        trees, family, path):
    """Greedy only and no P/D for block diffusion (a prefill yields no
    first token to ship); no P/D, no KV tier and no prefix index beside
    recurrent state (pages carry nothing of the state at their end); no
    P/D and no KV tier over latent rows (they ship K and V pages).  A
    dense Llama refuses nothing and builds both the index and the tier."""
    (_, cfg, begins), (feature, where) = FAMILIES[family], PATHS[path]
    refused = feature in cfg.refuses
    tier = object() if feature == "kv_tier" else None
    engine = LLMEngine(trees[family], cfg, EngineConfig(
        max_slots=2, num_pages=32, page_size=16, max_seq_len=128,
        prefill_buckets=(64,) if path == "chunked_prompt" else (64, 128)),
        kv_tier=tier)
    try:
        if path == "kv_tier":
            # a server hands every engine its worker's tier unasked
            assert engine.kv_tier is (None if refused else tier)
        elif path == "prefix_cache":
            assert (engine.prefix_cache is None) == refused
        elif path == "suffix_prefill":
            if refused:  # the program that continues from pages says why
                with pytest.raises(ValueError) as e:
                    lm.refuse(cfg, feature, where)
                assert "no model with recurrent layers" in str(e.value)
            else:
                lm.refuse(cfg, feature, where)
        elif not refused:
            _ask(engine, path, refused)
        else:
            with pytest.raises(ValueError) as e:
                _ask(engine, path, refused)
            said = str(e.value)
            assert said == cfg.refuses[feature].format(cfg=cfg, where=where)
            assert re.match(begins, said) and where in said
            assert engine._thread is None  # refused before anything started
    finally:
        engine.stop()
