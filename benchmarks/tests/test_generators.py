"""Every generator is a pure function of (seed, parameters)."""

import json

import numpy as np
import pytest

from benchmarks import common
from benchmarks.generators import (_common, closed_loop, open_loop, sessions,
                                   train_packed)

ENGINE = common.load_json("configs", "mistral7b_serve_1chip.json")["engine"]
VOCAB = 32768
MIXES = {"long_prompt": open_loop, "long_output": closed_loop,
         "chat_sessions": sessions}


def mix(name):
    return common.load_json("traffic", name + ".json")


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_schedule(name):
    a = MIXES[name].generate(mix(name), 7, 40.0, ENGINE, VOCAB)
    b = MIXES[name].generate(mix(name), 7, 40.0, ENGINE, VOCAB)
    c = MIXES[name].generate(mix(name), 8, 40.0, ENGINE, VOCAB)
    assert json.dumps(a) == json.dumps(b)  # times, lengths and token ids
    assert json.dumps(a) != json.dumps(c)


def _all_requests(s):
    return s.get("requests") or [r for c in s["clients"] for r in c]


@pytest.mark.parametrize("name", sorted(MIXES))
def test_program_receives_only_generated_inputs(name):
    """Requests are token ids and a token budget, nothing that names the
    cell; ids stay inside the vocabulary and clear of the special ids."""
    s = MIXES[name].generate(mix(name), 1, 40.0, ENGINE, VOCAB)
    for r in _all_requests(s) + s["warmup"] + s["prime"]:
        assert set(r) <= {"id", "due_s", "prompt", "max_tokens", "warm",
                          "session", "turn", "tenant"}
        assert min(r["prompt"]) >= _common.FIRST_TOKEN_ID
        assert max(r["prompt"]) < VOCAB
        assert len(r["prompt"]) + r["max_tokens"] <= ENGINE["max_seq_len"]


def test_open_loop_clips_and_rate():
    p = mix("long_prompt")
    s = open_loop.generate(p, 3, 200.0, ENGINE, VOCAB)
    lens = [len(r["prompt"]) for r in s["requests"]]
    outs = [r["max_tokens"] for r in s["requests"]]
    assert min(lens) >= p["prompt"]["min"] and max(lens) <= p["prompt"]["max"]
    assert min(outs) >= p["output"]["min"] and max(outs) <= p["output"]["max"]
    assert abs(np.median(lens) - p["prompt"]["median"]) < 80
    in_window = [r for r in s["requests"] if 0 <= r["due_s"] < 200.0]
    assert abs(len(in_window) / 200.0 - p["rate_per_s"]) < 0.15 * p["rate_per_s"]
    assert s["requests"][0]["due_s"] >= -p["lead_s"]
    # every bucket the prompts reach is warmed, and no other
    reach = {_common.bucket_for(n, ENGINE["prefill_buckets"]) for n in lens}
    assert {w["warm"] for w in s["warmup"]} == {f"cold:{b}" for b in reach}


def test_closed_loop_clients_and_clips():
    p = mix("long_output")
    s = closed_loop.generate(p, 3, 40.0, ENGINE, VOCAB)
    assert len(s["clients"]) == p["clients"] == 48
    later = [r for c in s["clients"] for r in c[1:]]
    assert min(r["max_tokens"] for r in later) >= p["output"]["min"]
    assert max(r["max_tokens"] for r in later) <= p["output"]["max"]
    firsts = [c[0]["max_tokens"] for c in s["clients"]]
    assert len(set(firsts)) > 24  # out of phase from the start
    lens = [len(r["prompt"]) for c in s["clients"] for r in c]
    assert min(lens) >= p["prompt"]["min"] and max(lens) <= p["prompt"]["max"]
    assert len({w["warm"] for w in s["warmup"]}) <= 3  # 3 buckets, not 8


def test_sessions_tenants_prefixes_and_working_set():
    p = mix("chat_sessions")
    ps = ENGINE["page_size"]
    systems, sess = sessions._sessions(p, 5, 300.0, VOCAB, ps)
    assert len(systems) == p["tenants"]
    for sp in systems:  # deliberately not page-aligned
        assert len(sp) % ps != 0
        assert p["system_prompt"]["min"] <= len(sp) <= p["system_prompt"]["max"] + ps
    counts = np.zeros(p["tenants"])
    for turns in sess:
        if turns:
            counts[turns[0]["tenant"]] += 1
    shares = counts / counts.sum()
    want = sessions.tenant_shares(p["tenants"], p["zipf_s"])
    assert abs(shares[0] - want[0]) < 0.06 and shares[0] > 3 * shares[-1]
    for turns in sess:
        for a, b in zip(turns, turns[1:]):
            # a later turn extends the previous prompt by the answer's
            # length of filler and the new user message
            assert b["prompt"][:len(a["prompt"])] == a["prompt"]
            grown = len(b["prompt"]) - len(a["prompt"]) - a["max_tokens"]
            assert p["user"]["min"] <= grown <= p["user"]["max"]
            assert b["due_s"] - a["due_s"] >= p["think_s"]["min"]
        for t in turns:
            assert len(t["prompt"]) + t["max_tokens"] <= p["max_context"]
            assert t["prompt"][:len(systems[t["tenant"]])] == systems[t["tenant"]]
    pool = (ENGINE["num_pages"] - 1) * ps
    ws = [sessions.working_set_tokens(p, seed, 40.0, ENGINE, VOCAB)
          for seed in (0, 5, 9, 11)]
    gross = np.mean([w["gross"] for w in ws]) / pool
    assert 0.9 <= gross <= 1.4, gross  # the pool is under pressure
    assert all(w["deduplicated"] < w["gross"] for w in ws)


def test_sessions_prime_what_is_mid_conversation():
    p = mix("chat_sessions")
    s = sessions.generate(p, 5, 40.0, ENGINE, VOCAB)
    assert len(s["prime"]) > p["tenants"]
    assert all(r["max_tokens"] == 1 for r in s["prime"])
    assert s["requests"][0]["due_s"] >= -p["lead_s"]
    assert any(r["turn"] > 0 and r["due_s"] < 5.0 for r in s["requests"])
    warm = {w["warm"] for w in s["warmup"]}
    assert "prefix:seed" in warm and "cold:2048" in warm


def test_train_packed_is_seeded_and_packed():
    p = common.load_json("traffic", "packed_4k.json")
    a = train_packed.pack_shard(p, 1, 3, VOCAB)
    assert a.shape == (p["seqs_per_shard"], p["seq_len"] + 1)
    assert np.array_equal(a, train_packed.pack_shard(p, 1, 3, VOCAB))
    assert not np.array_equal(a, train_packed.pack_shard(p, 1, 4, VOCAB))
    assert not np.array_equal(a, train_packed.pack_shard(p, 2, 3, VOCAB))
    assert a.min() >= _common.FIRST_TOKEN_ID and a.max() < VOCAB
    job = train_packed.generate(p, 1, 40.0, None, VOCAB)
    assert job["batch_rows"] * p["seq_len"] == p["global_batch_tokens"] == 32768
    r = _common.rng(0, 9)
    docs = _common.lognormal_clipped(r, p["doc"], 20000)
    assert docs.min() >= p["doc"]["min"] and docs.max() <= p["doc"]["max"]
    assert abs(np.median(docs) - p["doc"]["median"]) < 40


def test_warmup_prefix_pairs_share_an_unaligned_prefix():
    reqs = _common.warmup_requests(_common.rng(0, 5), VOCAB, [64],
                                   [64, 128], page_size=16)
    seed = next(r for r in reqs if r["warm"] == "prefix:seed")
    for b in (64, 128):
        r = next(r for r in reqs if r["warm"] == f"prefix:{b}")
        shared = 0
        while r["prompt"][shared] == seed["prompt"][shared]:
            shared += 1
        assert shared == 56 and shared % 16 != 0
        assert b // 2 < len(r["prompt"]) - shared <= b
