"""Open loop over multi-turn chat sessions behind tenants' system prompts.

Sessions arrive by a Poisson process.  A session belongs to a tenant drawn
by Zipf popularity; its first prompt is the tenant's system prompt plus the
user's first message; every later turn's prompt is the previous prompt, a
seeded filler as long as the answer that was asked for, and the new user
message.  (The filler stands in for the model's answer: the schedule stays
a pure function of the seed, and the next prompt does not wait for what the
server happened to generate.)  A turn is due a think time after the
previous one was due plus the time its answer is allowed; it is sent then,
whatever the server does.

Parameters: ``turn_rate_per_s`` (sessions arrive at this over the mean
number of turns); ``tenants``, ``zipf_s``; ``system_prompt`` {min, max}
uniform, never a multiple of the page size; ``turns`` {min, max};
``user`` and ``output`` {median, sigma, min, max} lognormal; ``think_s``
{min, max}; ``answer_token_s`` allowed per answer token before the user
reads; ``max_context``; ``drain_s``.

Sessions that are mid-conversation when the window opens began before it:
the generator starts the arrival process one longest-session earlier, and
returns, under ``prime``, the last prompt each such session sent before the
window (one token of output each), which set-up sends so that the cache
holds what it would hold.  Turns due inside the window are the requests.
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators import _common as g

RUNNER = "serve"


def tenant_shares(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def _sessions(p: dict, seed: int, seconds: float, vocab: int,
              page_size: int):
    """Every session that has a turn due in [-lead, seconds)."""
    turns_mean = (p["turns"]["min"] + p["turns"]["max"]) / 2.0
    longest = p["turns"]["max"] * (
        p["think_s"]["max"] + p["output"]["max"] * p["answer_token_s"])
    sched = p.get("schedule_seed", seed)  # as in open_loop.py
    starts = g.poisson_times(g.rng(sched, 1),
                             p["turn_rate_per_s"] / turns_mean,
                             -longest, float(seconds))
    r_sys, r_s, r_tok = g.rng(seed, 2), g.rng(sched, 3), g.rng(seed, 4)
    systems, r_len = [], g.rng(sched, 5)
    for _ in range(int(p["tenants"])):
        n = int(r_len.integers(p["system_prompt"]["min"],
                               p["system_prompt"]["max"] + 1))
        if n % page_size == 0:
            n += page_size // 2 - 1
        systems.append(g.tokens(r_sys, n, vocab))
    for sp, t in zip(systems, g.first_tokens(r_sys, len(systems), vocab,
                                             "traffic")):
        sp[0] = t  # tenants share nothing with one another
    shares = tenant_shares(int(p["tenants"]), float(p["zipf_s"]))
    out = []
    for sid, t0 in enumerate(starts):
        tenant = int(r_s.choice(len(systems), p=shares))
        n_turns = int(r_s.integers(p["turns"]["min"], p["turns"]["max"] + 1))
        user = g.lognormal_clipped(r_s, p["user"], n_turns)
        outs = g.lognormal_clipped(r_s, p["output"], n_turns)
        think = r_s.uniform(p["think_s"]["min"], p["think_s"]["max"],
                            n_turns)
        prompt, due, turns = list(systems[tenant]), t0, []
        for k in range(n_turns):
            prompt = prompt + g.tokens(r_tok, int(user[k]), vocab)
            if len(prompt) + int(outs[k]) > p["max_context"]:
                break
            turns.append({"session": sid, "turn": k, "tenant": tenant,
                          "due_s": float(due), "prompt": prompt,
                          "max_tokens": int(outs[k])})
            due += outs[k] * p["answer_token_s"] + think[k]
            prompt = prompt + g.tokens(r_tok, int(outs[k]), vocab)
        out.append(turns)
    return systems, out


def generate(p: dict, seed: int, seconds: float, engine: dict,
             vocab: int) -> dict:
    ps = int(engine["page_size"])
    systems, sessions = _sessions(p, seed, seconds, vocab, ps)
    lead = float(p["lead_s"])
    requests, prime = [], []
    for turns in sessions:
        before = [t for t in turns if t["due_s"] < -lead]
        later = [t for t in turns if t["due_s"] >= -lead]
        if before and later:  # mid-conversation when the lead begins
            prime.append({"prompt": before[-1]["prompt"], "max_tokens": 1})
        requests.extend(later)
    requests.sort(key=lambda t: t["due_s"])
    for i, t in enumerate(requests):
        t["id"] = i
    # every tenant's system prompt is resident when the window opens
    prime = [{"prompt": s + s[:1], "max_tokens": 1} for s in systems] + prime
    buckets = engine["prefill_buckets"]
    longest_new = p["user"]["max"] + p["output"]["max"] + ps
    prefix = [b for b in buckets if b <= g.bucket_for(longest_new, buckets)]
    cold = [b for b in buckets
            if b <= g.bucket_for(p["max_context"], buckets)]
    return {"mode": "open", "requests": requests, "prime": prime,
            "warmup": g.warmup_requests(
                g.rng(seed, 5), vocab, cold, prefix, page_size=ps,
                max_seq_len=engine["max_seq_len"]),
            "drain_s": float(p["drain_s"])}


def working_set_tokens(p: dict, seed: int, seconds: float, engine: dict,
                       vocab: int) -> dict:
    """Tokens of context held at the middle of the window, to compare with
    the page pool.  ``gross``: every live session's whole context plus the
    tenants' system prompts (what the issue's 1.0-1.3 x pool counts).
    ``deduplicated``: the same with each system prompt counted once, which
    is what the pages need when every prefix is shared perfectly."""
    systems, sessions = _sessions(p, seed, seconds, vocab,
                                  int(engine["page_size"]))
    mid = seconds / 2.0
    gross = dedup = sum(len(s) for s in systems)
    for turns in sessions:
        sent = [t for t in turns if t["due_s"] <= mid]
        if sent and turns[-1]["due_s"] + p["think_s"]["max"] >= mid:
            gross += len(sent[-1]["prompt"])
            dedup += len(sent[-1]["prompt"]) - len(systems[sent[-1]["tenant"]])
    return {"gross": float(gross), "deduplicated": float(dedup)}
