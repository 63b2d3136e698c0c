"""Closed loop: a fixed pool of workers, each sending its next request when
the last one has finished (offline generation, agents).  The server is kept
saturated: more clients than decode slots.

Parameters: ``clients``; ``prompt`` {min, max} uniform, unshared;
``output`` {median, sigma, min, max} lognormal; ``lead_s`` for which the
clients already run before the window opens, so that every slot is full;
``per_client`` requests prepared for each client.  The first request of
each client is cut to a uniform share of its length (its residual life), so
that the clients are out of phase from the start as they are in steady
state, and do not finish in waves.
"""

from __future__ import annotations

import numpy as np

from benchmarks.generators import _common as g

RUNNER = "serve"


def generate(p: dict, seed: int, seconds: float, engine: dict,
             vocab: int) -> dict:
    n, per = int(p["clients"]), int(p["per_client"])
    # round k (the k-th request of every client) has the same lengths for
    # every seed, dealt to the clients in another order
    r1, r2 = g.rng(seed, 1), g.rng(seed, 2)
    pl = np.stack([g.stratified(r1, g.uniform_quantiles(p["prompt"]), n)
                   for _ in range(per)], 1)
    ol = np.stack([g.stratified(r2, g.lognormal_quantiles(p["output"]), n)
                   for _ in range(per)], 1)
    residual = g.stratified(g.rng(seed, 3),
                            lambda q: 0.05 + 0.95 * q, n)
    tok = g.rng(seed, 4)
    firsts = g.first_tokens(g.rng(seed, 6), n * per, vocab, "traffic")
    clients = []
    for c in range(n):
        reqs = []
        for k in range(per):
            out = int(ol[c, k])
            if k == 0:
                out = max(8, int(out * residual[c]))
            prompt = g.tokens(tok, pl[c, k], vocab)
            prompt[0] = firsts[c * per + k]
            reqs.append({"id": c * per + k, "prompt": prompt,
                         "max_tokens": out})
        clients.append(reqs)
    cold = {g.bucket_for(int(x), engine["prefill_buckets"])
            for x in pl.flat}
    return {"mode": "closed", "clients": clients, "lead_s": float(p["lead_s"]),
            "warmup": g.warmup_requests(
                g.rng(seed, 5), vocab, cold,
                max_seq_len=engine["max_seq_len"]),
            "prime": [], "drain_s": 0.0}
