"""Open loop: independent users.  Requests arrive by a Poisson process at a
fixed rate whatever the server does, each with a fresh (unshared) prompt.
The process is conditioned on its count and the lengths are stratified
(``_common.poisson_times``, ``stratified``): every seed offers the same
work, at other instants and in another order.

Parameters (``traffic/<mix>.json``): ``rate_per_s``; ``prompt`` {median,
sigma, min, max} lognormal; ``output`` {min, max} uniform; ``lead_s`` of
arrivals before the window opens (steady state, not counted); ``drain_s``.
"""

from __future__ import annotations

from benchmarks.generators import _common as g

RUNNER = "serve"


def generate(p: dict, seed: int, seconds: float, engine: dict,
             vocab: int) -> dict:
    # ``schedule_seed`` in the mix fixes WHEN requests arrive and how long
    # they are, as a trace drawn once from the process; ``seed`` then only
    # draws the token ids.  Without it the schedule follows the seed too.
    sched = p.get("schedule_seed", seed)
    arrivals = g.poisson_times(g.rng(sched, 1), p["rate_per_s"],
                               -float(p["lead_s"]), float(seconds))
    n = len(arrivals)
    # the same lengths for every seed, in another order
    pl = g.stratified(g.rng(sched, 2), g.lognormal_quantiles(p["prompt"]), n)
    ol = g.stratified(g.rng(sched, 3), g.uniform_quantiles(p["output"]), n)
    tok = g.rng(seed, 4)
    requests = [{"id": i, "due_s": t, "prompt": g.tokens(tok, pl[i], vocab),
                 "max_tokens": int(ol[i])}
                for i, t in enumerate(arrivals)]
    for req, t in zip(requests, g.first_tokens(g.rng(seed, 6), n, vocab,
                                               "traffic")):
        req["prompt"][0] = t
    cold = {g.bucket_for(int(x), engine["prefill_buckets"]) for x in pl}
    return {"mode": "open", "requests": requests,
            "warmup": g.warmup_requests(
                g.rng(seed, 5), vocab, cold,
                max_seq_len=engine["max_seq_len"]),
            "prime": [], "drain_s": float(p["drain_s"])}
