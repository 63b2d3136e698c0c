"""``open_loop``'s schedule from the same parameters (the same draws: its
helpers, stream for stream), for a model served over pools by layer type
whose prompts are LONGER than the largest prefill bucket and are computed in
chunks of it: such a cell is run by ``runners/serve_windowed.py``.

``open_loop`` itself cannot make this cell's schedule: it chooses the
buckets to warm with ``bucket_for`` over the prompts' lengths, which raises
at a prompt over the largest bucket.  What a chunked prompt runs is
``jit_prefill`` at the largest bucket C (its first chunk), then
``jit_prefill_with_prefix`` at C (the chunks between) and at whichever
bucket its LAST chunk pads to, so the warm-up here is one request a bucket
a last chunk can take, each a prompt of one whole chunk and that bucket's
tail (C + b - 1 tokens: two programs), one request of three chunks (the
middle one at C), and a cold prefill of every bucket a prompt UNDER C can
take.  Nothing then compiles inside the window.
"""

from __future__ import annotations

from benchmarks.generators import _common as g

RUNNER = "serve_windowed"


def warmup_requests(r, vocab: int, buckets, short_buckets,
                    max_seq_len: int) -> list:
    """One request for every program the traffic can reach (each asks for a
    second token alone, which runs one whole burst of the decode path)."""
    C = int(buckets[-1])
    lengths = [(f"cold:{b}", b - 1) for b in sorted(set(short_buckets))]
    lengths += [(f"chunks:{C}+{b}", C + b - 1) for b in buckets]
    lengths.append((f"chunks:{C}x3", 3 * C))
    reqs = [{"prompt": g.tokens(r, min(n, max_seq_len - 2), vocab),
             "max_tokens": 2, "warm": warm} for warm, n in lengths]
    for req, t in zip(reqs, g.first_tokens(r, len(reqs), vocab, "warmup")):
        req["prompt"][0] = t
    return reqs


def generate(p: dict, seed: int, seconds: float, engine: dict,
             vocab: int) -> dict:
    # ``schedule_seed`` in the mix fixes WHEN requests arrive and how long
    # they are; ``seed`` then only draws the token ids (as ``open_loop``)
    sched = p.get("schedule_seed", seed)
    arrivals = g.poisson_times(g.rng(sched, 1), p["rate_per_s"],
                               -float(p["lead_s"]), float(seconds))
    n = len(arrivals)
    pl = g.stratified(g.rng(sched, 2), g.lognormal_quantiles(p["prompt"]), n)
    ol = g.stratified(g.rng(sched, 3), g.uniform_quantiles(p["output"]), n)
    tok = g.rng(seed, 4)
    requests = [{"id": i, "due_s": t, "prompt": g.tokens(tok, pl[i], vocab),
                 "max_tokens": int(ol[i])}
                for i, t in enumerate(arrivals)]
    for req, t in zip(requests, g.first_tokens(g.rng(seed, 6), n, vocab,
                                               "traffic")):
        req["prompt"][0] = t
    buckets = [int(b) for b in engine["prefill_buckets"]]
    short = {g.bucket_for(int(x), buckets) for x in pl if x <= buckets[-1]}
    return {"mode": "open", "requests": requests,
            "warmup": warmup_requests(g.rng(seed, 5), vocab, buckets, short,
                                      engine["max_seq_len"]),
            "prime": [], "drain_s": float(p["drain_s"])}
