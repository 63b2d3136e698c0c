"""Shared draws of the traffic generators.  Every generator is a pure
function of (parameters, seed): one ``numpy`` generator per purpose, made
from the seed and a fixed stream number, so that adding a draw to one
purpose never shifts another's."""

from __future__ import annotations

import math

import numpy as np

FIRST_TOKEN_ID = 3  # 0..2 are the byte tokenizer's pad, bos and eos


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def tokens(r: np.random.Generator, n: int, vocab: int) -> list:
    return r.integers(FIRST_TOKEN_ID, vocab, size=int(n)).tolist()


def first_tokens(r: np.random.Generator, n: int, vocab: int,
                 part: str) -> list:
    """``n`` DISTINCT first tokens from the range kept for ``part``.  The
    prefix cache matches a new prompt against every resident block that
    follows the same prefix, token by token: two "unshared" prompts that
    happen to begin with the same token share one token through a
    copy-on-write page copy and a resident-prefix prefill.  Prompts meant
    to be unshared therefore begin with tokens that differ, within a run
    and from the warm-up's and the correctness check's."""
    lo, hi = {"warmup": (FIRST_TOKEN_ID, vocab // 32),
              "check": (vocab // 32, vocab // 16),
              "traffic": (vocab // 16, vocab)}[part]
    if n > hi - lo:
        raise ValueError(f"{n} distinct first tokens asked of {hi - lo}")
    return (lo + r.choice(hi - lo, size=int(n), replace=False)).tolist()


def lognormal_clipped(r, spec: dict, size=None):
    """``spec``: {median, sigma, min, max}; whole numbers, clipped."""
    x = np.exp(r.normal(math.log(spec["median"]), spec["sigma"], size))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)


def lognormal_quantiles(spec: dict):
    """Quantile function of the same clipped lognormal, for ``stratified``."""
    from statistics import NormalDist

    def draw(q):
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        x = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
        return np.clip(np.rint(x), spec["min"], spec["max"]).astype(int)

    return draw


def uniform_quantiles(spec: dict):
    """Quantile function of whole numbers uniform on [min, max]."""
    return lambda q: np.floor(
        spec["min"] + q * (spec["max"] + 1 - spec["min"])).astype(int)


def bucket_for(n: int, buckets) -> int:
    for b in buckets:
        if n <= b:
            return int(b)
    raise ValueError(f"{n} tokens exceed the largest prefill bucket")


def poisson_times(r, rate: float, start: float, end: float) -> list:
    """Arrival instants of a Poisson process of ``rate``/s on [start, end),
    CONDITIONED ON ITS COUNT: exactly round(rate x length) arrivals, at
    sorted uniform instants (which is what a Poisson process is, given its
    count).  Every seed then offers the same amount of work and differs in
    when it arrives, so that runs compare."""
    n = int(round(rate * (end - start)))
    return np.sort(r.uniform(start, end, n)).tolist()


def stratified(r, draw, n: int):
    """``n`` values with the same spread of sizes for every seed: ``draw``
    maps quantiles in (0, 1) to values; the n mid-quantiles are taken, and
    the seed only shuffles them."""
    q = (np.arange(int(n)) + 0.5) / int(n)
    return r.permutation(draw(q))


def warmup_requests(r, vocab: int, cold_buckets, prefix_buckets=(),
                    page_size: int = 16, out_tokens: int = 2,
                    max_seq_len: int = 1 << 30) -> list:
    """One request for every program the traffic can reach: a cold prefill
    per bucket, and per prefix bucket a pair that shares a prefix which is
    deliberately not page-aligned (resident-prefix prefill and the
    copy-on-write page copy).  Each asks for a second token alone, which
    runs one whole burst of the decode path too."""
    reqs = []
    for b in sorted(set(cold_buckets)):
        reqs.append({"prompt": tokens(r, min(b - 1, max_seq_len
                                             - out_tokens), vocab),
                     "max_tokens": out_tokens, "warm": f"cold:{b}"})
    shared = None
    if prefix_buckets:
        # 3.5 pages shared; the first request fills the fourth page, so
        # that the followers match 3 pages and copy the fourth
        half = page_size // 2
        shared = tokens(r, 3 * page_size + half, vocab)
        reqs.append({"prompt": shared + tokens(r, page_size, vocab),
                     "max_tokens": 2, "warm": "prefix:seed"})
        for b in sorted(set(prefix_buckets)):
            reqs.append({"prompt": shared + tokens(r, b - half, vocab),
                         "max_tokens": 2, "warm": f"prefix:{b}"})
    firsts = first_tokens(r, len(reqs), vocab, "warmup")
    for req, t in zip(reqs, firsts):
        # the prefix family shares its first token by design
        req["prompt"][0] = firsts[-1] if req["warm"].startswith(
            "prefix") else t
    return reqs
