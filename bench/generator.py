"""The one traffic generator: reads a mix's parameters, makes its requests.

Every seed gets the same multiset of prompt lengths, output lengths and
inter-arrival gaps (quantiles of the stated distributions); the seed only
orders them and draws the token ids. The order is stratified: every block
of ``BLOCK`` consecutive requests holds one value from each band of the
sorted sizes, so that a window which serves only part of a backlog, or an
open loop cut at its close, still sees the same work whatever the seed.
So two seeds do the same work in a different order, and their runs differ
by the system's noise, not by the size of the work drawn.

Mix parameters (``bench/traffic/<mix>.json``):

- ``arrivals``: ``"poisson"`` (an open loop at ``rate_rps``, requests due
  over the whole window) or ``"backlog"`` (``requests`` queued before the
  window);
- ``prompt``: a length distribution ``{median, sigma, min, max}``
  (lognormal, clipped), or ``prefix`` + ``suffix``: a system prompt of
  ``prefix.tokens`` tokens, one of ``prefix.count`` with Zipf(``zipf_s``)
  popularity (``prefix.shared`` false: every request's own), then a user
  turn of ``suffix`` length;
- ``output``: the ``max_tokens`` distribution. Decoding is greedy.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np


BLOCK = 16


@dataclasses.dataclass
class Req:
    rid: int
    due: float            # seconds after the window opens (backlog: 0)
    prompt: np.ndarray    # int32 token ids
    max_tokens: int


def quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of a clipped lognormal."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    return np.clip(x, dist["min"], dist["max"]).astype(np.int64)


def spread(rng: np.random.Generator, values: np.ndarray) -> np.ndarray:
    """``values`` in an order drawn from ``rng`` in which each block of
    ``BLOCK`` consecutive entries takes one value from each band of the
    sorted values: block ``b`` of ``g`` holds the sorted values ``b, b + g,
    b + 2g, ...``, in a random order, and the blocks come in a random
    order."""
    s = np.sort(values)
    g = max(1, -(-len(s) // BLOCK))
    blocks = [rng.permutation(s[b::g]) for b in range(g)]
    return np.concatenate([blocks[b] for b in rng.permutation(g)])


def zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` requests use each of ``count`` prefixes under
    Zipf(s) popularity, by largest remainder."""
    p = 1.0 / np.arange(1, count + 1) ** s
    want = n * p / p.sum()
    got = np.floor(want).astype(np.int64)
    for i in np.argsort(-(want - got))[:n - got.sum()]:
        got[i] += 1
    return got


def request_count(mix: dict, seconds: float,
                  rate: Optional[float] = None) -> int:
    if mix["arrivals"] == "backlog":
        return int(mix["requests"])
    return int(math.floor((rate or mix["rate_rps"]) * seconds))


def make_requests(mix: dict, seed: int, seconds: float, vocab: int, *,
                  rate: Optional[float] = None, first_rid: int = 0
                  ) -> List[Req]:
    """The mix's requests for one run, sorted by due time."""
    rng = np.random.default_rng(seed)
    n = request_count(mix, seconds, rate)
    if n < 1:
        raise ValueError(f"mix yields no request in {seconds} s")
    out_len = spread(rng, quantiles(mix["output"], n))
    if mix["arrivals"] == "poisson":
        r = rate or mix["rate_rps"]
        u = (np.arange(n) + 0.5) / n
        gaps = spread(rng, -np.log1p(-u) / r)
        due = np.cumsum(gaps) - gaps[0] * 0.5
    elif mix["arrivals"] == "backlog":
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrivals {mix['arrivals']!r}")

    def tokens(k):
        return rng.integers(1, vocab, size=int(k), dtype=np.int32)

    if "prefix" in mix:
        pre = mix["prefix"]
        suffix = spread(rng, quantiles(mix["suffix"], n))
        if pre["shared"]:
            systems = [tokens(pre["tokens"]) for _ in range(pre["count"])]
            which = rng.permutation(np.repeat(
                np.arange(pre["count"]),
                zipf_counts(pre["count"], pre["zipf_s"], n)))
            heads = [systems[k] for k in which]
        else:
            heads = [tokens(pre["tokens"]) for _ in range(n)]
        prompts = [np.concatenate([heads[i], tokens(suffix[i])])
                   for i in range(n)]
    else:
        prompts = [tokens(k)
                   for k in spread(rng, quantiles(mix["prompt"], n))]
    return [Req(first_rid + i, float(due[i]), prompts[i], int(out_len[i]))
            for i in range(n)]


def longest_prompt(mix: dict) -> int:
    if "prefix" in mix:
        return mix["prefix"]["tokens"] + mix["suffix"]["max"]
    return mix["prompt"]["max"]
