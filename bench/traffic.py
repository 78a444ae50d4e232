"""The one traffic generator: a mix's parameter file -> seeded requests.

A traffic mix is a JSON file under ``bench/traffic/`` (see the files
there for the keys). This module turns it, a seed, the model's vocabulary
and the window length into:

* ``warmup``: waves of requests that touch every program shape the
  schedule can produce (each prefix seeded into the prefix cache, then
  one request per (prefix page count, prompt page count) pair), sent
  before the schedule and not measured;
* ``arrivals``: an open-loop schedule in three segments: pre-roll
  (``due < 0``), the window (``0 <= due < seconds``) and post-roll
  (``due >= seconds``, load that keeps flowing while the window's last
  requests get their first token).

Every seed gets the same work. Within a segment the lengths are the
stratified quantiles of their distribution, the prefix popularity counts
are the Zipf expectation, and the inter-arrival gaps are the quantiles of
an exponential, scaled so the segment holds exactly ``rate * length``
arrivals (the arrival process ``stratified_exponential``: Poisson-like
gaps with a fixed count, not a Poisson process); the seed permutes their
order and draws the token ids. So two seeds differ in which request
comes when, not in how much is asked.

A mix that sets ``schedule_seed`` fixes the order of the gaps and of the
lengths to one shuffle for every run, as a replayed trace would; the
run's seed then draws which prefix each request carries, and every token
id. A load above capacity needs this: its queue admits a contiguous run
of the schedule in each window, so a reordered schedule would put other
prompt and answer lengths into the window, and another seed would ask
for other work.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import List

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass
class Req:
    due: float               # seconds from the window's start
    prompt: List[int]
    max_new: int
    group: int               # prefix group (-1: none)
    segment: str             # "pre" | "window" | "post" | "warmup"


@dataclasses.dataclass
class Traffic:
    params: dict
    warmup: List[List[Req]]
    arrivals: List[Req]

    def segment(self, name: str) -> List[Req]:
        return [r for r in self.arrivals if r.segment == name]


def load(name: str, root: str = HERE) -> dict:
    path = os.path.join(root, "traffic", f"{name}.json")
    with open(path) as f:
        return json.load(f)


def _quantile(dist: dict, u: float) -> float:
    """Inverse CDF of a length distribution, truncated to [min, max]."""
    kind = dist["dist"]
    if kind == "fixed":
        return float(dist["value"])
    lo, hi = float(dist["min"]), float(dist["max"])
    if kind == "uniform":
        return lo + u * (hi - lo)
    if kind == "lognormal":
        mu, sigma = math.log(dist["median"]), float(dist["sigma"])
        nd = NormalDist(mu, sigma)
        a, b = nd.cdf(math.log(lo)), nd.cdf(math.log(hi))
        return math.exp(nd.inv_cdf(a + u * (b - a)))
    raise ValueError(f"unknown length distribution {kind!r}")


def stratified_lengths(dist: dict, n: int, rng) -> List[int]:
    """``n`` lengths at the quantiles ``(i + 0.5) / n``, in random order;
    rounded up to ``align`` where the distribution asks for it."""
    align = int(dist.get("align", 1))
    vals = [_quantile(dist, (i + 0.5) / n) for i in range(n)]
    vals = [int(-(-round(v) // align) * align) for v in vals]
    return [vals[i] for i in rng.permutation(n)]


def zipf_counts(n_items: int, s: float, n: int) -> List[int]:
    """Expected Zipf(s) counts of ``n`` draws over ``n_items`` ranks,
    rounded by largest remainder so they sum to ``n``."""
    w = np.array([1.0 / (k + 1) ** s for k in range(n_items)])
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(int)
    for k in np.argsort(-(exact - counts))[:n - counts.sum()]:
        counts[k] += 1
    return [int(c) for c in counts]


def exponential_gaps(n: int, length: float, rng) -> List[float]:
    """``n`` arrivals over ``length`` seconds: stratified exponential
    gaps, shuffled, scaled so the ``n + 1`` gaps span the segment."""
    if n == 0:
        return []
    q = np.array([-math.log(1.0 - (i + 0.5) / (n + 1)) for i in range(n + 1)])
    q = q[rng.permutation(n + 1)]
    q *= length / q.sum()
    return list(np.cumsum(q)[:n])


def _tokens(rng, n: int, vocab: int) -> List[int]:
    return [int(t) for t in rng.integers(0, vocab, n)]


def generate(params: dict, seed: int, vocab: int, seconds: float,
             page_size: int) -> Traffic:
    rng = np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, 7])
    pre = params.get("prefixes")
    prefixes: List[List[int]] = []
    if pre:
        # prefix k is the k-th most popular; its length is the same for
        # every seed (a fixed shuffle of the stratified lengths)
        plens = stratified_lengths(pre["length"], pre["count"],
                                   np.random.default_rng(0))
        prefixes = [_tokens(rng, n, vocab) for n in plens]
    if params["arrival"]["process"] != "stratified_exponential":
        raise ValueError(f"unknown arrival process "
                         f"{params['arrival']['process']!r}")
    rate = float(params["arrival"]["rate_per_s"])
    segments = (("pre", -float(params["preroll_s"]), float(params["preroll_s"])),
                ("window", 0.0, float(seconds)),
                ("post", float(seconds), float(params["postroll_s"])))
    fixed = params.get("schedule_seed")
    arrivals: List[Req] = []
    for i, (name, start, length) in enumerate(segments):
        n = int(round(rate * length))
        if n == 0:
            continue
        srng = rng if fixed is None else np.random.default_rng([int(fixed), i])
        times = exponential_gaps(n, length, srng)
        suffix = stratified_lengths(params["suffix"], n, srng)
        out = stratified_lengths(params["output"], n, srng)
        if prefixes:
            counts = zipf_counts(len(prefixes), pre["zipf_s"], n)
            groups = [k for k, c in enumerate(counts) for _ in range(c)]
            groups = [groups[i] for i in rng.permutation(n)]
        else:
            groups = [-1] * n
        for t, s, o, g in zip(times, suffix, out, groups):
            head = prefixes[g] if g >= 0 else []
            arrivals.append(Req(due=start + t, prompt=head + _tokens(rng, s, vocab),
                                max_new=o, group=g, segment=name))
    return Traffic(params=params,
                   warmup=warmup_waves(arrivals, prefixes, rng, vocab,
                                       page_size),
                   arrivals=arrivals)


def _pages(n: int, page_size: int) -> int:
    return -(-n // page_size)


def shape_key(req: "Req", prefixes, page_size: int):
    """What fixes the programs a request runs: the pages of its shared
    prefix (the prefix copy and the first chunk's position) and the
    pages of its prompt (the prefill width and the pages scattered)."""
    head = len(prefixes[req.group]) if req.group >= 0 else 0
    return _pages(head, page_size), _pages(len(req.prompt), page_size)


def warmup_waves(arrivals: List[Req], prefixes, rng, vocab: int,
                 page_size: int) -> List[List[Req]]:
    """Wave 1 puts every prefix into the prefix cache; wave 2 sends one
    request per :func:`shape_key` that the schedule holds, each on the
    first prefix of that length, so every prefill width, prefix copy
    and page scatter is compiled before the window. Each warm-up request
    decodes two tokens."""
    def req(prompt, group):
        return Req(due=0.0, prompt=prompt, max_new=2, group=group,
                   segment="warmup")

    wave1 = [req(p + _tokens(rng, 1, vocab), g)
             for g, p in enumerate(prefixes)]
    by_head = {}
    for g, p in enumerate(prefixes):
        by_head.setdefault(_pages(len(p), page_size), g)
    keys = sorted({shape_key(r, prefixes, page_size) for r in arrivals})
    wave2 = []
    for head_pages, k in keys:
        g = by_head[head_pages] if prefixes else -1
        head = prefixes[g] if g >= 0 else []
        n = k * page_size - len(head)         # fills k pages exactly
        wave2.append(req(head + _tokens(rng, n, vocab), g))
    return [w for w in (wave1, wave2) if w]
