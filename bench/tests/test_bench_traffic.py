"""The traffic generator: seeded, same work for every seed, the mix's
length and popularity parameters."""

import os
import statistics
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import traffic  # noqa: E402

MIXES = ("phi3m-overload", "repo-code")
PAGE = {"phi3m-overload": 256, "repo-code": 64}
# a mix with long prefixes of several page-aligned lengths (uniform),
# as repository contexts for code completion would be
REPO_CODE = {
    "arrival": {"process": "stratified_exponential", "rate_per_s": 0.5},
    "preroll_s": 15, "postroll_s": 60,
    "prefixes": {"count": 8, "zipf_s": 1.1, "length": {
        "dist": "uniform", "min": 1536, "max": 3072, "align": 64}},
    "suffix": {"dist": "uniform", "min": 128, "max": 768},
    "output": {"dist": "lognormal", "median": 32, "sigma": 0.7,
               "min": 8, "max": 128}}


def _params(mix):
    return REPO_CODE if mix == "repo-code" else traffic.load(mix)


def _gen(mix, seed, seconds=30.0, vocab=1000):
    return traffic.generate(_params(mix), seed, vocab, seconds, PAGE[mix])


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    a, b = _gen(mix, 2 ** 33 + 5), _gen(mix, 2 ** 33 + 5)
    assert [(r.due, r.prompt, r.max_new) for r in a.arrivals] == \
        [(r.due, r.prompt, r.max_new) for r in b.arrivals]
    assert [[r.prompt for r in w] for w in a.warmup] == \
        [[r.prompt for r in w] for w in b.warmup]


@pytest.mark.parametrize("mix", MIXES)
def test_seeds_share_the_work(mix):
    """Another seed reorders the same lengths and gaps."""
    a, b = _gen(mix, 1), _gen(mix, 2)
    for seg in ("pre", "window", "post"):
        ra, rb = a.segment(seg), b.segment(seg)
        assert sum(len(r.prompt) for r in ra) == \
            sum(len(r.prompt) for r in rb)
        assert sorted(r.group for r in ra) == sorted(r.group for r in rb)
        assert sorted(r.max_new for r in ra) == sorted(r.max_new for r in rb)
    assert [r.prompt for r in a.arrivals] != [r.prompt for r in b.arrivals]


@pytest.mark.parametrize("mix", MIXES)
def test_schedule_seed_fixes_the_order(mix):
    """With ``schedule_seed`` every seed gets the same gaps and lengths in
    the same order; the seed still draws the prefixes and the tokens."""
    p = dict(_params(mix), schedule_seed=1)
    a, b = (traffic.generate(p, s, 1000, 30.0, PAGE[mix]) for s in (1, 2))

    def sizes(t):
        head = {w.group: len(w.prompt) - 1 for w in t.warmup[0]}
        return [(r.segment, r.due, len(r.prompt) - head[r.group], r.max_new)
                for r in t.arrivals]

    assert sizes(a) == sizes(b)
    assert [r.prompt for r in a.arrivals] != [r.prompt for r in b.arrivals]


@pytest.mark.parametrize("mix", MIXES)
def test_rate_and_segments(mix):
    p = _params(mix)
    t = _gen(mix, 3, seconds=30.0)
    rate = p["arrival"]["rate_per_s"]
    win = t.segment("window")
    assert len(win) == round(rate * 30.0)
    assert all(0.0 <= r.due < 30.0 for r in win)
    assert all(-p["preroll_s"] <= r.due < 0.0 for r in t.segment("pre"))
    assert all(r.due >= 30.0 for r in t.segment("post"))


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_follow_the_mix(mix):
    p = _params(mix)
    t = _gen(mix, 4, seconds=200.0)
    out = [r.max_new for r in t.arrivals]
    assert min(out) >= p["output"]["min"] and max(out) <= p["output"]["max"]
    if p["output"]["dist"] == "lognormal":
        med = statistics.median(out)
        assert abs(med - p["output"]["median"]) <= 0.1 * p["output"]["median"]
    heads = {}
    for r in t.arrivals:
        heads.setdefault(r.group, len(r.prompt))
        heads[r.group] = min(heads[r.group], len(r.prompt))
    pre = p["prefixes"]
    suffix = [len(r.prompt) for r in t.arrivals]
    lo = pre["length"].get("min", pre["length"].get("value"))
    hi = pre["length"].get("max", pre["length"].get("value"))
    assert min(suffix) >= lo + p["suffix"]["min"]
    assert max(suffix) <= hi + p["suffix"]["max"] + 64


@pytest.mark.parametrize("mix", MIXES)
def test_popularity_is_zipf(mix):
    p = _params(mix)
    t = _gen(mix, 5, seconds=400.0)
    win = t.segment("window")
    counts = sorted((sum(1 for r in win if r.group == g)
                     for g in range(p["prefixes"]["count"])), reverse=True)
    assert counts == traffic.zipf_counts(p["prefixes"]["count"],
                                         p["prefixes"]["zipf_s"], len(win))
    assert counts[0] > 2 * counts[-1]


def test_zipf_counts_sum_and_order():
    c = traffic.zipf_counts(8, 1.1, 101)
    assert sum(c) == 101 and c == sorted(c, reverse=True)


@pytest.mark.parametrize("mix", MIXES)
def test_warmup_covers_every_shape(mix):
    """One warm-up request per (prefix pages, prompt pages) pair of the
    schedule, after one request per prefix."""
    t = _gen(mix, 6)
    ps = PAGE[mix]

    def key(r):
        head = next(len(w.prompt) - 1 for w in t.warmup[0]
                    if w.group == r.group)
        return -(-head // ps), -(-len(r.prompt) // ps)

    wave1, wave2 = t.warmup
    assert len(wave1) == _params(mix)["prefixes"]["count"]
    keys = [key(r) for r in wave2]
    assert sorted(keys) == sorted({key(r) for r in t.arrivals})
    assert all(len(r.prompt) % ps == 0 for r in wave2)
