"""The traffic generator: deterministic per seed, the same work for every
seed, the stated clips and medians."""

import collections
import json

import numpy as np
import pytest

from tiny_cell import ROOT  # noqa: F401  (puts the repo on sys.path)
from bench import traffic

MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


def _sizes(reqs):
    return (sorted(len(r.prompt) for r in reqs), sorted(r.n_out for r in reqs))


@pytest.mark.parametrize("name", MIXES)
def test_mix_is_deterministic_per_seed(name):
    mix = traffic.load_mix(name)
    if mix["loop"] == "open":
        a = traffic.open_requests(mix, 10.0, 32000, 2**31 + 5)
        b = traffic.open_requests(mix, 10.0, 32000, 2**31 + 5)
        assert [r.due_s for r in a] == [r.due_s for r in b]
    else:
        it_a = traffic.closed_requests(mix, 32000, 2**31 + 5)
        it_b = traffic.closed_requests(mix, 32000, 2**31 + 5)
        a = [next(it_a) for _ in range(100)]
        b = [next(it_b) for _ in range(100)]
    assert all(np.array_equal(x.prompt, y.prompt) and x.n_out == y.n_out
               for x, y in zip(a, b))


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_gets_the_same_work_in_another_order(name):
    mix = traffic.load_mix(name)
    if mix["loop"] == "open":
        a = traffic.open_requests(mix, 20.0, 32000, 1)
        b = traffic.open_requests(mix, 20.0, 32000, 99)
        assert len(a) == len(b) == round(mix["rate_rps"] * 20.0)
        assert sorted(np.diff([0] + [r.due_s for r in a])) == \
            pytest.approx(sorted(np.diff([0] + [r.due_s for r in b])))
        assert a[-1].due_s == pytest.approx(20.0)
    else:
        n = mix["block"] * 3
        it_a = traffic.closed_requests(mix, 32000, 1)
        it_b = traffic.closed_requests(mix, 32000, 99)
        a = [next(it_a) for _ in range(n)]
        b = [next(it_b) for _ in range(n)]
    assert _sizes(a) == _sizes(b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_keep_their_clips_and_fit_the_cache(name):
    mix = traffic.load_mix(name)
    for part in ("prompt", "output"):
        q = traffic.quantiles(mix[part], 1001)
        assert q.min() >= mix[part]["min"] and q.max() <= mix[part]["max"]
        if "median" in mix[part]:
            assert q[500] == mix[part]["median"]
    cfgs = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]
    for c in cfgs:
        if c["traffic"] == name:
            conf = json.loads((ROOT / "bench" / "configs" /
                               f"{c['config']}.json").read_text())
            assert traffic.length_bound(mix) <= conf["engine"]["max_len"]


def test_lognormal_and_loguniform_quantiles_by_hand():
    ln = {"dist": "lognormal", "median": 100, "sigma": 1.0, "min": 1,
          "max": 10**6}
    # quantile (j + 0.5) / 4 of exp(N(ln 100, 1)): z = -1.1503, -0.3186, ...
    assert list(traffic.quantiles(ln, 4)) == [32, 73, 138, 316]
    lu = {"dist": "loguniform", "min": 10, "max": 1000}
    assert list(traffic.quantiles(lu, 2)) == [32, 316]
    un = {"dist": "uniform", "min": 8, "max": 32}
    q = traffic.quantiles(un, 25)
    assert list(q) == list(range(8, 33))
    assert collections.Counter(q).most_common(1)[0][1] == 1
