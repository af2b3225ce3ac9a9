"""The traffic generator: deterministic per seed, the same work for
every seed, lengths inside each mix's range."""

import glob
import json
import os

import numpy as np
import pytest

from bench.tests.tiny import ROOT
from bench.lib import traffic

MIXES = sorted(glob.glob(os.path.join(ROOT, "bench", "traffic", "*.json")))
BIG_SEED = 2 ** 31 + 12345


def _load(path):
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_mix_is_deterministic_and_in_range(path):
    mix = _load(path)
    a = traffic.schedule(mix, BIG_SEED, 20.0, vocab=1000)
    b = traffic.schedule(mix, BIG_SEED, 20.0, vocab=1000)
    assert len(a) == len(b) == traffic.n_requests(mix["arrivals"], 20.0)
    for x, y in zip(a, b):
        assert x.due == y.due and x.out_len == y.out_len
        assert np.array_equal(x.prompt, y.prompt)
    p, o = mix["prompt_tokens"], mix["output_tokens"]
    for r in a:
        assert p["min"] <= len(r.prompt) <= p["max"]
        assert o["min"] <= r.out_len <= o["max"]
        assert 0.0 <= r.due < 20.0
        assert r.prompt.min() >= 0 and r.prompt.max() < 1000
    dues = [r.due for r in a]
    assert dues == sorted(dues)
    e = mix["engine"]
    assert max(len(r.prompt) + r.out_len for r in a) <= e["max_len"]


@pytest.mark.parametrize("path", MIXES, ids=os.path.basename)
def test_every_seed_gets_the_same_work(path):
    """Same due times and lengths for every seed; the tokens differ."""
    mix = _load(path)
    a = traffic.schedule(mix, 1, 20.0, vocab=1000)
    b = traffic.schedule(mix, 2, 20.0, vocab=1000)
    assert [(r.due, len(r.prompt), r.out_len) for r in a] == \
        [(r.due, len(r.prompt), r.out_len) for r in b]
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    # another arrangement of the same lengths under another mix seed
    c = traffic.schedule(dict(mix, arrangement_seed=1), 1, 20.0, 1000)
    assert sorted(len(r.prompt) for r in c) == \
        sorted(len(r.prompt) for r in a)
    assert [len(r.prompt) for r in c] != [len(r.prompt) for r in a]


def test_rate_override_and_count():
    mix = {"arrivals": {"kind": "poisson", "rate_per_s": 5.0},
           "prompt_tokens": {"dist": "uniform", "min": 8, "max": 48},
           "output_tokens": {"dist": "uniform", "min": 8, "max": 48}}
    assert len(traffic.schedule(mix, 3, 10.0, 100)) == 50
    assert len(traffic.schedule(mix, 3, 10.0, 100, rate=12.0)) == 120
    lens = traffic.lengths(mix["prompt_tokens"], 41)
    assert lens.min() == 8 and lens.max() == 48


def test_onoff_bursts_keep_the_mean_rate():
    arr = {"kind": "onoff", "rate_per_s": 10.0, "period_s": 10.0,
           "on_s": 2.0, "on_factor": 4.0}
    t = np.linspace(0, 10, 10001)[:-1]
    lam = traffic.intensity(arr, t)
    assert lam.mean() == pytest.approx(10.0, rel=1e-3)
    assert lam.max() / lam.min() == pytest.approx(4.0)
    mix = {"arrivals": arr,
           "prompt_tokens": {"dist": "uniform", "min": 8, "max": 48},
           "output_tokens": {"dist": "uniform", "min": 8, "max": 48}}
    s = traffic.schedule(mix, 9, 20.0, 100)
    on = sum(1 for r in s if r.due % 10.0 < 2.0)
    # 2 s of every 10 at 4x the off rate hold half the arrivals
    assert on / len(s) == pytest.approx(0.5, abs=0.03)
