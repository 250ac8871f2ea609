"""The traffic generator: seeded, deterministic, the same work for every
seed, and the stated rate and length distributions."""
import statistics

import benchutil  # noqa: F401  (the checkout root on the path)

import numpy as np
import pytest

from bench.harness import traffic as T

MIX = {"rate_per_s": 2.5,
       "prompt": {"median": 256, "sigma": 1.0, "min": 32, "max": 2048},
       "output": {"median": 384, "sigma": 0.8, "min": 32, "max": 2048}}


def test_ndtri_matches_the_normal_quantile():
    p = np.concatenate([np.linspace(1e-6, 0.02, 50),
                        np.linspace(0.02, 0.98, 200),
                        np.linspace(0.98, 1 - 1e-6, 50)])
    want = np.array([statistics.NormalDist().inv_cdf(x) for x in p])
    assert np.max(np.abs(T._ndtri(p) - want)) < 1e-8


def test_same_seed_same_requests():
    a = T.open_loop(MIX, 2 ** 40 + 3, 51.0, 49152, 4096)
    b = T.open_loop(MIX, 2 ** 40 + 3, 51.0, 49152, 4096)
    assert [(r.due_s, r.max_new) for r in a] == [(r.due_s, r.max_new)
                                                  for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


@pytest.mark.parametrize("seeds", [(1, 2), (7, 2 ** 33 + 5)])
def test_every_seed_gets_the_same_work_in_another_order(seeds):
    a, b = (T.open_loop(MIX, s, 51.0, 49152, 4096) for s in seeds)
    pairs = lambda rs: [(len(r.prompt), r.max_new) for r in rs]
    assert sorted(pairs(a)) == sorted(pairs(b))
    assert pairs(a) != pairs(b)
    pa, pb = (T.preroll(MIX, s, 12, 49152, 4096) for s in seeds)
    assert sorted(pairs(pa)) == sorted(pairs(pb))
    gaps = lambda rs: sorted(np.round(np.diff([r.due_s for r in rs]), 9))
    assert len(gaps(a)) == len(gaps(b))


@pytest.mark.parametrize("rate,seconds", [(2.5, 51.0), (0.13, 51.0),
                                          (10.0, 3.0)])
def test_rate_is_held_exactly(rate, seconds):
    reqs = T.open_loop(dict(MIX, rate_per_s=rate), 11, seconds, 100, 4096)
    assert len(reqs) == round(rate * seconds)
    due = [r.due_s for r in reqs]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < seconds
    assert [r.rid for r in reqs] == list(range(len(reqs)))


def test_lengths_follow_the_clipped_lognormal():
    n = 2001
    p = T.strata(n)
    x = T.lognormal_lengths(MIX["prompt"], p)
    assert x.min() >= 32 and x.max() <= 2048
    assert np.median(x) == 256
    # a lognormal's log-quartiles sit at median * exp(+-0.6745 sigma)
    q1, q3 = np.percentile(x, [25, 75])
    assert abs(q1 / (256 * np.exp(-0.6745)) - 1) < 0.01
    assert abs(q3 / (256 * np.exp(0.6745)) - 1) < 0.01


def test_token_ids_and_budgets_fit_the_engine():
    reqs = T.open_loop(MIX, 5, 51.0, 512, 1024)
    for r in reqs:
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 512
        assert len(r.prompt) + r.max_new <= 1024 and r.max_new >= 1


def test_preroll_stands_for_a_steady_state():
    pre = T.preroll(MIX, 3, 10, 512, 4096)
    assert len(pre) == 10
    assert all(r.due_s < 0 and r.rid < 0 for r in pre)
    assert all(r.max_new >= 1 and len(r.prompt) + r.max_new <= 4096
               for r in pre)
    again = T.preroll(MIX, 3, 10, 512, 4096)
    assert [len(r.prompt) for r in pre] == [len(r.prompt) for r in again]
    # the occupants are length-biased: their outputs run longer on
    # average than a fresh request's
    fresh = T.lognormal_lengths(MIX["output"], T.strata(4096)).mean()
    many = T.preroll(MIX, 3, 400, 512, 8192)
    made_and_left = np.mean([r.max_new for r in many]) * 2
    assert made_and_left > fresh
    assert T.preroll(MIX, 3, 0, 512, 4096) == []
