"""The traffic generator: the same work for every seed, released when due."""
import math

import numpy as np
import pytest

from bench import traffic

MIX = {"arrivals": {"kind": "jittered", "jitter": 1.0},
       "prompt": {"dist": "lognormal", "median": 2048, "sigma": 0.5,
                  "min": 1024, "max": 4096},
       "output": {"dist": "lognormal", "median": 128, "sigma": 0.5,
                  "min": 32, "max": 256}}


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 77, 2 ** 40 + 3])
def test_every_seed_gets_the_same_work_in_its_own_order(seed):
    base, _ = traffic.generate(MIX, 2.7, 51.0, 5, 1000)
    reqs, proc = traffic.generate(MIX, 2.7, 51.0, seed, 1000)
    assert len(reqs) == proc.n == math.floor(2.7 * 51.0)
    assert sorted(len(r.prompt) for r in reqs) \
        == sorted(len(r.prompt) for r in base)
    assert sorted(r.max_new for r in reqs) == sorted(r.max_new for r in base)
    assert [len(r.prompt) for r in reqs] != [len(r.prompt) for r in base]
    assert all(1024 <= len(r.prompt) <= 4096 for r in reqs)
    assert all(int(r.prompt.max()) < 1000 for r in reqs)


def test_jittered_releases_each_request_once_when_due():
    _, proc = traffic.generate(MIX, 2.0, 10.0, 3, 100)
    got, now = [], 0.0
    while now < 10.0:
        due = proc.release(now, 0)
        assert all(t <= now for t in due)
        got += due
        now = min(proc.wake(now), 10.0) if not due else now + 0.01
    got += proc.release(10.0, 0)
    assert len(got) == proc.n == 20
    assert got == sorted(got)
    # bounded bursts: request i is due within [i, i + 1) / rate
    assert all(i / 2.0 <= t < (i + 1) / 2.0 for i, t in enumerate(got))
    assert proc.wake(10.0) == math.inf


def test_lengths_are_stratified_quantiles():
    x = traffic.lengths(MIX["prompt"], 101)
    assert x[50] == 2048 and np.all(np.diff(x) >= 0)
