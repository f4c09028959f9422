"""The traffic generator: the same seed gives the same requests, every seed
the same sizes and gaps in another order, and open-loop arrivals at the
mix's rate; a mix of open-loop arrivals and several sizes runs as data
alone."""
import dataclasses
import math
from collections import Counter

import jax.numpy as jnp
import numpy as np
import pytest

import generator
from conftest import run_small, small_cell

BIG_SEED = 2 ** 31 + 12345


def mix(**kw):
    base = {"request": "mvm", "params": {}, "cols": 4,
            "inputs": {"dist": "normal"},
            "arrival": {"process": "closed", "clients": 1}}
    return dict(base, **kw)


def test_same_seed_same_requests():
    a, b = (generator.Traffic(mix(), 64, BIG_SEED) for _ in range(2))
    for i in (0, 1, 7):
        ra, rb = a.request(i), b.request(i)
        assert jnp.array_equal(ra.x, rb.x)
        assert jnp.array_equal(ra.key, rb.key)
        assert ra.x.shape == (64, 4) and ra.tenant is None
    c = generator.Traffic(mix(), 64, BIG_SEED + 1)
    assert not jnp.array_equal(a.request(0).x, c.request(0).x)
    assert not jnp.array_equal(a.request(0, warmup=True).x, a.request(0).x)


def test_sizes_same_multiset_every_seed():
    sizes = [1, 1, 8, 64]
    for seed in (3, BIG_SEED):
        t = generator.Traffic(mix(cols=sizes), 32, seed)
        assert t.shapes == [(32, 1), (32, 8), (32, 64)]
        for block in range(3):
            got = [t.sizes[block * 4 + k] for k in range(4)]
            assert sorted(got) == sorted(sizes)
        assert t.request(5).x.shape == (32, t.sizes[5])


def test_closed_loop_due_on_answers():
    t = generator.Traffic(mix(arrival={"process": "closed", "clients": 2}),
                          8, 1)
    done = [0.5, 0.7, 1.1]
    assert [t.due(i, done) for i in range(5)] == [0.0, 0.0, 0.5, 0.7, 1.1]


def _gaps(t, count):
    times = [t.due(i, []) for i in range(count)]
    return np.diff([0.0] + times)


def test_poisson_rate_and_same_gaps_every_seed():
    arrival = {"process": "poisson", "rate_per_s": 40.0}
    runs = [_gaps(generator.Traffic(mix(arrival=arrival), 8, s), 640)
            for s in (1, BIG_SEED)]
    for g in runs:
        assert np.all(g > 0)
        assert np.mean(g) == pytest.approx(1 / 40, rel=0.01)
        # exponential: the standard deviation is the mean
        assert np.std(g) == pytest.approx(1 / 40, rel=0.1)
    assert sorted(runs[0][:64]) == pytest.approx(sorted(runs[1][:64]))
    assert not np.allclose(runs[0][:64], runs[1][:64])


def test_poisson_bursts():
    # rate 10/s, 4x for the first second of every 4 s: 70 arrivals per
    # period, 40 of them in the burst.
    arrival = {"process": "poisson", "rate_per_s": 10.0,
               "burst": {"period_s": 4.0, "on_s": 1.0, "factor": 4.0}}
    t = generator.Traffic(mix(arrival=arrival), 8, 5)
    times = np.array([t.due(i, []) for i in range(70 * 40)])
    periods = times[-1] / 4.0
    assert len(times) / periods == pytest.approx(70, rel=0.02)
    hot = np.mean(np.mod(times, 4.0) < 1.0)
    assert hot == pytest.approx(40 / 70, abs=0.02)


def test_tenants_zipf_share():
    t = generator.Traffic(mix(tenants={"count": 4, "zipf": 1.0}), 8, 9)
    seen = Counter(t.request(i).tenant for i in range(64 * 4))
    weights = [1, 1 / 2, 1 / 3, 1 / 4]
    for tenant, w in enumerate(weights):
        assert seen[tenant] / (64 * 4) == pytest.approx(
            w / sum(weights), abs=1 / 64)


def test_apportion_largest_remainders():
    assert generator.apportion([1, 1, 1], 64) == [22, 21, 21]
    assert sum(generator.apportion([1, 0.5, 0.25], 10)) == 10


def test_open_loop_mix_of_sizes_runs_as_data():
    # A new mix of resident-mvm-b64's kind: open-loop arrivals and three
    # sizes, with no code of its own.
    cell = small_cell("resident-mvm-b64")
    cell = dataclasses.replace(cell, mix=mix(
        cols=[1, 8, 64],
        arrival={"process": "poisson", "rate_per_s": 8.0}))
    result = run_small("resident-mvm-b64", seconds=1.5, cell=cell)
    assert result["correct"], result["check"]
    assert result["failed"] == 0 and result["window_compiles"] == 0
    assert 1 <= result["attempted"] <= 40
    assert math.isfinite(result["metrics"]["mvm_cols_per_s"]["value"])
