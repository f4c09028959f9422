"""The one traffic generator: reads a mix file of ``bench/traffic`` and
makes every request of a run, and when it is due, from the seed.

A mix file is JSON::

    {"request": "solve",                       # bench/requests/<request>.py
     "params": {"solver": "cg", "tol": 5e-3, "maxiter": 12},  # for that kind
     "cols": 1,                                # or a list: the set of sizes
     "inputs": {"dist": "normal"},             # bench/inputs/<dist>.py
     "arrival": {"process": "closed", "clients": 1},
     "tenants": {"count": 4, "zipf": 1.0}}     # optional

``arrival`` is a closed loop (``clients`` clients, each sending its next
request when its last is answered) or ``{"process": "poisson",
"rate_per_s": r}``, open-loop arrivals at ``r`` a second, with an optional
``"burst": {"period_s": p, "on_s": d, "factor": f}`` that multiplies the
rate by ``f`` for the first ``d`` seconds of every ``p``.  ``tenants``
gives each request one of ``count`` tenants, tenant ``t`` ``1 / (t + 1)^zipf``
as often as tenant 0.

Every seed gets the same requests in another order: in each block of
requests the sizes, the tenants and the unit gaps between arrivals are the
same multiset, shuffled by the seed (the gaps are the quantiles of the
exponential law).  Request ``i`` draws its input from ``fold_in(inputs_key,
i)`` and its noise key ``fold_in(noise_key, i)``, both in one compiled call.
"""
from __future__ import annotations

import functools
import importlib.util
import math
from pathlib import Path
from typing import Any, List, NamedTuple, Optional

import jax
import numpy as np

INPUTS_DIR = Path(__file__).resolve().parent / "inputs"

# Streams folded out of the run's seed key, and of its numpy generators.
PROGRAM, INPUTS, NOISE, WARMUP = 0, 1, 2, 3
SIZES, GAPS, TENANTS = 4, 5, 6
# Requests per block whose gaps (and tenants) are one shuffled multiset.
BLOCK = 64


def seed_key(seed: int):
    """A PRNG key for any non-negative whole ``seed`` (more than 32 bits)."""
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


class Request(NamedTuple):
    i: int
    x: Any                   # the (n, cols) input panel, on the device
    key: Any                 # its noise key
    cols: int
    tenant: Optional[int]    # None where the mix names no tenants


def _input_module(dist: str):
    path = INPUTS_DIR / f"{dist}.py"
    spec = importlib.util.spec_from_file_location(f"bench_inputs_{dist}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Shuffled:
    """Entry ``i`` of a sequence made of blocks, each a permutation (drawn
    from the seed and the block's number) of the same ``items``."""

    def __init__(self, items: List, seed: int, stream: int):
        self.items = list(items)
        self.seed = [seed & 0xFFFFFFFF, seed >> 32, stream]
        self._block, self._order = -1, None

    def __getitem__(self, i: int):
        block, at = divmod(i, len(self.items))
        if block != self._block:
            rng = np.random.default_rng(self.seed + [block])
            self._block, self._order = block, rng.permutation(len(self.items))
        return self.items[self._order[at]]


def apportion(weights: List[float], total: int) -> List[int]:
    """``total`` split in proportion to ``weights`` (largest remainders)."""
    share = [w / sum(weights) * total for w in weights]
    counts = [math.floor(s) for s in share]
    for k in sorted(range(len(share)), key=lambda k: counts[k] - share[k]
                    )[:total - sum(counts)]:
        counts[k] += 1
    return counts


class Closed:
    """A closed loop: request ``i`` is due when request ``i - clients`` is
    answered (the first ``clients`` at the start)."""

    def __init__(self, spec: dict, seed: int):
        self.clients = int(spec["clients"])

    def due(self, i: int, done: List[float]) -> float:
        return 0.0 if i < self.clients else done[i - self.clients]


class Poisson:
    """Open-loop arrivals at ``rate_per_s``, bursts optional: unit gaps
    mapped through the inverse of the cumulative rate."""

    def __init__(self, spec: dict, seed: int):
        self.rate = float(spec["rate_per_s"])
        burst = spec.get("burst", {"period_s": 1.0, "on_s": 0.0,
                                   "factor": 1.0})
        self.period = float(burst["period_s"])
        self.on = float(burst["on_s"])
        self.hot = self.rate * float(burst["factor"])
        quantiles = [-math.log1p(-(k + 0.5) / BLOCK) for k in range(BLOCK)]
        mean = sum(quantiles) / BLOCK        # so that the rate is exact
        self.gaps = Shuffled([q / mean for q in quantiles], seed, GAPS)
        self.unit: List[float] = []       # cumulative unit time per arrival

    def _time(self, u: float) -> float:
        per_period = self.hot * self.on + self.rate * (self.period - self.on)
        m, r = divmod(u, per_period)
        if r < self.hot * self.on:
            return m * self.period + r / self.hot
        return m * self.period + self.on + (r - self.hot * self.on) / self.rate

    def due(self, i: int, done: List[float]) -> float:
        while len(self.unit) <= i:
            last = self.unit[-1] if self.unit else 0.0
            self.unit.append(last + self.gaps[len(self.unit)])
        return self._time(self.unit[i])


ARRIVALS = {"closed": Closed, "poisson": Poisson}


class Traffic:
    """The requests of one mix, for one seed, against an ``n``-row system."""

    def __init__(self, mix: dict, n: int, seed: int):
        self.mix = mix
        self.kind = mix["request"]
        self.params = dict(mix.get("params", {}))
        sizes = mix["cols"]
        sizes = [int(sizes)] if isinstance(sizes, int) else [int(c) for c
                                                             in sizes]
        self.n = n
        self.sizes = Shuffled(sizes, seed, SIZES)
        self.shapes = sorted({(n, c) for c in sizes})
        arrival = mix["arrival"]
        self.arrivals = ARRIVALS[arrival["process"]](arrival, seed)
        tenants = mix.get("tenants")
        self.tenants = None
        if tenants is not None:
            count = int(tenants["count"])
            weights = [(t + 1) ** -float(tenants["zipf"]) for t in
                       range(count)]
            per = apportion(weights, max(BLOCK, count))
            self.tenants = Shuffled([t for t in range(count)
                                     for _ in range(per[t])], seed, TENANTS)
        dist = _input_module(mix["inputs"]["dist"])
        spec = dict(mix["inputs"])
        self._draw = jax.jit(functools.partial(_draw, dist.draw, spec),
                             static_argnames=("shape",))
        root = seed_key(seed)
        self.program_key = jax.random.fold_in(root, PROGRAM)
        warm = jax.random.fold_in(root, WARMUP)
        self._keys = {False: (jax.random.fold_in(root, INPUTS),
                              jax.random.fold_in(root, NOISE)),
                      True: (jax.random.fold_in(warm, INPUTS),
                             jax.random.fold_in(warm, NOISE))}

    def request(self, i: int, *, warmup: bool = False,
                cols: Optional[int] = None) -> Request:
        """Request ``i`` (a warm-up request of ``cols`` columns where
        ``warmup``); its input and key are drawn on the device, not waited
        for."""
        cols = self.sizes[i] if cols is None else cols
        x, key = self._draw(*self._keys[warmup], np.uint32(i),
                            shape=(self.n, cols))
        tenant = None if self.tenants is None else self.tenants[i]
        return Request(i=i, x=x, key=key, cols=cols, tenant=tenant)

    def due(self, i: int, done: List[float]) -> float:
        """Seconds after the window's start at which request ``i`` is due,
        given when the requests before it were answered."""
        return self.arrivals.due(i, done)


def _draw(draw, spec, inputs_key, noise_key, i, *, shape):
    return (draw(jax.random.fold_in(inputs_key, i), shape, spec),
            jax.random.fold_in(noise_key, i))
