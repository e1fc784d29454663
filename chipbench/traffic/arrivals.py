"""An open-loop arrival schedule: when each request is due, how many
methods it holds, which output tier it asks for and which lines it sends.

The schedule is computed before the run and is a pure function of
``(params, seed, seconds, n_lines)``; the runner only sleeps until each due
instant. Every seed offers the same work: the number of requests is the rate
times the window (a Poisson process given its count, which is that many
uniform instants), and sizes and tiers are the distribution's own quantiles
in a seeded order, not draws. What differs between seeds is when each
request falls due, in which order the sizes come and which lines they send;
with free counts and sizes the offered rows varied by 3% from seed to seed
and the median latency with them (PERF.md, section 6).

Copied in idea from ``benchmarks/bench_mesh.py::make_profile`` (verdict in
PERF.md): what differs is that arrivals are a Poisson process at a fixed
request rate and not a fixed row rate, that sizes are heavy-tailed and not
uniform, and that every parameter is data.

Parameters (``traffic/<mix>.json``, key ``arrivals``)::

    rate_per_s   requests per second
    rows         {median, sigma, min, max}: methods per request, lognormal
    tiers        {tier: weight}

A mix names its schedule's generator (key ``generator``), so traffic of
another shape (bursts, replayed templates) is another file beside this one
with the same ``generate``, and this one stays as it was measured.
"""
from __future__ import annotations

from statistics import NormalDist
from typing import NamedTuple, Tuple

import numpy as np


class Schedule(NamedTuple):
    due_s: np.ndarray        # (n,) float64, seconds from the window's start
    rows: np.ndarray         # (n,) int64, methods in the request
    tier: np.ndarray         # (n,) int64, index into ``tiers``
    first_line: np.ndarray   # (n,) int64: it sends lines [first, first + rows)
    tiers: Tuple[str, ...]


def _shares(weights: np.ndarray, n: int) -> np.ndarray:
    """``n`` split in proportion to ``weights``, by largest remainder."""
    exact = weights / weights.sum() * n
    counts = np.floor(exact).astype(np.int64)
    short = n - int(counts.sum())
    counts[np.argsort(exact - counts)[::-1][:short]] += 1
    return counts


def requests(rng, params: dict, n: int, n_lines: int):
    """Sizes and tiers of ``n`` requests: the quantiles of the size
    distribution, the tiers in their exact shares and spread evenly over
    the sizes, the pairs in a seeded order; and a seeded first line each."""
    spec = params['rows']
    normal = NormalDist()
    z = np.array([normal.inv_cdf((i + 0.5) / n) for i in range(n)])
    rows = np.exp(np.log(spec['median']) + spec['sigma'] * z)
    rows = np.clip(np.rint(rows), spec['min'], spec['max']).astype(np.int64)
    names = tuple(sorted(params['tiers']))
    weights = np.array([params['tiers'][t] for t in names], np.float64)
    counts = _shares(weights, n)
    # tier k sits at the (j + 0.5) / counts[k] quantiles of the sizes
    at = np.concatenate([(np.arange(c) + 0.5) / c for c in counts])
    tier = np.repeat(np.arange(len(names)), counts)[
        np.argsort(at, kind='stable')]
    order = rng.permutation(n)
    rows, tier = np.minimum(rows[order], n_lines), tier[order]
    first_line = (rng.random(n) * (n_lines - rows + 1)).astype(np.int64)
    return rows, tier, first_line, names


def generate(params: dict, seed: int, seconds: float,
             n_lines: int) -> Schedule:
    rng = np.random.default_rng([int(seed), 0xA221])
    # Poisson arrivals on [0, seconds) given their number: sorted uniform
    # instants
    n = int(round(float(params['rate_per_s']) * seconds))
    due = np.sort(rng.random(n)) * seconds
    rows, tier, first_line, names = requests(rng, params, n, n_lines)
    return Schedule(due_s=due, rows=rows, tier=tier, first_line=first_line,
                    tiers=names)
