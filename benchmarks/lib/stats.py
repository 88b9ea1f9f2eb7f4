"""Percentile and spread arithmetic the benchmark reports with."""

from __future__ import annotations

import statistics

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (0..100) of ALL values, by linear
    interpolation between order statistics (numpy's default)."""
    v = np.asarray(values, np.float64)
    if v.size == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(v, q))


def quartile_spread(values) -> float:
    """(Q3 - Q1) / median, with the quartiles as
    ``statistics.quantiles(values, n=4)`` gives them — the spread the
    bounds in BENCHMARK.json are set from."""
    q1, med, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / med


def hist_median_bin(hist) -> int:
    """1-based index of the bin that holds the median sample of an
    integer histogram (bin i counts samples of value i + 1)."""
    h = np.asarray(hist, np.int64)
    if h.sum() <= 0:
        raise ValueError("median of an empty histogram")
    return int(np.searchsorted(np.cumsum(h), h.sum() / 2) + 1)
