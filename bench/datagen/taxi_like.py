"""Taxi-like trips: a copy of ``repro.data.synthetic.taxi_like``, with the
blob centres (the deployment's city layout) drawn from a seed of their own,
so that every run solves the same layout."""

from __future__ import annotations

import numpy as np

from data import rng


def generate(gen: np.random.Generator, n: int, d: int = 9, layout_seed: int = 0):
    """Low-dimensional trip-feature blobs with heavy-tailed targets (taxi
    ride-duration flavour, the paper's section 6.2).  The 16 blob centres
    come from ``layout_seed``; the trips from ``gen``."""
    centers = rng(layout_seed, 0).uniform(-2, 2, size=(16, d)).astype(np.float32)
    assign = gen.integers(0, 16, size=n)
    x = centers[assign] + 0.3 * gen.standard_normal((n, d), dtype=np.float32)
    base = np.linalg.norm(x[:, :2], axis=1) * 600.0
    y = base + 120.0 * gen.standard_normal(n) + 50.0 * np.abs(x[:, 2])
    return x, y.astype(np.float32)
