"""One-vs-all class blobs: a copy of ``repro.data.synthetic.krr_one_vs_all``,
with the class centres (the deployment's image classes) drawn from a seed of
their own, so that every run solves the same layout."""

from __future__ import annotations

import numpy as np

from data import rng


def generate(gen: np.random.Generator, n: int, d: int, num_classes: int = 10,
             layout_seed: int = 0):
    """Gaussian blobs around ``num_classes`` centres, with (n, num_classes)
    one-vs-all targets: +1 in the row's class column, -1 elsewhere.  The
    centres come from ``layout_seed``; the rows, their classes and their
    noise from ``gen``."""
    centers = 1.5 * rng(layout_seed, 0).standard_normal((num_classes, d), dtype=np.float32)
    labels = gen.integers(0, num_classes, size=n)
    x = centers[labels] + 0.6 * gen.standard_normal((n, d), dtype=np.float32)
    y = -np.ones((n, num_classes), np.float32)
    y[np.arange(n), labels] = 1.0
    return x, y
