"""Seeded inputs of the benchmark: training sets, each a pure function of
``(generator, sizes, seed)``.

A configuration's ``data`` entry names its generator, a module of its own
in ``bench/datagen/`` with a ``generate(gen, n, d, **kw) -> (x, y)``: the
benchmark makes its own inputs, so a change to the program's generators
cannot change what is measured.  Seeds of any size (they may exceed
32 bits) go through ``numpy.random.SeedSequence``.
"""

from __future__ import annotations

import numpy as np

import named


def rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of a run: ``seed`` with a fixed path of
    stream ids, so every draw is independent of the order of the others."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *stream]))


def small_seed(seed: int, *stream: int) -> int:
    """A seed in [0, 2**31) for the program's own PRNG keys."""
    return int(rng(seed, *stream).integers(0, 2**31 - 1))


def training_set(config: dict, seed: int):
    """(x, y) for a configuration, from the generator its ``data`` entry
    names; n and d are the configuration's own."""
    kw = dict(config["data"])
    generate = named.load("datagen", kw.pop("generator")).generate
    return generate(rng(seed, 0), config["n"], config["d"], **kw)
