"""Tests of the ``ova10-d784.solve`` cell that run on the CPU in seconds: the
cell end to end at a tiny size (kernels in Pallas interpret mode, d = 784 and
ten heads as configured), as the program is and with the timed path broken
underneath, and its data generator."""

from __future__ import annotations

import contextlib
import math
import os
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import data  # noqa: E402
import faults  # noqa: E402
import named  # noqa: E402
import run  # noqa: E402

CELL = "ova10-d784.solve"
#: the cell at a tiny size: n and the block cut, d and the ten heads kept,
#: lam raised so that 300 steps reach tol; tol tight enough that the
#: answer's residual is mostly cancellation, where a lower precision shows
#: (the control's resid_gap reads 8e-3-1.2e-2 against the limit of 1e-3, the
#: program's 1.3e-4-2.2e-4, over three seeds)
TINY = {"n": 512, "block_size": 64, "rank": 16, "tol": 5e-4, "max_iters": 300,
        "eval_every": 20, "warmup_iters": 20, "lam_unscaled": 1e-4}


@pytest.fixture(autouse=True)
def no_chip_needed(monkeypatch):
    """The harness's look for a chip is the one part a CPU run leaves out."""
    monkeypatch.setattr(run, "require_chips", lambda chips, backend: None)


def tiny_run(swap: str | None = None, seed: int = 2**33 + 5) -> dict:
    spec = run.load_cell(CELL)
    spec["config"].update(TINY, backend="interpret")
    ctx = (contextlib.nullcontext() if swap is None
           else faults.SWAPS[swap](spec["config"]))
    with ctx:
        return run.run_cell(spec, seed, 0.2, False)


def test_cell_is_correct_and_prints_its_metrics():
    res = tiny_run()
    spec = run.load_cell(CELL)
    assert res["correct"] is True, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "solve_s"}
    assert {m["name"] for m in spec["end_to_end"]} == {"setup_s", "solve_s"}
    assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("swap", ["control", "answer_altered"])
def test_broken_timed_path_is_not_correct(swap):
    res = tiny_run(swap)
    assert res["correct"] is False, res["checks"]


def test_diverged_answer_is_not_correct(monkeypatch):
    """A step whose iterate turns nan, as the step's dense algebra at one
    bf16 pass does on the chip at the cell's size: every solve stops at its
    first check, not converged, and its nan answer reads not correct (the
    cell's reference counts a non-finite residual as inf)."""
    import jax.numpy as jnp

    from repro.core import askotch

    real = askotch.make_step

    def make_step(problem, cfg, probs=None):
        step = real(problem, cfg, probs)

        def diverging(state):
            new, aux = step(state)
            return new._replace(w=new.w * jnp.nan, z=new.z * jnp.nan), aux

        return diverging

    monkeypatch.setattr(askotch, "make_step", make_step)
    res = tiny_run()
    assert res["correct"] is False, res["checks"]
    assert res["attempted"] > 0 and res["failed"] == res["attempted"]
    assert res["checks"]["resid_over_tol"]["value"] == math.inf
    assert all("20 iterations" in line for line in res["diagnostics"][:-1])


def test_generator_is_seeded_and_its_layout_fixed():
    gen = named.load("datagen", "ova_blobs").generate
    x, y = gen(data.rng(11, 0), 4000, 784)
    x2, y2 = gen(data.rng(11, 0), 4000, 784)
    np.testing.assert_array_equal(x, x2)
    np.testing.assert_array_equal(y, y2)
    assert x.shape == (4000, 784) and x.dtype == np.float32
    assert y.shape == (4000, 10) and set(np.unique(y)) == {-1.0, 1.0}
    np.testing.assert_array_equal((y == 1).sum(axis=1), 1)  # one class a row

    def class_means(x, y):
        return np.stack([x[y[:, c] == 1].mean(axis=0) for c in range(10)])

    # another seed draws other rows around the same ten centres (the noise,
    # 0.6 a feature, averages to ~0.03 over ~400 rows a class); another
    # layout_seed moves the centres (1.5 a feature apart)
    x3, y3 = gen(data.rng(12, 0), 4000, 784)
    assert not np.array_equal(x, x3)
    assert np.abs(class_means(x, y) - class_means(x3, y3)).max() < 0.3
    x4, y4 = gen(data.rng(11, 0), 4000, 784, layout_seed=1)
    assert np.abs(class_means(x, y) - class_means(x4, y4)).mean() > 1.0
