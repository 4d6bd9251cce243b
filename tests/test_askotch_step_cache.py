"""One ASkotch step executable per (configuration, shapes): the training set,
lam and the state are arguments of the jitted step, so equal-shaped solves
trace it once (``repro_askotch_step_traces_total``), and a swapped kernel
entry point is traced anew rather than bypassed.

Every test uses a row count of its own, so no other test in the process can
have traced its step before it."""

from __future__ import annotations

import json
import math
import os
import re
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest
from jax import monitoring

from repro.core import askotch
from repro.core.krr import KRRProblem
from repro.kernels import ops
from repro.obs import metrics

#: traces of the f32 step, whose dense algebra runs at full f32
TRACES = 'repro_askotch_step_traces_total{precision="highest"}'
SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
OPTIONS = dict(max_iters=40, eval_every=20, tol=1e-12, block_size=48, rank=12)


def traces() -> float:
    return metrics.snapshot().get(TRACES, 0.0)


def make_problem(n: int, *, data_seed: int = 0, target_seed: int = 1,
                 lam_unscaled: float = 1e-4, t: int = 1, kernel="rbf") -> KRRProblem:
    x = np.random.default_rng(data_seed).standard_normal((n, 3)).astype(np.float32)
    shape = (n,) if t == 1 else (n, t)
    y = np.random.default_rng(target_seed).standard_normal(shape).astype(np.float32)
    if kernel == "precomputed":
        sq = ((x[:, None, :] - x[None, :, :]) ** 2).sum(-1)
        x = np.exp(-sq / 8.0).astype(np.float32)
    return KRRProblem(x=jnp.asarray(x), y=jnp.asarray(y), kernel=kernel, sigma=2.0,
                      lam_unscaled=lam_unscaled)


def solve(problem: KRRProblem, seed: int = 0, **kw) -> askotch.SolveResult:
    options = {**OPTIONS, **kw}
    cfg = askotch.ASkotchConfig(block_size=options.pop("block_size"),
                                rank=options.pop("rank"))
    return askotch.solve(problem, cfg, seed=seed, **options)


FRESH = """
import json, sys
import numpy as np
sys.path.insert(0, sys.argv[1])
import test_askotch_step_cache as t
out = {}
for kw in json.loads(sys.argv[2]):
    seed = kw.pop("seed")
    out["w"] = np.asarray(t.solve(t.make_problem(**kw), seed=seed).w).tolist()
out["traces"] = t.traces()
print(json.dumps(out))
"""


def fresh_process(*solves: dict) -> dict:
    """The solves, in order, in a new process: its final w and its trace count."""
    env = {**os.environ, "PYTHONPATH": SRC + os.pathsep + os.environ.get("PYTHONPATH", "")}
    proc = subprocess.run(
        [sys.executable, "-c", FRESH, os.path.dirname(os.path.abspath(__file__)),
         json.dumps(solves)],
        env=env, capture_output=True, text=True, timeout=150, check=True)
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_two_same_shape_solves_trace_the_step_once_in_a_fresh_process():
    out = fresh_process({"n": 301, "seed": 0}, {"n": 301, "seed": 1})
    assert out["traces"] == 1.0


def test_new_targets_and_lam_reuse_the_step_and_match_a_fresh_process():
    solve(make_problem(302))
    before = traces()
    other = dict(n=302, target_seed=7, lam_unscaled=3e-3)
    compiles = []

    def listen(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)

    monitoring.register_event_duration_secs_listener(listen)
    try:
        w = np.asarray(solve(make_problem(**other)).w)
    finally:
        monitoring.unregister_event_duration_listener(listen)
    assert traces() == before
    assert compiles == []  # nothing of the solve, step or check, compiles again
    fresh = np.asarray(fresh_process({**other, "seed": 0})["w"], np.float32)
    np.testing.assert_allclose(w, fresh, rtol=1e-5, atol=1e-5 * np.abs(fresh).max())


@pytest.mark.parametrize("change", [{"block_size": 64}, {"t": 2}])
def test_a_new_block_size_or_head_count_traces_once_more(change):
    n = 303 if "block_size" in change else 304
    t = change.get("t", 1)
    solve(make_problem(n))
    before = traces()
    options = {k: v for k, v in change.items() if k != "t"}
    solve(make_problem(n, t=t), **options)
    assert traces() == before + 1
    solve(make_problem(n, t=t, target_seed=5), seed=3, **options)
    assert traces() == before + 1


def test_a_swapped_kernel_matvec_is_traced_into_the_step(monkeypatch):
    problem = make_problem(305)
    w_real = np.asarray(solve(problem).w)
    real = ops.kernel_matvec
    rows = []

    def spy(a, b, v, **kw):
        rows.append(a.shape[0])
        return real(a, b, v, **kw)

    with monkeypatch.context() as m:
        m.setattr(ops, "kernel_matvec", spy)
        w_spied = np.asarray(solve(problem).w)
    # the step's row-block matvec, not only the full-residual check (305 rows)
    assert OPTIONS["block_size"] in rows
    np.testing.assert_array_equal(w_spied, w_real)
    # the real entry point is back, and with it the first executable
    before = traces()
    np.testing.assert_array_equal(np.asarray(solve(problem).w), w_real)
    assert traces() == before


def dense_constant_sizes(module_text: str) -> list[int]:
    """Element counts of the constants that carry a payload (not a splat)."""
    sizes = []
    for m in re.finditer(r"stablehlo\.constant dense<([\"\[])[^\n]*: tensor<([^>]*)>",
                         module_text):
        dims = m.group(2).split("x")[:-1]
        sizes.append(math.prod(int(s) for s in dims))
    return sizes


@pytest.mark.parametrize("kernel", ["rbf", ("rbf", "laplacian"), "precomputed"],
                         ids=["rbf", "rbf+laplacian", "precomputed"])
def test_step_holds_no_data_constant(kernel):
    n = {"rbf": 306, "precomputed": 307}.get(kernel, 308)
    problem = make_problem(n, kernel=kernel)
    res = solve(problem)
    rel = [h["rel_residual"] for h in res.history]
    assert np.isfinite(rel).all() and rel[-1] < rel[0] < 1.0

    cfg = askotch.ASkotchConfig(block_size=OPTIONS["block_size"], rank=OPTIONS["rank"])
    step = askotch.make_step(problem, cfg)
    text = step.func.lower(*step.args, askotch.init_state(problem),
                           **step.keywords).as_text()
    sizes = dense_constant_sizes(text)
    assert sizes, "no payload constant found: the pattern no longer matches"
    assert max(sizes) < n, sorted(sizes)[-3:]
