"""The 10-class, d = 784 one-vs-all deployment on the CPU: a solve against a
float64 reference, the precision of the step's dense algebra (local,
sharded and the PCG preconditioner), the step's trace counter, and a solve
that stops on a non-finite residual."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import askotch
from repro.core.krr import KRRProblem
from repro.core.pcg import make_preconditioner
from repro.data.synthetic import krr_one_vs_all
from repro.obs import metrics

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
D, T = 784, 10


def sq_dists(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Squared distances in float64 from the expansion, (m, n)."""
    a, b = a.astype(np.float64), b.astype(np.float64)
    d2 = (a * a).sum(1)[:, None] + (b * b).sum(1)[None, :] - 2.0 * a @ b.T
    return np.maximum(d2, 0.0)


def median_sigma(x: np.ndarray) -> float:
    """The median rule: sigma = the median pairwise distance of the rows."""
    d2 = sq_dists(x, x)
    return float(np.median(np.sqrt(d2[np.triu_indices(len(x), 1)])))


def ova_problem(n: int, seed: int = 0, lam_unscaled: float = 1e-4,
                precision: str = "f32", sigma: float | None = None) -> KRRProblem:
    """Ten one-vs-all heads at d = 784; sigma by the median rule unless given."""
    x, y, *_ = krr_one_vs_all(seed, n, D, num_classes=T)
    sigma = median_sigma(np.asarray(x)) if sigma is None else sigma
    return KRRProblem(x=x, y=y, kernel="rbf", sigma=sigma,
                      lam_unscaled=lam_unscaled, precision=precision)


def f32_dot_precisions(module_text: str) -> list[str]:
    """The precision of every dot_general of two f32 operands in a lowered
    module ("DEFAULT" where none is named)."""
    out = []
    for line in module_text.splitlines():
        m = re.search(r"stablehlo\.dot_general.*: \(tensor<([^>]*)>, tensor<([^>]*)>\)", line)
        if m and m.group(1).endswith("f32") and m.group(2).endswith("f32"):
            p = re.search(r"precision = \[(\w+), \w+\]", line)
            out.append(p.group(1) if p else "DEFAULT")
    return out


def test_one_vs_all_solve_matches_float64_cholesky():
    n = 1500
    problem = ova_problem(n)
    cfg = askotch.ASkotchConfig(block_size=150, rank=100)
    res = askotch.solve(problem, cfg, max_iters=200, eval_every=50, tol=1e-12, seed=0)
    w = np.asarray(res.w, np.float64)

    x, y = np.asarray(problem.x), np.asarray(problem.y, np.float64)
    k_lam = np.exp(-sq_dists(x, x) / (2.0 * problem.sigma**2)) + problem.lam * np.eye(n)
    chol = np.linalg.cholesky(k_lam)
    w_ref = np.linalg.solve(chol.T, np.linalg.solve(chol, y))

    assert w.shape == (n, T)
    resid = np.linalg.norm(k_lam @ w - y, axis=0) / np.linalg.norm(y, axis=0)
    err = np.linalg.norm(w - w_ref, axis=0) / np.linalg.norm(w_ref, axis=0)
    # 200 steps of 150 rows take every head's residual to ~7e-6 (f32 floor
    # ~1e-6); 5e-5 leaves room for the draws and still fails a solve that
    # stalls after its first check (~3e-3)
    assert resid.max() < 5e-5, resid
    # error <= cond(K + lam I) * residual: lam = 0.15 against K's top
    # eigenvalue ~960 bounds it by ~0.04; it reads ~4e-4, so 5e-3 fails a
    # head that converged to another answer
    assert err.max() < 5e-3, err
    # the solver's own per-head report is the float64 residual of its w
    np.testing.assert_allclose(res.history[-1]["rel_residual_per_head"], resid,
                               rtol=0.05, atol=1e-7)


def lowered_step(problem: KRRProblem, block_size: int = 64, rank: int = 16) -> str:
    cfg = askotch.ASkotchConfig(block_size=block_size, rank=rank)
    step = askotch.make_step(problem, cfg)
    return step.func.lower(*step.args, askotch.init_state(problem),
                           **step.keywords).as_text()


def test_f32_step_dense_algebra_is_highest_and_bf16_is_left_alone():
    f32 = f32_dot_precisions(lowered_step(ova_problem(256)))
    # the sketch, powering, Woodbury/Cholesky applies and the row-block
    # matvec: every f32 product at full f32
    assert f32 and set(f32) == {"HIGHEST"}, f32
    bf16 = f32_dot_precisions(lowered_step(ova_problem(256, precision="bf16")))
    # under "bf16" nothing is forced: the step's own algebra keeps the
    # backend's default (one bf16 pass on a TPU)
    assert bf16.count("DEFAULT") >= len(f32) // 2, bf16


SHARDED = """
import json, sys
import jax
import numpy as np
from jax.sharding import Mesh
sys.path.insert(0, sys.argv[1])
from test_ova_f32_step import f32_dot_precisions
from repro.distributed.krr_dist import (
    DistKRRConfig, abstract_dist_inputs, make_dist_askotch_step)
assert len(jax.devices()) == 4
mesh = Mesh(np.asarray(jax.devices()).reshape(2, 2), ("data", "model"))
out = {}
for precision in ("f32", "bf16"):
    cfg = DistKRRConfig(n=512, d=784, sigma=63.0, block_size=64, rank=16, heads=10,
                        precision=precision)
    step, sh = make_dist_askotch_step(mesh, cfg)
    state, x, y = abstract_dist_inputs(cfg)
    place = lambda s, sharding: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)
    state = jax.tree.map(place, state, sh["state"])
    text = jax.jit(step).lower(state, place(x, sh["x"]), place(y, sh["y"])).as_text()
    out[precision] = f32_dot_precisions(text)
print(json.dumps(out))
"""


def test_sharded_f32_step_dense_algebra_is_highest():
    """The mesh step on four virtual CPU devices (a 2 x 2 mesh), lowered only."""
    env = {**os.environ, "PYTHONPATH": SRC + os.pathsep + os.environ.get("PYTHONPATH", ""),
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "JAX_PLATFORMS": "cpu"}
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(SHARDED),
         os.path.dirname(os.path.abspath(__file__))],
        env=env, capture_output=True, text=True, timeout=150)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["f32"] and set(out["f32"]) == {"HIGHEST"}, out["f32"]
    assert "DEFAULT" in out["bf16"], out["bf16"]


@pytest.mark.parametrize("precision,expected", [("f32", {"HIGHEST"}), ("bf16", {"DEFAULT"})])
def test_pcg_nystrom_apply_follows_the_precision(precision, expected):
    problem = ova_problem(300, precision=precision)
    apply = make_preconditioner(problem, "nystrom", rank=20)
    text = jax.jit(apply).lower(jnp.zeros((300, T), jnp.float32)).as_text()
    assert set(f32_dot_precisions(text)) == expected


def traces(label: str) -> float:
    return metrics.snapshot().get(
        f'repro_askotch_step_traces_total{{precision="{label}"}}', 0.0)


def test_equal_shaped_ova_solves_trace_the_step_once_per_precision():
    options = dict(max_iters=20, eval_every=10, tol=1e-12)
    cfg = askotch.ASkotchConfig(block_size=72, rank=24)
    before = {p: traces(p) for p in ("highest", "default")}
    first = ova_problem(401, seed=0)
    askotch.solve(first, cfg, seed=0, **options)
    assert traces("highest") == before["highest"] + 1
    # new rows, targets and lam; sigma, a static setting of the kernel, kept
    askotch.solve(ova_problem(401, seed=1, lam_unscaled=1e-5, sigma=first.sigma),
                  cfg, seed=1, **options)
    assert traces("highest") == before["highest"] + 1
    # the bf16 step is a program of its own, labelled with its precision
    askotch.solve(ova_problem(401, precision="bf16", sigma=first.sigma), cfg, seed=0,
                  **options)
    assert traces("highest") == before["highest"] + 1
    assert traces("default") == before["default"] + 1


NONFINITE = "repro_solve_nonfinite_stops_total"


def test_a_nonfinite_residual_stops_the_solve_at_that_check():
    problem = ova_problem(402)
    cfg = askotch.ASkotchConfig(block_size=72, rank=24)
    before = metrics.snapshot().get(NONFINITE, 0.0)
    finite = askotch.solve(problem, cfg, max_iters=30, eval_every=10, tol=1e-12)
    assert finite.iters == 30 and len(finite.history) == 3
    assert metrics.snapshot().get(NONFINITE, 0.0) == before

    # one planted nan target spreads to the iterate at the first step that
    # samples its row, and to every head's residual through K
    planted = KRRProblem(x=problem.x, y=problem.y.at[5, 3].set(jnp.nan),
                         kernel="rbf", sigma=problem.sigma,
                         lam_unscaled=problem.lam_unscaled)
    res = askotch.solve(planted, cfg, max_iters=400, eval_every=10, tol=1e-12)
    assert not res.converged
    assert res.iters < 400 and res.iters % 10 == 0
    assert len(res.history) == res.iters // 10
    assert not np.isfinite(res.history[-1]["rel_residual_per_head"]).all()
    assert all(np.isfinite(h["rel_residual_per_head"]).all() for h in res.history[:-1])
    assert metrics.snapshot().get(NONFINITE, 0.0) == before + 1
