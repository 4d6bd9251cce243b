"""End-to-end behaviour tests for the paper's system: solve -> predict ->
metrics through the public API, plus the launchers' happy paths."""

import argparse
import json
import subprocess
import sys
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.krr import KRRProblem, evaluate
from repro.core.solver_api import solve as solve_any
from repro.data import synthetic

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_end_to_end_regression_task():
    """The paper's workflow at test scale: data -> ASkotch (default hparams,
    §3.2) -> predictions beating a constant baseline by a wide margin."""
    x_tr, y_tr, x_te, y_te = synthetic.krr_regression(0, 3000, 8, 500)
    prob = KRRProblem(x=x_tr, y=y_tr, kernel="rbf", sigma=1.5, lam_unscaled=1e-6,
                      backend="xla")
    out = solve_any(prob, "askotch", max_iters=250, eval_every=125)
    pred = out.predict_fn(x_te)
    m = evaluate(pred, y_te)
    base_rmse = float(jnp.std(y_te))
    assert float(m.rmse) < 0.45 * base_rmse, (float(m.rmse), base_rmse)


def test_end_to_end_classification_task():
    x_tr, y_tr, x_te, y_te = synthetic.krr_classification(1, 3000, 8, 500)
    prob = KRRProblem(x=x_tr, y=y_tr, kernel="laplacian", sigma=3.0,
                      lam_unscaled=1e-6, backend="xla")
    out = solve_any(prob, "askotch", max_iters=250, eval_every=125)
    m = evaluate(out.predict_fn(x_te), y_te)
    assert float(m.accuracy) > 0.8, float(m.accuracy)


def test_taxi_like_workload_matern():
    x, y = synthetic.taxi_like(0, 2000, 9)
    prob = KRRProblem(x=x[:1600], y=y[:1600], kernel="matern52", sigma=3.0,
                      lam_unscaled=1e-6, backend="xla")
    out = solve_any(prob, "askotch", block_size=160, rank=80,
                    max_iters=200, eval_every=100)
    pred = out.predict_fn(x[1600:])
    m = evaluate(pred, y[1600:])
    assert float(m.rmse) < float(jnp.std(y[1600:]))


def test_krr_solve_launcher_cli():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.krr_solve", "--n", "2000", "--d", "6",
         "--method", "askotch", "--iters", "120", "--dataset", "regression"],
        capture_output=True, text=True, timeout=150, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["rel_residual"] < 0.5
    assert np.isfinite(rec["test_rmse"])


def test_krr_solve_run_names_device_and_backend():
    """The launcher's report says where it ran and which kernel backend the
    platform resolved to — and the in-process ``run(argv)`` is the CLI."""
    import jax

    from repro.kernels.ops import resolve_backend
    from repro.launch import krr_solve

    run = krr_solve.run(["--n", "400", "--n-test", "50", "--d", "4",
                         "--method", "askotch", "--iters", "30"])
    rep = run.report
    dev = jax.devices()[0]
    assert rep["platform"] == dev.platform
    assert rep["device_kind"] == dev.device_kind
    assert rep["device_count"] == jax.device_count()
    assert rep["backend"] == resolve_backend("auto")
    assert rep["rel_residual"] < 1.0
    assert run.problem.n == 400 and run.x_test.shape == (50, 4)


def test_compile_cache_dir_from_env_else_fixed(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins; otherwise the fixed .jax_cache/ at
    the checkout root, which stays off on the CPU backend."""
    import pathlib

    import jax

    from repro.launch import runtime

    root = pathlib.Path(__file__).resolve().parents[1]
    assert runtime.REPO_CACHE_DIR == root / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/cache/from/env")
    assert runtime.use_compile_cache() == "/cache/from/env"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert runtime.use_compile_cache() is None  # tests run on the CPU
    assert jax.config.jax_compilation_cache_dir == before


def test_openblas_pinned_to_one_thread():
    """The root conftest.py pins OpenBLAS, where jaxlib's CPU LAPACK runs, to
    one thread, in this process and in the children the subprocess tests
    start with ``dict(os.environ)``."""
    threadpoolctl = pytest.importorskip("threadpoolctl")
    import jax

    jax.block_until_ready(jax.jit(jnp.linalg.qr)(jnp.ones((160, 80))))
    pools = [p for p in threadpoolctl.threadpool_info()
             if p["internal_api"] == "openblas"]
    assert pools and all(p["num_threads"] == 1 for p in pools), pools
    out = subprocess.run(
        [sys.executable, "-c",
         "import os; print(os.environ.get('OPENBLAS_NUM_THREADS'))"],
        capture_output=True, text=True, timeout=150, env=dict(os.environ),
    )
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "1"


def test_train_launcher_loss_decreases(tmp_path):
    sys.path.insert(0, SRC)
    from repro.launch import train as train_mod

    args = argparse.Namespace(
        arch="rwkv6-1.6b", reduced=True, steps=25, batch=4, seq=32, lr=3e-3,
        seed=0, ckpt_dir=str(tmp_path), ckpt_every=100, log_every=5,
        resume=False, inject_failure=-1, straggler_factor=3.0,
    )
    res = train_mod.run(args)
    losses = [r["loss"] for r in res["history"]]
    assert losses[-1] < losses[0], losses


def test_serve_launcher_cli():
    env = dict(os.environ, PYTHONPATH=SRC)
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "llava-next-mistral-7b",
         "--reduced", "--batch", "2", "--prompt-len", "12", "--max-new", "4"],
        capture_output=True, text=True, timeout=150, env=env,
    )
    assert out.returncode == 0, out.stderr[-2000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["generated_shape"] == [2, 4]
