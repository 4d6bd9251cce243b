"""Smoke run of the ASkotch solve path on one TPU chip, through the entry
points a user calls.  It fails, and prints no result, where JAX finds no TPU.

    python chip_smoke.py               # one chip: phases (a)-(e)
    python chip_smoke.py --four-chips  # four chips: the multi-chip paths only

One chip, at the taxi deployment's settings of ``configs/askotch_krr.py``
(rbf, sigma = 1, lam_unscaled = 2e-7, d = 9) on the launcher's synthetic
regression data, with n cut from 10^8 to 10^6 rows (10^5 for pcg-nystrom):

  (a) device       JAX's first device must be a TPU; there is no fallback.
  (b) tile parity  every kernel, f32 and bf16, t in {1, 16}: the Pallas
                   tiles against the chunked-XLA reference (run at full f32
                   matmul precision), to the tolerances of
                   tests/test_kernels_pallas.py.
  (c) main path    ``repro.launch.krr_solve`` through its own argument
                   parser: askotch at n = 10^6, then pcg-nystrom with the
                   same seed at n = 10^5.  Each report must name the Pallas
                   backend and give a finite relative residual below 1 (its
                   value at w = 0) and a test RMSE below the predict-the-mean
                   RMSE.  Rank-100 Nystrom PCG needs more full n x n
                   matvecs than the smoke's time allows at n = 10^6 before
                   its residual settles below 1, hence its smaller n.
  (d) reference    askotch against the direct Cholesky solve at n = 4096.
  (e) serving      the askotch model of (c) in ``ServingEngine``: requests
                   of mixed sizes, each answered as ``predict_fn`` answers.

Four chips (``--four-chips``): sharded askotch on a 4x1 mesh against the
same solve on a 1x1 mesh, and ``method="dc"`` with 4 shards on the 4 chips
against the same 4 shards with no mesh.

Each phase prints its wall time and its compile time (tracing, lowering
and backend compiles, persistent-cache loads included) on a line of its
own: the timings of a smoke, not benchmark metrics.  A failed check raises
and the script exits non-zero.  Only when every phase has passed is the
last line of standard output the JSON object
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

# the taxi deployment's settings (configs/askotch_krr.py); n is cut below
TAXI = dict(kernel="rbf", sigma=1.0, lam_unscaled=2e-7, d=9)
N_ONE_CHIP = 1_000_000
N_PCG = 100_000
N_FOUR_CHIPS = 262_144
N_REFERENCE = 4096
SEED = 0

_COMPILE_EVENTS = (
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
)
_clock = {"compile_s": 0.0, "compiles": 0, "cache_hits": 0}


def _on_duration(event: str, duration: float, **_) -> None:
    if event in _COMPILE_EVENTS:
        _clock["compile_s"] += duration
    if event == "/jax/core/compile/backend_compile_duration":
        _clock["compiles"] += 1


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _clock["cache_hits"] += 1


@contextlib.contextmanager
def phase(name: str):
    before = dict(_clock)
    t0 = time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    print(
        f"[smoke] phase {name}: wall {wall:.3f} s, compile "
        f"{_clock['compile_s'] - before['compile_s']:.3f} s "
        f"({_clock['compiles'] - before['compiles']} backend compiles, "
        f"{_clock['cache_hits'] - before['cache_hits']} persistent-cache hits)",
        flush=True,
    )


def require_tpu():
    """Phase (a): the first device JAX reports must be a TPU."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX found no TPU (first device: {dev.platform}); "
            f"this smoke runs on the chip only"
        )
    return dev


def check(ok: bool, detail) -> None:
    """Fail the smoke (an explicit raise: ``assert`` vanishes under -O)."""
    if not ok:
        raise AssertionError(detail)


def _tol(ref: np.ndarray, rtol: float, atol: float) -> dict:
    # tests/test_kernels_pallas.py::_tol — atol scales with the reference
    return dict(rtol=rtol, atol=atol * max(1.0, float(np.abs(ref).max())))


def tile_parity(m: int = 4096, n: int = 65536, d: int = 9) -> None:
    """Phase (b): Pallas tiles against the chunked-XLA reference."""
    import jax

    from repro.core.kernels import KERNEL_NAMES
    from repro.kernels import ops

    rng = np.random.default_rng(SEED)
    a = rng.standard_normal((m, d)).astype(np.float32)
    b = rng.standard_normal((n, d)).astype(np.float32)
    v16 = rng.standard_normal((n, 16)).astype(np.float32)
    # f32: tests' 2e-4 / 2e-5; bf16: the tests' bf16 bound (0.05, 0.02)
    tols = {"f32": (2e-4, 2e-5), "bf16": (0.05, 0.02)}
    for kernel in KERNEL_NAMES:
        for precision in ops.PRECISIONS:
            for v in (v16[:, 0], v16):
                kw = dict(kernel=kernel, sigma=3.0, precision=precision)
                got = np.asarray(ops.kernel_matvec(a, b, v, backend="pallas", **kw))
                with jax.default_matmul_precision("highest"):
                    want = np.asarray(
                        ops.kernel_matvec(a, b, v, backend="xla", **kw)
                    )
                t = 1 if v.ndim == 1 else v.shape[1]
                np.testing.assert_allclose(
                    got, want, **_tol(want, *tols[precision]),
                    err_msg=f"{kernel} {precision} t={t}",
                )
    print(f"[smoke] tile parity: {len(KERNEL_NAMES)} kernels x f32/bf16 x "
          f"t in (1, 16) match at m={m}, n={n}, d={d}", flush=True)


def _mean_rmse(y_train, y_test) -> float:
    y_train, y_test = np.asarray(y_train), np.asarray(y_test)
    return float(np.sqrt(np.mean((y_test - y_train.mean()) ** 2)))


def main_path():
    """Phase (c): askotch and pcg-nystrom through ``krr_solve``."""
    from repro.launch import krr_solve

    base = [
        "--d", str(TAXI["d"]),
        "--kernel", TAXI["kernel"], "--sigma", str(TAXI["sigma"]),
        "--lam", str(TAXI["lam_unscaled"]), "--seed", str(SEED),
    ]
    runs = {}
    for method, n, iters in (("askotch", N_ONE_CHIP, 50),
                             ("pcg-nystrom", N_PCG, 20)):
        run = krr_solve.run(base + ["--method", method, "--n", str(n),
                                    "--iters", str(iters)])
        rep = run.report
        mean_rmse = _mean_rmse(run.problem.y, run.y_test)
        print(f"[smoke] {method}: " + json.dumps(
            {k: rep[k] for k in ("n", "iters", "rel_residual", "test_rmse",
                                 "seconds", "backend", "device_kind")}
            | {"mean_predictor_rmse": mean_rmse}), flush=True)
        check(rep["backend"] == "pallas", rep["backend"])
        rel = rep["rel_residual"]
        check(np.isfinite(rel) and rel < 1.0, rep)
        check(rep["test_rmse"] < mean_rmse, (rep["test_rmse"], mean_rmse))
        runs[method] = run
    return runs["askotch"]


def reference(n: int = N_REFERENCE, d: int = TAXI["d"]) -> None:
    """Phase (d): askotch against direct at small n, on the Pallas backend
    (the bound of tests/test_core_solvers.py::
    test_askotch_matches_direct_solution)."""
    import jax.numpy as jnp

    from repro.core.krr import KRRProblem
    from repro.core.solver_api import solve
    from repro.kernels.ops import resolve_backend

    r = np.random.default_rng(SEED + 3)
    x = jnp.asarray(r.standard_normal((n, d)).astype(np.float32))
    spec = dict(kernel="rbf", sigma=2.0, lam_unscaled=1e-5)
    w_true = jnp.asarray(r.standard_normal(n).astype(np.float32))
    y = KRRProblem(x=x, y=jnp.zeros(n), **spec).k_lam_matvec(w_true)
    prob = KRRProblem(x=x, y=y, **spec)
    check(resolve_backend(prob.backend) == "pallas", prob.backend)
    w_star = solve(prob, "direct").w
    res = solve(prob, "askotch", block_size=n // 5, rank=120, max_iters=800,
                eval_every=200, tol=1e-7)
    err = float(jnp.linalg.norm(res.w - w_star) / jnp.linalg.norm(w_star))
    print(f"[smoke] reference n={n}: askotch vs direct rel err {err!r}, "
          f"rel_residual {res.history[-1]['rel_residual']!r}", flush=True)
    check(err < 0.05, err)


def serving(run, requests: int = 36, max_batch: int = 512) -> None:
    """Phase (e): the (c) askotch model behind ``ServingEngine``."""
    from repro.serving.engine import ServingEngine

    x_test = np.asarray(run.x_test)
    rng = np.random.default_rng(SEED + 5)
    sizes = rng.integers(1, max_batch + 1, size=requests)
    starts = rng.integers(0, x_test.shape[0] - max_batch, size=requests)
    config = {"kernel": TAXI["kernel"], "sigma": TAXI["sigma"]}
    with ServingEngine(max_batch=max_batch) as engine:
        engine.register("taxi", config, run.problem.x, run.out.w)
        futures = [
            (s, q, engine.submit("taxi", x_test[s : s + q]))
            for s, q in zip(starts, sizes)
        ]
        engine.drain()
        for s, q, fut in futures:
            got = np.asarray(fut.result())
            want = np.asarray(run.out.predict_fn(x_test[s : s + q]))
            np.testing.assert_allclose(got, want, **_tol(want, 1e-5, 1e-6))
    print(f"[smoke] serving: {requests} requests of 1..{max_batch} rows match "
          f"predict_fn", flush=True)


def four_chip_paths(n: int = N_FOUR_CHIPS, iters: int = 50) -> None:
    """Sharded askotch 4x1 vs 1x1, and dc on 4 chips vs dc with no mesh."""
    import jax

    from repro.core.krr import KRRProblem
    from repro.core.solver_api import solve
    from repro.data import synthetic
    from repro.distributed.dc import solve_dc
    from repro.distributed.meshes import make_solver_mesh

    x, y, x_test, _ = synthetic.krr_regression(SEED, n, TAXI["d"], 2000)
    prob = KRRProblem(x=x, y=y, kernel=TAXI["kernel"], sigma=TAXI["sigma"],
                      lam_unscaled=TAXI["lam_unscaled"])
    mesh4, mesh1 = make_solver_mesh("4x1"), make_solver_mesh("1x1")
    chips = list(mesh4.devices.flat)
    check(len({c.id for c in chips}) == 4, chips)
    check(all(c.platform == "tpu" for c in chips), chips)

    def rel(a, b) -> float:
        a, b = np.asarray(a), np.asarray(b)
        return float(np.linalg.norm(a - b) / np.linalg.norm(b))

    with phase("four-chip sharded askotch"):
        kw = dict(max_iters=iters, eval_every=25)
        out4 = solve(prob, "askotch", mesh=mesh4, **kw)
        out1 = solve(prob, "askotch", mesh=mesh1, **kw)
        rows = {s.device.id: s.data.shape[0] for s in out4.w.addressable_shards}
        check(sorted(rows) == sorted(c.id for c in chips), rows)
        check(set(rows.values()) == {n // 4}, rows)
        w_err = rel(out4.w, out1.w)
        p_err = rel(out4.predict_fn(x_test), out1.predict_fn(x_test))
        r4 = out4.history[-1]["rel_residual"]
        r1 = out1.history[-1]["rel_residual"]
        print(f"[smoke] sharded askotch n={n}: rows per chip {rows}; 4x1 vs "
              f"1x1 rel diff w {w_err!r}, predictions {p_err!r}; "
              f"rel_residual 4x1 {r4!r}, 1x1 {r1!r}", flush=True)
        check(w_err < 1e-3 and p_err < 1e-3, (w_err, p_err))
        check(abs(r4 - r1) <= 1e-3 * abs(r1), (r4, r1))

    with phase("four-chip dc"):
        kw = dict(shards=4, method="askotch", max_iters=iters)
        dc4 = solve_dc(prob, mesh=mesh4, **kw)
        dc0 = solve_dc(prob, **kw)
        placed = [next(iter(o.w.devices())).id for o in dc4.shard_outputs]
        check(sorted(placed) == sorted(c.id for c in chips), placed)
        sizes = [int(o.w.shape[0]) for o in dc4.shard_outputs]
        check(sizes == [n // 4] * 4, sizes)
        w_err = rel(dc4.w, dc0.w)
        p_err = rel(dc4.predict_fn(x_test), dc0.predict_fn(x_test))
        print(f"[smoke] dc n={n}: shards on chips {placed}, rows {sizes}; "
              f"4 chips vs no mesh rel diff w {w_err!r}, predictions "
              f"{p_err!r}", flush=True)
        check(w_err < 1e-5 and p_err < 1e-5, (w_err, p_err))


def main(argv: list[str] | None = None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the multi-chip paths, on four chips")
    args = ap.parse_args(argv)

    import jax

    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    with phase("(a) device"):
        dev = require_tpu()
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.runtime import use_compile_cache

    print(f"[smoke] device {dev.device_kind}, {jax.device_count()} chip(s); "
          f"compile cache {use_compile_cache()}", flush=True)

    if args.four_chips:
        if jax.device_count() < 4:
            raise SystemExit(f"--four-chips needs 4 chips; JAX found "
                             f"{jax.device_count()}")
        four_chip_paths()
    else:
        with phase("(b) tile parity"):
            tile_parity()
        with phase("(c) main path"):
            run = main_path()
        with phase("(d) reference"):
            reference()
        with phase("(e) serving"):
            serving(run)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count(),
    }}))


if __name__ == "__main__":
    main()
