"""Multi-kernel KRR end to end: weight search -> warm refit -> serve.

    PYTHONPATH=src python examples/krr_multikernel.py [--n 4000]

The target mixes a smooth component (where the RBF kernel shines) with a
rough, kink-heavy component (where the Laplacian does) — no single kernel
family fits both.  The flow is the full production path (docs/tuning.md,
"Multi-kernel sweeps"):

  1. ``tune(kernels=(...))`` — himalaya-style Dirichlet random search over
     convex kernel combinations, every (weight, lam, fold) candidate riding
     ONE stacked solve per sigma on the fused multi-kernel tiles;
  2. refit the winning weighted combination on all rows, warm-started from
     the winner's fold-averaged CV solution (``apply_best(with_w0=True)``);
  3. serve from the exported best-config dict — the batched predict closure
     reconstructs the weighted-sum operator.
"""

import argparse

import numpy as np

from repro.core import KRRProblem, apply_best, evaluate, solve_any, tune


def make_data(seed: int, n: int, d: int):
    r = np.random.default_rng(seed)
    x = r.standard_normal((n, d)).astype(np.float32)
    # smooth + rough: a kernel mixture genuinely beats either family alone
    y = (np.sin(2.0 * x[:, 0]) + 0.5 * np.sign(np.sin(4.0 * x[:, 1]))).astype(
        np.float32
    )
    return x, y


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4_000)
    ap.add_argument("--d", type=int, default=6)
    ap.add_argument("--n-test", type=int, default=500)
    ap.add_argument("--n-weight-samples", type=int, default=8)
    ap.add_argument("--iters", type=int, default=200)
    args = ap.parse_args()

    import jax.numpy as jnp

    x, y = make_data(0, args.n + args.n_test, args.d)
    x_tr = jnp.asarray(x[: args.n])
    y_tr = jnp.asarray(y[: args.n])
    x_te, y_te = x[args.n :], y[args.n :]
    prob = KRRProblem(x=x_tr, y=y_tr)

    # 1. weight search: every (w, lam, fold) candidate shares kernel tiles
    result = tune(
        prob, kernels=("rbf", "laplacian", "matern52"),
        sigmas=(0.5, 1.0, 2.0), lams=(1e-4, 1e-2),
        folds=3, n_weight_samples=args.n_weight_samples,
        rank=min(64, args.n // 4), max_iters=args.iters, tol=1e-4,
    )
    w_str = ", ".join(f"{w:.2f}" for w in result.best["weights"])
    print(f"best: kernels={result.best['kernel']} weights=[{w_str}] "
          f"sigma={result.best['sigma']} lam={result.best['lam_unscaled']}")
    print(f"kernel sweeps: {result.sweeps:.1f} "
          f"(naive loop estimate: {result.info['naive_sweep_estimate']:.0f} "
          f"for {result.info['candidates']} candidates)")

    # 2. refit the winning combination, warm-started from the CV folds
    best_prob, w0 = apply_best(prob, result, with_w0=True)
    out = solve_any(best_prob, "pcg-nystrom", max_iters=args.iters, w0=w0)

    # 3. serve from the exported config (what --export hands a deployment)
    from repro.serving.krr_serve import make_krr_predict_fn_from_config

    predict = make_krr_predict_fn_from_config(result.best, x_tr, out.w)
    m = evaluate(np.asarray(predict(jnp.asarray(x_te))), jnp.asarray(y_te))
    print(f"serve: test rmse {float(m.rmse):.3f} mae {float(m.mae):.3f} "
          f"over the weighted {len(result.best['kernel'])}-kernel predictor")


if __name__ == "__main__":
    main()
