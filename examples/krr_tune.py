"""End-to-end hyperparameter tuning: tune -> refit -> serve.

    PYTHONPATH=src python examples/krr_tune.py [--n 4000 --classes 4]

A synthetic one-vs-all classification task goes through the whole production
path (docs/tuning.md): the tile-sharing (sigma, lam) sweep with k-fold CV
picks the config, the winner is refit on the full training set with one
multi-RHS ASkotch solve, and the exported best-config dict drives the batched
serving closure — the same three calls a real deployment makes.
"""

import argparse

import numpy as np

from repro.core import KRRProblem, apply_best, evaluate, solve_any, tune
from repro.data import synthetic
from repro.serving.krr_serve import make_krr_predict_fn_from_config


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4_000)
    ap.add_argument("--d", type=int, default=6)
    ap.add_argument("--n-test", type=int, default=500)
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--iters", type=int, default=200)
    args = ap.parse_args()

    x_tr, y_tr, _, x_te, y_te, labels_te = synthetic.krr_one_vs_all(
        0, args.n, args.d, num_classes=args.classes, n_test=args.n_test
    )
    prob = KRRProblem(x=x_tr, y=y_tr)

    # 1. tune: all (sigma, lam) candidates x folds x heads share kernel tiles
    result = tune(
        prob, sigmas=(0.5, 1.0, 2.0), lams=(1e-4, 1e-2), folds=3,
        rank=min(64, args.n // 4), max_iters=args.iters, tol=1e-4,
    )
    print(f"best config: {result.best}")
    print(f"kernel sweeps: {result.sweeps:.1f} "
          f"(naive loop estimate: {result.info['naive_sweep_estimate']:.0f})")

    # 2. refit the winner on ALL training rows — one multi-RHS solve
    out = solve_any(apply_best(prob, result), "askotch", max_iters=args.iters)

    # 3. serve from the exported config (what --export hands a deployment)
    predict = make_krr_predict_fn_from_config(result.best, x_tr, out.w)
    scores = np.asarray(predict(x_te))
    m = evaluate(scores, y_te)
    top1 = float(np.mean(scores.argmax(axis=1) == np.asarray(labels_te)))
    print(f"serve: test top-1 acc {top1:.3f} (rmse {float(m.rmse):.3f}) "
          f"over {args.classes} one-vs-all heads")


if __name__ == "__main__":
    main()
