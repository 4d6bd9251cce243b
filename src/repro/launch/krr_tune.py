"""KRR hyperparameter tuning launcher — tune, refit, evaluate, export.

    PYTHONPATH=src python -m repro.launch.krr_tune --n 4000 --d 8 \
        --sigmas 0.5,1.0,2.0 --lams 1e-6,1e-4,1e-2 --folds 5

    # random search over the grid, distributed over a device mesh
    PYTHONPATH=src python -m repro.launch.krr_tune --search random --samples 6 \
        --mesh 4x1 --dataset one-vs-all --classes 8

    # multi-kernel: random search over convex kernel combinations
    PYTHONPATH=src python -m repro.launch.krr_tune \
        --kernels rbf,laplacian,matern52 --n-weight-samples 8

    # successive halving (prune losers mid-solve) + sigma-continuation
    PYTHONPATH=src python -m repro.launch.krr_tune --policy halving \
        --sigma-continuation --lams 1e-8,1e-6,1e-4,1e-2

The sweep is the tile-sharing path of ``repro.core.tune`` (``--strategy
naive`` runs the per-candidate reference loop for comparison); ``--kernels``
(a comma list) grows the weight axis — himalaya-style Dirichlet random
search over convex kernel combinations on the same stacked engine.
``--policy halving`` runs successive halving: losing (lam[, weight])
candidates are frozen at rungs MID-SOLVE (strictly fewer kernel sweeps than
the grid at equal best config when the winner separates early);
``--sigma-continuation`` seeds each sigma group's solve and sketch from the
previous group's result.  The report includes the kernel-sweep count so the
sharing is visible.  After the sweep the best config is refit on the full
training set with ``--method`` (warm-started from the winner's
fold-averaged CV solution when the method supports ``w0``) and scored on
held-out test data; ``--export PATH`` writes the serving-ready best-config
JSON — including the per-candidate ``trace`` (rung scores + prune points)
so the search is auditable — consumed by ``serving.krr_serve.
make_krr_predict_fn_from_config``; ``--export-artifact DIR`` additionally
writes a full serving artifact (config + training rows + refit weights)
that ``repro.launch.krr_serve``/``ServingEngine.load_model`` hot-load from
disk.  See docs/tuning.md and docs/serving.md for the walkthroughs.
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from repro.core.kernels import KERNEL_NAMES
from repro.core.krr import KRRProblem, evaluate
from repro.core.solver_api import solve as solve_any
from repro.core.solver_api import tune
from repro.core.tune import apply_best
from repro.data import synthetic
from repro.launch.runtime import device_fields, use_compile_cache


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=4_000)
    ap.add_argument("--d", type=int, default=8)
    ap.add_argument("--n-test", type=int, default=1_000)
    ap.add_argument("--kernel", default="rbf", choices=KERNEL_NAMES,
                    help="kernel zoo name (core.kernels.KERNEL_NAMES)")
    ap.add_argument("--kernels", default=None,
                    help="comma-separated kernel zoo names: tune a convex "
                         "multi-kernel combination (weight random search)")
    ap.add_argument("--n-weight-samples", type=int, default=8,
                    help="Dirichlet weight draws for --kernels search")
    ap.add_argument("--dirichlet-alpha", type=float, default=1.0,
                    help="Dirichlet concentration of the weight draws")
    ap.add_argument("--sigmas", default="0.5,1.0,2.0",
                    help="comma-separated candidate bandwidths")
    ap.add_argument("--lams", default="1e-6,1e-4,1e-2",
                    help="comma-separated candidate unscaled regularizers")
    ap.add_argument("--folds", type=int, default=5)
    ap.add_argument("--search", default="grid", choices=["grid", "random"])
    ap.add_argument("--samples", type=int, default=None,
                    help="random-search candidate count (default: full grid)")
    ap.add_argument("--policy", default=None,
                    choices=["grid", "random", "halving"],
                    help="search policy (supersedes --search); 'halving' "
                         "prunes losing candidates at rungs mid-solve")
    ap.add_argument("--halving-eta", type=float, default=3.0,
                    help="successive-halving reduction factor (> 1)")
    ap.add_argument("--sigma-continuation", action="store_true",
                    help="seed each sigma group's solve + sketch from the "
                         "previous group instead of from zero")
    ap.add_argument("--strategy", default="shared", choices=["shared", "naive"])
    ap.add_argument("--rank", type=int, default=100,
                    help="Nystrom preconditioner rank")
    ap.add_argument("--iters", type=int, default=200)
    ap.add_argument("--tol", type=float, default=1e-5)
    ap.add_argument("--precision", default="f32", choices=["f32", "bf16"],
                    help="kernel tile-compute policy for the sweep AND the "
                         "refit: bf16 tiles with f32 accumulation, or f32")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh", default=None,
                    help="ROWSxMODEL device mesh (e.g. 4x1) or 'auto'; runs "
                         "the sweep over a ShardedKernelOperator")
    ap.add_argument("--dataset", default="regression",
                    choices=["regression", "classification", "one-vs-all", "taxi"])
    ap.add_argument("--classes", type=int, default=4)
    ap.add_argument("--method", default="askotch",
                    help="refit method for the best config")
    ap.add_argument("--refit-iters", type=int, default=300)
    ap.add_argument("--no-refit", action="store_true",
                    help="report the sweep only; skip refit + test metrics")
    ap.add_argument("--export", default=None,
                    help="write the best-config JSON here (serving input)")
    ap.add_argument("--export-artifact", default=None,
                    help="write a full serving artifact directory here "
                         "(config.json + weights.npz with the refit "
                         "solution; loadable by ServingEngine.load_model)")
    ap.add_argument("--telemetry", default=None, metavar="PATH",
                    help="write telemetry (spans + tune traces + metrics) "
                         "as JSONL to PATH (repro.obs)")
    args = ap.parse_args()
    if args.export_artifact and args.no_refit:
        ap.error("--export-artifact needs the refit weights; drop --no-refit")

    if args.dataset == "taxi":
        x, y = synthetic.taxi_like(args.seed, args.n + args.n_test, args.d)
        x_tr, y_tr, x_te, y_te = x[: args.n], y[: args.n], x[args.n :], y[args.n :]
    elif args.dataset == "one-vs-all":
        x_tr, y_tr, _, x_te, y_te, _labels = synthetic.krr_one_vs_all(
            args.seed, args.n, args.d, num_classes=args.classes,
            n_test=args.n_test,
        )
    else:
        gen = (synthetic.krr_classification if args.dataset == "classification"
               else synthetic.krr_regression)
        x_tr, y_tr, x_te, y_te = gen(args.seed, args.n, args.d, args.n_test)

    prob = KRRProblem(x=x_tr, y=y_tr, kernel=args.kernel,
                      precision=args.precision)
    mesh = None
    if args.mesh is not None:
        from repro.distributed.meshes import make_solver_mesh

        mesh = make_solver_mesh(args.mesh)

    t0 = time.perf_counter()
    tune_kw = dict(
        sigmas=tuple(float(s) for s in args.sigmas.split(",")),
        lams=tuple(float(l) for l in args.lams.split(",")),
        folds=args.folds,
        strategy=args.strategy,
        rank=args.rank,
        max_iters=args.iters,
        tol=args.tol,
        seed=args.seed,
        sigma_continuation=args.sigma_continuation,
    )
    tel = None
    if args.telemetry:
        from repro.obs import Telemetry

        tel = Telemetry(jsonl=args.telemetry)
        tune_kw["telemetry"] = tel
    if args.policy is not None:
        tune_kw.update(policy=args.policy, halving_eta=args.halving_eta)
    if args.kernels is not None:
        if args.search != "grid" or args.samples is not None:
            ap.error(
                "--search/--samples do not apply with --kernels; the weight "
                "axis IS the random search (use --n-weight-samples, or "
                "--policy halving to prune it)"
            )
        bad = [k for k in args.kernels.split(",") if k not in KERNEL_NAMES]
        if bad:
            ap.error(f"unknown kernel(s) {bad}; available: {KERNEL_NAMES}")
        # the weight axis: every (w, lam, fold, head) candidate rides the
        # same stacked solve (repro.core.tune.tune_multikernel)
        tune_kw.update(
            kernels=tuple(args.kernels.split(",")),
            n_weight_samples=args.n_weight_samples,
            dirichlet_alpha=args.dirichlet_alpha,
        )
    else:
        tune_kw.update(search=args.search, num_samples=args.samples)
    result = tune(prob, mesh=mesh, **tune_kw)
    report = {
        "best": result.best,
        "strategy": result.strategy,
        "search": result.search,
        "policy": result.info["policy"],
        "candidates": result.info["candidates"],
        "folds": result.folds,
        "kernel_sweeps": round(result.sweeps, 2),
        "naive_sweep_estimate": round(result.info["naive_sweep_estimate"], 2),
        "records": result.records,
        "trace": result.trace,
        **device_fields(prob.backend),
    }
    if args.kernels is not None:
        report["weight_samples"] = result.info["weight_samples"]
    if mesh is not None:
        report["mesh"] = dict(mesh.shape)

    if not args.no_refit:
        from repro.core.solver_api import METHOD_OPTIONS

        best_prob, w0 = apply_best(prob, result, with_w0=True)
        kw = {} if args.method == "direct" else {"max_iters": args.refit_iters}
        if args.method == "eigenpro":
            kw = {"epochs": max(1, args.refit_iters // 100)}
        if args.method == "falkon":
            kw["m"] = min(1000, max(50, args.n // 20), args.n)
        if tel is not None:
            kw["telemetry"] = tel  # refit rides the same JSONL stream
        if (w0 is not None and mesh is None
                and "w0" in METHOD_OPTIONS.get(args.method, ())):
            # warm-start the refit from the winner's fold-averaged CV
            # solution instead of zero
            kw["w0"] = w0
            report["refit_warm_start"] = True
        out = solve_any(best_prob, args.method, mesh=mesh, **kw)
        m = evaluate(np.asarray(out.predict_fn(x_te)), y_te)
        report["refit"] = {
            "method": args.method,
            "test_rmse": float(m.rmse),
            "test_mae": float(m.mae),
            "test_acc": float(m.accuracy),
        }
        if args.export_artifact:
            from repro.serving.engine import save_model_artifact

            # tune -> refit -> artifact: config + training rows + refit
            # weights as files on disk, hot-loadable by the serving engine
            save_model_artifact(args.export_artifact, result.best,
                                np.asarray(x_tr), np.asarray(out.w))
            report["exported_artifact"] = args.export_artifact
    report["seconds"] = round(time.perf_counter() - t0, 2)
    if tel is not None:
        tel.close()  # flush metric events after all spans close
        report["telemetry"] = args.telemetry

    if args.export:
        # the serving-ready best config PLUS the audit trail: serving
        # ignores unknown keys, so the same file feeds
        # make_krr_predict_fn_from_config and post-hoc search forensics
        with open(args.export, "w") as fh:
            json.dump({**result.best, "trace": result.trace}, fh, indent=2)
        report["exported"] = args.export
    print(json.dumps(report))


if __name__ == "__main__":
    main()
