"""End-to-end driver for the paper's workload: large-scale full KRR solve
with checkpointing, solver comparison, and final test metrics.

    PYTHONPATH=src python examples/krr_end_to_end.py [--n 50000]

This is the CPU-scale rendition of the paper's §6.2 taxi showcase: a
taxi-flavored dataset, the paper's default hyperparameters, a wall-clock
budget shared across solvers, and ASkotch checkpoint/restart mid-solve
(the solver state is just (w, v, z, key) — restart is exact).
"""

import argparse
import time

import jax
import jax.numpy as jnp

from repro.checkpoint import checkpointer
from repro.core import ASkotchConfig, KRRProblem, evaluate, solve_any
from repro.core.askotch import init_state, make_step
from repro.data import synthetic


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=50_000)
    ap.add_argument("--budget-s", type=float, default=60.0)
    ap.add_argument("--ckpt", default="/tmp/krr_ckpt")
    args = ap.parse_args()

    n = args.n
    x, y = synthetic.taxi_like(0, n + 5000, 9)
    x_tr, y_tr, x_te, y_te = x[:n], y[:n], x[n:], y[n:]
    prob = KRRProblem(x=x_tr, y=y_tr, kernel="rbf", sigma=1.0,
                      lam_unscaled=2e-7)

    # --- ASkotch with mid-solve checkpoint/restart -------------------------
    cfg = ASkotchConfig()
    step = jax.jit(make_step(prob, cfg))
    state = init_state(prob)
    t0 = time.perf_counter()
    it = 0
    while time.perf_counter() - t0 < args.budget_s / 2:
        state, _ = step(state)
        it += 1
        if it % 100 == 0:
            checkpointer.save(args.ckpt, it, {"w": state.w, "v": state.v,
                                              "z": state.z, "key": state.key})
    # simulate a restart: reload the latest checkpoint and keep solving
    if checkpointer.latest_step(args.ckpt):
        saved, _, it = checkpointer.restore(args.ckpt)
        state = state._replace(
            w=jnp.asarray(saved["w"]), v=jnp.asarray(saved["v"]),
            z=jnp.asarray(saved["z"]), key=jnp.asarray(saved["key"]),
        )
        print(f"[restart] resumed at iteration {it}")
    while time.perf_counter() - t0 < args.budget_s:
        state, _ = step(state)
        it += 1
    rel = float(prob.relative_residual(state.w))
    m = evaluate(prob.predict(state.w, x_te), y_te)
    print(f"askotch: iters={it} rel_res={rel:.3e} test_rmse={float(m.rmse):.2f}")

    # --- the comparison the paper runs (equal budget) -----------------------
    for method, kw in (
        ("falkon", dict(m=min(1000, n // 20), max_iters=10_000,
                        time_budget_s=args.budget_s)),
        ("pcg-nystrom", dict(rank=100, max_iters=10_000,
                             time_budget_s=args.budget_s)),
    ):
        out = solve_any(prob, method, **kw)
        mm = evaluate(out.predict_fn(x_te), y_te)
        print(f"{method}: iters={out.info.get('iters')} "
              f"test_rmse={float(mm.rmse):.2f}")

    print(f"const-baseline rmse: {float(jnp.std(y_te)):.2f}")


if __name__ == "__main__":
    main()
