"""The loads a traffic mix can name (its ``"load"`` key).

* ``solve_loop``: back-to-back ``repro.core.solver_api.solve`` calls, each
  from w = 0 until the configuration's ``tol`` (or its ``max_iters`` cap).

A load is built from (configuration, traffic mix, seed) and run in four
steps: ``setup`` (inputs, the model, warm-up of every shape the window
uses), ``window`` (the measured load), ``release`` (drop the program's
state, once the peak memory has been read) and ``check`` (compare what the
window produced with the plain reference).  Everything the window does is
drawn from the seed; the program receives only the generated inputs.
"""

from __future__ import annotations

import math
import time
from typing import NamedTuple

import numpy as np

import data
import named


class Check(NamedTuple):
    """One number compared with the reference; passes when value <= limit."""

    name: str
    value: float
    limit: float


def lam_of(config: dict) -> float:
    """The regularisation the system solves with: n * lam_unscaled."""
    return config["n"] * config["lam_unscaled"]


def _columns(a: np.ndarray) -> np.ndarray:
    return a[:, None] if a.ndim == 1 else a


class SolveLoop:
    """Back-to-back solves of one KRR problem, each from w = 0."""

    def __init__(self, config: dict, traffic: dict, limits: dict, seed: int):
        self.config, self.traffic, self.limits, self.seed = config, traffic, limits, seed
        self.solves: list[dict] = []
        self.window_s = 0.0

    def setup(self) -> None:
        import jax.numpy as jnp

        from repro.core.krr import KRRProblem

        c = self.config
        self.x, self.y = data.training_set(c, self.seed)
        self.sigma = c["sigma"]
        self.problem = KRRProblem(
            x=jnp.asarray(self.x), y=jnp.asarray(self.y), kernel=c["kernel"],
            sigma=self.sigma, lam_unscaled=c["lam_unscaled"],
            backend=c["backend"], precision=c["precision"],
        )
        self.options = {k: c[k] for k in c["solve_options"]}
        # the programs a solve runs do not depend on max_iters: a solve of
        # warmup_iters (for askotch, one check period) compiles all of them
        self._solve(data.small_seed(self.seed, 3), max_iters=c["warmup_iters"])

    def _solve(self, solver_seed: int, max_iters: int):
        import jax

        from repro.core.solver_api import solve

        out = solve(self.problem, self.config["solver"], seed=solver_seed,
                    max_iters=max_iters, tol=self.config["tol"], **self.options)
        jax.block_until_ready(out.w)
        return out

    def window(self, seconds: float) -> None:
        import jax

        t0 = time.perf_counter()
        k = 0
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation(f"bench.solve.{k}"):
                out = self._solve(data.small_seed(self.seed, 4, k),
                                  max_iters=self.config["max_iters"])
            last = out.history[-1]
            self.solves.append({
                "w": out.w,
                "iters": int(out.info["iters"]),
                "converged": bool(out.info["converged"]),
                "checks": len(out.history),
                "reported": np.asarray(last["rel_residual_per_head"], np.float64),
            })
            k += 1
        self.window_s = time.perf_counter() - t0

    @property
    def attempted(self) -> int:
        return len(self.solves)

    @property
    def failed(self) -> int:
        return sum(not s["converged"] for s in self.solves)

    def diagnostics(self) -> list[str]:
        return [f"solve {k}: {s['iters']} iterations, converged {s['converged']}, "
                f"reported residual {s['reported'].tolist()}"
                for k, s in enumerate(self.solves)]

    def end_to_end(self) -> dict[str, float]:
        return {"solve_s": self.window_s / max(len(self.solves), 1)}

    def record(self) -> dict:
        c = self.config
        return {
            "iters": [s["iters"] for s in self.solves],
            "resid_at_stop": [float(np.max(s["reported"])) for s in self.solves],
            "checks": [s["checks"] for s in self.solves],
            "n": c["n"], "d": c["d"], "t": c["t"], "b": c["block_size"],
            "kernel": c["kernel"],
        }

    def release(self) -> None:
        for s in self.solves:
            s["w"] = np.asarray(s["w"])
        del self.problem

    def check(self) -> list[Check]:
        """Every solve of the window against the reference: its residual
        over ``tol`` (the configuration's own limit), and the gap between
        the residual the solver reported and the reference's."""
        import jax.numpy as jnp

        c = self.config
        reference = named.load("", c["reference"])
        x = jnp.asarray(self.x)
        y = jnp.asarray(_columns(self.y))
        over, gap = 0.0, 0.0
        for s in self.solves:
            ref = np.asarray(reference.relative_residuals(
                x, y, jnp.asarray(_columns(s["w"])), kernel=c["kernel"],
                sigma=float(self.sigma), lam=lam_of(c)), np.float64)
            over = max(over, float(np.max(ref)) / c["tol"])
            gap = max(gap, float(np.max(np.abs(s["reported"] - ref) / ref)))
        if not self.solves:
            over = math.inf
        return [Check("resid_over_tol", over, 1.0),
                Check("resid_gap", gap, self.limits["resid_gap"])]


LOADS = {"solve_loop": SolveLoop}
