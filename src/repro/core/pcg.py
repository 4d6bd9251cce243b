"""Preconditioned conjugate gradient for full KRR (baseline, paper §4.1/§6).

Preconditioners:
  * "nystrom"    — rank-r Gaussian-Nystrom of the full K (Frangella et al.
                   2023), sketch computed with the fused streaming matvec;
                   supports the paper's "damped"/"regularization" rho modes.
  * "rpcholesky" — rank-r randomly-pivoted-Cholesky factor (Diaz et al. 2023).
  * "rff"        — rank-r random-Fourier-feature factors (``core/rff.py``);
                   rbf-only, built from one streamed feature pass with NO
                   kernel sweeps, applied through the same damped-rho
                   Woodbury formula as Nystrom.
  * "identity"   — plain CG.

The iteration is blocked CG over (n, t) right-hand sides (Diaz et al. 2023
formulate randomized-preconditioned PCG over block RHS the same way): each
column carries its own alpha/beta/residual, columns that hit ``tol`` are
frozen, and the O(n^2 d) streamed K matvec — exactly the scaling wall the
paper documents (Fig. 1: no PCG iteration finishes at n = 1e8, reproduced in
benchmarks/bench_table2_scaling.py) — is shared by all t columns per
iteration.  A 1-D y is the t = 1 special case.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp

from repro.core.blocked_cg import blocked_cg
from repro.core.krr import KRRProblem
from repro.core.nystrom import NystromFactors, nystrom_from_sketch
from repro.core.operator import as_multirhs, maybe_squeeze
from repro.core.rpcholesky import rp_cholesky
from repro.kernels.precision import solver_matmuls
from repro.obs.metrics import record_tile_work
from repro.obs.telemetry import as_telemetry


@dataclasses.dataclass
class PCGResult:
    w: jax.Array
    iters: int
    history: list[dict]
    converged: bool
    wall_time_s: float


def _nystrom_full(problem: KRRProblem, rank: int, key: jax.Array) -> NystromFactors:
    op = problem.op
    omega = jax.random.normal(key, (op.n, rank), jnp.float32)
    omega, _ = jnp.linalg.qr(omega)
    sketch = op.sketch(omega)
    return nystrom_from_sketch(sketch, omega, op.trace_est())


def _rff_full(problem: KRRProblem, rank: int, key: jax.Array) -> NystromFactors:
    from repro.core.rff import RFF_KERNELS, rff_factors  # local: keep pcg import-light

    if problem.kernel not in RFF_KERNELS:
        raise ValueError(
            'kind="rff" preconditioning needs a shift-invariant kernel with '
            f"an implemented spectral measure ({RFF_KERNELS}); got "
            f"kernel={problem.kernel!r} — use kind=\"nystrom\""
        )
    return rff_factors(
        key, problem.x, rank, float(problem.sigma), kernel=problem.kernel
    )


def make_preconditioner(
    problem: KRRProblem,
    kind: str = "nystrom",
    rank: int = 100,
    rho_mode: str = "damped",
    seed: int = 0,
) -> Callable[[jax.Array], jax.Array]:
    """Returns P^{-1} apply over a (n, t) residual block.  For Nystrom-type
    preconditioners:
    P^{-1} V = U diag((lam_r + rho)/(lam_j + rho)) U^T V + (V - U U^T V)."""
    lam = jnp.float32(problem.lam)
    if kind == "identity":
        return lambda v: v
    # the factors and their apply at the problem's precision (full f32
    # under "f32"; a TPU's default is one bf16 pass)
    with solver_matmuls(problem.precision):
        if kind == "nystrom":
            f = _nystrom_full(problem, rank, jax.random.PRNGKey(seed))
        elif kind == "rff":
            f = _rff_full(problem, rank, jax.random.PRNGKey(seed))
        elif kind == "rpcholesky":
            fmat, _ = rp_cholesky(jax.random.PRNGKey(seed), problem.op, rank)
            u, s, _ = jnp.linalg.svd(fmat, full_matrices=False)
            f = NystromFactors(u=u, lam=s * s)
        else:
            raise ValueError(f"unknown preconditioner {kind!r}")

    rho = lam + f.lam[-1] if rho_mode == "damped" else lam
    coeff = (f.lam[-1] + rho) / (f.lam + rho)

    def apply(v: jax.Array) -> jax.Array:
        with solver_matmuls(problem.precision):
            utv = f.u.T @ v
            scaled = utv * (coeff[:, None] if v.ndim == 2 else coeff)
            return f.u @ scaled + (v - f.u @ utv)

    return apply


def solve_pcg(
    problem: KRRProblem,
    *,
    precond: str = "nystrom",
    rank: int = 100,
    rho_mode: str = "damped",
    max_iters: int = 200,
    tol: float = 1e-8,
    seed: int = 0,
    time_budget_s: float | None = None,
    w0: jax.Array | None = None,
    telemetry=None,
) -> PCGResult:
    """Blocked PCG on (K + lam I) W = Y with per-column residual tracking.

    History records carry ``rel_residual`` (aggregate ||R||_F / ||Y||_F) and
    ``rel_residual_per_head``; convergence requires every column below tol.
    ``w0`` warm-starts the iteration (e.g. the fold-averaged CV solution a
    tuning sweep hands back, ``TuneResult.best_w0``) at the cost of one
    extra matvec for the initial residual.  ``telemetry`` adds a solve span,
    canonical per-iteration trace events, and tile-work metrics.
    """
    tel = as_telemetry(telemetry)
    n = problem.n
    d = problem.x.shape[1]
    precision = getattr(problem.op, "precision", "f32")
    recorder = tel.recorder("pcg", precision=precision, n=n)
    with tel.span("solve/pcg", n=n, t=problem.t, precond=precond, rank=rank,
                  max_iters=max_iters, tol=tol):
        t0 = time.perf_counter()
        pinv = make_preconditioner(problem, precond, rank, rho_mode, seed)
        matvec = jax.jit(problem.k_lam_matvec)
        pinv = jax.jit(pinv)

        y, squeeze = as_multirhs(problem.y)
        x0 = None
        if w0 is not None:
            x0, _ = as_multirhs(jnp.asarray(w0))
        res = blocked_cg(
            matvec, y, pinv, x0=x0, max_iters=max_iters, tol=tol, t0=t0,
            time_budget_s=time_budget_s, recorder=recorder,
        )
        if tel.enabled:
            # each CG iteration streams one full (n, n) K matvec; the warm
            # start costs one extra for the initial residual
            record_tile_work(n, n, d, precision,
                             count=res.iters + (1 if x0 is not None else 0))
    return PCGResult(
        w=maybe_squeeze(res.x, squeeze), iters=res.iters, history=res.history,
        converged=res.converged, wall_time_s=time.perf_counter() - t0,
    )
