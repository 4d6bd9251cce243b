"""Skotch (Algorithm 2) and ASkotch (Algorithm 3): approximate sketch-and-
project solvers for full KRR.

Per iteration (blocksize b, Nystrom rank r, n training points, t heads):
  1. sample block B                          — uniform or ARLS (paper §3.1)
  2. K_BB                                    — fused block build, O(b^2 d)
  3. K_hat_BB = Nystrom(K_BB, r)             — Algorithm 4, O(b^2 r)
  4. rho = lam + lam_r(K_hat_BB) ("damped")  — paper §3.2 default
  5. L_PB via randomized powering            — Algorithm 5, O(b r + b^2) * 10
  6. G_B = (K_lam)_{B,:} Z - Y_B             — fused kernel matvec, O(n b d)  << hot spot
  7. D_B = (K_hat_BB + rho I)^{-1} G_B       — Woodbury, O(b r t)
  8. iterate updates (+ Nesterov mixing for ASkotch), O(n t)

The solve is multi-RHS throughout: with Y of shape (n, t) (one-vs-all heads)
steps 1-5 are shared across all t heads and steps 6-8 batch over columns, so
a t-head solve performs the kernel-tile work of a single solve per iteration.
A 1-D y is the t = 1 special case (1-D w out, no API change).

Defaults (paper §3.2): b = n/100, r = 100, uniform sampling,
mu_hat = lam (clipped so mu_hat <= nu_hat and mu_hat * nu_hat <= 1),
nu_hat = n/b, eta = 1/max(L_PB, 1).

``make_step`` binds a problem to one module-level jitted step: the training
rows, targets, ARLS probabilities, lam and the acceleration weights are its
arguments, and only the config, the operator's settings and the shapes are
static, so equal-shaped solves (new seeds, folds, a lam grid) share one
executable.  ``solve`` calls it from a Python loop with residual tracking and
checkpoint callbacks, ``solve_scan`` from a pure lax.scan for benchmarking.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import time
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import samplers
from repro.core.get_l import get_l
from repro.core.krr import KRRProblem
from repro.core.nystrom import (
    NystromFactors,
    nystrom_from_sketch,
    stable_inv_apply,
    stable_inv_apply_setup,
    woodbury_inv_apply,
)
from repro.kernels import ops
from repro.kernels.precision import MATMUL_PRECISION, solver_matmuls
from repro.obs.metrics import counter, record_tile_work
from repro.obs.telemetry import as_telemetry


@dataclasses.dataclass(frozen=True)
class ASkotchConfig:
    """Hyperparameters; defaults are the paper's recommended settings."""

    block_size: int | None = None  # default n // 100 (>= rank + 8)
    rank: int = 100
    rho_mode: str = "damped"  # "damped" (lam + lam_r) | "regularization" (lam)
    sampling: str = "uniform"  # "uniform" | "arls"
    precond: str = "nystrom"  # "nystrom" | "identity" (Lin et al. ablation)
    accelerated: bool = True  # ASkotch; False -> Skotch
    mu: float | None = None  # default: lam (clipped)
    nu: float | None = None  # default: n / b
    stable_inv: bool = True  # f32-stable Cholesky Woodbury (App. A.1.1)
    backend: str = "auto"
    powering_iters: int = 10

    def resolve_block(self, n: int) -> int:
        b = self.block_size if self.block_size is not None else max(n // 100, 1)
        return int(min(max(b, self.rank + 8), n))


class SolverState(NamedTuple):
    w: jax.Array  # (n,) or (n, t) primal iterate
    v: jax.Array  # acceleration sequence (= w when not accelerated)
    z: jax.Array  # acceleration sequence (= w when not accelerated)
    key: jax.Array
    it: jax.Array  # iteration counter
    sketch_res: jax.Array  # ||G_B|| per head ((t,) or scalar) — progress proxy


class StepAux(NamedTuple):
    step_l: jax.Array  # L_PB estimate
    rho: jax.Array


def _accel_params(mu: float, nu: float) -> tuple[float, float, float]:
    """beta, gamma, alpha from (mu_hat, nu_hat) — Algorithm 3 preamble."""
    beta = 1.0 - math.sqrt(mu / nu)
    gamma = 1.0 / math.sqrt(mu * nu)
    alpha = 1.0 / (1.0 + gamma * nu)
    return beta, gamma, alpha


def resolve_accel_params(cfg: ASkotchConfig, n: int, lam: float) -> tuple[float, float]:
    """Paper §3.2: mu_hat = lam, nu_hat = n/b, with the two safeguards
    mu_hat <= nu_hat and mu_hat * nu_hat <= 1 enforced by clipping mu."""
    b = cfg.resolve_block(n)
    nu = cfg.nu if cfg.nu is not None else n / b
    mu = cfg.mu if cfg.mu is not None else lam
    mu = min(mu, nu, 1.0 / nu)
    return mu, nu


class _StepScalars(NamedTuple):
    """The step's scalars that follow the problem's values, not its shapes:
    f32 arguments of the step's executable, computed on the host.  The mixing
    weights of Algorithm 3 come with their complements (``1 - beta``,
    ``1 - alpha``), so each rounds to f32 once, from a host double.  ``None``
    for Skotch."""

    lam: jax.Array
    beta: jax.Array | None = None
    one_minus_beta: jax.Array | None = None
    gamma: jax.Array | None = None
    alpha: jax.Array | None = None
    one_minus_alpha: jax.Array | None = None


def _step_scalars(cfg: ASkotchConfig, n: int, lam: float) -> _StepScalars:
    """lam as f32, and for ASkotch the Algorithm 3 preamble's weights."""
    lam = float(np.float32(lam))
    if not cfg.accelerated:
        return _StepScalars(jnp.float32(lam))
    beta, gamma, alpha = _accel_params(*resolve_accel_params(cfg, n, lam))
    return _StepScalars(*(jnp.float32(v) for v in (
        lam, beta, 1.0 - beta, gamma, alpha, 1.0 - alpha)))


#: the ``repro.kernels.ops`` entry points a step's trace resolves; they key
#: the step's executable, so a swapped entry point is traced, not bypassed
_KERNEL_ENTRIES = ("kernel_matvec", "kernel_block", "kernel_matvec_multi",
                   "kernel_block_multi")


@functools.partial(jax.jit, static_argnames=("cfg", "op_spec", "entries"))
def _step(x, y, probs, scalars: _StepScalars, state: SolverState, *,
          cfg: ASkotchConfig, op_spec, entries):
    """One Skotch/ASkotch iteration.  Arrays and scalars are arguments; the
    static ``cfg``, the operator ``op_spec`` (its configuration over no rows)
    and the kernel ``entries`` decide the program, with the shapes.  The
    operator's precision policy sets the precision of the step's dense
    algebra (``MATMUL_PRECISION``: full f32 under "f32")."""
    del entries  # part of the executable's key only
    counter("repro_askotch_step_traces_total",
            labels={"precision": MATMUL_PRECISION[op_spec.precision]},
            help="traces of the Skotch/ASkotch step body").inc()
    with solver_matmuls(op_spec.precision):
        return _step_body(x, y, probs, scalars, state, cfg, op_spec.with_points(x))


def _step_body(x, y, probs, scalars: _StepScalars, state: SolverState,
               cfg: ASkotchConfig, op) -> tuple[SolverState, StepAux]:
    """The iteration itself, its phases under stable ``askotch/...`` scopes
    (``sample``, ``precond``, ``row_block``, ``update``), which name its
    operations in a profile."""
    n = x.shape[0]
    b = cfg.resolve_block(n)
    r = min(cfg.rank, b - 1)
    lam = scalars.lam
    head_axes = None if y.ndim == 1 else (0,)

    with jax.named_scope("askotch/sample"):
        if cfg.sampling == "arls":
            sampler = samplers.arls_sampler(probs, b)
        else:
            sampler = samplers.uniform_sampler(n, b)
        key, kb, knys, kl = jax.random.split(state.key, 4)
        idx = sampler(kb)
        xb = jnp.take(x, idx, axis=0)
        yb = jnp.take(y, idx, axis=0)
        zref = state.z if cfg.accelerated else state.w
        zb = jnp.take(zref, idx, axis=0)

    # -- block build + Nystrom preconditioner (shared across heads) ---------
    with jax.named_scope("askotch/precond"):
        kbb = op.block(xb)

        omega = jax.random.normal(knys, (b, r), dtype=kbb.dtype)
        omega, _ = jnp.linalg.qr(omega)
        factors = nystrom_from_sketch(kbb @ omega, omega, jnp.trace(kbb))

        if cfg.rho_mode == "damped":
            rho = lam + factors.lam[-1]
        else:
            rho = lam

        def kbb_lam_mv(u):
            return kbb @ u + lam * u

        if cfg.precond == "identity":
            # Ablation (paper §6.4 / Lin et al. 2024): K_hat = 0, rho = 1 =>
            # plain sketched-gradient step with powering-estimated stepsize.
            factors_id = NystromFactors(
                u=jnp.zeros((b, 1), kbb.dtype), lam=jnp.zeros((1,), kbb.dtype)
            )
            step_l = get_l(
                kl, kbb_lam_mv, factors_id, jnp.float32(1.0), num_iters=cfg.powering_iters
            )
            solve_g = lambda g: g  # noqa: E731
        else:
            step_l = get_l(kl, kbb_lam_mv, factors, rho, num_iters=cfg.powering_iters)
            if cfg.stable_inv:
                chol_l = stable_inv_apply_setup(factors, rho)
                solve_g = lambda g: stable_inv_apply(factors, rho, chol_l, g)  # noqa: E731
            else:
                solve_g = lambda g: woodbury_inv_apply(factors, rho, g)  # noqa: E731

        eta = 1.0 / jnp.maximum(step_l, 1.0)  # eta = 1 / hat-L_PB (Lemma 8)

    # -- fused O(nbt) kernel matvec: G_B = (K_lam)_{B,:} Z - Y_B ------------
    # one kernel-tile pass serves all t heads
    with jax.named_scope("askotch/row_block"):
        gb = op.row_block_matvec(xb, zref) + lam * zb - yb

    # the preconditioner's apply (Woodbury or stable Cholesky) is its cost
    with jax.named_scope("askotch/precond"):
        db = solve_g(gb)

    # -- iterate updates (batched over the head axis) ------------------------
    with jax.named_scope("askotch/update"):
        if cfg.accelerated:
            w_new = state.z.at[idx].add(-eta * db)
            v_new = (scalars.beta * state.v + scalars.one_minus_beta * state.z).at[idx].add(
                -scalars.gamma * eta * db
            )
            z_new = scalars.alpha * v_new + scalars.one_minus_alpha * w_new
        else:
            w_new = state.w.at[idx].add(-eta * db)
            v_new = w_new
            z_new = w_new

        new_state = SolverState(
            w=w_new,
            v=v_new,
            z=z_new,
            key=key,
            it=state.it + 1,
            sketch_res=jnp.linalg.norm(gb, axis=head_axes),
        )
    return new_state, StepAux(step_l=step_l, rho=rho)


def make_step(
    problem: KRRProblem, cfg: ASkotchConfig, probs: jax.Array | None = None
) -> Callable[[SolverState], tuple[SolverState, StepAux]]:
    """The Skotch/ASkotch step for ``problem``: ``step(state) -> (state, aux)``.

    It calls one jitted step whose arguments are the training rows, the
    targets, ``probs`` and :class:`_StepScalars`, and whose static part is
    ``cfg``, the operator's configuration and the kernel entry points; so one
    executable serves every problem of the same shapes and settings.

    The step is shape-polymorphic in the RHS: with y (n, t) every per-block
    quantity batches over the trailing head axis while the block sample, the
    Nystrom preconditioner, and the fused kernel tiles are computed once.
    """
    if cfg.sampling == "arls":
        if probs is None:
            raise ValueError("ARLS sampling requires precomputed probs")
    elif cfg.sampling != "uniform":
        raise ValueError(f"unknown sampling {cfg.sampling!r}")
    op = dataclasses.replace(problem.op, backend=cfg.backend)
    return functools.partial(
        _step, op.x, problem.y, probs, _step_scalars(cfg, problem.n, problem.lam),
        cfg=cfg, op_spec=op.with_points(None),
        entries=tuple(getattr(ops, name) for name in _KERNEL_ENTRIES),
    )


def init_state(problem: KRRProblem, seed: int = 0, w0: jax.Array | None = None) -> SolverState:
    """Zero-initialized state; iterates take the shape of problem.y
    ((n,) or (n, t)) so multi-head solves carry one column per head."""
    if w0 is None:
        w0 = jnp.zeros(problem.y.shape, jnp.float32)
    res0 = jnp.full(() if problem.y.ndim == 1 else (problem.t,), jnp.inf, jnp.float32)
    return SolverState(
        w=w0,
        v=w0,
        z=w0,
        key=jax.random.PRNGKey(seed),
        it=jnp.zeros((), jnp.int32),
        sketch_res=res0,
    )


@dataclasses.dataclass
class SolveResult:
    w: jax.Array
    iters: int
    history: list[dict]
    converged: bool
    wall_time_s: float


def _maybe_arls_probs(problem: KRRProblem, cfg: ASkotchConfig, seed: int):
    if cfg.sampling != "arls":
        return None
    scores = samplers.approx_rls_bless(
        jax.random.PRNGKey(seed + 1),
        dataclasses.replace(problem.op, backend=cfg.backend),
        lam=problem.lam,
    )
    return samplers.arls_probs(scores)


def solve(
    problem: KRRProblem,
    cfg: ASkotchConfig | None = None,
    *,
    max_iters: int = 500,
    tol: float = 1e-8,
    eval_every: int = 25,
    seed: int = 0,
    time_budget_s: float | None = None,
    callback: Callable[[int, SolverState, dict], None] | None = None,
    w0: jax.Array | None = None,
    telemetry=None,
) -> SolveResult:
    """Python-loop driver: jitted steps + periodic full-residual evaluation.

    The full relative residual costs one O(n^2 d) streamed matvec (shared by
    the per-head and aggregate reports), so it is only computed every
    ``eval_every`` iterations (and at the end).  History records carry
    ``rel_residual`` (aggregate over heads) and ``rel_residual_per_head``.
    A check with a non-finite residual on any head ends the solve, not
    converged (``repro_solve_nonfinite_stops_total``).

    ``telemetry`` (a ``repro.obs.Telemetry``) adds a solve span, canonical
    trace events mirroring the history records, and per-iteration tile-work
    metrics; ``None`` (default) keeps the whole telemetry path to a single
    identity check.
    """
    cfg = cfg or ASkotchConfig()
    tel = as_telemetry(telemetry)
    solver_name = "askotch" if cfg.accelerated else "skotch"
    n, b, d = problem.n, cfg.resolve_block(problem.n), problem.x.shape[1]
    precision = getattr(problem.op, "precision", "f32")
    recorder = tel.recorder(solver_name, precision=precision, n=n)
    probs = _maybe_arls_probs(problem, cfg, seed)
    step = make_step(problem, cfg, probs)  # already jitted: called as it is
    state = init_state(problem, seed, w0)
    history = recorder.history
    tel_enabled = tel.enabled  # hoisted: the loop pays one bool test
    with tel.span(f"solve/{solver_name}", n=n, t=problem.t, b=b,
                  max_iters=max_iters, tol=tol):
        t0 = time.perf_counter()
        converged = False
        it = 0
        for it in range(1, max_iters + 1):
            state, aux = step(state)
            if tel_enabled:
                # per-step kernel-tile work: K_BB block + the (b, n) fused
                # row-block matvec (host-loop counting — exact per execution)
                record_tile_work(b, b, d, precision)
                record_tile_work(b, n, d, precision)
            if it % eval_every == 0 or it == max_iters:
                rel_agg, rel_heads = problem.residual_report(state.w)
                rel = float(rel_agg)
                heads = [float(v) for v in rel_heads]
                rec = recorder.add(
                    it, rel,
                    rel_residual_per_head=heads,
                    sketch_res=float(jnp.linalg.norm(state.sketch_res)),
                    step_L=float(aux.step_l),
                    time_s=time.perf_counter() - t0,
                )
                if callback:
                    callback(it, state, rec)
                # every head must pass (aggregate alone dilutes a bad head by
                # ~1/sqrt(t)); identical to the aggregate test when t = 1, and
                # the same convergence meaning as blocked_cg
                if bool(jnp.all(rel_heads < tol)):
                    converged = True
                    break
                # a diverged iterate never comes back: stop at once
                if not all(math.isfinite(v) for v in heads):
                    counter("repro_solve_nonfinite_stops_total",
                            help="solves stopped on a non-finite residual").inc()
                    break
            if time_budget_s is not None and time.perf_counter() - t0 > time_budget_s:
                break
    return SolveResult(
        w=state.w,
        iters=it,
        history=history,
        converged=converged,
        wall_time_s=time.perf_counter() - t0,
    )


def solve_scan(
    problem: KRRProblem,
    cfg: ASkotchConfig | None = None,
    *,
    num_iters: int = 100,
    seed: int = 0,
    w0: jax.Array | None = None,
) -> tuple[jax.Array, jax.Array]:
    """Pure lax.scan solve (benchmarks / dry-run lowering): returns (w, per-
    iteration sketched residuals — (iters,) or (iters, t))."""
    cfg = cfg or ASkotchConfig()
    probs = _maybe_arls_probs(problem, cfg, seed)
    step = make_step(problem, cfg, probs)

    def body(state, _):
        state, _aux = step(state)
        return state, state.sketch_res

    state, res = jax.lax.scan(body, init_state(problem, seed, w0), None, length=num_iters)
    return state.w, res
