"""Distributed KRR solvers — the paper's methods on a production mesh,
built entirely from :class:`~repro.distributed.sharded_operator.
ShardedKernelOperator` composites (docs/architecture.md, layer 3).

Two solve paths share the operator layer and the mesh:

  * **ASkotch** (``make_dist_askotch_step`` / ``solve_askotch_dist``) — one
    fused shard_map per iteration whose body is operator composites: packed-
    psum block gather, distributed Nystrom, Woodbury applies, powering.
  * **PCG** (``solve_pcg_dist``) — the existing blocked multi-RHS CG loop
    (``core/blocked_cg.py``) driven by the operator's distributed
    ``k_lam_matvec``; the Nystrom preconditioner sketch is one distributed
    ``op.sketch`` pass.

Both are multi-RHS: a ``(n, t)`` Y (one-vs-all heads) yields a row-sharded
``(n, t)`` W, sharing block samples / preconditioners / kernel tiles across
heads exactly like the single-device stack.  Layout: rows of X / Y / iterates
shard over the non-"model" mesh axes (("pod", "data") on the multi-pod
mesh); block rows additionally shard over "model", so one ASkotch iteration
runs 512-way parallel:

  per iteration (b = 50k, r = 100, n = 1e8, d = 9, t heads):
    psum      packed x_B|y_B|z_B gather  b*(d+2t) f32   ~2.2 MB
    psum      Omega^T Y, B^T B           2*r^2 f32      ~80 KB
    allgather powering vectors           ~2*iters*b f32 ~4 MB
    psum      fused matvec partials      b*t f32        ~0.2 MB
    allgather packed [d_B | g_B]         2*b*t f32      ~0.4 MB
  local compute: O(n*b*d / 512) fused kernel-matvec  (~90 GFLOP/chip)

i.e. ~7 MB of wire traffic against ~90 GFLOP of MXU work per iteration —
the method is compute-bound by construction, which is exactly the property
the paper exploits on GPUs (§4.2) restated for a TPU pod.

Sampling is i.i.d. uniform (with replacement) as in Def. 9 — distinct-index
sampling of 5e4 from 1e8 would cost an O(n log n) sort per iteration for a
~1e-5 collision rate.  A mesh of total size 1 runs every code path with
no-op collectives, so the whole module is exercised by plain pytest.
"""

from __future__ import annotations

import dataclasses
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core.blocked_cg import blocked_cg
from repro.core.kernels import KERNEL_NAMES
from repro.core.krr import KRRProblem, residual_report, scaled_lam
from repro.core.nystrom import nystrom_from_sketch
from repro.core.operator import as_multirhs, maybe_squeeze
from repro.distributed.meshes import shard_map
from repro.distributed.sharded_operator import ShardedKernelOperator
from repro.kernels.precision import PRECISIONS

BACKENDS = ("auto", "xla", "pallas", "interpret")


class DistState(NamedTuple):
    w: jax.Array  # (n,) or (n, t) row-sharded
    v: jax.Array
    z: jax.Array
    key: jax.Array  # replicated
    sketch_res: jax.Array
    pv: jax.Array  # (b,) replicated — warm-start vector for the powering


@dataclasses.dataclass(frozen=True)
class DistKRRConfig:
    n: int
    d: int
    kernel: str | tuple[str, ...] = "rbf"
    sigma: float | tuple[float, ...] = 1.0
    weights: tuple[float, ...] | None = None  # multi-kernel combination
    lam_unscaled: float = 2e-7
    block_size: int = 50_000
    rank: int = 100
    heads: int = 1  # t right-hand sides (one-vs-all); 1 -> 1-D iterates
    accelerated: bool = True
    mu: float | None = None
    nu: float | None = None
    powering_iters: int = 10
    powering_warm_start: bool = False  # beyond-paper (§Perf): warm-start the
    #   powering with the previous block's eigenvector and run
    #   powering_warm_iters instead of powering_iters — blocks are
    #   statistically exchangeable under uniform sampling, so the top
    #   preconditioned eigenvector varies little between iterations
    powering_warm_iters: int = 3
    backend: str = "auto"  # local compute backend inside shards
    precision: str = "f32"  # kernel tile-compute policy: "f32" | "bf16"

    def __post_init__(self) -> None:
        # fail fast with the accepted values, in the solver_api
        # METHOD_OPTIONS style, instead of leaking into shape/key errors
        for field, minimum in (("n", 1), ("d", 1), ("block_size", 1),
                               ("rank", 1), ("heads", 1),
                               ("powering_iters", 1), ("powering_warm_iters", 1)):
            v = getattr(self, field)
            if not isinstance(v, int) or v < minimum:
                raise ValueError(
                    f"DistKRRConfig.{field} = {v!r} invalid; accepted: "
                    f"integers >= {minimum}"
                )
        if isinstance(self.kernel, tuple):
            # a kernel tuple is a weighted-sum combination; validation of the
            # names/sigmas/weights triple lives in ONE place
            from repro.core.multikernel import canonical_kernels

            canonical_kernels(self.kernel, self.sigma, self.weights)
        elif self.kernel not in KERNEL_NAMES:
            raise ValueError(
                f"DistKRRConfig.kernel = {self.kernel!r} invalid; accepted: "
                f"{KERNEL_NAMES} or a tuple of them"
            )
        if self.backend not in BACKENDS:
            raise ValueError(
                f"DistKRRConfig.backend = {self.backend!r} invalid; "
                f"accepted: {BACKENDS}"
            )
        if self.precision not in PRECISIONS:
            raise ValueError(
                f"DistKRRConfig.precision = {self.precision!r} invalid; "
                f"accepted: {PRECISIONS}"
            )
        sig = self.sigma if isinstance(self.sigma, tuple) else (self.sigma,)
        if not all(s > 0 for s in sig):
            raise ValueError(
                f"DistKRRConfig.sigma = {self.sigma!r} invalid; accepted: "
                f"positive floats (or a per-kernel tuple of them)"
            )
        if not self.lam_unscaled > 0:
            raise ValueError(
                f"DistKRRConfig.lam_unscaled = {self.lam_unscaled!r} invalid; "
                f"accepted: positive floats"
            )
        if self.rank > self.block_size:
            raise ValueError(
                f"DistKRRConfig.rank = {self.rank} invalid; accepted: "
                f"rank <= block_size (= {self.block_size})"
            )
        for field in ("mu", "nu"):
            v = getattr(self, field)
            if v is not None and not v > 0:
                raise ValueError(
                    f"DistKRRConfig.{field} = {v!r} invalid; accepted: "
                    f"None or positive floats"
                )

    @property
    def lam(self) -> float:
        return scaled_lam(self.n, self.lam_unscaled)


def _operator_for(mesh: Mesh, cfg: DistKRRConfig) -> ShardedKernelOperator:
    """Unbound operator carrying (mesh, kernel config) for the step body."""
    return ShardedKernelOperator(
        mesh=mesh, kernel=cfg.kernel, sigma=cfg.sigma, backend=cfg.backend,
        weights=cfg.weights, precision=cfg.precision,
    )


def make_dist_askotch_step(mesh: Mesh, cfg: DistKRRConfig):
    """Returns (step_fn, shardings) with step_fn jit-able under `mesh`.

    step_fn(state, x, y) -> state.  x: (n, d) f32; y: (n,) f32 when
    cfg.heads == 1, else (n, t).  The body is ONE shard_map composed of
    ShardedKernelOperator shard-level composites — no hand-rolled
    collectives, no direct kernel dispatch.
    """
    op = _operator_for(mesh, cfg)
    rows = op.rows
    n, b, r, t = cfg.n, cfg.block_size, cfg.rank, cfg.heads
    lam = jnp.float32(cfg.lam)
    if n % op.n_row_shards:
        raise ValueError(
            f"n = {n} does not shard over {op.n_row_shards} row shard(s) of "
            f"mesh axes {rows}; accepted: n divisible by the row-axis product"
        )
    if b % op.n_model:
        raise ValueError(
            f"block_size = {b} does not shard over {op.n_model} model "
            f"shard(s); accepted: multiples of {op.n_model}"
        )

    if cfg.accelerated:
        nu = cfg.nu if cfg.nu is not None else n / b
        mu = cfg.mu if cfg.mu is not None else min(float(lam), nu, 1.0 / nu)
        beta = 1.0 - (mu / nu) ** 0.5
        gamma = 1.0 / (mu * nu) ** 0.5
        alpha = 1.0 / (1.0 + gamma * nu)

    as2d = (lambda a: a) if t > 1 else (lambda a: a[:, None])
    like_y = (lambda a: a) if t > 1 else (lambda a: a[:, 0])

    def local(state: DistState, x_l, y_l):
        key, kb, knys, kl = jax.random.split(state.key, 4)
        idx = jax.random.randint(kb, (b,), 0, n)  # replicated draw
        zref = state.z if cfg.accelerated else state.w

        # ---- gather x_B, y_B, z_B from the row shards (ONE packed psum) ----
        (xb, yb, zb), owned, local_pos = op.shard_gather_rows(
            x_l, idx, (y_l, zref)
        )
        yb_l = op.shard_block_slice(as2d(yb))  # (b/M, t)
        zb_l = op.shard_block_slice(as2d(zb))

        # ---- distributed Nystrom of K_BB (U rows over "model") -------------
        u_l, lam_ny = op.shard_block_nystrom(xb, r, knys)
        rho = lam + lam_ny[-1]  # damped (paper default)

        # ---- get_L: randomized powering (Algorithm 5) -----------------------
        if cfg.powering_warm_start:
            v0 = state.pv
            n_power = cfg.powering_warm_iters
        else:
            v0 = jax.random.normal(kl, (b,), jnp.float32)
            n_power = cfg.powering_iters
        pv, step_l = op.shard_block_powering(
            xb, u_l, lam_ny, rho, lam, v0, n_power
        )
        eta = 1.0 / jnp.maximum(step_l, 1.0)

        # ---- the O(nbt) fused matvec: G_B = (K_lam)_{B,:} Z - Y_B -----------
        # one kernel-tile pass over this row shard serves all t heads
        xb_l = op.shard_block_slice(xb)
        part = op.shard_row_block_matvec(x_l, xb_l, zref)  # (b/M[, t])
        g_l = as2d(part) + lam * zb_l - yb_l
        d_l = op.shard_woodbury_apply(u_l, lam_ny, rho, g_l)  # (b/M, t)
        # packed gather: [D | G] in one collective, residual norm locally
        dg = op.model_all_gather(jnp.concatenate([d_l, g_l], axis=1))
        d_full = dg[:, :t]  # (b, t)
        sk_res = jnp.linalg.norm(dg[:, t:])

        # ---- scatter updates on the owned rows -------------------------------
        upd = like_y(jnp.where(owned[:, None] > 0, -eta * d_full, 0.0))
        if cfg.accelerated:
            w_new = state.z.at[local_pos].add(upd)
            v_new = (beta * state.v + (1.0 - beta) * state.z).at[local_pos].add(
                gamma * upd
            )
            z_new = alpha * v_new + (1.0 - alpha) * w_new
        else:
            w_new = state.w.at[local_pos].add(upd)
            v_new = w_new
            z_new = w_new
        return DistState(w=w_new, v=v_new, z=z_new, key=key, sketch_res=sk_res,
                         pv=pv)

    vec = op.vec_spec(1 if t == 1 else 2)
    state_specs = DistState(w=vec, v=vec, z=vec, key=P(), sketch_res=P(), pv=P())
    step = shard_map(
        local,
        mesh=mesh,
        in_specs=(state_specs, P(rows, None), vec),
        out_specs=state_specs,
    )
    shardings = {
        "state": jax.tree.map(lambda s: NamedSharding(mesh, s), state_specs,
                              is_leaf=lambda s: isinstance(s, P)),
        "x": NamedSharding(mesh, P(rows, None)),
        "y": NamedSharding(mesh, vec),
    }
    return step, shardings


def init_dist_state(cfg: DistKRRConfig, seed: int = 0) -> DistState:
    shape = (cfg.n,) if cfg.heads == 1 else (cfg.n, cfg.heads)
    z = jnp.zeros(shape, jnp.float32)
    pv = jax.random.normal(jax.random.PRNGKey(seed + 7), (cfg.block_size,), jnp.float32)
    return DistState(
        w=z, v=z, z=z, key=jax.random.PRNGKey(seed),
        sketch_res=jnp.array(jnp.inf, jnp.float32), pv=pv,
    )


def abstract_dist_inputs(cfg: DistKRRConfig):
    """ShapeDtypeStructs for the dry-run (no allocation)."""
    shape = (cfg.n,) if cfg.heads == 1 else (cfg.n, cfg.heads)
    vec = jax.ShapeDtypeStruct(shape, jnp.float32)
    state = DistState(
        w=vec, v=vec, z=vec,
        key=jax.ShapeDtypeStruct((2,), jnp.uint32),
        sketch_res=jax.ShapeDtypeStruct((), jnp.float32),
        pv=jax.ShapeDtypeStruct((cfg.block_size,), jnp.float32),
    )
    x = jax.ShapeDtypeStruct((cfg.n, cfg.d), jnp.float32)
    return state, x, vec


# ---------------------------------------------------------------------------
# solve drivers (the mesh= path behind core.solver_api.solve)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DistSolveResult:
    w: jax.Array  # (n,) or (n, t) global array, row-sharded on op.mesh
    iters: int
    history: list[dict]
    converged: bool
    wall_time_s: float
    op: ShardedKernelOperator  # bound operator — serving/predict reuse it


def _bind(problem: KRRProblem, mesh: Mesh, backend: str) -> ShardedKernelOperator:
    return ShardedKernelOperator.bind(
        mesh, problem.x, kernel=problem.kernel, sigma=problem.sigma,
        backend=backend, weights=problem.weights,
        precision=problem.precision,
    )


def solve_askotch_dist(
    problem: KRRProblem,
    mesh: Mesh,
    *,
    accelerated: bool = True,
    block_size: int | None = None,
    rank: int = 100,
    mu: float | None = None,
    nu: float | None = None,
    powering_iters: int = 10,
    backend: str = "auto",
    max_iters: int = 500,
    tol: float = 1e-8,
    eval_every: int = 25,
    seed: int = 0,
    time_budget_s: float | None = None,
) -> DistSolveResult:
    """Mesh-distributed (A)Skotch with the same driver contract as
    ``core.askotch.solve``: jitted steps + periodic full-residual evaluation,
    multi-RHS throughout.  W stays row-sharded; predictions flow through the
    returned bound operator."""
    t0 = time.perf_counter()
    op0 = ShardedKernelOperator(mesh=mesh, backend=backend)
    b = block_size if block_size is not None else max(problem.n // 100, 1)
    b = int(min(max(b, rank + 8), problem.n))
    b += (-b) % op0.n_model  # round up so block rows shard over "model"
    cfg = DistKRRConfig(
        n=problem.n, d=problem.x.shape[1], kernel=problem.kernel,
        sigma=problem.sigma, weights=problem.weights,
        lam_unscaled=problem.lam_unscaled,
        block_size=b, rank=min(rank, b), heads=problem.t,
        accelerated=accelerated, mu=mu, nu=nu, powering_iters=powering_iters,
        backend=backend, precision=problem.precision,
    )
    step, sh = make_dist_askotch_step(mesh, cfg)
    bound = _bind(problem, mesh, backend)
    # the step's iterates follow cfg.heads: a (n, 1) y is the t = 1 case and
    # solves as 1-D (the column is restored on the way out)
    y_in = problem.y[:, 0] if (problem.y.ndim == 2 and problem.t == 1) else problem.y
    y = jax.device_put(y_in, sh["y"])
    x = bound.x
    state = jax.device_put(init_dist_state(cfg, seed), sh["state"])
    jstep = jax.jit(step)

    history: list[dict] = []
    converged = False
    it = 0
    for it in range(1, max_iters + 1):
        state = jstep(state, x, y)
        if it % eval_every == 0 or it == max_iters:
            rel_agg, rel_heads = residual_report(bound, y, cfg.lam, state.w)
            history.append({
                "iter": it,
                "rel_residual": float(rel_agg),
                "rel_residual_per_head": [float(v) for v in rel_heads],
                "sketch_res": float(state.sketch_res),
                "time_s": time.perf_counter() - t0,
            })
            if bool(jnp.all(rel_heads < tol)):
                converged = True
                break
        if time_budget_s is not None and time.perf_counter() - t0 > time_budget_s:
            break
    w = state.w if y_in is problem.y else state.w[:, None]
    return DistSolveResult(
        w=w, iters=it, history=history, converged=converged,
        wall_time_s=time.perf_counter() - t0, op=bound,
    )


def solve_pcg_dist(
    problem: KRRProblem,
    mesh: Mesh,
    *,
    precond: str = "nystrom",
    rank: int = 100,
    rho_mode: str = "damped",
    backend: str = "auto",
    max_iters: int = 200,
    tol: float = 1e-8,
    seed: int = 0,
    time_budget_s: float | None = None,
) -> DistSolveResult:
    """Mesh-distributed blocked PCG on (K + lam I) W = Y.

    The iteration is the SAME ``core.blocked_cg`` loop every single-device
    CG-family solver uses — the only distributed pieces are the operator's
    ``k_lam_matvec`` (explicit collectives inside) and the one ``op.sketch``
    pass that builds the Nystrom preconditioner.  Columns that reach ``tol``
    freeze exactly as on one device.
    """
    t0 = time.perf_counter()
    if precond not in ("nystrom", "identity"):
        raise ValueError(
            f"unknown distributed preconditioner {precond!r}; accepted: "
            f"('nystrom', 'identity')"
        )
    lam = jnp.float32(problem.lam)
    bound = _bind(problem, mesh, backend)
    y2, squeeze = as_multirhs(problem.y)
    y_sh = jax.device_put(y2, bound.sharding(2))

    pinv = None
    if precond == "nystrom":
        r = min(rank, problem.n)
        omega = jax.random.normal(jax.random.PRNGKey(seed), (problem.n, r),
                                  jnp.float32)
        omega, _ = jnp.linalg.qr(omega)
        omega = jax.device_put(omega, bound.sharding(2))
        f = nystrom_from_sketch(bound.sketch(omega), omega, bound.trace_est())
        rho = lam + f.lam[-1] if rho_mode == "damped" else lam
        coeff = (f.lam[-1] + rho) / (f.lam + rho)

        def apply(v: jax.Array) -> jax.Array:
            utv = f.u.T @ v
            return f.u @ (utv * coeff[:, None]) + (v - f.u @ utv)

        pinv = jax.jit(apply)

    matvec = jax.jit(lambda v: bound.k_lam_matvec(v, lam))
    res = blocked_cg(
        matvec, y_sh, pinv, max_iters=max_iters, tol=tol, t0=t0,
        time_budget_s=time_budget_s,
    )
    return DistSolveResult(
        w=maybe_squeeze(res.x, squeeze), iters=res.iters, history=res.history,
        converged=res.converged, wall_time_s=time.perf_counter() - t0,
        op=bound,
    )
