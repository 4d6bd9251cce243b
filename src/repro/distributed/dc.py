"""Divide-and-conquer KRR: full local solves per shard, zero collectives.

The communication-avoiding tier (DC-KRR / BKRR — You, Demmel, Hsieh &
Vuduc 2018).  Where the ``ShardedKernelOperator`` path pays a psum +
all_gather on EVERY matvec of an iterative solve, :func:`solve_dc`
partitions the training set into k shards (``distributed.partition``),
runs a complete, unmodified local solve per shard through a plain
per-shard ``KernelOperator`` — every ``solve()`` method, kernel tuple,
and precision policy works unchanged — and combines the per-shard
predictions at query time.  The shards never exchange a byte during
iteration: the only cross-device event is the final host gather of k
weight vectors.  ``info["collective_dispatches"]`` records the measured
``repro_collective_dispatch_total`` delta across the solve (asserted
== 0 in tests and reported by ``bench_dist_scaling.py``).

Cost model: a local solve is O((n/k)^2) kernel work per shard — k shards
in parallel on k devices is O(n^2 / k^2) critical-path work and ZERO
collective traffic, vs the sharded path's O(n^2 / D) per-device work
PLUS two collectives per iteration.  The price is approximation: local
models never see cross-shard interactions, so test error degrades as k
grows — the accuracy/communication frontier ``bench_dist_scaling.py``
measures.  At k = 1 the tier degenerates EXACTLY (bit-for-bit) to the
plain solver.

Device parallelism: with ``mesh=``, shard s is pinned to mesh device
``s % D`` (inputs ``device_put`` there, one host thread per device
driving its local solves).  A shard_map would buy nothing here — the
body of a local solve is a host-driven adaptive loop (stopping tests,
telemetry, per-iteration traces), not a single traceable computation,
and with zero cross-shard communication a mapped axis has no collectives
to fuse; explicit placement gives the same device parallelism while
keeping every solver feature intact.  Without a mesh the shards run
sequentially on the default device — same results, keyed by shard index
(a 1-device mesh is bit-identical to the sequential fallback; tested).

Combiners (``dc_combiner=``):

  * ``"uniform"`` — plain average, weight 1/k per shard (BKRR).
  * ``"softmax"`` — per-query weights ``softmax_s(-||x - c_s||^2 /
    (2 tau^2))`` over the partition centers c_s: queries trust the local
    model whose region they fall in.  ``tau`` defaults to the mean
    pairwise center distance.

Both produce weights that sum to 1 per query; k = 1 short-circuits to
the single shard's prediction verbatim.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.partition import (
    PARTITION_KINDS,
    Partition,
    chunked_sq_dists,
    make_partition,
)
from repro.obs import metrics as obs_metrics
from repro.obs.telemetry import as_telemetry

#: accepted prediction combiners (the ``dc_combiner=`` vocabulary)
COMBINERS = ("uniform", "softmax")

_COLLECTIVE_METRIC = "repro_collective_dispatch_total"


def collective_dispatch_delta(
    before: dict[str, float], after: dict[str, float]
) -> float:
    """Total ``repro_collective_dispatch_total`` growth between two metric
    :func:`repro.obs.metrics.snapshot` dicts — the DC tier's headline
    number (it stays 0.0; the sharded path pays two per iteration)."""
    return sum(
        v
        for k, v in obs_metrics.diff(before, after).items()
        if k.startswith(_COLLECTIVE_METRIC)
    )


def combiner_weights(
    part: Partition,
    xq,
    combiner: str = "uniform",
    softmax_temp: float | None = None,
) -> np.ndarray:
    """Per-query shard weights, a (q, k) row-stochastic array.

    ``"uniform"`` ignores the queries (every row is 1/k).  ``"softmax"``
    weights shard s by ``softmax_s(-||x - c_s||^2 / (2 tau^2))`` with
    ``tau = softmax_temp`` (default: mean pairwise distance between the
    partition centers — the natural length scale of the partition).
    """
    if combiner not in COMBINERS:
        raise ValueError(
            f"unknown combiner {combiner!r}; accepted: {COMBINERS}"
        )
    xq = np.asarray(xq, np.float32)
    q, k = xq.shape[0], part.k
    if combiner == "uniform" or k == 1:
        return np.full((q, k), 1.0 / k, np.float32)
    if softmax_temp is None:
        c2 = chunked_sq_dists(part.centers, part.centers)
        off = c2[~np.eye(k, dtype=bool)]
        softmax_temp = float(np.sqrt(np.maximum(off, 0.0)).mean()) or 1.0
    logits = -chunked_sq_dists(xq, part.centers) / (
        2.0 * float(softmax_temp) ** 2
    )
    logits -= logits.max(axis=1, keepdims=True)
    w = np.exp(logits)
    return (w / w.sum(axis=1, keepdims=True)).astype(np.float32)


@dataclasses.dataclass
class DCSolveResult:
    """Everything :func:`solve_dc` produced: the partition, the per-shard
    ``SolveOutput``s (each a full local solve), and the combined
    ``predict_fn``.  ``w`` scatters per-shard weights back to the original
    row order — zeros never mix across shards because row i's weight lives
    only in shard ``assignments[i]``."""

    partition: Partition
    shard_outputs: list
    w: jax.Array | None
    predict_fn: Any
    info: dict[str, Any]
    history: list[dict]


def _shard_problem(problem, idx: np.ndarray, device=None):
    take = jnp.asarray(idx)
    x, y = problem.x[take], problem.y[take]
    if device is not None:
        x, y = jax.device_put(x, device), jax.device_put(y, device)
    return dataclasses.replace(problem, x=x, y=y)


def solve_dc(
    problem,
    *,
    shards: int = 2,
    partition: str | Partition = "random",
    combiner: str = "uniform",
    method: str = "askotch",
    softmax_temp: float | None = None,
    mesh=None,
    telemetry=None,
    **kw,
) -> DCSolveResult:
    """Divide-and-conquer solve: k independent local solves, combined at
    query time, with zero collective traffic in between.

    Args:
      problem: the :class:`~repro.core.krr.KRRProblem` (multi-RHS heads,
        kernel tuples, and ``precision`` all ride through unchanged —
        each shard is just a smaller problem of the same shape).
      shards: shard count k (ignored when ``partition`` is already a
        :class:`Partition`); k = 1 reproduces the plain solver bit-for-bit.
      partition: a :data:`~repro.distributed.partition.PARTITION_KINDS`
        name or a prebuilt :class:`Partition` (e.g. round-tripped through
        ``Partition.from_json``).
      combiner: one of :data:`COMBINERS`.
      method: the INNER solver run per shard — any single-device
        ``solve()`` method except ``"dc"`` itself.
      softmax_temp: temperature for the softmax combiner (default: mean
        pairwise center distance).
      mesh: optional ``jax.sharding.Mesh``; shard s runs on device
        ``s % D`` (explicit placement, no collectives — see module
        docstring for why this is not a shard_map).
      telemetry: optional ``repro.obs.Telemetry`` — records a ``solve/dc``
        span around the tier and a ``dc/shard`` span per local solve.
      **kw: inner-method options, validated fail-fast by the inner
        ``solve()`` against ``METHOD_OPTIONS[method]``.

    Returns:
      A :class:`DCSolveResult`; ``info["collective_dispatches"]`` is the
      measured collective-dispatch delta (0.0 — the point of the tier).
    """
    from repro.core.solver_api import METHODS, solve  # lazy: avoids cycle

    if method == "dc" or method not in METHODS:
        inner = sorted(set(METHODS) - {"dc"})
        raise ValueError(
            f"dc_method {method!r} is not a valid inner solver; accepted: "
            f"{inner}"
        )
    if problem.kernel == "precomputed":
        raise ValueError(
            "kernel='precomputed' cannot run through method='dc': a shard's "
            "subproblem re-slices raw features into a local KernelOperator — "
            "pass the features with a kernel name instead"
        )
    if isinstance(partition, Partition):
        part = partition
        if part.n != problem.n:
            raise ValueError(
                f"partition covers {part.n} rows but the problem has "
                f"{problem.n}"
            )
    elif partition in PARTITION_KINDS:
        part = make_partition(
            problem.x, shards, kind=partition, seed=int(kw.get("seed", 0) or 0)
        )
    else:
        raise ValueError(
            f"unknown partition {partition!r}; accepted: {PARTITION_KINDS} "
            f"or a Partition instance"
        )
    if combiner not in COMBINERS:
        raise ValueError(
            f"unknown combiner {combiner!r}; accepted: {COMBINERS}"
        )

    tel = as_telemetry(telemetry)
    shard_idx = part.shard_indices()
    k = part.k
    devices = list(mesh.devices.flat) if mesh is not None else [None]

    def run_shard(s: int):
        device = devices[s % len(devices)]
        sub = _shard_problem(problem, shard_idx[s], device)
        # the local solvers close over x and start from fresh arrays: the
        # default device, not the inputs, decides where their steps run
        place = jax.default_device(device) if device is not None else (
            contextlib.nullcontext())
        with place, tel.span("dc/shard", shard=s, n=sub.n, method=method):
            return solve(sub, method, telemetry=telemetry, **kw)

    before = obs_metrics.snapshot()
    t0 = time.perf_counter()
    with tel.span("solve/dc", n=problem.n, t=problem.t, shards=k,
                  partition=part.kind, combiner=combiner, method=method,
                  mesh=dict(mesh.shape) if mesh is not None else None):
        if mesh is not None and len(devices) > 1 and k > 1:
            # one host thread per device drives its shards' local solves
            with ThreadPoolExecutor(
                max_workers=min(len(devices), k)
            ) as pool:
                outputs = list(pool.map(run_shard, range(k)))
        else:
            outputs = [run_shard(s) for s in range(k)]
    wall = time.perf_counter() - t0
    collectives = collective_dispatch_delta(before, obs_metrics.snapshot())

    # scatter per-shard weights back to original row order when the inner
    # method produces one weight per training row (everything but falkon,
    # whose w lives on m inducing points — predictions still combine fine)
    w_global = None
    if all(
        np.ndim(out.w) >= 1 and out.w.shape[0] == len(idx)
        for out, idx in zip(outputs, shard_idx)
    ):
        wg = np.zeros((problem.n,) + tuple(np.shape(outputs[0].w)[1:]),
                      np.float32)
        for out, idx in zip(outputs, shard_idx):
            wg[idx] = np.asarray(out.w, np.float32)
        w_global = jnp.asarray(wg)

    shard_predict = [out.predict_fn for out in outputs]

    def predict_fn(xt):
        if k == 1:  # exact single-shard degeneracy: the plain prediction
            return shard_predict[0](xt)
        wgt = combiner_weights(part, xt, combiner, softmax_temp)
        preds = [np.asarray(fn(xt), np.float32) for fn in shard_predict]
        extra = (1,) * (preds[0].ndim - 1)
        combined = sum(
            wgt[:, s].reshape((-1,) + extra) * preds[s] for s in range(k)
        )
        return jnp.asarray(combined)

    per_shard_iters = [int(out.info.get("iters", 0)) for out in outputs]
    history: list[dict] = []
    for s, out in enumerate(outputs):
        rec = {"shard": s, "n": int(len(shard_idx[s])),
               "iters": per_shard_iters[s]}
        if out.history:
            rec["rel_residual"] = out.history[-1].get("rel_residual")
        history.append(rec)
    shard_rels = [
        r["rel_residual"] for r in history if r.get("rel_residual") is not None
    ]
    # aggregate record last: consumers that read history[-1]["rel_residual"]
    # (krr_solve's summary line) see the worst local residual
    history.append({
        "shard": None, "iters": max(per_shard_iters, default=0),
        "rel_residual": max(shard_rels) if shard_rels else None,
    })
    info = {
        "shards": k,
        "partition": part.kind,
        "combiner": combiner,
        "inner_method": method,
        "per_shard_iters": per_shard_iters,
        "converged": all(
            bool(out.info.get("converged", True)) for out in outputs
        ),
        "wall_time_s": wall,
        "collective_dispatches": collectives,
        "mesh": dict(mesh.shape) if mesh is not None else None,
        "t": problem.t,
    }
    return DCSolveResult(
        partition=part,
        shard_outputs=outputs,
        w=w_global,
        predict_fn=predict_fn,
        info=info,
        history=history,
    )
