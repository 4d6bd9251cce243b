"""Distributed scaling: sharded-operator matvec + ASkotch iteration +
tuning-sweep throughput vs. host-device count, and the divide-and-conquer
accuracy/communication frontier.

Each device count needs its own process (XLA_FLAGS must be set before the
first jax import), so this bench spawns one subprocess per point and
aggregates the timings.  Emits, per devices in {1, 2, 4, 8}:

    dist_matvec_dev{D}       — sharded k_lam_matvec, (n, t) RHS
    dist_askotch_dev{D}      — one fused distributed ASkotch iteration
    dist_tune_dev{D}         — a full tune(mesh=...) sweep (the tuning
                               column: wall + kernel sweeps per device count)
    dc_dev{D}                — solve(method="dc", dc_shards=D) vs the
                               collective-heavy sharded PCG at the same
                               device count: wall speedup, the MEASURED
                               collective-dispatch counts of both paths
                               (repro_collective_dispatch_total — DC's is
                               ~zero, that is the point), and the test-RMSE
                               delta (the accuracy price of avoiding the
                               communication) — the frontier
    derived: speedup vs. the 1-device run

Every run appends the full machine-readable frontier record to
``BENCH_DIST.json`` via ``write_results``.

``BENCH_DIST_SMOKE=1`` shrinks the problem and the device sweep for CI:
structure (every column present) and DC k=1 parity with the plain solver
are ENFORCED (non-zero exit on violation); the frontier numbers are
reported but unenforced, since CPU "devices" share the same cores.

On CPU the collectives are in-process memcpy, so this measures the sharding
overhead floor, not real scaling — the point is that the overhead stays flat
while per-device work shrinks (the dry-run roofline covers real meshes).
Device counts the host cannot force (or that time out) are skipped with a
note rather than failing the harness.  The children always run with
``JAX_PLATFORMS=cpu``, so every row (and the record's ``platform``) is a
CPU row, and no child contends for an accelerator the parent holds.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmarks.common import emit, note, write_results

SMOKE = os.environ.get("BENCH_DIST_SMOKE") == "1"
DEVICE_COUNTS = (1, 2) if SMOKE else (1, 2, 4, 8)
N, D, T, ITERS = (512, 6, 2, 5) if SMOKE else (2048, 8, 4, 10)

_CHILD = """
import json, time
import jax, jax.numpy as jnp, numpy as np
from repro.core.krr import KRRProblem
from repro.distributed.krr_dist import (DistKRRConfig, init_dist_state,
                                        make_dist_askotch_step)
from repro.distributed.meshes import make_solver_mesh
from repro.distributed.sharded_operator import ShardedKernelOperator

n, d, t, iters = {n}, {d}, {t}, {iters}
mesh = make_solver_mesh(({rows}, {model}))
r = np.random.default_rng(0)
x = jnp.asarray(r.standard_normal((n, d)).astype(np.float32))
v = jnp.asarray(r.standard_normal((n, t)).astype(np.float32))
op = ShardedKernelOperator.bind(mesh, x, kernel="rbf", sigma=1.5, backend="xla")
v = jax.device_put(v, op.sharding(2))

def timeit(fn, reps=3):
    fn()  # warmup/compile
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return ts[len(ts) // 2] * 1e6

mv_us = timeit(lambda: jax.block_until_ready(op.k_lam_matvec(v, 0.5)))

y = jnp.asarray(r.standard_normal((n, t)).astype(np.float32))
cfg = DistKRRConfig(n=n, d=d, sigma=1.5, lam_unscaled=1e-5, block_size=128,
                    rank=32, heads=t)
step, sh = make_dist_askotch_step(mesh, cfg)
jstep = jax.jit(step)
state = jax.device_put(init_dist_state(cfg), sh["state"])
xs = jax.device_put(x, sh["x"]); ys = jax.device_put(y, sh["y"])

def run_iters():
    s = state
    for _ in range(iters):
        s = jstep(s, xs, ys)
    jax.block_until_ready(s.w)

ask_us = timeit(run_iters) / iters

# the tuning column: one full tune(mesh=...) sweep through the stacked
# engine (sigma x lam x fold columns over the sharded operator)
from repro.core.solver_api import tune
prob = KRRProblem(x=x, y=y[:, 0], backend="xla")
tune_res = {{}}
def run_tune():
    tune_res["r"] = tune(prob, mesh=mesh, sigmas=(0.8, 1.5), lams=(1e-3, 1e-1),
                         folds=2, rank=32, max_iters=40, tol=1e-4, seed=0)
tune_us = timeit(run_tune, reps=1)
print(json.dumps({{"matvec_us": mv_us, "askotch_us": ask_us,
                   "tune_us": tune_us, "tune_sweeps": tune_res["r"].sweeps}}))
"""

# the frontier child: sharded PCG (collective-heavy) vs solve(method="dc")
# (communication-avoiding) at the same device count, measuring wall, the
# collective-dispatch counter, and test RMSE for both paths
_DC_CHILD = """
import json, time
import jax, jax.numpy as jnp
from repro.core.krr import KRRProblem
from repro.core.solver_api import solve
from repro.data.synthetic import krr_regression
from repro.distributed.dc import collective_dispatch_delta
from repro.distributed.meshes import make_solver_mesh
from repro.obs import metrics as M

n, d, devices, check_parity = {n}, {d}, {rows}, {parity}
mesh = make_solver_mesh(({rows}, 1))
x, y, xt, yt = krr_regression(0, n, d, n_test=max(n // 4, 64))
prob = KRRProblem(x=x, y=y, sigma=1.5, lam_unscaled=1e-5, backend="xla")
kw = dict(rank=32, max_iters=60, tol=1e-5, seed=0)

def rmse(pred):
    return float(jnp.sqrt(jnp.mean((jnp.asarray(pred) - yt) ** 2)))

def measured(fn):
    before = M.snapshot()
    t0 = time.perf_counter()
    out = fn()
    wall = time.perf_counter() - t0
    return out, wall, collective_dispatch_delta(before, M.snapshot())

sh_out, sh_wall, sh_coll = measured(
    lambda: solve(prob, "pcg-nystrom", mesh=mesh, **kw))
dc_out, dc_wall, dc_coll = measured(
    lambda: solve(prob, "dc", dc_shards=devices, dc_method="pcg-nystrom",
                  mesh=mesh, **kw))
rec = {{
    "sharded_wall_s": sh_wall, "sharded_collectives": sh_coll,
    "sharded_rmse": rmse(sh_out.predict_fn(xt)),
    "dc_wall_s": dc_wall, "dc_collectives": dc_coll,
    "dc_rmse": rmse(dc_out.predict_fn(xt)),
    "dc_iters": dc_out.info["per_shard_iters"],
}}
if check_parity:
    plain = solve(prob, "pcg-nystrom", **kw)
    dc1 = solve(prob, "dc", dc_shards=1, dc_method="pcg-nystrom", **kw)
    rec["k1_parity"] = bool(jnp.array_equal(plain.w, dc1.w))
print(json.dumps(rec))
"""


def _spawn(code: str, devices: int, tag: str) -> dict | None:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    # the children run on forced host devices, never on an accelerator the
    # parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = "src" + os.pathsep + env.get("PYTHONPATH", "")
    try:
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=600, env=env,
        )
    except subprocess.TimeoutExpired:
        note(f"dist bench: {tag} at {devices} devices timed out; skipped")
        return None
    if out.returncode != 0:
        err = (out.stderr.strip().splitlines() or ["?"])[-1]
        note(f"dist bench: {tag} at {devices} devices failed; skipped "
             f"({err[:120]})")
        return None
    return json.loads(out.stdout.strip().splitlines()[-1])


def _run_point(devices: int) -> dict | None:
    code = _CHILD.format(n=N, d=D, t=T, iters=ITERS, rows=devices, model=1)
    return _spawn(code, devices, "sharded")


def _run_dc_point(devices: int) -> dict | None:
    code = _DC_CHILD.format(n=N, d=D, rows=devices,
                            parity=(devices == DEVICE_COUNTS[0]))
    return _spawn(code, devices, "dc")


def main() -> None:
    note(f"distributed scaling: n={N} d={D} t={T}, rows-only meshes, "
         f"devices {DEVICE_COUNTS}" + (" [smoke]" if SMOKE else ""))
    base: dict | None = None
    record: dict = {"platform": "cpu", "smoke": SMOKE, "n": N, "d": D, "t": T,
                    "device_counts": list(DEVICE_COUNTS), "points": {}}
    for devices in DEVICE_COUNTS:
        res = _run_point(devices)
        if res is None:
            continue
        if base is None:
            base = res
        record["points"].setdefault(str(devices), {}).update(res)
        for key, tag in (("matvec_us", "matvec"), ("askotch_us", "askotch")):
            speedup = base[key] / res[key] if base else 1.0
            emit(f"dist_{tag}_dev{devices}", res[key],
                 f"platform=cpu_speedup_vs_1dev={speedup:.2f}")
        if "tune_us" in res:
            speedup = base["tune_us"] / res["tune_us"]
            emit(f"dist_tune_dev{devices}", res["tune_us"],
                 f"platform=cpu_sweeps={res['tune_sweeps']:.1f}"
                 f"_speedup_vs_1dev={speedup:.2f}")

    # the accuracy/communication frontier: DC vs sharded, same device count
    for devices in DEVICE_COUNTS:
        res = _run_dc_point(devices)
        if res is None:
            continue
        record["points"].setdefault(str(devices), {}).update(res)
        speedup = res["sharded_wall_s"] / res["dc_wall_s"]
        emit(
            f"dc_dev{devices}", res["dc_wall_s"] * 1e6,
            f"platform=cpu_collectives={res['dc_collectives']:.0f}"
            f"_vs_sharded={res['sharded_collectives']:.0f}"
            f"_speedup_vs_sharded={speedup:.2f}"
            f"_rmse_delta={res['dc_rmse'] - res['sharded_rmse']:+.4f}",
        )
        if "k1_parity" in res and not res["k1_parity"]:
            raise SystemExit(
                "dc bench: k=1 DC solve is NOT bit-identical to the plain "
                "solver — the degeneracy contract is broken"
            )
    dc_points = [p for p in record["points"].values() if "dc_wall_s" in p]
    if SMOKE and not dc_points:
        raise SystemExit("dc bench (smoke): no dc_dev point completed")
    if SMOKE and not any("k1_parity" in p for p in dc_points):
        raise SystemExit("dc bench (smoke): k=1 parity check never ran")
    write_results("dist", record)


if __name__ == "__main__":
    main()
