"""Distributed paths.

Two tiers per scenario (the 1-device-fallback satellite):

  * ``*_inprocess_*`` — run in THIS pytest process on the largest solver
    mesh the process' devices allow (a (1, 1) mesh on plain single-device
    runs: size-1 axes make every collective a no-op, so the whole sharded
    code path executes).  These MUST pass everywhere.
  * subprocess tests — force a genuinely multi-device host platform
    (``--xla_force_host_platform_device_count``), which must not leak into
    other tests' single-device world.  xfail(strict=False): multi-device CPU
    collectives time out in constrained containers; they pass (XPASS) where
    the host supports them.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
sys.path.insert(0, SRC)


def run_py(code: str, devices: int = 8, timeout: int = 150) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={devices}"
        " --xla_cpu_collective_call_warn_stuck_timeout_seconds=60"
        " --xla_cpu_collective_call_terminate_timeout_seconds=120"
    )
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr[-3000:]}"
    return out.stdout


def _solver_mesh():
    """Largest (rows, model) solver mesh this process can build."""
    import jax

    from repro.distributed.meshes import make_solver_mesh

    nd = len(jax.devices())
    shape = (2, 2) if nd >= 4 else ((2, 1) if nd >= 2 else (1, 1))
    return make_solver_mesh(shape)


# ---------------------------------------------------------------------------
# in-process variants — MUST pass (1-device mesh fallback)
# ---------------------------------------------------------------------------


def _mrhs_problem(n=256, d=5, t=3, seed=0):
    import jax.numpy as jnp
    import numpy as np

    from repro.core.krr import KRRProblem

    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((n, d)).astype(np.float32))
    base = KRRProblem(x=x, y=jnp.zeros(n), kernel="rbf", sigma=2.0,
                      lam_unscaled=1e-5, backend="xla")
    w_true = jnp.asarray(r.standard_normal((n, t)).astype(np.float32))
    y = base.op.k_lam_matvec(w_true, base.lam)
    return KRRProblem(x=x, y=y, kernel="rbf", sigma=2.0, lam_unscaled=1e-5,
                      backend="xla")


def test_dist_askotch_inprocess_matches_single_device():
    """solve(..., mesh=...) ASkotch with (n, t) RHS converges on the same
    problem the single-device solver handles — the parity acceptance test."""
    from repro.core.solver_api import solve

    prob = _mrhs_problem()
    out = solve(prob, "askotch", mesh=_solver_mesh(), block_size=64, rank=24,
                max_iters=400, eval_every=50, tol=1e-6)
    assert out.w.shape == (256, 3)
    assert out.history[-1]["rel_residual"] < 0.01
    assert len(out.history[-1]["rel_residual_per_head"]) == 3
    pred = out.predict_fn(prob.x[:10])
    assert pred.shape == (10, 3)


def test_dist_pcg_inprocess_matches_single_device():
    """Distributed blocked PCG == single-device blocked PCG (same blocked_cg
    loop, operator matvec swapped) on a (n, t) one-vs-all system."""
    import jax.numpy as jnp

    from repro.core.solver_api import solve

    prob = _mrhs_problem()
    ref = solve(prob, "pcg-nystrom", rank=64, max_iters=200, tol=1e-9)
    out = solve(prob, "pcg-nystrom", mesh=_solver_mesh(), rank=64,
                max_iters=200, tol=1e-9)
    assert out.history[-1]["rel_residual"] < 1e-6
    dw = float(jnp.linalg.norm(out.w - ref.w) / jnp.linalg.norm(ref.w))
    assert dw < 1e-2, dw  # both sit on the true solution (tol 1e-9)
    # 1-D RHS path
    prob1 = _mrhs_problem(t=1)
    out1 = solve(prob1, "cg", mesh=_solver_mesh(), max_iters=300, tol=1e-9)
    assert out1.w.shape == (256, 1)
    assert out1.history[-1]["rel_residual"] < 1e-6


def test_dist_askotch_single_column_rhs():
    """(n, 1)-shaped y (t = 1 as a column) solves like the single-device
    path and keeps its column on the way out."""
    from repro.core.solver_api import solve

    prob = _mrhs_problem(t=1)  # y shape (256, 1)
    assert prob.y.ndim == 2 and prob.t == 1
    out = solve(prob, "askotch", mesh=_solver_mesh(), block_size=64, rank=24,
                max_iters=200, eval_every=50, tol=1e-6)
    assert out.w.shape == (256, 1)
    assert out.history[-1]["rel_residual"] < 0.05
    assert out.predict_fn(prob.x[:7]).shape == (7, 1)


def test_small_mesh_dryrun_inprocess_single_device():
    """Reduced-config lower+compile through the dryrun cell builder on a
    (1, 1) mesh — the sharding-spec machinery without forced devices."""
    from repro.configs.base import get_reduced_config
    from repro.launch.dryrun import lower_cell
    from repro.launch.mesh import make_test_mesh
    from repro.models.model_api import ShapeConfig

    mesh = make_test_mesh((1, 1), ("data", "model"))
    cfg = get_reduced_config("qwen2-1.5b")
    shape = ShapeConfig("train_small", "train", 64, 8)
    compiled = lower_cell(cfg, shape, mesh).compile()
    assert compiled.memory_analysis().temp_size_in_bytes >= 0


def test_elastic_checkpoint_inprocess_single_device(tmp_path):
    """Save row-sharded state, restore under a DIFFERENT sharding layout —
    the elastic-restore path on 1-device meshes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.checkpoint import checkpointer
    from repro.distributed.meshes import make_mesh

    mesh_a = make_mesh((1,), ("data",))
    arr = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    sharded = jax.device_put(arr, NamedSharding(mesh_a, P("data", None)))
    checkpointer.save(str(tmp_path), 1, {"params": {"w": sharded}})
    mesh_b = make_mesh((1,), ("data",))
    sh_b = {"params": {"w": NamedSharding(mesh_b, P(None, None))}}
    restored, _, _ = checkpointer.restore(str(tmp_path), shardings=sh_b)
    assert np.array_equal(np.asarray(restored["params"]["w"]), np.asarray(arr))


# ---------------------------------------------------------------------------
# subprocess multi-device tests (forced host platform; may time out in
# constrained containers — xfail non-strict, pass where supported)
# ---------------------------------------------------------------------------


@pytest.mark.xfail(strict=False, reason="forced multi-device CPU collectives can time out on constrained hosts; non-strict — XPASSes where supported (the in-process variants above are the hard gate)")
def test_dist_askotch_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, json
        from repro.launch.mesh import make_test_mesh
        from repro.distributed.krr_dist import (DistKRRConfig,
            make_dist_askotch_step, init_dist_state)
        from repro.core.krr import KRRProblem
        mesh = make_test_mesh((2, 4), ("data", "model"))
        n, d = 512, 5
        cfg = DistKRRConfig(n=n, d=d, sigma=2.0, lam_unscaled=1e-5,
                            block_size=64, rank=24)
        step, sh = make_dist_askotch_step(mesh, cfg)
        r = np.random.default_rng(0)
        X = jnp.asarray(r.standard_normal((n, d)).astype(np.float32))
        base = KRRProblem(x=X, y=jnp.zeros(n), kernel="rbf", sigma=2.0,
                          lam_unscaled=1e-5, backend="xla")
        y = base.k_lam_matvec(jnp.asarray(r.standard_normal(n).astype(np.float32)))
        prob = KRRProblem(x=X, y=y, kernel="rbf", sigma=2.0,
                          lam_unscaled=1e-5, backend="xla")
        state = init_dist_state(cfg)
        with mesh:
            jstep = jax.jit(step)
            Xs = jax.device_put(X, sh["x"]); ys = jax.device_put(y, sh["y"])
            state = jax.device_put(state, sh["state"])
            for _ in range(200):
                state = jstep(state, Xs, ys)
                jax.block_until_ready(state.w)
        print(json.dumps({"rel": float(prob.relative_residual(state.w))}))
    """)
    rel = json.loads(out.strip().splitlines()[-1])["rel"]
    assert rel < 0.01, rel  # single-device reaches ~1e-3 in 200 iters


@pytest.mark.xfail(strict=False, reason="forced multi-device CPU collectives can time out on constrained hosts; non-strict — XPASSes where supported (the in-process variants above are the hard gate)")
def test_small_mesh_dryrun_two_archs():
    """Reduced-config lower+compile through the dryrun cell builder on a
    (2, 4) mesh — proves the sharding spec machinery end to end."""
    out = run_py("""
        import json, jax
        from repro.launch.mesh import make_test_mesh
        from repro.launch.dryrun import lower_cell
        from repro.configs.base import get_reduced_config
        from repro.models.model_api import ShapeConfig
        mesh = make_test_mesh((2, 4), ("data", "model"))
        results = {}
        shapes = [ShapeConfig("train_small", "train", 64, 8),
                  ShapeConfig("decode_small", "decode", 64, 8)]
        for arch in ("qwen2-1.5b", "rwkv6-1.6b"):
            cfg = get_reduced_config(arch)
            for shape in shapes:
                lowered = lower_cell(cfg, shape, mesh)
                compiled = lowered.compile()
                ma = compiled.memory_analysis()
                results[f"{arch}:{shape.name}"] = int(ma.temp_size_in_bytes)
        print(json.dumps(results))
    """, devices=8)
    res = json.loads(out.strip().splitlines()[-1])
    assert len(res) == 4
    assert all(v >= 0 for v in res.values())


@pytest.mark.xfail(strict=False, reason="forced multi-device CPU collectives can time out on constrained hosts; non-strict — XPASSes where supported (the in-process variants above are the hard gate)")
def test_elastic_checkpoint_across_meshes(tmp_path):
    """Save sharded state from a (4,) mesh; restore onto a (2,) mesh."""
    out = run_py(f"""
        import json, jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.checkpoint import checkpointer
        from repro.distributed.meshes import make_mesh
        mesh_a = make_mesh((4,), ("data",))
        arr = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
        sharded = jax.device_put(arr, NamedSharding(mesh_a, P("data", None)))
        checkpointer.save({str(tmp_path)!r}, 1, {{"params": {{"w": sharded}}}})
        mesh_b = make_mesh((2,), ("data",))
        sh_b = {{"params": {{"w": NamedSharding(mesh_b, P("data", None))}}}}
        restored, _, _ = checkpointer.restore({str(tmp_path)!r}, shardings=sh_b)
        w = restored["params"]["w"]
        ok = bool(np.array_equal(np.asarray(w), np.asarray(arr)))
        nshards = len(w.sharding.device_set)
        print(json.dumps({{"ok": ok, "nshards": nshards}}))
    """, devices=4)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["ok"] and res["nshards"] == 2


def test_fault_injection_restart(tmp_path):
    """Training survives an injected failure via checkpoint-restart and the
    post-restart trajectory is deterministic (same data cursor)."""
    import argparse

    from repro.launch import train as train_mod

    args = argparse.Namespace(
        arch="qwen2-1.5b", reduced=True, steps=30, batch=4, seq=16, lr=1e-3,
        seed=0, ckpt_dir=str(tmp_path), ckpt_every=10, log_every=5,
        resume=False, inject_failure=17, straggler_factor=3.0,
    )
    res = train_mod.run(args)
    assert res["final_step"] == 30
    # clean run for comparison
    args2 = argparse.Namespace(**{**vars(args), "ckpt_dir": str(tmp_path) + "_clean",
                                  "inject_failure": -1})
    res2 = train_mod.run(args2)
    final = {r["step"]: r["loss"] for r in res["history"]}
    final2 = {r["step"]: r["loss"] for r in res2["history"]}
    # the last logged loss must agree to float tolerance (bit-exact data resume)
    assert abs(final[30] - final2[30]) < 1e-4, (final[30], final2[30])


@pytest.mark.slow
def test_production_mesh_krr_dryrun_compiles():
    """The paper-workload cell on the real 512-device multi-pod mesh."""
    out = run_py("""
        import json
        from repro.launch.mesh import make_production_mesh
        from repro.launch.dryrun import lower_krr_cell
        mesh = make_production_mesh(multi_pod=True)
        lowered, _ = lower_krr_cell(mesh)
        compiled = lowered.compile()
        ma = compiled.memory_analysis()
        print(json.dumps({"temp": int(ma.temp_size_in_bytes)}))
    """, devices=512, timeout=1200)
    res = json.loads(out.strip().splitlines()[-1])
    assert res["temp"] < 16 * 2**30
