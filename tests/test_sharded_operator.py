"""Sharded/dense parity: ShardedKernelOperator vs the single-device
KernelOperator, all three kernels, 1-D and (n, t) RHS.

The mesh adapts to the process' device count: (2, 2) under the
distributed-smoke CI job (XLA_FLAGS=--xla_force_host_platform_device_count=4),
degrading to (2, 1) / (1, 1) in a plain pytest run — size-1 axes make every
collective a no-op, so the SAME code paths run everywhere (the 1-device
fallback satellite) and genuinely multi-device under the smoke job.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.operator import KernelOperator
from repro.distributed.meshes import make_mesh
from repro.distributed.sharded_operator import ShardedKernelOperator

N, D, T, B = 64, 5, 4, 12
TOL = 1e-5  # relative error floor from f32 reduction-order differences
KERNELS = ("rbf", "laplacian", "matern52")


def _mesh_shape():
    nd = len(jax.devices())
    if nd >= 4:
        return (2, 2)
    if nd >= 2:
        return (2, 1)
    return (1, 1)


@pytest.fixture(scope="module")
def mesh():
    return make_mesh(_mesh_shape(), ("data", "model"))


@pytest.fixture(scope="module")
def data():
    # module-scoped: owns its generator (the shared rng fixture is
    # function-scoped by design — see tests/conftest.py)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((N, D)).astype(np.float32))
    v1 = jnp.asarray(rng.standard_normal((N,)).astype(np.float32))
    vt = jnp.asarray(rng.standard_normal((N, T)).astype(np.float32))
    a = jnp.asarray(rng.standard_normal((B, D)).astype(np.float32))
    idx = jnp.asarray(rng.integers(0, N, B))
    return x, v1, vt, a, idx


def _ops(mesh, x, kernel):
    op = KernelOperator(x=x, kernel=kernel, sigma=1.5, backend="xla")
    sop = ShardedKernelOperator.bind(mesh, x, kernel=kernel, sigma=1.5,
                                     backend="xla")
    return op, sop


def _rel(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return np.abs(got - want).max() / max(np.abs(want).max(), 1e-30)


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("ndim", [1, 2])
def test_matvec_parity(mesh, data, kernel, ndim):
    x, v1, vt, _, _ = data
    v = v1 if ndim == 1 else vt
    op, sop = _ops(mesh, x, kernel)
    v_sh = jax.device_put(v, sop.sharding(ndim))
    got = sop.matvec(v_sh)
    assert got.shape == v.shape
    assert _rel(got, op.matvec(v)) < TOL


@pytest.mark.parametrize("kernel", KERNELS)
@pytest.mark.parametrize("ndim", [1, 2])
def test_row_block_matvec_parity(mesh, data, kernel, ndim):
    x, v1, vt, a, _ = data
    v = v1 if ndim == 1 else vt
    op, sop = _ops(mesh, x, kernel)
    v_sh = jax.device_put(v, sop.sharding(ndim))
    got = sop.row_block_matvec(a, v_sh)
    assert got.shape == (B,) + v.shape[1:]
    assert _rel(got, op.row_block_matvec(a, v)) < TOL


@pytest.mark.parametrize("kernel", KERNELS)
def test_block_idx_parity(mesh, data, kernel):
    x, _, _, _, idx = data
    op, sop = _ops(mesh, x, kernel)
    assert _rel(sop.block_idx(idx), op.block_idx(idx)) < TOL


@pytest.mark.parametrize("kernel", KERNELS)
def test_block_parity(mesh, data, kernel):
    x, _, _, a, _ = data
    op, sop = _ops(mesh, x, kernel)
    assert _rel(sop.block(a, x[:16]), op.block(a, x[:16])) < TOL


def test_gather_rows_packed(mesh, data):
    """ONE packed psum moves x rows and every extra together."""
    x, v1, vt, _, idx = data
    _, sop = _ops(mesh, x, "rbf")
    v1_sh = jax.device_put(v1, sop.sharding(1))
    vt_sh = jax.device_put(vt, sop.sharding(2))
    (xb, v1b, vtb), owned = sop.gather_rows(idx, v1_sh, vt_sh)
    np.testing.assert_allclose(np.asarray(xb), np.asarray(x[idx]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(v1b), np.asarray(v1[idx]), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(vtb), np.asarray(vt[idx]), rtol=1e-6)
    # each sampled row is owned by exactly one row shard
    per_shard = np.asarray(owned).reshape(sop.n_row_shards, B)
    np.testing.assert_allclose(per_shard.sum(axis=0), np.ones(B))


def test_restrict_returns_replicated_operator(mesh, data):
    x, _, _, _, idx = data
    op, sop = _ops(mesh, x, "rbf")
    rop = sop.restrict(idx)
    assert isinstance(rop, KernelOperator)
    assert _rel(rop.block(rop.x), op.restrict(idx).block(np.asarray(x)[idx])) < TOL


@pytest.mark.parametrize("ndim", [1, 2])
def test_k_lam_matvec_and_sketch(mesh, data, ndim):
    x, v1, vt, _, _ = data
    v = v1 if ndim == 1 else vt
    op, sop = _ops(mesh, x, "rbf")
    v_sh = jax.device_put(v, sop.sharding(ndim))
    assert _rel(sop.k_lam_matvec(v_sh, 0.5), op.k_lam_matvec(v, 0.5)) < TOL
    assert float(sop.trace_est()) == float(op.trace_est()) == N


def test_with_points_and_divisibility_error(mesh, data):
    x, _, _, _, _ = data
    _, sop = _ops(mesh, x, "rbf")
    sub = sop.with_points(x[: sop.n_row_shards * 8])
    assert sub.n == sop.n_row_shards * 8
    if sop.n_row_shards > 1:
        with pytest.raises(ValueError, match="shard evenly"):
            sop.with_points(x[: sop.n_row_shards * 8 + 1])


def test_unbound_operator_errors(mesh):
    sop = ShardedKernelOperator(mesh=mesh)
    with pytest.raises(ValueError, match="unbound"):
        sop.matvec(jnp.zeros((8,)))


def test_serving_sharded_predict_parity(mesh, data):
    """serving/krr_serve drives the same closure over the sharded operator."""
    from repro.serving.krr_serve import (
        make_krr_predict_fn,
        make_sharded_krr_predict_fn,
    )

    x, _, vt, a, _ = data
    op, _ = _ops(mesh, x, "rbf")
    ref = make_krr_predict_fn(op, vt)(a)
    got = make_sharded_krr_predict_fn(mesh, x, vt, kernel="rbf", sigma=1.5,
                                      backend="xla")(a)
    assert got.shape == (B, T)
    assert _rel(got, ref) < TOL
    # empty request stays shape-correct without tracing a bucket
    empty = make_sharded_krr_predict_fn(mesh, x, vt, kernel="rbf", sigma=1.5,
                                        backend="xla")(a[:0])
    assert empty.shape == (0, T)
