"""The divide-and-conquer tier: partitioners, combiners, solve(method="dc").

The contract under test, in the order the ISSUE states it: deterministic
size-balanced partitioners that round-trip through JSON; exact k=1
degeneracy (bit-parity with the plain solver); row-stochastic combiner
weights; a 1-device mesh matching the sequential fallback; and — the point
of the tier — ZERO collective dispatches recorded by the
``repro_collective_dispatch_total`` counter across a whole DC solve.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.krr import KRRProblem
from repro.core.solver_api import DC_METHOD_OPTIONS, METHODS, solve
from repro.distributed.dc import (
    COMBINERS,
    collective_dispatch_delta,
    combiner_weights,
    solve_dc,
)
from repro.distributed.partition import (
    PARTITION_KINDS,
    Partition,
    balanced_sizes,
    kmeans_partition,
    make_partition,
    random_partition,
)
from repro.obs import metrics as obs_metrics


def _data(n=240, d=4, seed=0, n_test=40):
    r = np.random.default_rng(seed)
    x = jnp.asarray(r.standard_normal((n + n_test, d)).astype(np.float32))
    y = jnp.asarray(r.standard_normal((n + n_test,)).astype(np.float32))
    return x[:n], y[:n], x[n:]


def _problem(n=240, d=4, seed=0, **kw):
    x, y, _ = _data(n, d, seed)
    kw.setdefault("backend", "xla")
    return KRRProblem(x=x, y=y, sigma=1.5, lam_unscaled=1e-4, **kw)


# ---------------------------------------------------------------------------
# partitioners
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kind", PARTITION_KINDS)
def test_partition_deterministic_and_balanced(kind):
    x = np.random.default_rng(7).standard_normal((101, 3)).astype(np.float32)
    a = make_partition(x, 4, kind=kind, seed=3)
    b = make_partition(x, 4, kind=kind, seed=3)
    np.testing.assert_array_equal(a.assignments, b.assignments)
    np.testing.assert_array_equal(a.centers, b.centers)
    # balanced to within one row: 101 over 4 -> (26, 25, 25, 25)
    np.testing.assert_array_equal(np.sort(a.sizes), np.sort(balanced_sizes(101, 4)))
    # a different seed must actually move rows (not a fixed split)
    c = make_partition(x, 4, kind=kind, seed=4)
    assert not np.array_equal(a.assignments, c.assignments)
    # shard_indices: ascending within each shard, a disjoint cover of range(n)
    idx = a.shard_indices()
    assert all(np.all(np.diff(i) > 0) for i in idx if len(i) > 1)
    np.testing.assert_array_equal(np.sort(np.concatenate(idx)), np.arange(101))


def test_kmeans_partition_groups_separated_clusters():
    r = np.random.default_rng(0)
    blobs = np.concatenate([
        r.standard_normal((30, 2)).astype(np.float32) + 20.0 * np.asarray(off)
        for off in ((0, 0), (1, 0), (0, 1))
    ])
    part = kmeans_partition(blobs, 3, seed=1)
    # with well-separated equal blobs the balanced assignment recovers them:
    # each shard is one blob (up to shard relabeling)
    labels = np.repeat(np.arange(3), 30)
    for j in range(3):
        assert len(set(part.assignments[labels == j])) == 1


def test_partition_json_roundtrip():
    x = np.random.default_rng(1).standard_normal((57, 5)).astype(np.float32)
    part = kmeans_partition(x, 3, seed=9)
    back = Partition.from_json(part.to_json())
    np.testing.assert_array_equal(part.assignments, back.assignments)
    np.testing.assert_array_equal(part.centers, back.centers)  # exact: f32<->f64
    assert (back.kind, back.seed) == ("kmeans", 9)
    # and a round-tripped partition drives a solve unchanged
    p = _problem(n=57, d=5, seed=1)
    out = solve(p, "dc", dc_partition=back, dc_method="direct")
    assert out.info["shards"] == 3


def test_partition_k1_is_identity():
    x = np.random.default_rng(2).standard_normal((20, 3)).astype(np.float32)
    for kind in PARTITION_KINDS:
        part = make_partition(x, 1, kind=kind, seed=0)
        np.testing.assert_array_equal(part.shard_indices()[0], np.arange(20))


def test_partition_validation():
    x = np.zeros((10, 2), np.float32)
    with pytest.raises(ValueError, match="invalid"):
        random_partition(x, 0)
    with pytest.raises(ValueError, match="invalid"):
        random_partition(x, 11)
    with pytest.raises(ValueError, match="unknown partition kind"):
        make_partition(x, 2, kind="voronoi")


# ---------------------------------------------------------------------------
# combiners
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("combiner", COMBINERS)
def test_combiner_weights_sum_to_one(combiner):
    x = np.random.default_rng(3).standard_normal((90, 4)).astype(np.float32)
    part = kmeans_partition(x, 3, seed=0)
    xq = np.random.default_rng(4).standard_normal((17, 4)).astype(np.float32)
    w = combiner_weights(part, xq, combiner)
    assert w.shape == (17, 3) and np.all(w >= 0)
    np.testing.assert_allclose(w.sum(axis=1), 1.0, atol=1e-6)


def test_softmax_combiner_favors_nearest_center():
    x = np.concatenate([
        np.zeros((10, 2), np.float32), np.full((10, 2), 30.0, np.float32)
    ])
    part = kmeans_partition(x, 2, seed=0)
    xq = np.asarray([[0.0, 0.0], [30.0, 30.0]], np.float32)
    w = combiner_weights(part, xq, "softmax")
    near = np.argmin(
        ((xq[:, None, :] - part.centers[None]) ** 2).sum(-1), axis=1
    )
    assert np.array_equal(w.argmax(axis=1), near)
    # a sharp temperature turns far-apart blobs into hard assignment
    w_sharp = combiner_weights(part, xq, "softmax", softmax_temp=1.0)
    assert w_sharp.min(axis=1).max() < 1e-6 and w_sharp.max() > 1.0 - 1e-6


# ---------------------------------------------------------------------------
# solve(method="dc")
# ---------------------------------------------------------------------------


def test_dc_k1_bitparity_with_plain_solver():
    p = _problem()
    base = solve(p, "pcg-nystrom", max_iters=120, tol=1e-7, seed=0, rank=40)
    dc = solve(p, "dc", dc_shards=1, dc_method="pcg-nystrom",
               max_iters=120, tol=1e-7, seed=0, rank=40)
    assert jnp.array_equal(base.w, dc.w)  # bitwise, not allclose
    _, _, xt = _data()
    assert jnp.array_equal(base.predict_fn(xt), dc.predict_fn(xt))


def test_dc_records_zero_collective_dispatches():
    p = _problem()
    before = obs_metrics.snapshot()
    out = solve(p, "dc", dc_shards=3, dc_method="askotch", max_iters=30,
                seed=0)
    after = obs_metrics.snapshot()
    assert collective_dispatch_delta(before, after) == 0.0
    assert out.info["collective_dispatches"] == 0.0


def test_dc_one_device_mesh_matches_sequential():
    from repro.distributed.meshes import make_solver_mesh

    p = _problem()
    mesh = make_solver_mesh("1x1")
    seq = solve(p, "dc", dc_shards=3, dc_method="pcg-nystrom", max_iters=60,
                seed=0)
    par = solve(p, "dc", dc_shards=3, dc_method="pcg-nystrom", max_iters=60,
                seed=0, mesh=mesh)
    assert jnp.array_equal(seq.w, par.w)
    _, _, xt = _data()
    np.testing.assert_array_equal(
        np.asarray(seq.predict_fn(xt)), np.asarray(par.predict_fn(xt))
    )
    assert par.info["mesh"] == {"data": 1, "model": 1}
    assert par.info["collective_dispatches"] == 0.0


@pytest.mark.parametrize("method", ["askotch", "pcg-nystrom"])
def test_dc_mesh_shards_run_on_their_devices(method):
    """Shard s's local solve runs on mesh device s % D: its weights live
    there (one device in plain runs, four in the distributed-smoke job)."""
    import jax

    from repro.distributed.meshes import make_solver_mesh

    mesh = make_solver_mesh("auto")
    devices = list(mesh.devices.flat)
    res = solve_dc(_problem(), shards=4, method=method, max_iters=20,
                   mesh=mesh)
    for s, sub in enumerate(res.shard_outputs):
        assert sub.w.devices() == {devices[s % len(devices)]}
    assert jax.device_count() == len(devices)


def test_dc_scattered_weights_match_shard_solves():
    p = _problem()
    out = solve(p, "dc", dc_shards=3, dc_method="direct")
    res = solve_dc(p, shards=3, method="direct")
    for sub, idx in zip(res.shard_outputs, res.partition.shard_indices()):
        np.testing.assert_array_equal(
            np.asarray(out.w)[idx], np.asarray(sub.w)
        )


def test_dc_multirhs_and_multikernel_ride_through():
    r = np.random.default_rng(5)
    x = jnp.asarray(r.standard_normal((150, 4)).astype(np.float32))
    y = jnp.asarray(r.standard_normal((150, 3)).astype(np.float32))
    p = KRRProblem(x=x, y=y, sigma=1.5, lam_unscaled=1e-4, backend="xla")
    out = solve(p, "dc", dc_shards=2, dc_method="pcg-nystrom", max_iters=60,
                kernel=("rbf", "laplacian"), weights=(0.7, 0.3), seed=0)
    assert out.w.shape == (150, 3)
    xt = jnp.asarray(r.standard_normal((11, 4)).astype(np.float32))
    pred = out.predict_fn(xt)
    assert pred.shape == (11, 3) and bool(jnp.all(jnp.isfinite(pred)))


def test_dc_estimator_and_serving_consume_predict_fn():
    # KernelRidge(solver="dc") — the front end needs no DC-specific code
    from repro.estimators import KernelRidge

    x, y, xt = _data()
    est = KernelRidge(
        alpha=0.1, sigma=1.5, solver="dc",
        solver_opts={"dc_shards": 3, "dc_method": "pcg-nystrom",
                     "max_iters": 60, "seed": 0},
    )
    est.fit(np.asarray(x), np.asarray(y))
    pred = est.predict(np.asarray(xt))
    assert pred.shape == (len(xt),) and np.all(np.isfinite(pred))


def test_dc_option_validation():
    p = _problem()
    assert "dc" in METHODS and set(DC_METHOD_OPTIONS) >= {"dc_shards"}
    with pytest.raises(ValueError, match="inner solver"):
        solve(p, "dc", dc_method="dc")
    with pytest.raises(ValueError, match="dc_bogus"):
        solve(p, "dc", dc_bogus=1)
    with pytest.raises(ValueError, match="unknown option"):
        solve(p, "dc", dc_shards=2, dc_method="direct", max_iters=5)
    with pytest.raises(ValueError, match="combiner"):
        solve(p, "dc", dc_combiner="median")
    with pytest.raises(ValueError, match="partition"):
        solve(p, "dc", dc_partition="voronoi")
    with pytest.raises(ValueError, match="precomputed"):
        gram = np.eye(16, dtype=np.float32)
        gp = KRRProblem(x=jnp.asarray(gram), y=jnp.zeros(16),
                        kernel="precomputed")
        solve(gp, "dc", dc_shards=2)
    part = random_partition(np.zeros((10, 2), np.float32), 2)
    with pytest.raises(ValueError, match="covers 10 rows"):
        solve_dc(p, partition=part, method="direct")


def test_dc_telemetry_spans():
    from repro.obs import RingSink, Telemetry

    sink = RingSink(256)
    tel = Telemetry(sink=sink)
    p = _problem()
    solve(p, "dc", dc_shards=2, dc_method="direct", telemetry=tel)
    tel.close()
    names = [e.get("name") for e in sink.events() if e.get("type") == "span"]
    assert "solve/dc" in names
    assert names.count("dc/shard") == 2
