"""Telemetry subsystem correctness: schemas round-trip through JSONL, solver
traces are a pure VIEW (histories bit-identical with telemetry on/off), the
disabled path is near-free, sinks survive concurrent writers, the metrics
registry agrees with the tuning engine's own sweep accounting, and the
Prometheus exposition is well-formed.
"""

import json
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.krr import KRRProblem
from repro.core.solver_api import solve, tune
from repro.obs import (
    NULL_TELEMETRY,
    RingSink,
    Telemetry,
    as_telemetry,
    counter,
    diff,
    log_buckets,
    prometheus_text,
    snapshot,
    span,
    validate_event,
    validate_jsonl,
)
from repro.obs.metrics import Histogram
from repro.obs.report import main as report_main

N, D = 400, 5


@pytest.fixture(scope="module")
def problem():
    r = np.random.default_rng(7)
    x = jnp.asarray(r.standard_normal((N, D)).astype(np.float32))
    y = jnp.sin(2.0 * x[:, 0]) + 0.3 * x[:, 1]
    return KRRProblem(x=x, y=y, kernel="rbf", sigma=1.0, lam_unscaled=1e-4,
                      backend="xla")


# ---------------------------------------------------------------------------
# schemas + JSONL round-trip
# ---------------------------------------------------------------------------


def test_jsonl_round_trip_and_schema(problem, tmp_path):
    path = str(tmp_path / "tel.jsonl")
    tel = Telemetry(jsonl=path)
    solve(problem, "askotch", max_iters=20, telemetry=tel)
    tel.close()

    counts = validate_jsonl(path)
    assert counts["span"] >= 1
    assert counts["trace"] >= 1
    assert counts["metric"] >= 1

    # every line is standalone JSON an external consumer can parse
    with open(path) as fh:
        events = [json.loads(line) for line in fh]
    solvespans = [e for e in events if e["type"] == "span"
                  and e["name"] == "solve/askotch"]
    assert len(solvespans) == 1 and solvespans[0]["dur_s"] > 0

    traces = [e for e in events if e["type"] == "trace"]
    assert all(e["solver"] == "askotch" for e in traces)
    assert traces[-1]["rel_residual"] <= traces[0]["rel_residual"] * 1.01


def test_validate_rejects_mutations(tmp_path):
    good = {"type": "trace", "solver": "pcg", "iter": 1, "wall_s": 0.1,
            "rel_residual": 0.5}
    validate_event(good)
    with pytest.raises(ValueError, match="unknown fields"):
        validate_event({**good, "bogus": 1})
    with pytest.raises(ValueError, match="missing fields"):
        validate_event({k: v for k, v in good.items() if k != "rel_residual"})
    with pytest.raises(ValueError, match="unknown event type"):
        validate_event({"type": "nope"})

    path = tmp_path / "bad.jsonl"
    path.write_text(json.dumps(good) + "\n" + json.dumps({**good, "x": 1}) + "\n")
    with pytest.raises(ValueError, match="bad.jsonl:2"):
        validate_jsonl(str(path))
    path.write_text("not json\n")
    with pytest.raises(ValueError, match="not valid JSON"):
        validate_jsonl(str(path))


# ---------------------------------------------------------------------------
# traces are a VIEW: histories identical with telemetry on and off
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("method,solver,keys", [
    ("askotch", "askotch", {"iter", "rel_residual", "rel_residual_per_head",
                            "sketch_res", "step_L", "time_s"}),
    ("pcg-nystrom", "pcg", {"iter", "rel_residual", "rel_residual_per_head",
                            "time_s"}),
])
def test_trace_parity_with_legacy_history(problem, method, solver, keys):
    off = solve(problem, method, max_iters=15)
    tel = Telemetry(ring=True)
    on = solve(problem, method, max_iters=15, telemetry=tel)

    assert len(off.history) == len(on.history) > 0
    assert set(off.history[0]) == keys
    for a, b in zip(off.history, on.history):
        assert set(a) == set(b) == keys
        for k in keys - {"time_s"}:  # wall time differs run to run
            assert a[k] == b[k], (method, k)

    traces = [e for e in tel.ring.events() if e["type"] == "trace"]
    assert len(traces) == len(on.history)
    for ev, rec in zip(traces, on.history):
        validate_event(ev)
        assert ev["solver"] == solver and ev["iter"] == rec["iter"]
        assert ev["rel_residual"] == rec["rel_residual"]


# ---------------------------------------------------------------------------
# disabled-path overhead
# ---------------------------------------------------------------------------


def test_null_telemetry_overhead_is_negligible(problem):
    tel = as_telemetry(None)
    assert tel is NULL_TELEMETRY and not tel.enabled

    # a fixed 400 iterations and one check: 1-2 s on the CPU under parallel
    # workers, so that the ratio compares work, not timer noise
    t0 = time.perf_counter()
    solve(problem, "askotch", max_iters=400, eval_every=400)
    solve_s = time.perf_counter() - t0

    # what a solve actually pays per iteration when disabled: one enabled
    # check + one span fast path + one recorder identity check.  10k of
    # those (>> any real iteration count) must cost <5% of the small solve.
    rec = tel.recorder("askotch", n=N)
    t0 = time.perf_counter()
    for i in range(10_000):
        _ = tel.enabled
        with tel.span("solve/askotch", n=N):
            pass
        rec.add(i, 0.5, time_s=0.0)
    null_s = time.perf_counter() - t0
    assert null_s < 0.05 * solve_s, (null_s, solve_s)


# ---------------------------------------------------------------------------
# thread safety: concurrent serving clients through one JSONL sink
# ---------------------------------------------------------------------------


def test_concurrent_serving_clients_one_jsonl(tmp_path):
    from repro.serving.engine import ServingEngine

    r = np.random.default_rng(0)
    x = r.standard_normal((64, D)).astype(np.float32)
    w = r.standard_normal((64,)).astype(np.float32)
    cfg = {"kernel": "rbf", "sigma": 1.0, "backend": "xla", "precision": "f32"}

    path = str(tmp_path / "serve.jsonl")
    tel = Telemetry(jsonl=path)
    with ServingEngine(max_batch=32, max_wait_ms=1.0, telemetry=tel) as eng:
        eng.register("m", cfg, x, w)

        def client(seed):
            rr = np.random.default_rng(seed)
            for _ in range(5):
                q = int(rr.integers(1, 9))
                eng.predict("m", rr.standard_normal((q, D)).astype(np.float32))

        threads = [threading.Thread(target=client, args=(s,)) for s in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        eng.drain()
        stats = eng.stats("m")
        assert stats["n_requests"] == 40
        assert stats["p50_ms"] > 0 and stats["p99_ms"] >= stats["p50_ms"]

        prom = eng.prometheus_text()
        assert 'repro_serving_requests_total{model="m"} 40.0' in prom
        assert 'repro_serving_latency_ms_bucket{model="m",le="+Inf"} 40' in prom

        eng.reset_stats()
        assert eng.stats("m")["n_requests"] == 0
    tel.close()

    counts = validate_jsonl(path)  # every concurrent line intact + valid
    assert counts["span"] >= 1 and counts["metric"] >= 1


# ---------------------------------------------------------------------------
# metrics registry: sweep accounting agreement + Prometheus format
# ---------------------------------------------------------------------------


def test_registry_agrees_with_sweep_counter(problem):
    snap0 = snapshot()
    res = tune(problem, sigmas=(0.5, 1.0), lams=(1e-4, 1e-2), folds=3,
               max_iters=50, seed=0)
    delta = diff(snap0, snapshot())
    # TuneResult.sweeps is pairs/n^2; the registry counted the same pairs
    pairs = delta["repro_kernel_pairs_total"]
    assert pairs / float(problem.n) ** 2 == pytest.approx(res.sweeps)
    assert delta["repro_cg_iterations_total"] > 0


def test_prometheus_exposition_format():
    c = counter("repro_test_events_total", labels={"case": "prom"},
                help="test counter")
    c.inc(3)
    text = prometheus_text()
    assert "# HELP repro_test_events_total test counter" in text
    assert "# TYPE repro_test_events_total counter" in text
    assert 'repro_test_events_total{case="prom"} 3' in text

    h = Histogram("t_ms", labels=(), help="", buckets=log_buckets(1, 100, 1))
    for v in (0.5, 5.0, 50.0, 500.0):
        h.observe(v)
    pairs = h.bucket_counts()
    assert pairs[-1] == (float("inf"), 4)  # cumulative, ends at +Inf
    assert h.quantile(0.5) <= h.quantile(0.99)
    assert h.count == 4 and h.sum == pytest.approx(555.5)


def test_spans_nest_and_isolate_threads():
    ring = RingSink()
    with span("outer", sink=ring):
        with span("inner", sink=ring):
            pass
    inner, outer = ring.events()  # inner closes first
    assert inner["name"] == "inner" and outer["name"] == "outer"
    assert inner["parent_id"] == outer["span_id"] and inner["depth"] == 1

    seen = []

    def worker():
        with span("t", sink=ring) as s:
            seen.append(s.parent_id)

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert seen == [0]  # fresh stack per thread: no cross-thread parent


# ---------------------------------------------------------------------------
# report CLI
# ---------------------------------------------------------------------------


def test_report_cli_smoke(problem, tmp_path, capsys):
    path = str(tmp_path / "tel.jsonl")
    with Telemetry(jsonl=path) as tel:
        solve(problem, "askotch", max_iters=10, telemetry=tel)
    assert report_main([path]) == 0
    out = capsys.readouterr().out
    assert "schema OK" in out and "trace[askotch]" in out

    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"type": "mystery"}\n')
    assert report_main([str(bad)]) == 1
